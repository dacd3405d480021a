"""The PROGRAM's own host spans, read from this run's profiler trace.

``accord_tpu.obs.devprof.span`` enters a ``jax.profiler.TraceAnnotation``
while a profiler session is open, so every span of the serving loop
(``srv.*``) and every ``kernel_times`` kind is an event on its thread's line
of the ``/host:`` plane, on the clock of the device operations.  The
program says which names are its own (``devprof.SPAN_PREFIXES``); a program
that does not (the parent of the PR that brought this file) has no such
span, and every reader here then returns ``None``.

Reduced per host THREAD line, inside the ``bench.slice`` window, with
``trace_reduce._flatten``'s rule (a segment belongs to the innermost open
span, so a span's SELF time leaves out the spans nested under it):

    {"window_s": the slice,
     "threads": {line: {"self_s": {name: seconds}, "count": {name: events},
                        "server_s": seconds under any ``srv.*`` span}},
     "loop": the line with the most time under ``srv.*`` spans}

A line's key is its place in the file and its name (``"3 python3"``): the
profiler names every thread of the process alike.

The benchmark's own ``client.submit`` annotation is left out of the nesting
(16 client tasks hold it open at once: it overlaps itself).  A program span
is entered and left inside one synchronous callback of one thread, so the
spans of a line nest."""

import glob
import os
import sys

from . import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), ".bench_run")
SERVER = "srv."
REPLY = "srv.client_reply"
_CACHE = {}


def _process_started():
    """Epoch seconds this process started at (to the second), or None
    where /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(ln.split()[1]) for ln in f
                        if ln.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK") - 1.0
    except (OSError, ValueError, StopIteration, IndexError):
        return None


def _cell():
    """The cell this process runs (``run.py --workload <name>``), whose
    scratch directory holds its trace; ``*`` where the command line does
    not say (``record`` carries neither the name nor the trace's path:
    a note for the next ``benchmark`` PR, as is the second parse of the
    xplane here after run.py's own)."""
    argv = sys.argv
    for i, arg in enumerate(argv[:-1]):
        if arg == "--workload":
            return argv[i + 1]
    return "*"


def trace_file():
    """The newest ``*.xplane.pb`` under this cell's ``.bench_run/<cell>/
    trace/`` written since this process started: this run's, or None."""
    started = _process_started()
    if started is None:
        print("program_spans: /proc does not say when this process "
              "started, so no trace can be told to be this run's: the "
              "span metrics are left out", file=sys.stderr)
        return None
    found = [p for p in glob.glob(os.path.join(
        SCRATCH, _cell(), "trace", "plugins", "profile", "*", "*.xplane.pb"))
        if os.path.getmtime(p) >= started]
    return max(found, key=os.path.getmtime) if found else None


def prefixes():
    """What the program's span names start with, or None from a program
    that exports no such thing."""
    try:
        from accord_tpu.obs import devprof
    except ImportError:
        return None
    found = getattr(devprof, "SPAN_PREFIXES", None)
    return tuple(found) if found else None


def reduce(trace, names):
    """``trace`` as ``trace_reduce.load`` gives it; ``names`` the prefixes
    of the program's span names (module docstring for the result)."""
    window = None
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == trace_reduce.SLICE:
                    window = (start, start + dur)
    if window is None:
        return None
    w0, w1 = window
    threads, seen = {}, 0
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            seen += 1
            spans, count = [], {}
            for name, start, dur in line["events"]:
                if not name.startswith(names):
                    continue
                s, e = max(start, w0), min(start + dur, w1)
                if e > s:
                    spans.append((s, e, name))
                    count[name] = count.get(name, 0) + 1
            if not spans:
                continue
            self_s = {}
            for s, e, name in trace_reduce._flatten(spans):
                self_s[name] = self_s.get(name, 0.0) + (e - s) * 1e-9
            # the srv.* spans alone, flattened: their union on this line
            server = sum(e - s for s, e, _n in trace_reduce._flatten(
                [sp for sp in spans if sp[2].startswith(SERVER)]))
            threads[f"{seen} {line['name']}"] = {
                "self_s": self_s, "count": count, "server_s": server * 1e-9}
    if not threads:
        return None
    return {"window_s": (w1 - w0) * 1e-9, "threads": threads,
            "loop": max(threads, key=lambda ln: threads[ln]["server_s"])}


def spans():
    """This run's reduction (loaded once a trace file), or None: no traced
    run, no trace, or a program without spans."""
    names = prefixes()
    path = trace_file() if names else None
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE.clear()
        _CACHE[path] = reduce(trace_reduce.load(path), names)
    return _CACHE[path]


def self_seconds(red, wanted, off_loop=False):
    """Self seconds of the spans whose name ``wanted`` accepts, on the
    loop's thread (or, ``off_loop``, on every other thread)."""
    lines = [ln for ln in red["threads"] if (ln != red["loop"]) == off_loop]
    return sum(secs for ln in lines
               for name, secs in red["threads"][ln]["self_s"].items()
               if wanted(name))


def replies(red):
    """Reply frames to clients in the slice: the txns answered there."""
    return sum(cell["count"].get(REPLY, 0)
               for cell in red["threads"].values())


def ms_per_reply(record, wanted, off_loop=False):
    """What a ``.serve`` span metric reads: self milliseconds of the wanted
    spans in the slice / client replies in the slice; None where there is
    nothing to read."""
    if record.get("driver") != "served":
        return None
    red = spans()
    if red is None or not replies(red):
        return None
    return self_seconds(red, wanted, off_loop) * 1e3 / replies(red)
