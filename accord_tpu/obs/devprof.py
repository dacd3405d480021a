"""The wall-clock half of ``obs``: ONE host-span primitive for every host
clock the program keeps, and the Chrome-trace profiler it feeds.

``span(name, into=None)`` is a slotted context manager that, on one
``perf_counter`` pair,

1. enters / leaves a ``jax.profiler.TraceAnnotation(name)`` while a
   profiler session is open, so the span is an event of the ``/host:``
   plane on the clock of the device ops (without a session it costs the
   inactive ``TraceMe`` check; the class is resolved lazily, and only in
   a process that has imported JAX already);
2. adds ``[1, seconds]`` to ``into[name]`` when a table is given
   (``DeviceState.kernel_times`` for the store kinds, ``NodeServer
   .loop_times`` for the serving loop's ``srv.*`` names);
3. hands the slice to ``PROFILER`` when one is armed: every span becomes
   one Chrome-trace complete event (``chrome://tracing`` /
   ``ui.perfetto.dev`` JSON).

A span is entered and left inside ONE synchronous callback of one thread,
never across an ``await``: the spans of a thread nest, and self time is
well defined.  A table belongs to one thread: a span on a worker thread
gets a table of its own, which its owner folds in with ``merge`` once it
has joined the worker.

Wall-clock timings are NOT deterministic, so nothing here ever touches
the metrics registry or the sim stats (the burn's determinism gates
compare those byte-for-byte).  Arming the profiler is explicit and
process-global:

    from accord_tpu.obs import devprof
    with devprof.capture() as prof:
        ... run the workload ...
    prof.write_chrome("trace.json")

``ACCORD_TPU_OBS=off`` (read once, when the process starts) silences
``span``: no annotation, no profiler slice (capture() then yields an inert
profiler that records nothing), and without a table no clock either; nor
does a span take a clock that no table, no armed profiler and no open
session would read.  A table its owner still keeps is still counted:
``DeviceState.kernel_times`` is a counter the tests and the benchmark read,
as the metrics registry is; a serving node keeps no ``loop_times`` under
the knob, so its ``srv.*`` spans cost one check each."""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from time import perf_counter
from typing import Dict, List, Optional

# the process-global armed profiler; spans read this once
PROFILER: Optional["DeviceProfiler"] = None

# what a span's name starts with: the serving loop's names, then the
# kernel_times kinds.  A reader of the profiler's trace (benchmarks/lib/
# program_spans.py) tells the program's spans from XLA's host events by it.
SPAN_PREFIXES = ("srv.", "dispatch_", "wait_", "host_", "sync_",
                 "drain_tick_", "range_index_sync", "fused_", "register",
                 "snapshot_cols", "pack_queries", "choose_route")

# jax.profiler.TraceAnnotation once JAX is in the process; False when the
# import failed (then no session can be open either)
_TRACE_ME = None


def enabled() -> bool:
    """The ACCORD_TPU_OBS escape hatch: default ON; "off"/"0"/"false"/"no"
    disables span recording, histograms, the device profiler and what a
    host span emits.  A pure read of the environment: it changes nothing."""
    return os.environ.get("ACCORD_TPU_OBS", "").lower() not in (
        "off", "0", "false", "no")


# what ``span`` follows: the knob as the process started with it (a span
# cannot afford the read itself; a test that flips spans sets this)
_ON = enabled()


def _trace_me():
    global _TRACE_ME
    if "jax" not in sys.modules:
        return None         # nobody can have opened a profiler session
    try:
        from jax.profiler import TraceAnnotation
        _TRACE_ME = TraceAnnotation
    except Exception:       # noqa: BLE001 — a span never fails its caller
        _TRACE_ME = False
    return _TRACE_ME


class span:
    """``with span(name, into):`` one timed slice (module docstring).
    ``pid`` / ``tid`` / ``args`` are the Chrome event's (node, store,
    free-form), read only while a profiler is armed."""

    __slots__ = ("name", "into", "pid", "tid", "args", "_t0", "_ann")

    def __init__(self, name: str, into: Optional[dict] = None,
                 pid: int = 0, tid: int = 0, args: Optional[dict] = None):
        self.name = name
        self.into = into
        self.pid = pid
        self.tid = tid
        self.args = args

    def __enter__(self):
        self._ann = None
        if _ON:
            tm = _TRACE_ME
            if tm is None:
                tm = _trace_me()
            if tm and tm.is_enabled():
                ann = self._ann = tm(self.name)
                ann.__enter__()
            elif self.into is None and PROFILER is None:
                self._t0 = None     # nobody listens: no clock
                return self
        elif self.into is None:
            self._t0 = None         # silenced, and nobody counts: no clock
            return self
        self._t0 = perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        t0 = self._t0
        if t0 is None:
            return False
        t1 = perf_counter()
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        into = self.into
        if into is not None:
            cell = into.get(self.name)
            if cell is None:
                into[self.name] = [1, t1 - t0]
            else:
                cell[0] += 1
                cell[1] += t1 - t0
        prof = PROFILER
        if prof is not None:
            prof.complete(self.name, t0, t1, pid=self.pid, tid=self.tid,
                          args=self.args)
        return False


def merge(into: dict, part: dict) -> None:
    """Fold a worker thread's table into its owner's, on the owner's
    thread, after the join."""
    for name, (calls, secs) in part.items():
        cell = into.get(name)
        if cell is None:
            into[name] = [calls, secs]
        else:
            cell[0] += calls
            cell[1] += secs


class DeviceProfiler:
    """Bounded in-memory collector of Chrome-trace complete events."""

    def __init__(self, capacity: int = 500_000):
        self.capacity = capacity
        self.events: List[dict] = []
        self.dropped = 0
        self._t0 = time.perf_counter()

    def _ts(self, t: float) -> float:
        return (t - self._t0) * 1e6        # Chrome trace wants micros

    def complete(self, name: str, t_start: float, t_end: float,
                 pid: int = 0, tid: int = 0,
                 args: Optional[dict] = None) -> None:
        """One finished slice [t_start, t_end] (perf_counter seconds)."""
        if len(self.events) >= self.capacity:
            self.dropped += 1
            return
        ev = {"name": name, "cat": "host", "ph": "X",
              "ts": round(self._ts(t_start), 3),
              "dur": round((t_end - t_start) * 1e6, 3),
              "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    # -- export --------------------------------------------------------------
    def chrome_trace(self) -> dict:
        counts: Dict[str, int] = {}
        for ev in self.events:
            counts[ev["name"]] = counts.get(ev["name"], 0) + 1
        return {"traceEvents": self.events,
                "displayTimeUnit": "ms",
                "otherData": {"producer": "accord_tpu.obs.devprof",
                              "event_counts": counts,
                              "dropped": self.dropped}}

    def write_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


@contextlib.contextmanager
def capture(capacity: int = 500_000):
    """Arm a profiler for the with-body (process-global; nesting keeps the
    outer one armed again afterwards).  Under ``ACCORD_TPU_OBS=off`` the
    yielded profiler is never armed, so instrumentation stays silent and
    the trace exports empty — the escape hatch is total."""
    global PROFILER
    prof = DeviceProfiler(capacity)
    prev = PROFILER
    if enabled():
        PROFILER = prof
    try:
        yield prof
    finally:
        PROFILER = prev
