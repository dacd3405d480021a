"""From a jax.profiler trace to device busy time, the top device operations
and the idle gaps named by what the host was doing.

A trace is read into plain data first — ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}`` — from an
``.xplane.pb`` (jax.profiler.ProfileData, nothing but JAX) or from a
``.json`` of that same shape (the small recorded trace the tests keep), and
every number is computed from the plain data.

- A DEVICE plane is one named ``/device:<KIND>:<n>``; its operations are the
  events of its ``XLA Ops`` line (all its lines but ``Steps`` and
  ``XLA Modules`` where it has no such line: a module's span covers the
  stalls between its operations, so it would count idle time as busy).
- The WINDOW is the benchmark's own ``bench.slice`` host annotation; device
  events are clipped to it.
- busy = the union of the operations' intervals, per device, averaged over
  the devices.  A trace with no device event is busy 0: idle 100 %.
- Idle gaps are the complement of device 0's union inside the window, each
  split over the innermost benchmark annotation open at the time."""

import bisect
import gzip
import json
import re

SLICE = "bench.slice"
NO_SPAN = "(no benchmark span open)"
_DEVICE = re.compile(r"^/device:[A-Za-z]+:\d+$")
_NOT_OPS = ("Steps", "XLA Modules")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_LAYOUT = re.compile(r"\{[^}]*\}")


def load(path):
    if path.endswith(".json") or path.endswith(".json.gz"):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]}
            for line in plane.lines]}
        for plane in data.planes]}


def outline(trace):
    """[plane, line, events] rows: what a reader looks at by hand first."""
    return [[p["name"], ln["name"], len(ln["events"])]
            for p in trace["planes"] for ln in p["lines"]]


def op_label(name):
    """A device event is named by its whole HLO instruction, hundreds of
    characters long; what a reader needs is ``<result> <opcode> [<custom-call
    target>] <result type>``."""
    if " = " not in name:
        return name[:96]
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE.search(" " + rhs)
    if m is None:
        return name[:96]
    kind = _LAYOUT.sub("", rhs[:max(m.start() - 1, 0)]).strip()
    target = _TARGET.search(rhs)
    parts = [lhs, m.group(1)] + ([target.group(1)] if target else []) \
        + [kind[:48]]
    return " ".join(parts)


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _flatten(spans):
    """Properly nested (start, end, name) spans -> disjoint, sorted
    (start, end, name) segments carrying the INNERMOST open span's name."""
    out, stack, t = [], [], 0.0

    def emit(upto):
        nonlocal t
        if stack and upto > t:
            out.append((t, upto, stack[-1][2]))
        t = max(t, upto)

    for s in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= s[0]:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(s[0])
        t = s[0]
        stack.append(s)
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def _device_ops(plane):
    lines = [ln for ln in plane["lines"] if ln["name"] == "XLA Ops"] \
        or [ln for ln in plane["lines"] if ln["name"] not in _NOT_OPS]
    return [ev for ln in lines for ev in ln["events"] if ev[2] > 0]


def reduce(trace, annotations=()):
    """{"window_s", "busy_s", "n_devices", "device_events", "device_ops":
    [[name, seconds]] (top 10), "idle_gaps": [[name, seconds]] (top 10),
    "spans": {annotation: [count, seconds]}} — seconds as measured."""
    names = set(annotations)
    spans, window = [], None
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == SLICE:
                    window = (start, start + dur)
                elif name in names:
                    spans.append((start, start + dur, name))
    devices = [p for p in trace["planes"] if _DEVICE.match(p["name"])]
    ops = [_device_ops(p) for p in devices]
    if window is None:           # no slice annotation: everything recorded
        every = [(s, s + d) for dev in ops for _n, s, d in dev] \
            + [(s, e) for s, e, _n in spans]
        if not every:
            return {"window_s": 0.0, "busy_s": 0.0, "n_devices": len(devices),
                    "device_events": 0, "device_ops": [], "idle_gaps": [],
                    "spans": {}}
        window = (min(s for s, _e in every), max(e for _s, e in every))
    w0, w1 = window

    def clip(s, e):
        return max(s, w0), min(e, w1)

    by_op, unions, n_events = {}, [], 0
    for dev in ops:
        ivs = []
        for name, start, dur in dev:
            s, e = clip(start, start + dur)
            if e > s:
                ivs.append((s, e))
                label = op_label(name)
                by_op[label] = by_op.get(label, 0.0) + (e - s)
                n_events += 1
        unions.append(_union(ivs))
    busy = [sum(e - s for s, e in u) for u in unions]
    busy_ns = sum(busy) / len(busy) if busy else 0.0

    # idle gaps of the first device, named by the innermost open annotation
    gaps, t = [], w0
    for s, e in (unions[0] if unions else []):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    segs = _flatten([(max(s, w0), min(e, w1), n) for s, e, n in spans
                     if min(e, w1) > max(s, w0)])
    seg_starts = [s for s, _e, _n in segs]
    by_gap = {}
    for g0, g1 in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(seg_starts, g0) - 1, 0)
        while i < len(segs) and segs[i][0] < g1:
            s, e = max(segs[i][0], g0), min(segs[i][1], g1)
            if e > s:
                by_gap[segs[i][2]] = by_gap.get(segs[i][2], 0.0) + (e - s)
                covered += e - s
            i += 1
        if g1 - g0 > covered:
            by_gap[NO_SPAN] = by_gap.get(NO_SPAN, 0.0) + (g1 - g0 - covered)
    span_tot = {}
    for s, e, n in spans:
        s, e = clip(s, e)
        if e > s:
            cell = span_tot.setdefault(n, [0, 0.0])
            cell[0] += 1
            cell[1] += (e - s) * 1e-9

    def top(table):
        return [[k, v * 1e-9] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_ns * 1e-9,
            "n_devices": len(devices), "device_events": n_events,
            "device_ops": top(by_op), "idle_gaps": top(by_gap),
            "spans": span_tot}
