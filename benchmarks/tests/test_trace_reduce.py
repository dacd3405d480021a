"""lib/trace_reduce.py on recorded traces, against numbers worked by hand."""

import os

import pytest

from benchmarks.lib import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = ("flush.begin", "flush.collect", "deps.build", "store.register")


def test_synthetic_trace_by_hand():
    """Window [1000, 2000) ns.  XLA Ops: [1100,1150) u [1120,1170) u
    [1300,1400) = 170 ns busy (copy.3 lies before the window; the module's
    400 ns and the step are not operations).  Gaps: [1000,1100) = 50 no span
    + 50 flush.begin; [1170,1300) = 80 flush.begin + 50 flush.collect;
    [1400,2000) = 50 flush.collect + 550 no span."""
    red = trace_reduce.reduce(
        trace_reduce.load(os.path.join(DATA, "trace_synthetic.json")), SPANS)
    assert red["n_devices"] == 1 and red["device_events"] == 3
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(170e-9)
    assert dict(red["device_ops"]) == pytest.approx(
        {"fusion.1": 150e-9, "sort.2": 50e-9})
    assert dict(red["idle_gaps"]) == pytest.approx(
        {trace_reduce.NO_SPAN: 600e-9, "flush.begin": 130e-9,
         "flush.collect": 100e-9})
    assert red["busy_s"] + sum(s for _n, s in red["idle_gaps"]) \
        == pytest.approx(red["window_s"])


def test_recorded_chip_trace_against_its_module_line():
    """140 ms (two flushes) cut from the traced slice of a
    store-100k.scan-b64 run on a TPU v5e (PR 23).  Worked by hand from the
    file: the union of its 1,169 clipped ``XLA Ops`` events is 14,378,566 ns
    (their plain sum, 15.2 ms, counts overlaps twice); the ten ``XLA
    Modules`` spans — the runtime's own record of when a program held the
    core — sum to 14,388,968 ns, 0.07 % more."""
    trace = trace_reduce.load(os.path.join(DATA, "trace_b64_chip.json.gz"))
    red = trace_reduce.reduce(trace, SPANS)
    assert red["n_devices"] == 1 and red["device_events"] == 1169
    assert red["window_s"] == pytest.approx(0.140)
    assert red["busy_s"] == pytest.approx(14_378_566e-9)
    modules = next(ln for p in trace["planes"] if p["name"] == "/device:TPU:0"
                   for ln in p["lines"] if ln["name"] == "XLA Modules")
    held = sum(min(s + d, 140e6) - max(s, 0.0)
               for _n, s, d in modules["events"])
    assert red["busy_s"] == pytest.approx(held * 1e-9, rel=2e-3)
    assert red["spans"]["flush.begin"][0] == 2
    # the host sat in deps_query_batch_begin for most of the idle time
    assert red["idle_gaps"][0][0] == "flush.begin"
    assert red["idle_gaps"][0][1] == pytest.approx(0.104793961)
    assert red["busy_s"] + sum(s for _n, s in red["idle_gaps"]) \
        == pytest.approx(red["window_s"])
    # HLO instruction texts are shortened to result, opcode, target, type
    assert ["%custom-call.19 custom-call X64Combine s64[131072,8]",
            pytest.approx(946_879e-9)] in red["device_ops"]
    assert all(len(name) <= 120 for name, _s in red["device_ops"])


def test_no_device_event_is_idle_not_an_error():
    trace = trace_reduce.load(os.path.join(DATA, "trace_synthetic.json"))
    trace["planes"] = [p for p in trace["planes"]
                       if not p["name"].startswith("/device:")]
    red = trace_reduce.reduce(trace, SPANS)
    assert red["busy_s"] == 0.0 and red["window_s"] > 0
    assert red["device_ops"] == []
    record = {"driver": "store", "trace": red}
    import importlib.util
    path = os.path.join(os.path.dirname(DATA), "..", "metrics",
                        "device_idle_share.store.py")
    spec = importlib.util.spec_from_file_location("idle", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read(record) == 100.0


def test_overlapping_unnested_spans_do_not_break_the_flattening():
    # closed-loop clients' spans overlap without nesting
    segs = trace_reduce._flatten([(0, 10, "a"), (5, 15, "b"), (12, 20, "c")])
    assert [s[:2] for s in segs] == [(0, 5), (5, 12), (12, 20)]
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
