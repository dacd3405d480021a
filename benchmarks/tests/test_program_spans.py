"""lib/program_spans.py on a two-thread trace, against self times worked by
hand (as test_trace_reduce.py does for the device side)."""

import importlib.util
import os

import pytest

from benchmarks.lib import program_spans, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
NAMES = ("srv.", "dispatch_", "wait_", "host_")


def _reduced():
    return program_spans.reduce(trace_reduce.load(
        os.path.join(DATA, "trace_program_spans.json")), NAMES)


def _metric(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_two_thread_trace_by_hand():
    """Window [1000, 3000) ns.  The loop's line: srv.decode [1000,1100) =
    100 self; srv.store_setup [1200,1800) holds srv.handler [1250,1700),
    which holds srv.deps_flush [1300,1500) (dispatch_host 80 and
    host_attr_finalize 60 inside it) and an inline srv.journal.sync
    [1550,1600): self 150 / 200 / 60 / 50; srv.flush_tick [1850,2000): self
    150; srv.rsp.PreAcceptOk [2100,2400) holds srv.client_reply 50:
    self 250; a second srv.client_reply [2950,3100) is cut at the window:
    50.  srv.timer [700,990) lies before the window; client.submit overlaps
    itself and XLA's PjitFunction is not the program's: neither is read.
    Under srv.*: 100 + 600 + 150 + 300 + 50 = 1200 of 2000 ns.  The worker's
    line: srv.journal.sync 300, wait_header_attr_bucketed 200 (no srv.*
    there but the sync)."""
    red = _reduced()
    assert red["window_s"] == pytest.approx(2000e-9)
    # the profiler names every thread "python3": a line is told by its place
    assert red["loop"] == "1 python3"
    assert set(red["threads"]) == {"1 python3", "2 python3"}
    loop = red["threads"]["1 python3"]
    assert {k: round(v * 1e9) for k, v in loop["self_s"].items()} == {
        "srv.decode": 100, "srv.store_setup": 150, "srv.handler": 200,
        "srv.deps_flush": 60, "dispatch_host": 80, "host_attr_finalize": 60,
        "srv.journal.sync": 50, "srv.flush_tick": 150,
        "srv.rsp.PreAcceptOk": 250, "srv.client_reply": 100}
    assert loop["count"]["srv.client_reply"] == 2
    assert "srv.timer" not in loop["count"]
    assert loop["server_s"] == pytest.approx(1200e-9)
    worker = red["threads"]["2 python3"]
    assert {k: round(v * 1e9) for k, v in worker["self_s"].items()} == {
        "srv.journal.sync": 300, "wait_header_attr_bucketed": 200}
    assert worker["server_s"] == pytest.approx(300e-9)
    assert program_spans.replies(red) == 2
    # the journal's time on the loop, and its workers' beside it
    assert program_spans.self_seconds(
        red, lambda n: n.startswith("srv.journal.")) == pytest.approx(50e-9)
    assert program_spans.self_seconds(
        red, lambda n: n.startswith("srv.journal."),
        off_loop=True) == pytest.approx(300e-9)


def test_the_span_metric_files_read_it(monkeypatch):
    red = _reduced()
    monkeypatch.setattr(program_spans, "spans", lambda: red)
    record = {"driver": "served"}
    per_reply = {"wire_decode_per_txn.serve": 100,
                 "wire_encode_per_txn.serve": 150 + 100,
                 "protocol_handlers_per_txn.serve": 150 + 200 + 250,
                 "journal_time_per_txn.serve": 50,
                 "journal_fsync_wall_per_txn.serve": 300}
    for name, ns in per_reply.items():
        assert _metric(name).read(record) == pytest.approx(ns * 1e-6 / 2), \
            name
        assert _metric(name).read({"driver": "store"}) is None
    assert _metric("loop_named_share.serve").read(record) \
        == pytest.approx(60.0)


def test_a_program_without_spans_reads_as_nothing(monkeypatch):
    """The parent of the PR that brought the spans: no SPAN_PREFIXES, so no
    reader looks for a trace, and every span metric is left out."""
    monkeypatch.setattr(program_spans, "prefixes", lambda: None)
    assert program_spans.spans() is None
    for name in ("wire_decode_per_txn.serve", "wire_encode_per_txn.serve",
                 "protocol_handlers_per_txn.serve",
                 "journal_time_per_txn.serve",
                 "journal_fsync_wall_per_txn.serve",
                 "loop_named_share.serve"):
        assert _metric(name).read({"driver": "served"}) is None
    # a trace in which nothing of the program's was recorded reduces to None
    trace = trace_reduce.load(os.path.join(DATA, "trace_synthetic.json"))
    assert program_spans.reduce(trace, NAMES) is None


def test_the_counter_metric_files():
    kinds = {"host_attr_index": [10, 0.1], "dispatch_host": [10, 0.4],
             "host_attr_filter": [10, 0.2], "host_attr_finalize": [10, 0.3],
             "register": [640, 0.5], "sync_tables": [10, 9.0],
             "snapshot_cols": [10, 0.6], "pack_queries": [20, 0.7],
             "choose_route": [10, 0.8]}
    served = {"driver": "served", "acked": 100,
              "counters": {"kernel_times": kinds}}
    store = {"driver": "store", "flushes": 10,
             "counters": {"kernel_times": kinds}}
    assert _metric("host_deps_route_per_txn.serve").read(served) \
        == pytest.approx(10.0)
    assert _metric("host_deps_route_per_txn.serve").read(store) is None
    assert _metric("host_finalize_per_flush.store").read(store) \
        == pytest.approx(30.0)
    assert _metric("host_register_per_flush.store").read(store) \
        == pytest.approx(50.0)
    new = {"register": 50.0, "snapshot_cols": 60.0, "pack_queries": 70.0,
           "choose_route": 80.0}
    for kind, ms in new.items():
        assert _metric(f"host_{kind}_per_flush.store").read(store) \
            == pytest.approx(ms)
        assert _metric(f"host_{kind}_per_flush.store").read(served) is None
    for kind in new:             # the parent's program has no such kinds
        del kinds[kind]
        assert _metric(f"host_{kind}_per_flush.store").read(store) is None
    assert _metric("host_finalize_per_flush.store").read(served) is None
