"""The r09 unified observability subsystem: metrics registry, causal span
tracing, device-launch profiler.

Contracts under test:

- registry: labeled counters/gauges/log-bucketed histograms, DETERMINISTIC
  snapshot order, snapshot/diff, the LegacyStats dict-view the sim's
  ``Cluster.stats`` migrated onto (byte-compatible keys);
- spans: phase trees in sim time, canonical byte-stable export, capacity
  bounding, None-safety (every call site guards with one None check);
- devprof: Chrome-trace validity, armed/unarmed behavior, and the
  acceptance artifact — a 16-store fused launch run whose trace shows the
  coalesced launches;
- the ACCORD_TPU_OBS=off escape hatch: emission is safe when disabled and
  a disabled run still completes green (observability is never
  load-bearing — mirrored by the conftest canary on the whole tier-1).

Burn-level double-run byte-identity (metrics snapshot + span export,
incl. crash-restart and device-fault legs) extends the determinism matrix
in tests/test_burn.py.
"""

import json

import pytest

from accord_tpu.obs import Observability, devprof, enabled
from accord_tpu.obs.metrics import (Histogram, LegacyStats, MetricsRegistry,
                                    index_counters)
from accord_tpu.obs.spans import SpanRecorder


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_labels():
    reg = MetricsRegistry()
    reg.counter("q", route="host").inc(3)
    reg.counter("q", route="host").inc(2)
    reg.counter("q", route="dense").inc()
    reg.gauge("cap", store=0).set(64)
    snap = reg.snapshot()
    assert snap["q{route=host}"] == 5
    assert snap["q{route=dense}"] == 1
    assert snap["cap{store=0}"] == 64


def test_snapshot_order_is_sorted_not_insertion():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("x").inc()
    a.counter("a").inc()
    b.counter("a").inc()
    b.counter("x").inc()
    assert list(a.snapshot()) == list(b.snapshot()) == ["a", "x"]
    assert a.snapshot() == b.snapshot()


def test_histogram_log_buckets_and_percentiles():
    h = Histogram()
    for v in (0, 1, 3, 1000, 1000, 1000, 2_000_000):
        h.observe(v)
    assert h.count == 7 and h.vmin == 0 and h.vmax == 2_000_000
    # p50 lands in the 1000s bucket [512, 1023]; clamped to max=1023<=1000s
    assert h.percentile(0.5) in range(512, 1024) or h.percentile(0.5) == 1000
    assert h.percentile(0.99) == 2_000_000       # clamped to exact max
    assert h.percentile(0.01) == 0
    r = h.render()
    assert r["count"] == 7 and r["sum"] == 0 + 1 + 3 + 3 * 1000 + 2_000_000
    # same observations in another order -> identical render (pure ints)
    h2 = Histogram()
    for v in (1000, 2_000_000, 0, 1000, 3, 1, 1000):
        h2.observe(v)
    assert h2.render() == r


def test_diff():
    reg = MetricsRegistry()
    reg.counter("c").inc(5)
    reg.histogram("h", phase="p").observe(100)
    before = reg.snapshot()
    reg.counter("c").inc(2)
    reg.counter("new").inc()
    reg.histogram("h", phase="p").observe(50)
    d = reg.diff(before)
    assert d["c"] == 2 and d["new"] == 1
    assert d["h{phase=p}"] == {"count": 1, "sum": 50}
    assert "untouched" not in d


def test_legacy_stats_dict_compat():
    """The Cluster.stats migration: byte-compatible dict semantics over
    registry counters."""
    reg = MetricsRegistry()
    st = LegacyStats(reg)
    st["PreAccept"] = st.get("PreAccept", 0) + 1
    st["PreAccept"] = st.get("PreAccept", 0) + 1
    st["DepsRoute.host"] = st.get("DepsRoute.host", 0) + 7
    assert dict(st) == {"PreAccept": 2, "DepsRoute.host": 7}
    assert st.get("absent", 0) == 0
    assert "absent" not in st          # reads never create keys
    assert "absent" not in dict(st)
    assert st["PreAccept"] == 2 and len(st) == 2
    # the same cells ride the registry snapshot
    snap = reg.snapshot()
    assert snap["PreAccept"] == 2 and snap["DepsRoute.host"] == 7
    del st["PreAccept"]
    assert "PreAccept" not in st and "PreAccept" not in reg.snapshot()


def test_phase_percentiles_readout():
    reg = MetricsRegistry()
    for v in (1000, 2000, 3000):
        reg.histogram("phase_micros", phase="preaccept").observe(v)
    out = reg.phase_percentiles()
    assert set(out) == {"preaccept"}
    assert out["preaccept"]["n"] == 3
    assert 1000 <= out["preaccept"]["p50"] <= 3000


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------

def _recorder(metrics=None):
    clock = {"t": 0}
    rec = SpanRecorder(lambda: clock["t"], metrics)
    return rec, clock


def test_span_tree_and_export():
    reg = MetricsRegistry()
    rec, clock = _recorder(reg)
    rec.begin_txn("t1", node=1, kind="Write")
    sp = rec.begin("t1", "preaccept", node=1)
    clock["t"] = 100
    rec.end(sp, oks=3)
    rec.decision("t1", "fast")
    rec.event("t1", "deps_route", route="host", store=0)
    clock["t"] = 250
    rec.end_txn("t1", "ok")
    [root] = rec.export()
    assert root["txn"] == "t1" and root["dur"] == 250
    assert root["attrs"]["path"] == "fast"
    [child] = root["children"]
    assert child["name"] == "preaccept" and child["dur"] == 100
    assert child["attrs"]["oks"] == 3
    assert root["events"][0]["name"] == "deps_route"
    # the fast/slow decision fed the KPI metric
    assert rec.fast_path_rate() == 1.0
    assert reg.snapshot()["txn_path{path=fast}"] == 1
    # phase histogram observed the sim-time duration
    assert reg.snapshot()["phase_micros{phase=preaccept}"]["sum"] == 100
    # canonical export is byte-stable across identical replays
    rec2, clock2 = _recorder(MetricsRegistry())
    rec2.begin_txn("t1", node=1, kind="Write")
    sp2 = rec2.begin("t1", "preaccept", node=1)
    clock2["t"] = 100
    rec2.end(sp2, oks=3)
    rec2.decision("t1", "fast")
    rec2.event("t1", "deps_route", route="host", store=0)
    clock2["t"] = 250
    rec2.end_txn("t1", "ok")
    assert rec.export_json() == rec2.export_json()


def test_span_none_safety_and_unknown_keys():
    rec, _clock = _recorder()
    rec.end(None)                     # FSM held no span: no-op
    rec.end_txn("never-began")        # unknown key: no-op
    rec.event("never-began", "deps_route", route="host")   # dropped
    rec.decision("never-began", "fast")                    # root-less: safe
    assert rec.export() == []
    # a phase beginning without a coordinated root (recovery on another
    # node) synthesizes the root rather than erroring
    sp = rec.begin("recovered-txn", "accept", node=3)
    rec.end(sp)
    [root] = rec.export()
    assert root["txn"] == "recovered-txn"
    assert root["children"][0]["name"] == "accept"


def test_span_capacity_bounds():
    rec, _clock = _recorder()
    rec.capacity = 4
    for i in range(10):
        rec.begin(f"t{i}", "preaccept")   # root + child = 2 spans each
    assert rec.n_spans <= 4
    assert rec.dropped > 0
    assert json.loads(rec.export_json())["dropped"] == rec.dropped


def test_open_spans_export_unfinished():
    rec, clock = _recorder()
    rec.begin_txn("t1", node=1)
    rec.begin("t1", "apply", node=1)       # never ends: coordinator died
    clock["t"] = 5
    [root] = rec.export()
    assert root["end"] is None and root["children"][0]["end"] is None
    json.loads(rec.export_json())           # still valid canonical JSON


# ---------------------------------------------------------------------------
# the ACCORD_TPU_OBS knob
# ---------------------------------------------------------------------------

def test_obs_env_knob(monkeypatch):
    monkeypatch.delenv("ACCORD_TPU_OBS", raising=False)
    assert enabled()
    for off in ("off", "0", "false", "no", "OFF"):
        monkeypatch.setenv("ACCORD_TPU_OBS", off)
        assert not enabled()
    monkeypatch.setenv("ACCORD_TPU_OBS", "on")
    assert enabled()


def test_observability_disabled_is_inert(monkeypatch):
    monkeypatch.setenv("ACCORD_TPU_OBS", "off")
    o = Observability(now=lambda: 0)
    assert o.spans is None               # spans stand down...
    o.metrics.counter("still_works").inc()   # ...the registry does not
    assert o.metrics.snapshot()["still_works"] == 1
    # arming the profiler under the escape hatch records nothing
    with devprof.capture() as prof:
        assert devprof.PROFILER is None
        prof2 = devprof.PROFILER
    assert prof.events == [] and prof2 is None


def test_burn_green_with_obs_off(monkeypatch):
    """Observability must never be load-bearing: a disabled-mid-run flip
    (the cluster built with obs off) completes the burn with identical
    protocol stats."""
    from accord_tpu.sim.burn import run_burn
    a = run_burn(3, n_ops=20)
    monkeypatch.setenv("ACCORD_TPU_OBS", "off")
    b = run_burn(3, n_ops=20)
    assert b.ops_unresolved == 0
    assert b.span_export is None and b.fast_path_rate is None
    assert a.stats == b.stats, \
        "disabling observability changed the protocol stream"
    assert a.metrics_snapshot is not None and b.metrics_snapshot is not None
    # the disabled run's snapshot = the enabled one minus span-fed series
    span_fed = ("phase_micros", "txn_path", "txn_domain")
    strip = lambda s: {k: v for k, v in s.items()          # noqa: E731
                       if not k.startswith(span_fed)}
    assert strip(a.metrics_snapshot) == strip(b.metrics_snapshot)


# ---------------------------------------------------------------------------
# device profiler + chrome trace
# ---------------------------------------------------------------------------

def _validate_chrome(doc):
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert ev["name"] and "pid" in ev and "tid" in ev
        assert ev["dur"] >= 0


def test_devprof_capture_and_export(tmp_path):
    if not enabled():
        pytest.skip("ACCORD_TPU_OBS=off canary run")
    with devprof.capture() as prof:
        assert devprof.PROFILER is prof
        with devprof.span("upload", tid=3, args={"bytes": 128}):
            pass
    assert devprof.PROFILER is None      # disarmed on exit
    doc = prof.chrome_trace()
    _validate_chrome(doc)
    assert doc["otherData"]["event_counts"] == {"upload": 1}
    assert doc["traceEvents"][0]["args"] == {"bytes": 128}
    assert doc["traceEvents"][0]["tid"] == 3
    p = prof.write_chrome(str(tmp_path / "t.json"))
    _validate_chrome(json.load(open(p)))


def test_devprof_16store_fused_run_trace(tmp_path, monkeypatch):
    """The r09 acceptance artifact: a 16-store fused launch run emits a
    valid Chrome trace whose fused_flush_dispatch slices carry the member
    counts — the launch-coalescing win as a timeline.  The fused-vs-solo
    pricing is PINNED to fused: it is a wall-clock-calibrated cost model
    that may legitimately flip on a loaded box, and this test exercises
    the profiler, not the model (tests/test_routing covers pricing)."""
    if not enabled():
        pytest.skip("ACCORD_TPU_OBS=off canary run")
    from accord_tpu.local.dispatch import DeviceDispatcher, fusion_enabled
    if not fusion_enabled():
        pytest.skip("ACCORD_TPU_FUSION=off canary run")
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import bench_launch_amortized_harness
    monkeypatch.setattr(DeviceDispatcher, "_fused_flush_pays",
                        lambda self, hints: True)
    with devprof.capture() as prof:
        r = bench_launch_amortized_harness(stores=16, rounds=4,
                                           warm_rounds=2, fusion=True)
    doc = json.loads(json.dumps(prof.chrome_trace()))   # JSON round-trip
    _validate_chrome(doc)
    fused = [e for e in doc["traceEvents"]
             if e["name"] == "fused_flush_dispatch"]
    assert fused, "16-store fused run produced no fused launch slices"
    assert all(e["args"]["members"] == 16 for e in fused)
    # r10 two-stage downloads: the harvest is a header slice plus an
    # entry-prefix slice (the wait split the compacted transfer exposes)
    harvests = [e for e in doc["traceEvents"]
                if e["name"] == "fused_flush_harvest_header"]
    assert harvests, "fused launches were never harvested"
    assert [e for e in doc["traceEvents"]
            if e["name"] == "fused_flush_harvest_entries"], \
        "two-stage harvest emitted no entry-prefix slice"
    assert r["launches"] < r["nq"] / 16, "launches were not coalesced"
    path = str(tmp_path / "fused16.json")
    prof.write_chrome(path)
    _validate_chrome(json.load(open(path)))


def test_devprof_unarmed_records_nothing():
    assert devprof.PROFILER is None
    # the span path: a DeviceState flush with no profiler armed
    # must not create events anywhere (PROFILER stays None)
    from accord_tpu.primitives.deps import DepsBuilder
    from tests.test_routing import _build
    store, dev, safe, entries, floor, qs = _build(3)
    dev.deps_query_batch_attributed(safe, qs[:8],
                                    [DepsBuilder() for _ in qs[:8]])
    assert devprof.PROFILER is None


# ---------------------------------------------------------------------------
# sim integration: registry-backed Cluster.stats + index_counters parity
# ---------------------------------------------------------------------------

def test_cluster_stats_are_registry_backed():
    from accord_tpu.sim.burn import run_burn
    r = run_burn(1, n_ops=15)
    assert r.ops_unresolved == 0
    snap = r.metrics_snapshot
    # every legacy stats key rides the registry snapshot with its value
    for k in ("PreAccept", "Commit", "Apply"):
        assert snap.get(k) == r.stats.get(k), k
    # the structured labeled families exist alongside
    assert any(k.startswith("deps_route_queries{") for k in snap), \
        list(snap)[:20]
    # per-store device gauges were collected
    assert any(k.startswith("device_dispatches{") for k in snap)


def test_index_counters_match_attributes():
    from tests.test_routing import _build
    from accord_tpu.primitives.deps import DepsBuilder
    store, dev, safe, entries, floor, qs = _build(7)
    dev.deps_query_batch_attributed(safe, qs[:8],
                                    [DepsBuilder() for _ in qs[:8]])
    idx = index_counters(dev)
    # exact legacy key set, in the # index: line order
    assert list(idx)[:6] == ["host_queries", "bucketed_queries",
                             "dense_queries", "mesh_queries",
                             "mesh_bucketed_queries", "dispatches"]
    assert idx["dispatches"] == dev.n_dispatches
    assert idx["host_queries"] == dev.n_host_queries
    assert idx["oom_degraded"] == int(dev.host_pinned)
    assert sum(idx[k] for k in ("host_queries", "bucketed_queries",
                                "dense_queries", "mesh_queries")) >= 8


def test_maelstrom_rows_carry_phase_latencies():
    from accord_tpu.maelstrom.runner import MaelstromRunner
    r = MaelstromRunner(3, seed=0, shards=8, device_mode=False)
    res = r.run_workload(n_ops=40, n_keys=20, keys_per_txn=1,
                         spread_ring=True)
    fields = res.obs_row_fields()
    if not enabled():
        assert fields == {}
        return
    assert 0 <= fields["fast_path_rate"] <= 1
    phases = fields["phases_ms"]
    assert {"preaccept", "stable", "apply", "txn"} <= set(phases)
    for row in phases.values():
        assert row["p50_ms"] <= row["p99_ms"]
        assert row["n"] > 0
