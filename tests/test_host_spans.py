"""The host-span primitive (obs.devprof.span) and what is sited on it: the
``kernel_times`` kinds, the journal's clocks, the serving loop's timers, the
``recover`` phase span; and the serving node's span recorder, which retires
finished trees."""

import threading
import time

import pytest

from accord_tpu.obs import Observability, devprof, enabled
from accord_tpu.obs.spans import SpanRecorder


@pytest.fixture
def obs_off(monkeypatch):
    """ACCORD_TPU_OBS=off for one test: the environment for what asks
    ``enabled()``, and what a span follows (read once at import) beside it."""
    monkeypatch.setenv("ACCORD_TPU_OBS", "off")
    monkeypatch.setattr(devprof, "_ON", False)
    assert not enabled()


def _needs_obs():
    if not enabled():
        pytest.skip("ACCORD_TPU_OBS=off canary run")


# -- the primitive ----------------------------------------------------------

def test_spans_nest_and_sum_table_equals_chrome_slices():
    """One perf_counter pair a span: what the table sums, the Chrome
    slices hold and the durations say is the same number, and a child lies
    inside its parent."""
    _needs_obs()
    table = {}
    with devprof.capture() as prof:
        for _ in range(3):
            with devprof.span("srv.outer", table, pid=2, tid=1):
                with devprof.span("srv.inner", table, args={"n": 1}):
                    time.sleep(0.002)
                with devprof.span("srv.inner", table):
                    pass
    assert {k: c for k, (c, _s) in table.items()} == \
        {"srv.outer": 3, "srv.inner": 6}
    by_name = {}
    for ev in prof.events:
        by_name.setdefault(ev["name"], []).append(ev)
    for name, (calls, secs) in table.items():
        assert len(by_name[name]) == calls
        assert sum(ev["dur"] for ev in by_name[name]) * 1e-6 \
            == pytest.approx(secs, abs=1e-8 * calls)
    assert table["srv.inner"][1] >= 3 * 0.002
    assert table["srv.outer"][1] >= table["srv.inner"][1]
    outers = sorted(by_name["srv.outer"], key=lambda ev: ev["ts"])
    inners = sorted(by_name["srv.inner"], key=lambda ev: ev["ts"])
    for i, outer in enumerate(outers):
        assert (outer["pid"], outer["tid"]) == (2, 1)
        for inner in inners[2 * i:2 * i + 2]:
            assert outer["ts"] <= inner["ts"]
            assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] \
                + 1e-3
    assert inners[0]["args"] == {"n": 1} and "args" not in inners[1]


def test_a_span_without_a_table_feeds_the_profiler_alone():
    _needs_obs()
    with devprof.capture() as prof:
        with devprof.span("fused_tick_harvest", args={"members": 4}):
            pass
    assert [ev["name"] for ev in prof.events] == ["fused_tick_harvest"]
    with devprof.span("nobody_listens"):      # unarmed, no table: inert
        pass
    assert devprof.PROFILER is None


def test_a_span_nobody_listens_to_takes_no_clock():
    """No table, no armed profiler, no open profiler session: the span is
    entered and left without a ``perf_counter`` read (a sim's or a burn's
    ``srv.*`` sites); any listener brings the clock back."""
    _needs_obs()
    with devprof.span("srv.req.PreAccept") as sp:
        assert sp._t0 is None and sp._ann is None
    with devprof.span("srv.req.PreAccept", {}) as sp:
        assert sp._t0 is not None
    with devprof.capture():
        with devprof.span("srv.req.PreAccept") as sp:
            assert sp._t0 is not None


def test_asking_whether_obs_is_enabled_changes_nothing(monkeypatch):
    """``enabled()`` reads the environment; what a span follows is the
    knob the process started with, whoever asks later."""
    before = devprof._ON
    monkeypatch.setenv("ACCORD_TPU_OBS", "off")
    assert not enabled() and devprof._ON is before
    monkeypatch.delenv("ACCORD_TPU_OBS")
    assert enabled() and devprof._ON is before


def test_a_span_that_raises_is_still_closed_and_counted():
    _needs_obs()
    table = {}
    with pytest.raises(KeyError):
        with devprof.span("srv.raises", table):
            raise KeyError("x")
    assert table["srv.raises"][0] == 1


def test_obs_off_silences_a_span_but_not_its_owners_counter(obs_off):
    """No annotation, no Chrome slice, and without a table no clock; a
    table somebody keeps (DeviceState.kernel_times, which tests and the
    benchmark read) still counts, as it did before it was a span's."""
    table = {}
    with devprof.capture() as prof:
        assert devprof.PROFILER is None
        with devprof.span("srv.silent") as sp:
            assert sp._t0 is None
        with devprof.span("dispatch_host", table) as sp:
            assert sp._ann is None
    assert prof.events == []
    assert table["dispatch_host"][0] == 1


def test_a_serving_node_keeps_no_loop_table_under_obs_off(obs_off):
    from accord_tpu.net.server import NodeServer
    node = NodeServer("n1", "127.0.0.1", 1, {})
    assert node.loop_times is None and node.loop_members is None
    loop = node.stats()["loop"]
    assert loop == {"members": {}, "timer_fires": 0, "timer_lag_s": 0.0,
                    "timer_lag_max_s": 0.0}


def test_merge_folds_a_worker_threads_table_in():
    _needs_obs()
    mine, theirs = {"wait_header_x": [1, 0.5]}, {}

    def work():
        with devprof.span("wait_header_x", theirs):
            pass
        with devprof.span("wait_entries_x", theirs):
            pass

    t = threading.Thread(target=work)
    t.start()
    t.join()
    devprof.merge(mine, theirs)
    assert mine["wait_header_x"][0] == 2 and mine["wait_entries_x"][0] == 1
    assert mine["wait_header_x"][1] >= 0.5


def test_every_exported_span_name_carries_an_exported_prefix():
    """What benchmarks/lib/program_spans.py tells the program's events by."""
    for name in ("srv.decode", "srv.req.PreAccept", "srv.journal.sync",
                 "dispatch_attr_bucketed", "wait_header_attr_dense",
                 "host_attr_finalize", "sync_tables", "drain_tick_host",
                 "range_index_sync", "fused_flush_dispatch", "register",
                 "snapshot_cols", "pack_queries", "choose_route"):
        assert name.startswith(devprof.SPAN_PREFIXES), name
    assert not "client.submit".startswith(devprof.SPAN_PREFIXES)
    assert not "store.register".startswith(devprof.SPAN_PREFIXES)


# -- the store kinds ---------------------------------------------------------

# kind -> calls of ONE flush of eight queries over tests.test_routing._build's
# store, as the parent of the PR that brought the spans counted them
# (its ``_ktime`` clocks), by route and by who downloads; less ``host_decode``
# (once a device-route flush), which nothing read (PERF.md §3's audit)
_BEFORE = {
    ("host", True): {"dispatch_host": 1, "host_attr_filter": 1,
                     "host_attr_finalize": 1, "host_attr_index": 1},
    ("device", True): {
        "dispatch_attr_sharded": 1, "dispatch_attr_sharded_bucketed": 1,
        "host_attr_finalize": 1, "host_attr_index": 1,
        "wait_entries_attr_sharded": 1,
        "wait_entries_attr_sharded_bucketed": 1,
        "wait_header_attr_sharded": 1,
        "wait_header_attr_sharded_bucketed": 1},
}
_BEFORE["device", False] = _BEFORE["device", True]
_BEFORE[None, True] = _BEFORE["host", True]
# the four kinds new with the spans
_NEW = {
    ("host", True): {"pack_queries": 1},
    ("device", True): {"pack_queries": 2},
    ("device", False): {"pack_queries": 2, "snapshot_cols": 1},
    (None, True): {"pack_queries": 1, "choose_route": 1},
}


@pytest.mark.parametrize("route,immediate", sorted(
    _BEFORE, key=lambda k: (str(k[0]), k[1])))
def test_a_flush_times_the_kinds_it_timed_before_plus_the_new_ones(
        route, immediate):
    from accord_tpu.primitives.deps import DepsBuilder
    from tests.test_routing import _build
    _store, dev, safe, _entries, _floor, qs = _build(3)
    built = {k: c for k, (c, _s) in dev.kernel_times.items()}
    # registration: a call a txn, the range txns' index beneath it
    assert built["register"] == 220 and 0 < built["range_index_sync"] < 220
    assert set(built) == {"register", "range_index_sync"}
    dev.route_override = route
    handle = dev.deps_query_batch_begin(qs[:8], immediate=immediate)
    dev.deps_query_batch_end_attributed(
        safe, handle, [DepsBuilder() for _ in qs[:8]])
    flush = {k: c - built.get(k, 0) for k, (c, _s)
             in dev.kernel_times.items() if c - built.get(k, 0)}
    assert flush == {**_BEFORE[route, immediate], **_NEW[route, immediate]}
    assert all(secs >= 0.0 for _c, secs in dev.kernel_times.values())
    # the worker's waits are folded in at the join: its own table is gone
    # from nobody's sight, and inside the flush's wall time
    if not immediate:
        assert handle[0][0]["box"]["times"].keys() <= dev.kernel_times.keys()


# -- the journal -------------------------------------------------------------

def test_journal_appends_are_counted_and_a_sync_is_a_span(tmp_path):
    """The owner's table holds both; the Chrome trace (as the profiler's)
    the sync alone: an append is a bare clock pair, 27 a txn being too many
    spans (journal/wal.py)."""
    _needs_obs()
    from accord_tpu.journal.commit import GroupCommit
    from accord_tpu.journal.wal import WriteAheadLog
    wal = WriteAheadLog(str(tmp_path / "wal"))
    commit = GroupCommit(wal, defer=lambda _delay, _fn: None,
                         window_micros=10_000_000)
    times = wal.times = commit.times = {}
    released = []
    with devprof.capture() as prof:
        for i in range(5):
            commit.append({"k": "probe", "i": i})
        commit.after_durable(lambda: released.append(dict(times)))
        commit.flush()
    assert times["srv.journal.append"][0] == 5
    assert times["srv.journal.append"][1] > 0.0
    assert times["srv.journal.sync"][0] == 1
    assert [ev["name"] for ev in prof.events] == ["srv.journal.sync"]
    # the waiters run after the span: the one released here saw the sync
    # already counted
    assert released and released[0]["srv.journal.sync"][0] == 1
    wal.close()


def test_an_offloaded_fsync_is_a_span_on_the_workers_thread(tmp_path):
    _needs_obs()
    from accord_tpu.journal.commit import GroupCommit
    from accord_tpu.journal.wal import WriteAheadLog
    wal = WriteAheadLog(str(tmp_path / "wal"))
    ran = []

    def async_exec(work, done):
        t = threading.Thread(target=lambda: (
            ran.append(threading.get_ident()), work()))
        t.start()
        t.join()
        done(None)

    commit = GroupCommit(wal, defer=lambda _delay, _fn: None,
                         window_micros=10_000_000, async_exec=async_exec)
    commit._offload_pays = True
    times = wal.times = commit.times = {}
    with devprof.capture() as prof:
        commit.append({"k": "probe"})
        commit.flush()
    assert ran and ran[0] != threading.get_ident()
    # the cycle on the owner's thread and the fsync on the worker's
    assert times["srv.journal.sync"][0] == 2
    assert [ev["name"] for ev in prof.events].count("srv.journal.sync") == 2
    assert wal.durable_seq == wal.tail_seq
    wal.close()


# -- the serving loop's timers -------------------------------------------------

def test_a_fired_timer_is_a_span_and_its_lag_is_counted():
    _needs_obs()
    import asyncio
    from accord_tpu.net.server import AsyncioScheduler

    async def drive():
        loop = asyncio.get_running_loop()
        times = {}
        sched = AsyncioScheduler(loop, times)
        fired = []
        sched.once(1_000, lambda: fired.append("once"))
        rec = sched.recurring(2_000, lambda: fired.append("tick"))
        gone = sched.once(1_000, lambda: fired.append("never"))
        gone.cancel()
        time.sleep(0.02)            # a slow callback: the loop is late
        await asyncio.sleep(0.03)
        rec.cancel()
        return sched, times, fired

    sched, times, fired = asyncio.run(drive())
    assert "once" in fired and fired.count("tick") >= 2
    assert "never" not in fired
    assert times["srv.timer"][0] == sched.n_fires == len(fired)
    assert sched.lag_s >= sched.lag_max_s >= 0.015


# -- the recover phase span ------------------------------------------------

def test_a_forced_recovery_is_a_closed_recover_span_with_its_cause():
    """The coordinator's Commit is dropped after the fast-path decision;
    the home shard's progress log finds the txn without progress and
    recovers it: a ``recover`` PHASE span on the txn's tree, closed, with
    who asked and how long the txn had been idle, and the attempt counted
    by cause."""
    _needs_obs()
    from accord_tpu.messages.commit import Commit
    from accord_tpu.sim.kvstore import kv_txn
    from tests.test_e2e_basic import make_cluster, submit
    cluster = make_cluster(seed=11)
    cluster.message_filter = lambda src, dst, req: \
        isinstance(req, Commit) and src == 1
    out = submit(cluster, 1, kv_txn([10], {10: ("orphan",)}))
    cluster.run_until_quiescent()
    cluster.message_filter = None
    cluster.run_until_quiescent()
    spans = cluster.obs.spans
    recovers = [ch for root in spans.export()
                for ch in root.get("children", ()) if ch["name"] == "recover"]
    assert recovers, "nothing recovered the orphan"
    for sp in recovers:
        assert sp["end"] is not None and sp["dur"] >= 0
        assert sp["attrs"]["cause"].startswith(("home.", "watchdog"))
        assert sp["attrs"]["outcome"]
    asked = [sp for sp in recovers if sp["attrs"]["cause"].startswith("home.")]
    assert asked and all(sp["attrs"]["idle_micros"] > 0 for sp in asked)
    hist = cluster.obs.metrics.histogram("phase_micros", phase="recover")
    assert hist.count == len(recovers)
    by_cause = cluster.obs.metrics.counter_totals("recoveries", by="cause")
    assert sum(n for cause, n in by_cause.items() if cause) \
        == cluster.obs.metrics.counter_totals(
            "recoveries", by="event")["attempt"]
    assert out is not None


# -- the serving node's recorder ----------------------------------------------

def test_a_serving_recorder_retires_finished_trees_and_keeps_feeding():
    """3 x capacity txns through a recorder with a ring: nothing is
    refused, the histogram keeps counting, the resident trees stay bounded,
    ``retired`` says what left, and an open tree is never retired."""
    obs = Observability(now=lambda: 0, spans_on=True, retire_roots=16)
    clock = [0]
    rec = SpanRecorder(lambda: clock[0], obs.metrics, capacity=300,
                       retire_roots=16)
    rec.begin_txn("stuck", node=1)
    stuck = rec.begin("stuck", "preaccept", node=1)
    n = 3 * rec.capacity
    for i in range(n):
        key = f"t{i}"
        rec.begin_txn(key, node=1)
        sp = rec.begin(key, "preaccept", node=1)
        clock[0] += 5
        rec.end(sp, oks=3)
        rec.event(key, "deps_route", route="host")
        late = rec.begin(key, "apply", node=1)
        rec.end_txn(key)
        assert key in rec.roots      # its apply phase is still open
        rec.end(late)
        assert key not in rec.roots
        # a replica-side span of a txn coordinated elsewhere: a synthetic
        # root nobody ends leaves with its last span
        wait = rec.begin(f"remote{i}", "deps_wait", node=2)
        rec.end(wait)
    assert rec.dropped == 0
    hist = obs.metrics.histogram("phase_micros", phase="preaccept")
    assert hist.count == n
    assert obs.metrics.histogram("phase_micros", phase="txn").count == n
    assert list(rec.roots) == ["stuck"] and stuck.end is None
    assert len(rec.finished) == 16
    assert rec.retired == 2 * n - 16
    assert len(rec) <= 2 + 16 * 3 and rec.n_events <= 16
    exported = rec.export()
    assert [r["txn"] for r in exported][-1] == "stuck"
    assert len(exported) == 17
    # a finished tree is no cycle: it leaves the ring by its last reference,
    # with no full collection to wait for
    import gc
    for root in rec.finished:
        assert root.children and not any(
            ref is root for ch in root.children
            for ref in gc.get_referents(ch))


def test_a_runs_recorder_keeps_every_tree_and_refuses_at_capacity():
    """The sim's recorder (no ring): unchanged."""
    rec = SpanRecorder(lambda: 0, None, capacity=10)
    for i in range(8):
        rec.begin_txn(f"t{i}")
        rec.end(rec.begin(f"t{i}", "preaccept"))
        rec.end_txn(f"t{i}")
    assert rec.retired == 0 and not rec.finished
    assert len(rec.roots) == 5 and rec.dropped == 6 and len(rec) == 10
    assert [r["txn"] for r in rec.export()] == [f"t{i}" for i in range(5)]


# -- a served cluster's stats()["loop"] -----------------------------------------

# ``srv.journal.append`` is a counter of the table and no span; ISSUE 35's
# ``srv.encode`` is inside ``srv.flush_tick`` (PERF.md §3's table)
SERVED_SPANS = ("srv.decode", "srv.txn", "srv.store_setup", "srv.handler",
                "srv.deps_plan", "srv.deps_flush", "srv.journal.append",
                "srv.journal.sync", "srv.flush_tick", "srv.client_reply",
                "srv.timer")


async def _serve_three(journal_root, txns=40):
    import asyncio
    from accord_tpu.net.client import ClusterClient
    from accord_tpu.net.harness import free_ports
    from accord_tpu.net.server import NodeServer
    names = ["n1", "n2", "n3"]
    addrs = {n: ("127.0.0.1", p) for n, p in zip(names, free_ports(3))}
    servers = [NodeServer(n, *addrs[n], dict(addrs), device_mode=True,
                          durability=False,
                          journal_dir=str(journal_root / n),
                          journal_sync="client", wire_codec_name="binary")
               for n in names]
    client = ClusterClient([(n, *addrs[n]) for n in names], timeout=60.0,
                           codec="binary")
    try:
        for s in servers:
            await s.start()
        await client.connect()
        for n in names:
            await client.ping(n, timeout=60.0)

        async def one(i):
            for j in range(txns // 4):
                key = (i * 7 + j) % 5 * (1 << 28)
                op = ["append", key, i * 1000 + j] if j % 2 else \
                    ["r", key, None]
                await client.submit_retry([op], node=names[(i + j) % 3])
        await asyncio.gather(*[one(i) for i in range(4)])
        await asyncio.sleep(0.6)          # the sweeper and a scan fire
        stats = [s.stats() for s in servers]
        for st, s in zip(stats, servers):     # batches the stores drained
            st["_batches"] = sum(
                n for store in s.proc.node.command_stores.stores
                for n in store.group_sizes.values())
        answered = client.n_ok
        recorders = [s.proc.obs.spans for s in servers]
    finally:
        await client.close()
        for s in servers:
            for link in s.links.values():
                await link.close()
        for s in servers:
            if s.frame_server is not None:
                await asyncio.wait_for(s.close(), 30.0)
    return stats, answered, recorders


def test_a_served_clusters_loop_stats_name_what_the_loop_does(tmp_path):
    """Three NodeServers in this process, device on, journal on: every span
    of PERF.md's table is in ``stats()["loop"]`` with calls > 0, a request
    family and a reply family beside them, the members of the grouped runs,
    and the timers' lag; the recorder of a serving node has a ring."""
    import asyncio
    import gc
    threshold = gc.get_threshold()
    try:
        stats, answered, recorders = asyncio.run(_serve_three(tmp_path))
    finally:
        gc.unfreeze()            # NodeServer.start() retunes the collector
        gc.set_threshold(*threshold)
    assert answered == 40
    if not enabled():            # the canary: nothing is named
        assert all(st["loop"]["members"] == {} and not any(
            k.startswith("srv.") for k in st["loop"]) for st in stats)
        return
    for st in stats:
        loop = st["loop"]
        for name in SERVED_SPANS:
            calls, secs = loop[name]
            assert calls > 0 and secs >= 0.0, (st["name"], name)
        reqs = {k: v for k, v in loop.items() if k.startswith("srv.req.")}
        rsps = {k: v for k, v in loop.items() if k.startswith("srv.rsp.")}
        assert "srv.req.PreAccept" in reqs and "srv.rsp.PreAcceptOk" in rsps
        # a span a delivered run of one verb, its members beside it
        assert set(loop["members"]) == set(reqs)
        assert all(loop["members"][k] >= reqs[k][0] for k in reqs)
        assert loop["timer_fires"] == loop["srv.timer"][0] > 0
        assert loop["timer_lag_s"] >= loop["timer_lag_max_s"] >= 0.0
        # inclusive times: a store's drain holds its batches' handlers,
        # one span a batch and not an op
        assert loop["srv.store_setup"][1] >= loop["srv.handler"][1] * 0.5
        assert loop["srv.handler"][0] == st["_batches"]
        assert "srv.encode" not in loop
    # the reply spans are the replies: txn_ok, init_ok and the pings' pongs
    assert sum(st["loop"]["srv.client_reply"][0] for st in stats) \
        == sum(st["client_replies"] for st in stats) >= answered
    assert sum(st["loop"]["srv.txn"][0] for st in stats) >= answered
    for rec in recorders:
        assert rec.retire_roots and rec.dropped == 0
        assert len(rec.finished) <= rec.retire_roots
