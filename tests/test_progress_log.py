"""Liveness: the progress log recovers stalled txns and fetches missed state
without any manual intervention.

Modelled on ref: impl/SimpleProgressLog.java behavior under the burn test's
message-loss scenarios.
"""

import pytest

from accord_tpu import api
from accord_tpu.impl.progress_log import (
    _HOME_PATIENCE_CAP_MICROS, SimpleProgressLog, _Progress)
from accord_tpu.local.node import Node
from accord_tpu.messages.apply import Apply
from accord_tpu.messages.commit import Commit
from accord_tpu.sim.kvstore import kv_txn

from tests.test_e2e_basic import make_cluster, submit


def test_progress_log_recovers_dead_coordinator():
    """Coordinator's Stable round is lost and it never retries: home-shard
    replicas must notice and recover the txn to completion on their own."""
    cluster = make_cluster(seed=41)
    cluster.message_filter = (lambda s, d, r: isinstance(r, Commit) and s == 1)
    out = []
    cluster.nodes[1].coordinate(kv_txn([10], {10: ("auto",)})).begin(
        lambda r, f: out.append((r, f)))
    # run past the coordinator timeout with the filter still up, then heal
    cluster.run_for(2_000_000)
    assert out and out[0][1] is not None, "original coordinate should time out"
    cluster.message_filter = None

    # no manual recovery: the progress log must finish the txn
    cluster.run_until_quiescent()
    assert cluster.failures == []
    read = submit(cluster, 2, kv_txn([10], {}))
    cluster.run_until_quiescent()
    assert read[0][1] is None
    assert read[0][0].reads == {10: ("auto",)}, \
        "progress log failed to recover the orphaned txn"


def test_progress_log_unblocks_missed_apply():
    """A replica that missed Commit+Apply of T1 must fetch T1's outcome when
    a later txn blocks on it, instead of stalling forever."""
    cluster = make_cluster(seed=43)
    # node 3 misses everything post-PreAccept for T1
    cluster.message_filter = (lambda s, d, r:
                              isinstance(r, (Commit, Apply)) and d == 3)
    out1 = submit(cluster, 1, kv_txn([10], {10: ("t1",)}))
    cluster.run_until_quiescent()
    assert out1[0][1] is None, f"T1 should commit without node 3: {out1}"
    cluster.message_filter = None

    # T2 at node 3 depends on T1, which node 3 never saw commit
    out2 = submit(cluster, 3, kv_txn([10], {10: ("t2",)}))
    cluster.run_until_quiescent()
    assert cluster.failures == []
    assert out2 and out2[0][1] is None, f"T2 stalled: {out2}"
    assert out2[0][0].reads == {10: ("t1",)}

    read = submit(cluster, 2, kv_txn([10], {}))
    cluster.run_until_quiescent()
    assert read[0][0].reads == {10: ("t1", "t2")}


def test_progress_log_quiesces_after_durable():
    """After a healthy txn persists, no progress entries linger and the sim
    reaches true quiescence (self-disarming timer)."""
    cluster = make_cluster(seed=47)
    out = submit(cluster, 1, kv_txn([10], {10: ("x",)}))
    cluster.run_until_quiescent()
    assert out[0][1] is None
    for node in cluster.nodes.values():
        for store in node.command_stores.unsafe_all_stores():
            pl = store.progress_log
            assert not pl.home, f"leaked home entries: {pl.home}"
            assert not pl.blocked, f"leaked blocked entries: {pl.blocked}"
            assert pl._scheduled is None


def test_inform_of_txn_starts_home_tracking():
    """InformOfTxnId makes home-shard replicas track (and so recover) a txn
    they only know by id (ref: messages/InformOfTxnId.java)."""
    from accord_tpu.messages.commit import Commit
    from accord_tpu.messages.inform import InformOfTxnId
    cluster = make_cluster(seed=59)
    cluster.message_filter = (lambda s, d, r: isinstance(r, Commit) and s == 1)
    out = []
    cluster.nodes[1].coordinate(kv_txn([10], {10: ("inf",)})).begin(
        lambda r, f: out.append((r, f)))
    cluster.run_for(1_500_000)
    cluster.message_filter = None

    # find the stalled txn and its route, clear all home tracking, then
    # re-kick it purely via InformOfTxnId
    tid = route = None
    for node in cluster.nodes.values():
        for store in node.command_stores.unsafe_all_stores():
            store.progress_log.home.clear()
            for tok, cfk in store.commands_for_key.items():
                if tok == 10 and cfk.size():
                    tid = cfk.txn_ids()[0]
                    cmd = store.command_if_present(tid)
                    if cmd is not None and cmd.route is not None:
                        route = cmd.route
    assert tid is not None and route is not None

    cluster.nodes[2].send(2, InformOfTxnId(tid, route))
    cluster.run_until_quiescent()
    assert cluster.failures == []
    read = submit(cluster, 3, kv_txn([10], {}))
    cluster.run_until_quiescent()
    assert read[0][0].reads == {10: ("inf",)}, \
        "InformOfTxnId did not lead to recovery"


def test_progress_log_determinism():
    def run(seed):
        cluster = make_cluster(seed=seed)
        cluster.message_filter = (lambda s, d, r: isinstance(r, Commit) and s == 1)
        out = []
        cluster.nodes[1].coordinate(kv_txn([10], {10: ("a",)})).begin(
            lambda r, f: out.append((r, f)))
        cluster.run_for(2_000_000)
        cluster.message_filter = None
        cluster.run_until_quiescent()
        read = submit(cluster, 2, kv_txn([10], {}))
        cluster.run_until_quiescent()
        return read[0][0].reads, dict(cluster.stats)

    assert run(53) == run(53)


# -- the home entry's patience, learned from the node's own txns -----------


def _wave(cluster, first, per_node):
    """``per_node`` blind writes from each node, each on a key of its own."""
    outs = []
    for i, nid in enumerate(sorted(cluster.nodes)):
        for j in range(per_node):
            key = (first + i * per_node + j) * 7919 % 1_000_000
            outs.append(submit(cluster, nid, kv_txn([key], {key: ("v",)})))
    return outs


def _slow_cluster(seed, latency=600_000):
    """Three nodes whose every link takes ``latency`` on average, so a
    healthy txn takes seconds to reach majority durability and a replica
    sees no phase change for longer than two scans (0.5 s + a stagger of
    up to 0.4 s each).  Each node first coordinates DURABLE_EVERY txns:
    the samples its patience learns from."""
    cluster = make_cluster(seed=seed, mean_latency_micros=latency,
                           request_timeout_micros=30_000_000,
                           device_mode=False)
    _wave(cluster, 0, Node.DURABLE_EVERY)
    cluster.run_until_quiescent()
    assert all(n.n_durable_samples >= Node.DURABLE_EVERY
               for n in cluster.nodes.values())
    return cluster


def _recoveries(cluster):
    return cluster.obs.metrics.counter_totals(
        "recoveries", by="event").get("attempt", 0)


def _live_slow_txns(seed):
    """After the learning, four more healthy txns from each node: the
    recoveries and patience deferrals they cost."""
    cluster = _slow_cluster(seed)
    recovered = _recoveries(cluster)
    deferred = sum(n.n_patience_deferrals for n in cluster.nodes.values())
    outs = _wave(cluster, 1_000, 4)
    cluster.run_until_quiescent()
    assert cluster.failures == []
    assert all(o and o[0][1] is None for o in outs)
    return (cluster, _recoveries(cluster) - recovered,
            sum(n.n_patience_deferrals for n in cluster.nodes.values())
            - deferred)


def _countdowns(node):
    return [s.progress_log._home_countdown()
            for s in node.command_stores.unsafe_all_stores()]


def test_learned_patience_spares_live_slow_txns(monkeypatch):
    """Healthy txns slower than two scans: once each node has its samples
    no Recover starts, and the learned patience is what held them back."""
    cluster, recovered, deferred = _live_slow_txns(71)
    assert all(min(_countdowns(n)) > 2 for n in cluster.nodes.values())
    assert recovered == 0
    assert deferred > 0
    # the same seed under the fixed two-scan rule recovers live txns
    monkeypatch.setattr(SimpleProgressLog, "_home_countdown",
                        lambda self: 2)
    _, recovered, deferred = _live_slow_txns(71)
    assert recovered > 0
    assert deferred == 0


def test_learned_patience_still_recovers_dead_coordinator(monkeypatch):
    """After a history of slow healthy txns, a coordinator whose Stable
    round is lost is still recovered by the home replicas, no later than
    the patience's ceiling plus one scan after the txn last progressed."""
    cluster = _slow_cluster(73)
    assert all(2 * n.durable_p99_micros > _HOME_PATIENCE_CAP_MICROS
               for n in cluster.nodes.values()), "the ceiling should bind"
    idle = []
    investigate = SimpleProgressLog._investigate

    def timed(self, entry, asked):
        if asked is _Progress.Expected:
            idle.append((self.store.node.now_micros() - entry.since,
                         self.scan_period_micros))
        investigate(self, entry, asked)

    monkeypatch.setattr(SimpleProgressLog, "_investigate", timed)
    cluster.message_filter = (lambda s, d, r: isinstance(r, Commit)
                              and s == 1)
    submit(cluster, 1, kv_txn([10], {10: ("dead",)}))
    cluster.run_for(20_000_000)
    cluster.message_filter = None
    cluster.run_until_quiescent()
    assert cluster.failures == []
    read = submit(cluster, 2, kv_txn([10], {}))
    cluster.run_until_quiescent()
    assert read[0][0].reads == {10: ("dead",)}
    assert idle
    for waited, period in idle:
        assert 2 * period < waited <= _HOME_PATIENCE_CAP_MICROS + period


def test_home_countdown_is_two_until_learned_and_while_fast():
    """Fewer than DURABLE_EVERY samples, or a 2 x p99 within two scans,
    leave the countdown at exactly 2: the fixed rule."""
    cluster = make_cluster(seed=79, device_mode=False)
    slow, fast, sim = (cluster.nodes[n] for n in (1, 2, 3))
    for _ in range(Node.DURABLE_EVERY - 1):
        slow.note_coordinated_durable(slow.now_micros() - 5_000_000)
    assert slow.durable_p99_micros == 0
    assert _countdowns(slow) == [2, 2]
    slow.note_coordinated_durable(slow.now_micros() - 5_000_000)
    assert slow.durable_p99_micros == 5_000_000
    assert min(_countdowns(slow)) > 2
    for _ in range(2 * Node.DURABLE_EVERY):
        fast.note_coordinated_durable(fast.now_micros() - 400_000)
    assert fast.durable_p99_micros == 400_000
    assert _countdowns(fast) == [2, 2]
    # the sim's own healthy txns (1 ms links) stay under the floor
    outs = [submit(cluster, 3, kv_txn([k], {k: ("x",)}))
            for k in range(100, 100 + Node.DURABLE_EVERY)]
    cluster.run_until_quiescent()
    assert all(o and o[0][1] is None for o in outs)
    assert sim.n_durable_samples == Node.DURABLE_EVERY
    assert 0 < sim.durable_p99_micros < 500_000
    assert _countdowns(sim) == [2, 2]


def test_durable_latency_ignores_recovery_and_failed_coordination():
    """Only a txn this node coordinated from its start, through its own
    Persist, is a sample: not a recovery's Persist (the coordinator's own
    watchdog re-executing it, the home replicas') and not a failed
    coordination; no start is left behind."""
    cluster = make_cluster(seed=41, request_timeout_micros=60_000_000,
                           device_mode=False,
                           progress_log_factory=lambda store:
                           api.NoOpProgressLog())
    cluster.message_filter = (lambda s, d, r: isinstance(r, Commit)
                              and s == 1)
    out = submit(cluster, 1, kv_txn([10], {10: ("w",)}))
    cluster.run_for(2_000_000)
    cluster.message_filter = None
    cluster.run_until_quiescent()
    assert out and out[0][1] is None, "the watchdog completes the txn"
    causes = cluster.obs.metrics.counter_totals("recoveries", by="cause")
    assert causes == {"watchdog": 1, "": 1}
    assert [n.n_durable_samples for n in cluster.nodes.values()] == [0, 0, 0]

    cluster = make_cluster(seed=41, device_mode=False)
    cluster.message_filter = (lambda s, d, r: isinstance(r, Commit)
                              and s == 1)
    out = submit(cluster, 1, kv_txn([10], {10: ("w",)}))
    cluster.run_for(2_000_000)
    assert out and out[0][1] is not None, "the coordination fails"
    cluster.message_filter = None
    cluster.run_until_quiescent()
    assert [n.n_durable_samples for n in cluster.nodes.values()] == [0, 0, 0]
    read = submit(cluster, 2, kv_txn([10], {}))
    cluster.run_until_quiescent()
    assert read[0][0].reads == {10: ("w",)}
    assert [n.n_durable_samples for n in cluster.nodes.values()] == [0, 1, 0]
    assert not any(n._coordinate_started for n in cluster.nodes.values())


def test_learned_patience_is_deterministic():
    def run():
        cluster, recovered, deferred = _live_slow_txns(83)
        return (dict(cluster.stats), recovered, deferred,
                [n.durable_p99_micros for n in cluster.nodes.values()])

    first = run()
    assert first[2] > 0, "the learned patience should engage"
    assert run() == first
