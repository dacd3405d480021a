"""A peer the transport knows to be down fails its callbacks at once.

(i) the sink alone; (ii) the fast-path tracker under a failure; (iii) five
NodeServers in this process over loopback TCP (as test_served_multikey.py
hosts them), one crash-stopped with txns in flight on it, the request timeout
at its 20 s default: the survivors answer without waiting for it, recover the
crashed node's orphans, and the history holds; (iv) the crashed node started
again: its peers' links report it up and wait for its answers again; (v) a
txn whose PreAccept reached only replicas outside its home shard before its
coordinator died is reported to the home shard and recovered."""

import asyncio
import gc
import itertools
import random
import time

import pytest

from accord_tpu.coordinate.errors import Timeout
from accord_tpu.coordinate.tracking import FastPathTracker, RequestStatus
from accord_tpu.local.status import Status
from accord_tpu.maelstrom.node import (MaelstromSink, node_name_to_id,
                                       token_of)
from accord_tpu.net.harness import free_ports
from accord_tpu.primitives.keys import Range
from accord_tpu.primitives.timestamp import Timestamp
from accord_tpu.sim import serial_kv
from accord_tpu.sim.elle import CompositeVerifier, ListAppendCycleChecker
from accord_tpu.sim.verifier import StrictSerializabilityVerifier
from accord_tpu.topology.shard import Shard
from accord_tpu.topology.topology import Topologies, Topology
from accord_tpu.utils.random_source import RandomSource


# -- (i) the sink --------------------------------------------------------

class _Scheduler:
    def __init__(self):
        self.queued = []

    def now(self, run):
        self.queued.append(run)

    def run_all(self):
        while self.queued:
            self.queued.pop(0)()


class _Proc:
    request_timeout_micros = 20_000_000

    def __init__(self):
        self.t = 0
        self.sent = []
        self.down = set()
        self.failures = []
        self.scheduler = _Scheduler()

    def now_micros(self):
        return self.t

    def emit_packet(self, to, body):
        self.sent.append((to, body))

    def peer_known_down(self, to):
        return to in self.down

    def durable_journal(self):
        return None


class _Callback:
    def __init__(self):
        self.ok, self.fail = [], []

    def on_success(self, frm, reply):
        self.ok.append((frm, reply))

    def on_failure(self, frm, exc):
        self.fail.append((frm, exc))


class _Raises(_Callback):
    def on_failure(self, frm, exc):
        super().on_failure(frm, exc)
        raise RuntimeError("a callback's own fault")


class _Reply:
    def is_final(self):
        return True


def test_sink_fails_at_once_for_a_down_peer_and_on_the_links_drop():
    proc = _Proc()
    sink = MaelstromSink(proc)
    req = Timestamp.from_values(1, 1, 1)       # any wire-encodable request

    # pending on a peer that is up, then its link drops: failed now, not at
    # the 20 s deadline, and only those of that peer
    on3, on4 = [_Callback() for _ in range(3)], _Callback()
    for cb in on3:
        sink.send_with_callback(3, req, cb)
    sink.send_with_callback(4, req, on4)
    assert len(proc.sent) == 4 and len(sink.pending) == 4
    ids_to_3 = [body["msg_id"] for to, body in proc.sent if to == 3]
    proc.down.add(3)
    sink.fail_peer(3)
    for cb in on3:
        assert [type(e) for _f, e in cb.fail] == [Timeout] and not cb.ok
        assert cb.fail[0][0] == 3
    assert not on4.fail and list(sink.pending) == [proc.sent[3][1]["msg_id"]]
    assert sink.n_failed_by_drop == 3

    # a reply that still arrives is dropped: never delivered, never twice
    for msg_id in ids_to_3 + ids_to_3:
        sink.on_response(3, msg_id, _Reply())
    assert all(not cb.ok and len(cb.fail) == 1 for cb in on3)

    # a request to the peer known down: nothing is emitted, nothing pends,
    # the callback fails at the next scheduler hop (never inside the send)
    late = _Callback()
    sink.send_with_callback(3, req, late)
    assert not late.fail and len(proc.sent) == 4 and len(sink.pending) == 1
    proc.scheduler.run_all()
    assert [type(e) for _f, e in late.fail] == [Timeout]
    assert sink.n_failed_at_once == 1
    sink.send(3, req)          # no callback: emitted, the link's to drop
    assert len(proc.sent) == 5

    # the peer that is up still answers, and still times out by the sweeper
    sink.on_response(4, proc.sent[3][1]["msg_id"], _Reply())
    assert len(on4.ok) == 1
    silent = _Callback()
    sink.send_with_callback(4, req, silent)
    proc.t = 21_000_000
    sink.sweep()
    assert [type(e) for _f, e in silent.fail] == [Timeout]
    assert sink.n_timed_out == 1 and not sink.pending

    # back up: sent and pending again
    proc.down.clear()
    again = _Callback()
    sink.send_with_callback(3, req, again)
    proc.scheduler.run_all()
    assert not again.fail and proc.sent[-1][0] == 3 and len(sink.pending) == 1


def test_fail_peer_outlives_a_callback_that_raises():
    proc = _Proc()
    sink = MaelstromSink(proc)
    req = Timestamp.from_values(1, 1, 1)
    callbacks = [_Callback(), _Raises(), _Callback()]
    for cb in callbacks:
        sink.send_with_callback(3, req, cb)
    sink.fail_peer(3)                      # does not raise
    assert all(len(cb.fail) == 1 for cb in callbacks) and not sink.pending
    assert sink.n_failed_by_drop == 3
    assert [type(e) for e in proc.failures] == [RuntimeError]


# -- (ii) the tracker ----------------------------------------------------

def _rf3_tracker():
    shard = Shard(Range(0, 100), [1, 2, 3])
    assert (shard.fast_path_quorum_size, shard.slow_path_quorum_size,
            shard.max_failures) == (3, 2, 1)
    return FastPathTracker(Topologies.single(Topology(1, [shard])))


@pytest.mark.parametrize("order", ["fail_first", "fail_between", "fail_last"])
def test_fast_path_tracker_two_oks_and_a_failure_decide_the_slow_path(order):
    tracker = _rf3_tracker()
    steps = {"fail_first": ["f3", "s1", "s2"], "fail_between":
             ["s1", "f3", "s2"], "fail_last": ["s1", "s2", "f3"]}[order]
    seen = []
    for step in steps:
        node = int(step[1])
        seen.append(tracker.record_failure(node) if step[0] == "f"
                    else tracker.record_success(node, True))
    # undecided until the third event, whichever it is: two fast votes of
    # three electors are no fast quorum, and one failure rejects it
    assert seen == [RequestStatus.NoChange, RequestStatus.NoChange,
                    RequestStatus.Success]
    assert not tracker.has_fast_path_accepted()


def test_fast_path_tracker_two_failures_fail_the_shard():
    tracker = _rf3_tracker()
    assert tracker.record_success(1, True) is RequestStatus.NoChange
    assert tracker.record_failure(2) is RequestStatus.NoChange
    assert tracker.record_failure(3) is RequestStatus.Failed
    # terminal: reported once
    assert tracker.record_success(2, True) is RequestStatus.NoChange


# -- (iii), (iv) five served nodes, one crash-stopped ---------------------

NODES, KEYS, WIDTH, CRASH = 5, 300, 4, "n5"
ANSWER_LIMIT_S = 2.0


def _now_us():
    return time.monotonic_ns() // 1_000


class _Cluster:
    def __init__(self, journal_root, device_mode=True):
        from accord_tpu.net.client import ClusterClient
        self.journal_root = journal_root
        self.device_mode = device_mode
        self.names = [f"n{i}" for i in range(1, NODES + 1)]
        self.addrs = {n: ("127.0.0.1", p)
                      for n, p in zip(self.names, free_ports(NODES))}
        self.servers = {n: self._server(n) for n in self.names}
        self.client = ClusterClient(
            [(n, *self.addrs[n]) for n in self.names], timeout=10.0,
            codec="binary")
        self.keys = [k * ((1 << 32) // KEYS) for k in range(KEYS)]
        random.Random(5).shuffle(self.keys)            # rank -> key
        self.verifier = CompositeVerifier(StrictSerializabilityVerifier(),
                                          ListAppendCycleChecker())
        self.answered, self.unanswered, self.acked = [], [], {}
        self.latencies = []
        self.counter = itertools.count(1)
        self.closed = []

    def _server(self, name):
        from accord_tpu.net.server import NodeServer
        # request_timeout_ms is left alone: the product's 20 s
        return NodeServer(name, *self.addrs[name], dict(self.addrs),
                          device_mode=self.device_mode, durability=False,
                          journal_dir=str(self.journal_root / name),
                          journal_sync="client", wire_codec_name="binary")

    def survivors(self):
        return [self.servers[n] for n in self.names if n != CRASH]

    async def one_client(self, rng, txns, nodes, record=True):
        """``record`` False: the txn is timed and left out of the history
        (the final read-back came before it)."""
        for _ in range(txns):
            chosen = []
            while len(chosen) < WIDTH:
                key = self.keys[rng.next_zipf(KEYS, 0.9)]
                if key not in chosen:
                    chosen.append(key)
            ops, writes = [], {}
            for key in chosen:
                if rng.decide(0.5):
                    value = next(self.counter)
                    ops.append(["append", key, value])
                    writes[token_of(key)] = (value,)
                else:
                    ops.append(["r", key, None])
            op_id, start = self.verifier.begin(), _now_us()
            try:
                body = await self.client.submit(
                    ops, node=nodes[rng.next_int(len(nodes))])
            except (ConnectionError, KeyError, asyncio.TimeoutError) as e:
                # in flight on the crashed node: indeterminate
                if writes:
                    self.unanswered.append((start, writes))
                self.latencies.append((None, repr(e)))
                continue
            end = _now_us()
            self.latencies.append(((end - start) / 1e6, None))
            if not record:
                continue
            reads = {token_of(op[1]): tuple(op[2])
                     for op in body["txn"] if op[0] == "r"}
            self.verifier.on_result(op_id, start, end, reads, writes)
            self.answered.append((start, end, reads, writes))
            for t, vals in writes.items():
                self.acked.setdefault(t, []).extend(vals)

    def orphans(self):
        """(node, txn id, status) of every txn the crashed node coordinated
        that a survivor holds undecided."""
        crashed = node_name_to_id(CRASH)
        return [(s.name, str(tid), cmd.save_status.status.name)
                for s in self.survivors()
                for store in s.proc.node.command_stores.stores
                for tid, cmd in store.commands.items()
                if tid.node == crashed
                and not cmd.save_status.status.has_been(Status.Committed)]

    async def read_back(self):
        finals = {}
        for at in range(0, KEYS, 50):
            start = _now_us()
            body = await self.client.submit(
                [["r", key, None] for key in self.keys[at:at + 50]])
            reads = {token_of(op[1]): tuple(op[2]) for op in body["txn"]}
            self.answered.append((start, _now_us(), reads, {}))
            finals.update(reads)
        return finals

    async def close(self):
        await self.client.close()
        servers = list(self.servers.values()) + self.closed
        for s in servers:
            for link in s.links.values():
                await link.close()
        for s in servers:
            if s.frame_server is not None:
                await asyncio.wait_for(s.close(), 30.0)


async def _crash_and_drive(journal_root):
    c = _Cluster(journal_root)
    out = {}
    try:
        for s in c.servers.values():
            await s.start()
        await c.client.connect()
        for n in c.names:
            await c.client.ping(n, timeout=60.0)
        await asyncio.gather(*[c.one_client(RandomSource(100 + i), 15,
                                            c.names) for i in range(4)])
        assert all(err is None for _lat, err in c.latencies), c.latencies
        c.latencies.clear()

        # crash-stop with txns in flight everywhere, n5 among the
        # coordinators
        through = [asyncio.ensure_future(c.one_client(
            RandomSource(200 + i), 6, c.names)) for i in range(4)]
        await asyncio.sleep(0.03)
        in_flight_on_crashed = len(c.client.conns[CRASH]._pending)
        c.servers[CRASH].crash_stop()
        await c.client.remove_node(CRASH)
        await asyncio.gather(*through)
        out["through"] = list(c.latencies)
        out["in_flight_on_crashed"] = in_flight_on_crashed
        c.latencies.clear()

        # the survivors' progress logs recover what the crashed node left
        t0 = time.monotonic()
        while c.orphans() and time.monotonic() - t0 < 20.0:
            await asyncio.sleep(0.1)
        out["orphans"] = c.orphans()
        out["settle_s"] = time.monotonic() - t0

        # 40 four-key txns through the survivors
        live = [n for n in c.names if n != CRASH]
        before = [s.stats() for s in c.survivors()]
        await asyncio.gather(*[c.one_client(RandomSource(300 + i), 10, live)
                               for i in range(4)])
        out["after"] = list(c.latencies)
        c.latencies.clear()
        out["stats"] = (before, [s.stats() for s in c.survivors()])
        out["finals"] = await c.read_back()
        out["orphans_at_end"] = c.orphans()
        out["failures"] = sum(len(s.proc.failures) for s in c.survivors())
        out["duplicates"] = c.client.duplicate_replies()

        # quiescent: every acknowledged append on both live replicas
        crashed = node_name_to_id(CRASH)
        topology = c.survivors()[0].proc.node.topology().current()
        by_id = {s.proc.node.node_id: s for s in c.survivors()}
        thin = []
        for _ in range(50):
            thin = []
            for token, values in c.acked.items():
                shard = next(s for s in topology if s.contains_token(token))
                live_replicas = [n for n in shard.nodes if n != crashed]
                for n in live_replicas:
                    held = by_id[n].proc.node.data_store.get(token)
                    thin += [(token, v, n) for v in values if v not in held]
            if not thin:
                break
            await asyncio.sleep(0.1)
        out["thin"] = thin

        # (iv) the crashed node starts again on its address and journal
        old = c.servers[CRASH]
        c.closed.append(old)
        for link in old.links.values():
            await link.close()
        await asyncio.wait_for(old.close(), 30.0)
        c.servers[CRASH] = c._server(CRASH)
        await c.servers[CRASH].start()
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0 and any(
                s.links[CRASH].down for s in c.survivors()):
            await asyncio.sleep(0.05)
        out["up_after_s"] = time.monotonic() - t0
        out["links_after_restart"] = [s.links[CRASH].stats()
                                      for s in c.survivors()]
        at_once = [s.proc.sink.n_failed_at_once for s in c.survivors()]
        sent = [s.links[CRASH].n_sent for s in c.survivors()]
        await asyncio.gather(*[c.one_client(RandomSource(400 + i), 5, live,
                                            record=False)
                               for i in range(4)])
        out["restarted"] = list(c.latencies)
        out["at_once_delta"] = [s.proc.sink.n_failed_at_once - a
                                for s, a in zip(c.survivors(), at_once)]
        out["sent_delta"] = [s.links[CRASH].n_sent - a
                             for s, a in zip(c.survivors(), sent)]
        out["cluster"] = c
    finally:
        await c.close()
    return out


@pytest.fixture(scope="module")
def crash_run(tmp_path_factory):
    """One run of the cluster for the tests below."""
    from accord_tpu.local.device_index import DeviceState
    threshold = gc.get_threshold()
    calib = DeviceState._CALIB
    DeviceState._CALIB = None
    try:
        return asyncio.run(_crash_and_drive(tmp_path_factory.mktemp("wal")))
    finally:
        DeviceState._CALIB = calib
        gc.unfreeze()            # NodeServer.start() retunes the collector
        gc.set_threshold(*threshold)


def test_survivors_answer_without_waiting_for_the_crashed_node(crash_run):
    """With the request timeout at its 20 s default, 40 txns through the
    four survivors are each answered in under 2 s: no round waits on the
    dead replica, every failed callback failed at once, no read was sent
    its way, and most txns took the slow path."""
    after = crash_run["after"]
    assert len(after) == 40 and all(err is None for _l, err in after), after
    assert max(lat for lat, _e in after) < ANSWER_LIMIT_S, after
    before, stats = crash_run["stats"]
    delta = {k: sum(a["peer_failures"][k] - b["peer_failures"][k]
                    for a, b in zip(stats, before))
             for k in stats[0]["peer_failures"]}
    assert delta["failed_at_once"] > 40, delta
    assert delta["timed_out"] == delta["failed_by_drop"] == 0, delta
    assert delta["reads_to_down_replica"] == 0, delta
    assert delta["peer_down_events"] == 0     # all four before the 40 began
    assert all(st["peer_failures"]["peer_down_events"] == 1
               and st["links"][CRASH]["down"] for st in stats)
    # nothing was queued towards the dead peer during the 40
    assert sum(a["links"][CRASH]["enqueued"] - b["links"][CRASH]["enqueued"]
               for a, b in zip(stats, before)) == 0
    if stats[0]["coordination"] is not None:
        fast = sum(a["coordination"]["fast"] - b["coordination"]["fast"]
                   for a, b in zip(stats, before))
        slow = sum(a["coordination"]["slow"] - b["coordination"]["slow"]
                   for a, b in zip(stats, before))
        assert fast + slow >= 40 and slow > fast, (fast, slow)


def test_orphans_of_the_crashed_coordinator_are_recovered(crash_run):
    """Txns were in flight on the crashed node; the attempts are
    indeterminate to their clients, and the survivors' progress logs bring
    every one to committed or invalidated."""
    assert crash_run["in_flight_on_crashed"] > 0
    through = crash_run["through"]
    assert any(err is not None and "closed" in err for _l, err in through)
    # the others went through the crash without a timeout of their own
    assert all(err is None or "n5" in err for _l, err in through), through
    assert crash_run["orphans"] == [] and crash_run["orphans_at_end"] == []
    assert crash_run["settle_s"] < 20.0
    assert crash_run["failures"] == 0 and crash_run["duplicates"] == 0


def test_history_through_the_crash_is_strict_serializable(crash_run):
    c, finals = crash_run["cluster"], crash_run["finals"]
    assert len(finals) == KEYS
    for token, final in finals.items():
        c.verifier.set_final(token, final)
    c.verifier.verify()
    serial_kv.replay(c.answered, c.unanswered, finals)
    missing = [(t, v) for t, vals in c.acked.items() for v in vals
               if v not in finals.get(t, ())]
    assert not missing, missing[:5]
    # and on both live replicas of its shard (a slow quorum of rf 3)
    assert not crash_run["thin"], crash_run["thin"][:5]


def test_restarted_node_is_up_again_for_its_peers(crash_run):
    """The links re-dial on their backoff; the first hello that leaves makes
    the peer up: requests are sent again and pend again."""
    assert crash_run["up_after_s"] < 10.0
    for link in crash_run["links_after_restart"]:
        assert not link["down"] and link["connected"]
        assert link["downs"] == 1 and link["ups"] == 1
    restarted = crash_run["restarted"]
    assert len(restarted) == 20 and all(e is None for _l, e in restarted)
    assert sum(crash_run["at_once_delta"]) == 0
    assert sum(crash_run["sent_delta"]) > 0


async def _link_drops_under_a_raising_callback(journal_root):
    c = _Cluster(journal_root, device_mode=False)
    try:
        for s in c.servers.values():
            await s.start()
        await c.client.connect()
        for n in c.names:
            await c.client.ping(n, timeout=60.0)
        n1, crashed = c.servers["n1"], node_name_to_id(CRASH)
        link, sink = n1.links[CRASH], n1.proc.sink
        while not link.connected:      # down is for a link that was up
            await asyncio.sleep(0.02)
        # two requests pend on n5 (held back from the wire: n5 must not
        # answer them); the first one's callback raises when it fails
        emit, sink._emit = sink._emit, lambda to, body: None
        callbacks = [_Raises(), _Callback()]
        for cb in callbacks:
            sink.send_with_callback(crashed, Timestamp.from_values(1, 1, 1),
                                    cb)
        sink._emit = emit
        c.servers[CRASH].crash_stop()
        await c.client.remove_node(CRASH)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0 and not (
                link.down and all(cb.fail for cb in callbacks)):
            await asyncio.sleep(0.02)
        out = {"down": link.down, "failed_s": time.monotonic() - t0,
               "fails": [len(cb.fail) for cb in callbacks],
               "node_failures": [type(e) for e in n1.proc.failures],
               "task_alive": not link._task.done()}
        # n5 starts again: the link that ran the raising callback re-dials
        old = c.servers[CRASH]
        c.closed.append(old)
        for old_link in old.links.values():
            await old_link.close()
        await asyncio.wait_for(old.close(), 30.0)
        c.servers[CRASH] = c._server(CRASH)
        await c.servers[CRASH].start()
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0 and link.down:
            await asyncio.sleep(0.05)
        out["after_restart"] = link.stats()
        return out
    finally:
        await c.close()


def test_a_raising_callback_does_not_kill_the_link(tmp_path):
    """The link's drop fails the pending callbacks from the scheduler, not
    from the link's own task: one that raises is recorded as the node's
    failure, the next one still fails at once, and the link lives to re-dial
    and to report the peer up."""
    threshold = gc.get_threshold()
    try:
        out = asyncio.run(_link_drops_under_a_raising_callback(tmp_path))
    finally:
        gc.unfreeze()
        gc.set_threshold(*threshold)
    assert out["down"] and out["fails"] == [1, 1] and out["failed_s"] < 2.0, out
    assert out["node_failures"] == [RuntimeError] and out["task_alive"]
    after = out["after_restart"]
    assert not after["down"] and after["connected"]
    assert after["downs"] == 1 and after["ups"] == 1


# -- (v) an orphan that no home replica has heard of ----------------------

async def _orphan_outside_the_home_shard(journal_root):
    """n5 coordinates a txn over four shards whose home shard is (n1, n2,
    n3); its PreAccept leaves for n4 alone, a replica of two of the other
    shards, and n5 crash-stops."""
    c = _Cluster(journal_root, device_mode=False)
    try:
        for s in c.servers.values():
            await s.start()
        await c.client.connect()
        for n in c.names:
            await c.client.ping(n, timeout=60.0)
        keys = [k << 26 for k in (1, 17, 33, 49)]     # shards 0, 4, 8, 12
        topology = c.servers["n1"].proc.node.topology().current()
        owners = [{f"n{n - 1}" for n in sh.nodes}
                  for key in keys for sh in topology
                  if sh.contains_token(key)]
        n5 = c.servers[CRASH]
        emit = n5.proc._emit_raw
        n5.proc._emit_raw = lambda dest, body: (
            None if dest in n5.links and dest != "n4" else emit(dest, body))
        attempt = asyncio.ensure_future(c.client.submit(
            [["append", key, 7] for key in keys], node=CRASH))
        # crash once n4 has witnessed it (a fixed 0.1 s was too short for a
        # loaded machine: n4 had nothing yet and no orphan existed)
        t0 = time.monotonic()
        while not any(o[0] == "n4" for o in c.orphans()) \
                and time.monotonic() - t0 < 10.0:
            await asyncio.sleep(0.02)
        n5.crash_stop()
        await c.client.remove_node(CRASH)
        with pytest.raises(ConnectionError):
            await attempt
        at_crash = c.orphans()
        t0 = time.monotonic()
        while c.orphans() and time.monotonic() - t0 < 30.0:
            await asyncio.sleep(0.1)
        ends = {(s.name, cmd.save_status.status.name)
                for s in c.survivors()
                for store in s.proc.node.command_stores.stores
                for tid, cmd in store.commands.items()
                if tid.node == node_name_to_id(CRASH)
                and str(tid) in {o[1] for o in at_crash}}
        # and the keys serve again
        body = await c.client.submit([["r", key, None] for key in keys],
                                     node="n1", timeout=5.0)
        return (owners, at_crash, c.orphans(), time.monotonic() - t0, ends,
                body["txn"])
    finally:
        await c.close()


def test_orphan_known_outside_its_home_shard_only_is_recovered(tmp_path):
    threshold = gc.get_threshold()
    try:
        owners, at_crash, left, settle_s, ends, reads = asyncio.run(
            _orphan_outside_the_home_shard(tmp_path))
    finally:
        gc.unfreeze()
        gc.set_threshold(*threshold)
    assert owners[0] == {"n1", "n2", "n3"} and "n4" not in owners[1]
    assert "n4" in owners[2] and "n4" in owners[3]
    # n4 alone had witnessed it, pre-accepted, and is no home replica
    assert {o[0] for o in at_crash} == {"n4"}
    assert {o[2] for o in at_crash} == {"PreAccepted"}
    assert left == [] and settle_s < 30.0, (left, settle_s)
    assert {status for _n, status in ends} <= {
        "Invalidated", "Committed", "Stable", "PreApplied", "Applied"}
    assert {name for name, _s in ends} >= {"n1", "n2", "n3", "n4"}
    # all four keys together: the append landed whole or not at all
    assert len({tuple(op[2]) for op in reads}) == 1
