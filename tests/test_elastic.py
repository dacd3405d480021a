"""Elastic serving (r17): live epoch reconfiguration on the TCP cluster.

Pure units (planners, topology docs, retirement, rebalance admission,
chunk streaming, mixed-epoch hello) plus the end-to-end TCP legs: one
node joins AND one node leaves mid-load (tier-1), and the kill -9
mid-reconfiguration legs (slow tier; the fault-matrix reconfig leg runs
them too)."""

import asyncio
import base64

import pytest

from accord_tpu.net import bootstrap as nboot
from accord_tpu.net import codec as wcodec
from accord_tpu.net.reconfig import (doc_nodes_info, plan_join, plan_leave,
                                     plan_move, topology_from_doc,
                                     topology_to_doc)
from accord_tpu.sim.topology_factory import build_topology
from accord_tpu.topology.manager import TopologyManager


# ---------------------------------------------------------------------------
# epoch planners: pure, deterministic, boundary-preserving
# ---------------------------------------------------------------------------

def test_plan_join_preserves_boundaries_and_adds_member():
    t1 = build_topology(1, (1, 2, 3), 3, 4)
    t2 = plan_join(t1, 5)
    assert t2.epoch == 2
    assert sorted(t2.nodes()) == [1, 2, 3, 5]
    assert [s.range for s in t2.shards] == [s.range for s in t1.shards]
    # replication degree per shard is kept
    for s1, s2 in zip(t1.shards, t2.shards):
        assert len(s2.nodes) == len(s1.nodes)
    # determinism: same input, same plan
    assert plan_join(t1, 5) == t2
    with pytest.raises(ValueError):
        plan_join(t1, 2)   # already a member: reject, don't re-deal


def test_plan_leave_drops_member_and_respects_quorums():
    t1 = plan_join(build_topology(1, (1, 2, 3), 3, 4), 5)
    t2 = plan_leave(t1, 2)
    assert 2 not in t2.nodes()
    assert sorted(t2.nodes()) == [1, 3, 5]
    for s in t2.shards:
        assert len(s.nodes) == 3
    with pytest.raises(ValueError):
        plan_leave(build_topology(1, (1,), 1, 2), 1)
    with pytest.raises(ValueError):
        plan_leave(t1, 9)   # not a member (typo'd name): reject


def test_plan_move_single_shard_handoff():
    t1 = build_topology(1, (1, 2, 3, 4), 3, 4)
    token = t1.shards[2].range.start
    before = t1.shards[2].nodes
    target = next(n for n in sorted(t1.nodes()) if n not in before)
    t2 = plan_move(t1, token, target)
    moved = [i for i, (a, b) in enumerate(zip(t1.shards, t2.shards))
             if tuple(a.nodes) != tuple(b.nodes)]
    assert moved == [2], "exactly one shard changes owners"
    assert target in t2.shards[2].nodes
    with pytest.raises(ValueError):
        plan_move(t1, token, 99)   # non-member target
    # a no-op move (target already replicates the shard) keeps every
    # shard — electorates included — untouched
    noop = plan_move(t1, token, before[0])
    assert [(s.nodes, s.fast_path_electorate) for s in noop.shards] \
        == [(s.nodes, s.fast_path_electorate) for s in t1.shards]


def test_topology_doc_roundtrip_and_codec_safety():
    t = plan_join(build_topology(1, (1, 2, 3), 3, 4), 5)
    info = {n: (f"n{n - 1}", "127.0.0.1", 7000 + n) for n in t.nodes()}
    doc = topology_to_doc(t, info, proposer="n1")
    back = topology_from_doc(doc)
    assert back == t
    assert doc_nodes_info(doc) == info
    # the doc must ride BOTH wire codecs untouched (msgpack + JSON)
    import json
    pkt = {"src": "n1", "dest": "n2",
           "body": {"type": "topo_new", "topology": doc}}
    for codec in ("binary", "json"):
        assert wcodec.decode_payload(wcodec.encode_packet(pkt, codec)) \
            == pkt
    json.dumps(doc)


# ---------------------------------------------------------------------------
# epoch retirement
# ---------------------------------------------------------------------------

def test_topology_manager_retire_below():
    tm = TopologyManager(1)
    for e in range(1, 5):
        tm.on_topology_update(build_topology(e, (1, 2, 3), 3, 2))
    # epochs 2..4 need sync; ack them from a quorum
    for e in range(2, 5):
        for n in (1, 2):
            tm.on_epoch_sync_complete(n, e)
    assert tm.min_epoch() == 1
    n = tm.retire_below(3)
    assert n == 2 and tm.min_epoch() == 3
    assert not tm.has_epoch(2) and tm.has_epoch(3) and tm.has_epoch(4)
    # the newest epoch NEVER retires, even if asked
    assert tm.retire_below(99) == 1          # drops 3, keeps 4
    assert tm.min_epoch() == 4 and tm.epoch() == 4
    assert tm.retire_below(99) == 0
    # an unsynced epoch blocks retirement at its position
    tm2 = TopologyManager(1)
    tm2.on_topology_update(build_topology(1, (1, 2, 3), 3, 2))
    tm2.on_topology_update(build_topology(2, (1, 2, 3), 3, 2))
    tm2.on_topology_update(build_topology(3, (1, 2, 3), 3, 2))
    for n_ in (1, 2):
        tm2.on_epoch_sync_complete(n_, 3)
    assert tm2.retire_below(3) == 1          # epoch 1 (auto-synced) only
    assert tm2.min_epoch() == 2, "unsynced epoch 2 must not retire"


# ---------------------------------------------------------------------------
# rebalance-aware admission
# ---------------------------------------------------------------------------

def test_rebalance_health_prices_budget_cut_never_collapse():
    from accord_tpu.net.admission import rebalance_health_of
    from accord_tpu.primitives.keys import Range, Ranges

    class FakeRFE:
        def __init__(self, ranges):
            self._r = ranges

        def current(self):
            return self._r

    class FakeStore:
        def __init__(self, owned, booting):
            self.ranges_for_epoch = FakeRFE(owned)
            self.bootstrapping = booting

    class FakeNode:
        def __init__(self, stores):
            self.command_stores = type("CS", (), {"stores": stores})()

    owned = Ranges([Range(0, 1000)])
    assert rebalance_health_of(
        FakeNode([FakeStore(owned, Ranges.empty())])) == 1.0
    # half the ownership migrating: budget scaled to 0.75
    half = FakeNode([FakeStore(owned, Ranges([Range(0, 500)]))])
    assert abs(rebalance_health_of(half) - 0.75) < 1e-9
    # EVERYTHING migrating: floored at 0.5 — a cut, never a collapse
    full = FakeNode([FakeStore(owned, Ranges([Range(0, 1000)]))])
    assert rebalance_health_of(full) == 0.5


# ---------------------------------------------------------------------------
# chunk streaming (the snapshot-fed bootstrap data plane)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["binary", "json"])
def test_chunk_stream_reassembles_byte_identical(codec):
    payload = wcodec.encode_packet(
        {"src": "n1", "dest": "n2",
         "body": {"type": "accord_rsp", "msg_id": 1, "in_reply_to": 2,
                  "payload": {"blob": "x" * (3 * nboot.CHUNK_PART_BYTES
                                             + 17)}}}, codec)
    frames = nboot.chunk_payload_frames("n1", "n2", payload, codec)
    assert len(frames) == 4
    re = nboot.ChunkReassembler()
    from accord_tpu.net.framing import FrameDecoder
    dec = FrameDecoder()
    out = None
    for f in frames:
        for p in dec.feed_raw(f):
            body = wcodec.decode_payload(p)["body"]
            assert body["type"] == "accord_chunk"
            got = re.feed(body)
            if got is not None:
                assert out is None, "stream completed twice"
                out = got
    assert out == payload
    assert re.n_streams_done == 1 and re.pending_bytes() == 0


def test_chunk_streams_interleave_and_bound_memory():
    a = b"A" * (2 * nboot.CHUNK_PART_BYTES)
    b = b"B" * (2 * nboot.CHUNK_PART_BYTES)
    fa = nboot.chunk_payload_frames("n1", "n3", a, "binary")
    fb = nboot.chunk_payload_frames("n2", "n3", b, "binary")
    re = nboot.ChunkReassembler()

    def body_of(frame):
        return wcodec.decode_payload(frame[4:])["body"]

    # interleaved delivery: both streams complete with their own bytes
    outs = []
    for f in (fa[0], fb[0], fa[1], fb[1]):
        got = re.feed(body_of(f))
        if got is not None:
            outs.append(got)
    assert outs == [a, b]
    # memory bound: the OLDEST partial stream is evicted, never the
    # currently-fed one
    small = nboot.ChunkReassembler(
        max_pending=2 * nboot.CHUNK_PART_BYTES)
    small.feed(body_of(nboot.chunk_payload_frames("nX", "n3",
                                                  a, "binary")[0]))
    fb2 = nboot.chunk_payload_frames("nY", "n3", b, "binary")
    small.feed(body_of(fb2[0]))
    got = small.feed(body_of(fb2[1]))
    assert got == b, "the live stream survived the eviction"
    assert small.n_streams_dropped == 1
    # ...but ONE stream alone exceeding the whole budget is dropped too:
    # a single hostile cid must not hold unbounded receiver memory
    hostile = nboot.ChunkReassembler(max_pending=nboot.CHUNK_PART_BYTES)
    assert hostile.feed({"cid": "evil", "seq": 0, "n": 1000,
                         "part": b"E" * nboot.CHUNK_PART_BYTES}) is None
    assert hostile.feed({"cid": "evil", "seq": 1, "n": 1000,
                         "part": b"E" * nboot.CHUNK_PART_BYTES}) is None
    assert hostile.pending_bytes() <= nboot.CHUNK_PART_BYTES
    assert hostile.n_streams_dropped >= 1
    # a stale partial from a dead sender incarnation (same cid, different
    # declared n) restarts the stream instead of corrupting the join
    mixed = nboot.ChunkReassembler()
    mixed.feed({"cid": "s", "seq": 3, "n": 5, "part": b"OLD"})
    assert mixed.feed({"cid": "s", "seq": 0, "n": 2, "part": b"NE"}) is None
    assert mixed.feed({"cid": "s", "seq": 1, "n": 2, "part": b"W"}) == b"NEW"


def test_chunk_part_accepts_bytes_and_base64():
    re = nboot.ChunkReassembler()
    raw = b"snapshot-bytes"
    assert re.feed({"cid": "x", "seq": 0, "n": 1, "part": raw}) == raw
    assert re.feed({"cid": "y", "seq": 0, "n": 1,
                    "part": base64.b64encode(raw).decode()}) == raw


# ---------------------------------------------------------------------------
# mixed-epoch codec_hello interop
# ---------------------------------------------------------------------------

def test_hello_body_epoch_optional_and_interops():
    old = wcodec.hello_body("n1", "binary")
    assert "epoch" not in old, "epochless hello must stay byte-stable"
    new = wcodec.hello_body("n1", "binary", epoch=7)
    assert new["epoch"] == 7
    # both shapes ride both codecs on one stream
    for body in (old, new):
        for codec in ("binary", "json"):
            pkt = {"src": "n1", "dest": "", "body": body}
            assert wcodec.decode_payload(
                wcodec.encode_packet(pkt, codec)) == pkt


# ---------------------------------------------------------------------------
# departed-peer regressions (satellite: the r13 tombstone-heap contract
# extended to links dropped by drain-on-leave)
# ---------------------------------------------------------------------------

def test_sink_departed_peer_callbacks_time_out_and_compact():
    """A peer that LEFT the cluster (its link dropped by drain-on-leave)
    is, to the sink, a peer that never answers: every pending callback to
    it must resolve as Timeout at its horizon, and a burst of such
    requests must compact out of the deadline heap instead of lingering
    tombstones for the slow-read horizon."""
    from accord_tpu.coordinate.errors import Timeout
    from accord_tpu.maelstrom.node import MaelstromSink
    from accord_tpu.primitives.timestamp import Timestamp

    class Proc:
        request_timeout_micros = 1_000_000

        def __init__(self):
            self.t = 0

        def now_micros(self):
            return self.t

        def emit_packet(self, to, body):
            pass   # the departed peer's frames go nowhere

    class CB:
        def __init__(self):
            self.fail = []

        def on_success(self, frm, reply):
            pass

        def on_failure(self, frm, exc):
            self.fail.append(exc)

    proc = Proc()
    sink = MaelstromSink(proc)
    req = Timestamp.from_values(1, 1, 1)
    # a resolve burst (live traffic) interleaved with requests to the
    # departed peer: compaction may never lose a departed-peer callback
    departed = [CB() for _ in range(20)]
    it = iter(departed)

    class Reply:
        def is_final(self):
            return True

    for i in range(400):
        if i % 20 == 0:
            sink.send_with_callback(9, req, next(it))   # departed peer
        sink.send_with_callback(2, req, CB())
        sink.on_response(2, sink._next_msg_id, Reply())
    assert len(sink._timeouts) <= len(sink.pending) + 64, \
        "tombstones outgrew the compaction bound"
    proc.t = 2_000_000
    sink.sweep()
    for cb in departed:
        assert len(cb.fail) == 1 and isinstance(cb.fail[0], Timeout), \
            "a departed-peer callback was lost by compaction"
    assert len(sink.pending) == 0
    assert len(sink._timeouts) <= 64


def test_client_pending_fail_over_on_close_and_remove():
    """r17 drive-by fix pinned: a NodeConnection closed mid-request
    (re-dial, or remove_node after a leave) fails its pending futures
    IMMEDIATELY — cancellation used to skip the cleanup, hanging callers
    for their full client timeout.  remove_node also carries the
    duplicate census."""
    from accord_tpu.net.client import ClusterClient, NodeConnection
    from accord_tpu.net.framing import encode_frame

    async def scenario():
        served = []
        over = asyncio.Event()

        async def handler(reader, writer):
            # read one frame's worth and never reply
            served.append(await reader.read(64))
            await over.wait()
            writer.close()   # or wait_closed() below waits for ever (3.12)

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = ClusterClient([("n1", "127.0.0.1", port)], timeout=20.0)
        await client.connect()
        conn = client.conns["n1"]
        task = asyncio.get_event_loop().create_task(
            conn.request({"type": "txn", "txn": []}, 1, timeout=20.0))
        await asyncio.sleep(0.2)
        assert not task.done()
        conn.duplicate_replies = 3   # pretend some were observed
        t0 = asyncio.get_event_loop().time()
        await client.remove_node("n1")
        with pytest.raises(ConnectionError):
            await task
        took = asyncio.get_event_loop().time() - t0
        assert took < 2.0, f"pending request hung {took:.1f}s after close"
        assert client.duplicate_replies() == 3, \
            "departed node's duplicate census was dropped"
        assert client.addrs == []
        over.set()
        server.close()
        await server.wait_closed()
        return True

    assert asyncio.run(scenario())


# ---------------------------------------------------------------------------
# end-to-end TCP legs
# ---------------------------------------------------------------------------

def test_elastic_join_and_leave_mid_load():
    """Tier-1 tentpole proof: a journaled 3-node TCP cluster admits a
    4th node (snapshot-fed bootstrap over the wire) and retires a member,
    under client load — every op succeeds, zero duplicate replies, every
    surviving node converges on the same final epoch, the old epoch
    retires, and wait_ready keeps converging as membership changes (it is
    called after both the join and the leave inside the scenario)."""
    from accord_tpu.net.harness import run_reconfig_smoke
    result = run_reconfig_smoke(n_txns=10)
    assert result["duplicate_replies"] == 0
    assert all(result["alive"].values())
    epochs = {n: rc.get("epoch_current")
              for n, rc in result["reconfig"].items() if rc}
    assert set(epochs.values()) == {3}, epochs
    retired = max(rc.get("epochs_retired", 0)
                  for rc in result["reconfig"].values() if rc)
    assert retired >= 1, "no epoch ever retired"
    joiner_rc = result["reconfig"].get(result["joiner"]) or {}
    assert joiner_rc.get("handoff_ranges", 0) > 0, \
        "the joiner never adopted ranges"
    assert joiner_rc.get("bootstrap_bytes_rx", 0) > 0, \
        "the joiner never fetched a snapshot over the wire"


@pytest.mark.slow
def test_reconfig_kill9_joiner_mid_bootstrap():
    """kill -9 the JOINING node mid-bootstrap: the respawned incarnation
    recovers its epoch ledger (journal) or refetches it (hello-epoch
    gossip) and completes the join; the cluster converges on one epoch
    with zero duplicate replies.  (Also a fault-matrix reconfig leg.)"""
    from accord_tpu.net.harness import run_reconfig_smoke
    result = run_reconfig_smoke(n_txns=10, kill_joiner=True)
    assert result["duplicate_replies"] == 0
    epochs = {n: rc.get("epoch_current")
              for n, rc in result["reconfig"].items() if rc}
    assert len(set(epochs.values())) == 1, epochs


@pytest.mark.slow
def test_reconfig_kill9_proposer_mid_propose():
    """kill -9 the epoch PROPOSER immediately after it minted epoch N+1:
    the topology record is journaled durable BEFORE the first broadcast,
    so recovery re-ingests (and re-gossips) the epoch — never a lost or
    forked epoch.  (Also a fault-matrix reconfig leg.)"""
    from accord_tpu.net.harness import run_reconfig_smoke
    result = run_reconfig_smoke(n_txns=10, kill_proposer=True)
    assert result["duplicate_replies"] == 0
    epochs = {n: rc.get("epoch_current")
              for n, rc in result["reconfig"].items() if rc}
    assert len(set(epochs.values())) == 1, epochs
