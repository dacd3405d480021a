"""Maelstrom adapter: wire serde round-trips, in-process Runner
linearizability, determinism, and the real stdin/stdout node.

Ref behavior to match: accord-maelstrom/src/test/java/accord/maelstrom/
Runner.java:123-190 (in-process sim of the real node logic), JsonTest
(serde round-trips); externally Main.java speaks the Maelstrom protocol.
"""

import json
import os
import subprocess
import sys

import pytest

from accord_tpu import wire
from accord_tpu.maelstrom import MaelstromRunner
from accord_tpu.maelstrom.node import node_name_to_id, token_of
from accord_tpu.sim import cluster as cluster_mod
from accord_tpu.sim.cluster import Cluster
from accord_tpu.sim.kvstore import KVDataStore, kv_txn
from accord_tpu.sim.topology_factory import build_topology


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

def test_wire_round_trips_live_protocol_traffic(monkeypatch):
    """Capture every message and reply a real sim run sends and round-trip
    each through JSON — the codec must cover the full verb set."""
    topology = build_topology(1, (1, 2, 3), 3, 4)
    cluster = Cluster(topology=topology, seed=3,
                      data_store_factory=KVDataStore)
    seen = []
    orig_send = cluster_mod.NodeSink.send
    orig_swc = cluster_mod.NodeSink.send_with_callback
    orig_reply = cluster_mod.NodeSink.reply
    monkeypatch.setattr(cluster_mod.NodeSink, "send",
                        lambda self, to, request:
                        (seen.append(request), orig_send(self, to, request))[1])
    monkeypatch.setattr(cluster_mod.NodeSink, "send_with_callback",
                        lambda self, to, request, cb:
                        (seen.append(request),
                         orig_swc(self, to, request, cb))[1])
    monkeypatch.setattr(cluster_mod.NodeSink, "reply",
                        lambda self, to, ctx, reply:
                        (seen.append(reply), orig_reply(self, to, ctx, reply))[1])
    out = []
    for i in range(6):
        cluster.nodes[1 + (i % 3)].coordinate(
            kv_txn([i * 10, (i + 1) * 10], {i * 10: (f"v{i}",)})).begin(
            lambda r, f: out.append((r, f)))
    cluster.run_until_quiescent()
    # exercise the ephemeral-read and range-read verbs too
    from accord_tpu.coordinate.barrier import barrier
    from accord_tpu.primitives.keys import Range, Ranges
    from accord_tpu.sim.kvstore import kv_ephemeral_read, kv_range_read
    cluster.nodes[2].coordinate(kv_ephemeral_read([10])).begin(
        lambda r, f: out.append((r, f)))
    cluster.nodes[3].coordinate(
        kv_range_read(Ranges.of(Range(0, 100)))).begin(
        lambda r, f: out.append((r, f)))
    barrier(cluster.nodes[1], Ranges.of(Range(0, 1_000_000)),
            global_=True).begin(lambda r, f: out.append((r, f)))
    cluster.run_until_quiescent()
    # the deps/conflict probes and the fused shard-durable round
    # (ref: GetDeps.java, GetMaxConflict.java, ApplyThenWaitUntilApplied.java)
    from accord_tpu.coordinate.collect_deps import (collect_deps,
                                                    fetch_max_conflict)
    from accord_tpu.coordinate.durability import coordinate_shard_durable
    from accord_tpu.primitives.timestamp import Domain, TxnKind
    node1 = cluster.nodes[1]
    probe_id = node1.next_txn_id(TxnKind.Read, Domain.Key)
    probe_route = node1.compute_route(probe_id, kv_txn([10, 20], {}).keys)
    collect_deps(node1, probe_id, probe_route, kv_txn([10, 20], {}).keys,
                 node1.unique_now()).begin(lambda r, f: out.append((r, f)))
    fetch_max_conflict(node1, Ranges.of(Range(0, 100))).begin(
        lambda r, f: out.append((r, f)))
    coordinate_shard_durable(node1, Ranges.of(Range(0, 1_000_000))).begin(
        lambda r, f: out.append((r, f)))
    cluster.run_until_quiescent()
    # home-durability gossip (ref: InformHomeDurable.java)
    from accord_tpu.local.status import Durability
    from accord_tpu.messages.inform import InformHomeDurable
    wtxn = next(m for m in seen if type(m).__name__ == "Apply")
    cluster.nodes[2].send(1, InformHomeDurable(
        wtxn.txn_id, wtxn.route, wtxn.execute_at, Durability.Majority))
    cluster.run_until_quiescent()
    assert cluster.failures == []
    assert all(f is None for _r, f in out), out
    names = {type(m).__name__ for m in seen}
    assert {"GetEphemeralReadDeps", "ReadEphemeralTxnData",
            "WaitUntilApplied", "GetDeps", "GetDepsOk", "GetMaxConflict",
            "GetMaxConflictOk", "ApplyThenWaitUntilApplied",
            "InformHomeDurable", "SetShardDurable"} <= names, names
    assert len(seen) > 50
    for msg in seen:
        doc = json.loads(json.dumps(wire.encode(msg)))
        back = wire.decode(doc)
        assert type(back) is type(msg)
        # idempotent re-encode proves no information was lost on the fields
        # the codec carries
        assert wire.encode(back) == wire.encode(msg)


def test_wire_rejects_unknown():
    class Foo:
        pass
    with pytest.raises(TypeError):
        wire.encode(Foo())


# ---------------------------------------------------------------------------
# in-process runner (the north-star gate: lin-kv list-append passing)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_runner_list_append_linearizable(seed):
    r = MaelstromRunner(n_nodes=3, seed=seed)
    res = r.run_workload(n_ops=100, n_keys=8)   # verify=True checks history
    assert res.ops_unresolved == 0, res
    assert res.ops_ok >= res.ops_failed, res


def test_runner_five_nodes_string_keys():
    r = MaelstromRunner(n_nodes=5, seed=7)
    res = r.run_workload(n_ops=30, n_keys=6)
    assert res.ops_unresolved == 0, res


def test_runner_deterministic():
    a = MaelstromRunner(n_nodes=3, seed=11).run_workload(n_ops=30, n_keys=8)
    b = MaelstromRunner(n_nodes=3, seed=11).run_workload(n_ops=30, n_keys=8)
    assert (a.ops_ok, a.ops_failed, a.packets) == \
        (b.ops_ok, b.ops_failed, b.packets)


def test_runner_mixed_datum_kinds():
    """Reference datum parity (ROADMAP item 5 slice): one in-process run
    whose appended values cycle through all four reference datum kinds —
    strings, 64-bit longs, doubles and HASH documents — crossing the
    client JSON boundary in wire form and checked strict-serializable on
    canonical decoded values (DatumHash compares by value)."""
    from accord_tpu.primitives.datum import DatumHash
    r = MaelstromRunner(n_nodes=3, seed=5)
    res = r.run_workload(n_ops=80, n_keys=8,
                         value_kinds=("long", "string", "double", "hash"))
    assert res.ops_unresolved == 0, res
    assert res.ops_ok >= res.ops_failed, res
    # every kind actually landed in the stores' value logs
    kinds = set()
    for proc in r.processes.values():
        for tok in proc.node.data_store.tokens():
            for v in proc.node.data_store.get(tok):
                if isinstance(v, DatumHash):
                    kinds.add("hash")
                elif isinstance(v, str):
                    kinds.add("string")
                elif isinstance(v, float):
                    kinds.add("double")
                elif isinstance(v, int):
                    kinds.add("long")
    assert kinds == {"long", "string", "double", "hash"}, kinds


def test_datum_wire_and_json_roundtrip():
    """DatumHash through both boundaries: the tagged wire doc (inter-node
    protocol bodies) and the {"hash": n} client JSON form."""
    from accord_tpu.primitives.datum import (DatumHash, datum_from_json,
                                             datum_to_json)
    h = DatumHash(123456789)
    doc = json.loads(json.dumps(wire.encode(h)))
    assert wire.decode(doc) == h
    assert datum_from_json(datum_to_json(h)) == h
    for scalar in ("s", 7, (1 << 40) + 3, 2.25, None, True):
        assert datum_from_json(datum_to_json(scalar)) == scalar
    # ordering/hashing: usable in the verifier's tuples and sets
    assert DatumHash(1) < DatumHash(2)
    assert len({DatumHash(1), DatumHash(1), DatumHash(2)}) == 2


def test_token_mapping():
    assert token_of(5) == 5
    assert token_of("foo") == token_of("foo")
    assert token_of("foo") != token_of("bar")
    assert node_name_to_id("n0") == 1   # ids must be nonzero
    assert node_name_to_id("n3") == 4


# ---------------------------------------------------------------------------
# the real stdin/stdout node (ref: Main.java listen loop)
# ---------------------------------------------------------------------------

def test_stdin_stdout_node():
    env = dict(os.environ)
    env["ACCORD_TPU_DEVICE"] = "0"   # host path: fast cold start
    env["JAX_PLATFORMS"] = "cpu"     # a test child never takes the chip
    p = subprocess.Popen([sys.executable, "-m", "accord_tpu.maelstrom"],
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    try:
        def send(obj):
            p.stdin.write(json.dumps(obj) + "\n")
            p.stdin.flush()

        def recv():
            line = p.stdout.readline()
            assert line, "node closed stdout"
            return json.loads(line)

        send({"src": "c1", "dest": "n0",
              "body": {"type": "init", "msg_id": 1, "node_id": "n0",
                       "node_ids": ["n0"]}})
        assert recv()["body"]["type"] == "init_ok"
        send({"src": "c1", "dest": "n0",
              "body": {"type": "txn", "msg_id": 2,
                       "txn": [["append", 7, 1], ["r", 7, None]]}})
        body = recv()["body"]
        assert body["type"] == "txn_ok"
        assert body["txn"] == [["append", 7, 1], ["r", 7, [1]]]
        send({"src": "c1", "dest": "n0",
              "body": {"type": "txn", "msg_id": 3,
                       "txn": [["r", 7, None]]}})
        body = recv()["body"]
        assert body["type"] == "txn_ok"
        assert body["txn"] == [["r", 7, [1]]]
    finally:
        p.stdin.close()
        p.wait(timeout=60)
    assert p.returncode == 0


def test_runner_multi_partition_zipf_workload():
    """The configs[1]-shaped gate: 5 nodes, keys strided across the whole
    token ring (genuinely multi-partition), pinned 4-key txns, Zipf-0.9
    skew — strict serializability checked over the full wire codec."""
    from accord_tpu.maelstrom.runner import MaelstromRunner
    runner = MaelstromRunner(5, seed=3, shards=8, device_mode=False)
    res = runner.run_workload(n_ops=120, n_keys=2_000, keys_per_txn=4,
                              zipf_skew=0.9, spread_ring=True)
    assert res.ops_unresolved == 0
    assert res.ops_ok >= 110, res
    assert res.p99_micros() is not None and res.p99_micros() > 0
    # genuinely multi-partition: data landed across the ring, not shard 0
    toks = set()
    for proc in runner.processes.values():
        toks |= set(proc.node.data_store.tokens())
    assert max(toks) > (1 << 31), "keys all collapsed into low shards"


def test_init_warms_the_flush_the_first_preaccept_launches(monkeypatch):
    """``init`` warms every store's device path through the PRODUCT flush
    (deps_query_batch_begin -> deps_query_batch_end_attributed), never
    through a program the protocol does not launch: each warm-up handle
    is an attributed one (it carries the flush's AttrIndex, its parts are
    host entries or ``attr_*`` kernels) and is collected by the product
    collector."""
    from accord_tpu.local.device_index import DeviceState
    from accord_tpu.maelstrom.node import MaelstromProcess
    from tests.test_store_group import _Scheduler

    begun, ended = [], []
    begin = DeviceState.deps_query_batch_begin
    end = DeviceState.deps_query_batch_end_attributed

    def spy_begin(self, queries, **kw):
        handle = begin(self, queries, **kw)
        begun.append((kw, handle))
        return handle

    def spy_end(self, safe, handle, builders):
        ended.append(handle)
        return end(self, safe, handle, builders)

    monkeypatch.setattr(DeviceState, "deps_query_batch_begin", spy_begin)
    monkeypatch.setattr(DeviceState, "deps_query_batch_end_attributed",
                        spy_end)
    sent = []
    proc = MaelstromProcess(
        emit=lambda dest, body: sent.append((dest, body)),
        scheduler=_Scheduler(), now_micros=lambda: 0, num_stores=2,
        device_mode=True, durability=False)
    proc.handle({"src": "c1", "dest": "n1",
                 "body": {"type": "init", "msg_id": 1, "node_id": "n1",
                          "node_ids": ["n1", "n2", "n3"]}})
    assert [b["type"] for _d, b in sent] == ["init_ok"]
    stores = proc.node.command_stores.stores
    assert len(stores) == 2 and all(s.device is not None for s in stores)
    assert len(begun) == len(stores), "one warm-up flush a store"
    for kw, handle in begun:
        assert "prune_floors" not in kw and "attributed" not in kw
        parts, fmeta = handle[0], handle[6]
        assert fmeta["aidx"] is not None
        assert parts and all("ent" in p if p["kind"] == "host"
                             else p["kind"].startswith("attr_")
                             for p in parts)
    assert [id(h) for h in ended] == [id(h) for _kw, h in begun]
    assert sum(s.device.n_queries for s in stores) == len(stores)
