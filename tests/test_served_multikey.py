"""Five NodeServers in this process over loopback TCP, device on, under
multi-key txns on a skewed key space (the lin-kv-5n-zipf shape at a small
size): the history replays through the plain reference and passes the
composite verifier, and ``stats()["coordination"]`` accounts for every txn
the nodes coordinated."""

import asyncio
import gc
import itertools
import random
import time

from accord_tpu.maelstrom.node import token_of
from accord_tpu.net.harness import free_ports
from accord_tpu.sim import serial_kv
from accord_tpu.sim.elle import CompositeVerifier, ListAppendCycleChecker
from accord_tpu.sim.verifier import StrictSerializabilityVerifier
from accord_tpu.utils.random_source import RandomSource

NODES, CLIENTS, TXNS_PER_CLIENT, KEYS, WIDTH = 5, 4, 75, 300, 4


def _now_us():
    return time.monotonic_ns() // 1_000


async def _serve_and_drive(journal_root):
    from accord_tpu.net.client import ClusterClient
    from accord_tpu.net.server import NodeServer
    names = [f"n{i}" for i in range(1, NODES + 1)]
    addrs = {n: ("127.0.0.1", p) for n, p in zip(names, free_ports(NODES))}
    servers = [NodeServer(n, *addrs[n], dict(addrs), device_mode=True,
                          durability=False,
                          journal_dir=str(journal_root / n),
                          journal_sync="client", wire_codec_name="binary")
               for n in names]
    client = ClusterClient([(n, *addrs[n]) for n in names], timeout=60.0,
                           codec="binary")
    keys = [k * ((1 << 32) // KEYS) for k in range(KEYS)]
    random.Random(5).shuffle(keys)                 # rank -> key
    verifier = CompositeVerifier(StrictSerializabilityVerifier(),
                                 ListAppendCycleChecker())
    answered, counter = [], itertools.count(1)

    async def one_client(rng):
        for _ in range(TXNS_PER_CLIENT):
            chosen = []
            while len(chosen) < WIDTH:
                key = keys[rng.next_zipf(KEYS, 0.9)]
                if key not in chosen:
                    chosen.append(key)
            ops, writes = [], {}
            for key in chosen:
                if rng.decide(0.5):
                    value = next(counter)
                    ops.append(["append", key, value])
                    writes[token_of(key)] = (value,)
                else:
                    ops.append(["r", key, None])
            op_id, start = verifier.begin(), _now_us()
            body = await client.submit_retry(
                ops, node=names[rng.next_int(NODES)])
            reads = {token_of(op[1]): tuple(op[2])
                     for op in body["txn"] if op[0] == "r"}
            end = _now_us()
            verifier.on_result(op_id, start, end, reads, writes)
            answered.append((start, end, reads, writes))

    try:
        for s in servers:
            await s.start()
        await client.connect()
        for n in names:
            await client.ping(n, timeout=60.0)
        before = [s.stats()["coordination"] for s in servers]
        await asyncio.gather(*[one_client(RandomSource(100 + i))
                               for i in range(CLIENTS)])
        after = [s.stats()["coordination"] for s in servers]
        finals = {}
        for at in range(0, KEYS, 50):              # read back, 50 keys a txn
            start = _now_us()
            body = await client.submit_retry(
                [["r", key, None] for key in keys[at:at + 50]])
            reads = {token_of(op[1]): tuple(op[2]) for op in body["txn"]}
            answered.append((start, _now_us(), reads, {}))
            finals.update(reads)
        failures = sum(len(s.proc.failures) for s in servers)
        retries = client.n_retries
    finally:
        await client.close()
        for s in servers:
            for link in s.links.values():
                await link.close()
        for s in servers:
            if s.frame_server is not None:
                await asyncio.wait_for(s.close(), 30.0)
    return verifier, answered, finals, before, after, failures, retries


def test_five_served_nodes_multikey_zipf_replay_and_coordination(tmp_path):
    threshold = gc.get_threshold()
    try:
        verifier, answered, finals, before, after, failures, retries = \
            asyncio.run(_serve_and_drive(tmp_path))
    finally:
        gc.unfreeze()            # NodeServer.start() retunes the collector
        gc.set_threshold(*threshold)
    assert failures == 0
    assert len(finals) == KEYS
    for token, final in finals.items():
        verifier.set_final(token, final)
    verifier.verify()
    order = serial_kv.replay(answered, [], finals)
    assert len(order) == len(answered) == CLIENTS * TXNS_PER_CLIENT + KEYS // 50
    if after[0] is None:         # ACCORD_TPU_OBS=off: nothing counts paths
        return
    assert all(set(c) == {"fast", "slow", "recoveries"} for c in after)
    decided = sum(a["fast"] + a["slow"] - b["fast"] - b["slow"]
                  for a, b in zip(after, before))
    # every client txn was coordinated once by the node it was sent to (a
    # retried attempt once more); the read-back came after the second look
    assert CLIENTS * TXNS_PER_CLIENT <= decided \
        <= CLIENTS * TXNS_PER_CLIENT + retries
    assert sum(a["fast"] for a in after) > 0
