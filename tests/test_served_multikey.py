"""Five NodeServers in this process over loopback TCP, device on, under
multi-key txns on a skewed key space (the lin-kv-5n-zipf shape at a small
size): the history replays through the plain reference and passes the
composite verifier, ``stats()["coordination"]`` accounts for every txn the
nodes coordinated, and the stores' drain ticks, whose live sets are a few
slots, were swept on the host by the router's choice, but for the audits."""

import asyncio
import gc
import itertools
import random
import time

import pytest

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
        devs = [st.device for s in servers
                for st in s.proc.node.command_stores.stores]
        device_stats = [s.stats()["device"] for s in servers]
    finally:
        await client.close()
        for s in servers:
            for link in s.links.values():
                await link.close()
        for s in servers:
            if s.frame_server is not None:
                await asyncio.wait_for(s.close(), 30.0)
    return (verifier, answered, finals, before, after, failures, retries,
            devs, device_stats)


@pytest.fixture(scope="module")
def served_run(tmp_path_factory):
    """One run of the cluster for every test of this file."""
    from accord_tpu.local.device_index import DeviceState
    threshold = gc.get_threshold()
    calib = DeviceState._CALIB
    # The run prices with what this host measures, but for the link and
    # the sweep: a round trip is the chip's millisecond (a CPU "device"
    # answers in 10 us) and a swept row an idle core's microsecond.  Under
    # tier-1's six workers the probe read the sweep 5-10 x slower, and with
    # a 10 us round trip beside it a live set of ten rows priced to the
    # device: ticks that were no audits, some of them fused, which
    # test_served_drain_ticks_are_swept_on_the_host_by_price then counted.
    DeviceState._CALIB = {**DeviceState._measure_route_calibration(),
                          "rtt": 1e-3, "rtt_mesh": 1e-3, "c_sweep": 1e-6}
    try:
        return asyncio.run(_serve_and_drive(tmp_path_factory.mktemp("wal")))
    finally:
        DeviceState._CALIB = calib
        gc.unfreeze()            # NodeServer.start() retunes the collector
        gc.set_threshold(*threshold)


def test_five_served_nodes_multikey_zipf_replay_and_coordination(served_run):
    verifier, answered, finals, before, after, failures, retries = \
        served_run[:7]
    assert failures == 0
    assert len(finals) == KEYS
    for token, final in finals.items():
        verifier.set_final(token, final)
    verifier.verify()
    order = serial_kv.replay(answered, [], finals)
    assert len(order) == len(answered) == CLIENTS * TXNS_PER_CLIENT + KEYS // 50
    if after[0] is None:         # ACCORD_TPU_OBS=off: nothing counts paths
        return
    assert all(set(c) == {"fast", "slow", "recoveries", "range_txns",
                          "key_txns", "scan_rows", "deps_txns",
                          "deps_entries", "patience_deferrals",
                          "patience_micros", "n_max_conflicts_adds",
                          "max_conflicts_boundaries"} for c in after)
    # the home patience is never shorter than two scans of 0.5 s
    assert all(c["patience_micros"] >= 1_000_000
               and c["patience_deferrals"] >= 0 for c in after)
    # every coordinator made the deps of the txns it decided stable, and
    # on hot Zipf keys some of them named a dependency
    assert all(c["deps_txns"] > 0 for c in after)
    assert sum(c["deps_entries"] for c in after) > 0
    # every node's stores spliced the run's keys into their MaxConflicts
    assert all(a["n_max_conflicts_adds"] > b["n_max_conflicts_adds"]
               and a["max_conflicts_boundaries"] > 0
               for a, b in zip(after, before))
    # every decision was a key-domain txn's: nothing here scans
    assert all(c["key_txns"] == c["fast"] + c["slow"]
               and c["range_txns"] == c["scan_rows"] == 0 for c in after)
    decided = sum(a["fast"] + a["slow"] - b["fast"] - b["slow"]
                  for a, b in zip(after, before))
    # every client txn was coordinated once by the node it was sent to (a
    # retried attempt once more); the read-back came after the second look
    assert CLIENTS * TXNS_PER_CLIENT <= decided \
        <= CLIENTS * TXNS_PER_CLIENT + retries
    assert sum(a["fast"] for a in after) > 0


def test_served_drain_ticks_are_swept_on_the_host_by_price(served_run):
    """Hot keys chain txns and every store ticks, but no tick's live set
    pays for a device round trip: the router sweeps them on the host, none
    as a fallback, in the run whose history the test above verifies.  The
    device ticks there are, are the stores' audits of the device route, its
    first tick and then one a store every TICK_AUDIT_MICROS at most, and none
    is fused."""
    devs = served_run[7]
    kinds = {kind: sum(d.kernel_times.get(kind, (0, 0.0))[0] for d in devs)
             for kind in ("drain_tick_host", "drain_tick_wait",
                          "drain_tick_dispatch")}
    audits = sum(d.n_audit_ticks for d in devs)
    assert kinds["drain_tick_host"] > 0, kinds
    assert kinds["drain_tick_wait"] == kinds["drain_tick_dispatch"] == audits
    for d in devs:
        uptime = d.store.node.now_micros()
        assert 1 <= d.n_audit_ticks <= 1 + uptime // d.TICK_AUDIT_MICROS
    assert sum(d.n_priced_host_ticks for d in devs) == kinds["drain_tick_host"]
    assert sum(d.n_host_ticks + d.n_fused_ticks + d.n_device_faults
               for d in devs) == 0


def test_attribution_index_is_refreshed_by_token_and_timed(served_run):
    """Every flush crosses DeviceState._attr_index once, under its own
    kernel_times kind; the index is maintained (far fewer token reads than
    flushes x tokens held); and a run whose queries all priced to the host
    never had a device image assembled.  stats()["device"] carries the
    counters."""
    devs, device_stats = served_run[7], served_run[8]

    def calls(kind):
        return sum(d.kernel_times.get(kind, (0, 0.0))[0] for d in devs)

    on_device = sum(d.n_bucketed_queries + d.n_dense_queries
                    + d.n_fused_queries + d.n_mesh_queries for d in devs)
    assert on_device == 0, "the run's queries were to price to the host"
    flushes = calls("dispatch_host")
    assert flushes > 0 and calls("host_attr_index") == flushes
    assert sum(d.n_attr_device_builds for d in devs) == 0
    refreshes = sum(d.n_attr_refreshes for d in devs)
    reads = sum(d.n_attr_tokens_refreshed for d in devs)
    held = sum(d.n_attr_tokens for d in devs)
    assert 0 < refreshes <= flushes and held > 0
    assert reads < flushes * held // 10, (reads, flushes, held)
    assert {k: sum(st[k] for st in device_stats) for k in device_stats[0]} \
        == {"attr_refreshes": refreshes, "attr_tokens_refreshed": reads,
            "attr_device_builds": 0, "attr_tokens": held,
            "range_queries": 0, "range_device_queries": 0,
            # no flush went to the device, so no table was ever synced
            "sync_launches": 0, "sync_uploads": 0,
            # every txn the run witnessed is live in the index (nothing
            # truncates), none of it installed as a history
            "live_slots": sum(d.n_live_slots for d in devs),
            "history_registered": 0,
            # the host route's index, built whole once a store and then
            # kept by folding in the registrations between flushes
            "host_index_builds": sum(d.n_host_index_builds for d in devs),
            "host_index_folds": sum(d.n_host_index_folds for d in devs)}
    assert sum(d.n_live_slots for d in devs) > 0
    assert 0 < sum(d.n_host_index_builds for d in devs) \
        < sum(d.n_host_index_folds for d in devs)
