"""The scan op on the served path: three NodeServers in this process over
loopback TCP, device on, records loaded through install_snapshot (the route
a bootstrapping replica's snapshot takes) and inserted by clients while
scans run.  ``["scan", [lo, hi], null]`` is coordinated as a range-domain
Read through ClusterClient.submit; the history, scans expanded over the
known keys, passes the composite verifier and replays through the plain
reference; the counters of ``stats()`` account for it."""

import asyncio
import bisect
import gc
import random
import time

import pytest

from accord_tpu.net.harness import free_ports
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
from accord_tpu.sim import serial_kv
from accord_tpu.sim.elle import CompositeVerifier, ListAppendCycleChecker
from accord_tpu.sim.verifier import StrictSerializabilityVerifier

NODES, LOADED, CLIENTS, OPS_PER_CLIENT = 3, 240, 4, 40
TOKEN_SPACE = 1 << 32
SHARD = TOKEN_SPACE // 16


def _now_us():
    return time.monotonic_ns() // 1_000


def _datum(n):
    return f"record-{n}-" + "x" * 40


async def _serve_and_drive(journal_root):
    from accord_tpu.net.client import ClusterClient, TxnFailed
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
    rng = random.Random(11)
    tokens = rng.sample(range(TOKEN_SPACE), LOADED + CLIENTS * OPS_PER_CLIENT)
    loaded = {t: (_datum(n),) for n, t in enumerate(tokens[:LOADED])}
    to_insert = list(enumerate(tokens[LOADED:], start=LOADED))
    known = sorted(loaded)           # every key loaded or issued so far
    verifier = CompositeVerifier(StrictSerializabilityVerifier(),
                                 ListAppendCycleChecker())
    answered, refused = [], []

    async def scan(lo, hi, node=None):
        op_id, start = verifier.begin(), _now_us()
        in_range = known[bisect.bisect_left(known, lo):
                         bisect.bisect_left(known, hi)]
        body = await client.submit_retry([["scan", [lo, hi], None]],
                                         node=node)
        end = _now_us()
        (f, bounds, rows), = body["txn"]
        assert f == "scan" and bounds == [lo, hi]
        rows = [(k, tuple(v)) for k, v in rows]
        got = dict(rows)
        verifier.on_result(op_id, start, end,
                           {k: got.get(k, ()) for k in in_range} | got, {})
        answered.append((start, end, {}, {}, [((lo, hi), rows)]))
        return rows

    async def one_client(crng):
        while to_insert:
            node = names[crng.randrange(NODES)]
            if crng.random() < 0.5:
                n, token = to_insert.pop()
                bisect.insort(known, token)
                op_id, start = verifier.begin(), _now_us()
                await client.submit_retry([["append", token, _datum(n)]],
                                          node=node)
                end = _now_us()
                writes = {token: (_datum(n),)}
                verifier.on_result(op_id, start, end, {}, writes)
                answered.append((start, end, {}, writes))
            else:
                at = crng.randrange(len(known))
                lo = known[at]
                hi = known[min(at + crng.randint(1, 20), len(known) - 1)] + 1
                await scan(lo, hi, node)

    try:
        for s in servers:
            await s.start()
        # the load: a snapshot installed on every replica's data store at a
        # timestamp below every txn
        load_id = TxnId.create(1, 1, TxnKind.Write, Domain.Key, 1)
        for s in servers:
            s.proc.node.data_store.install_snapshot(
                {t: [(v, load_id, load_id)] for t, v in loaded.items()})
        await client.connect()
        for n in names:
            await client.ping(n, timeout=60.0)
        before = [s.stats() for s in servers]
        boundary = await scan(7 * SHARD - SHARD // 2, 7 * SHARD + SHARD // 2)
        two_stores = await scan(TOKEN_SPACE // 2 - SHARD,
                                TOKEN_SPACE // 2 + SHARD)
        await asyncio.gather(*[one_client(random.Random(100 + i))
                               for i in range(CLIENTS)])
        for ops in ([["scan", [0, 100], None], ["append", 5, "v"]],
                    [["scan", [100, 100], None]],
                    [["scan", [0, TOKEN_SPACE + 1], None]]):
            try:
                await client.submit(ops)
                refused.append(None)
            except TxnFailed as e:
                refused.append(e.body)
        mixed = (await client.submit_retry(
            [["r", known[3], None], ["scan", [known[10], known[12] + 1],
                                     None]]))["txn"]
        # read back: scans that cover the whole token space
        finals = {}
        for lo in range(0, TOKEN_SPACE, TOKEN_SPACE // 8):
            finals.update(await scan(lo, lo + TOKEN_SPACE // 8))
        after = [s.stats() for s in servers]
        failures = sum(len(s.proc.failures) for s in servers)
        stores_held = [[sorted(t for t in loaded
                               if st.owned_current().contains_token(t))
                        for st in s.proc.node.command_stores.stores]
                       for s in servers]
    finally:
        await client.close()
        for s in servers:
            for link in s.links.values():
                await link.close()
        for s in servers:
            if s.frame_server is not None:
                await asyncio.wait_for(s.close(), 30.0)
    return dict(verifier=verifier, answered=answered, finals=finals,
                loaded=loaded, known=known, boundary=boundary,
                two_stores=two_stores, refused=refused, mixed=mixed,
                before=before, after=after, failures=failures,
                stores_held=stores_held)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One run of the cluster for every test of this file."""
    from accord_tpu.local.device_index import DeviceState
    threshold = gc.get_threshold()
    calib = DeviceState._CALIB
    DeviceState._CALIB = None    # the run prices with what it measures
    try:
        return asyncio.run(_serve_and_drive(tmp_path_factory.mktemp("wal")))
    finally:
        DeviceState._CALIB = calib
        gc.unfreeze()            # NodeServer.start() retunes the collector
        gc.set_threshold(*threshold)


def _held(loaded, lo, hi):
    return [(t, v) for t, v in sorted(loaded.items()) if lo <= t < hi]


def test_a_scan_crosses_a_shard_boundary_and_both_stores(run):
    loaded = run["loaded"]
    lo, hi = 7 * SHARD - SHARD // 2, 7 * SHARD + SHARD // 2
    assert run["boundary"] == _held(loaded, lo, hi)
    assert any(t < 7 * SHARD for t, _v in run["boundary"])
    assert any(t >= 7 * SHARD for t, _v in run["boundary"])
    lo, hi = TOKEN_SPACE // 2 - SHARD, TOKEN_SPACE // 2 + SHARD
    assert run["two_stores"] == _held(loaded, lo, hi)
    # each node's two stores each hold part of what that scan returned
    scanned = {t for t, _v in run["two_stores"]}
    for stores in run["stores_held"]:
        assert len(stores) == 2
        assert all(scanned & set(held) for held in stores)


def test_scans_see_loaded_and_inserted_records_and_the_history_is_serial(run):
    assert run["failures"] == 0
    finals, loaded, known = run["finals"], run["loaded"], run["known"]
    assert sorted(finals) == known
    assert len(finals) == LOADED + CLIENTS * OPS_PER_CLIENT > LOADED
    assert all(finals[t] == v for t, v in loaded.items())
    verifier = run["verifier"]
    for token, final in finals.items():
        verifier.set_final(token, final)
    verifier.verify()
    order = serial_kv.replay(run["answered"], [], finals, initial=loaded)
    assert len(order) == len(run["answered"])


def test_scan_with_append_and_malformed_ranges_are_refused_with_code_10(run):
    assert [r and r["code"] for r in run["refused"]] == [10, 10, 10]
    assert "scan with append" in run["refused"][0]["text"]


def test_a_point_read_rides_a_scan_txn_as_a_width_1_range(run):
    known, finals = run["known"], run["finals"]
    (f1, k1, v1), (f2, bounds, rows) = run["mixed"]
    assert (f1, k1, tuple(v1)) == ("r", known[3], finals[known[3]])
    assert f2 == "scan" and [k for k, _v in rows] == known[10:13]


def test_stats_count_range_txns_scan_rows_and_the_data_stores_reads(run):
    before, after = run["before"], run["after"]
    if after[0]["coordination"] is None:     # ACCORD_TPU_OBS=off
        return

    def moved(section, key):
        return sum(a[section][key] - b[section][key]
                   for a, b in zip(after, before))

    scans = [t for t in run["answered"] if len(t) == 5]
    inserts = [t for t in run["answered"] if len(t) == 4]
    # refused txns coordinate nothing; the mixed r+scan txn is one more
    assert moved("coordination", "range_txns") >= len(scans) + 1
    assert moved("coordination", "key_txns") >= len(inserts)
    rows = sum(len(rows) for *_x, ((_b, rows),) in scans)
    assert moved("coordination", "scan_rows") >= rows + 3
    assert moved("data", "scan_calls") >= len(scans)
    assert moved("data", "scan_host_s") > 0
    assert moved("device", "range_queries") >= len(scans)
    assert 0 <= moved("device", "range_device_queries") \
        <= moved("device", "range_queries")
