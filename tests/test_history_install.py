"""A replica's untruncated history installed in one pass
(``Node.install_history`` -> ``CommandStore.install_history`` ->
``DeviceState.register_history``): the device mirror it leaves is the one N
per-txn registrations leave, its capacity is sized once, and a served
cluster holding such a history runs YCSB E traffic over it: every answer
strictly serializable and replayed by the plain reference, no history txn
ever fetched, and an insert into a scanned range executes past the history
scan it depends on without waiting."""

import asyncio
import bisect
import collections
import gc
import random
import time

import numpy as np
import pytest

from accord_tpu.local.commands_for_key import CommandsForKey, InternalStatus
from accord_tpu.local.device_index import _DepsMirror
from accord_tpu.net.harness import free_ports
from accord_tpu.primitives.deps import DepsBuilder
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
from accord_tpu.sim import serial_kv
from accord_tpu.sim.elle import CompositeVerifier, ListAppendCycleChecker
from accord_tpu.sim.verifier import StrictSerializabilityVerifier
from tests import deps_oracle
from tests.conftest import make_device_state
from tests.test_routing import _unpack_builders

KEYSPACE = 1 << 20
APPLIED = int(InternalStatus.APPLIED)
COLUMNS = ("msb", "lsb", "node", "kind", "domain", "status", "lo", "hi",
           "emsb", "elsb", "enode", "eknown", "obj")


def _history(seed, n):
    """``n`` applied txns of a YCSB E mix: scans (range reads over one
    interval) and inserts (key writes on one point), ids ascending."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        node = 1 + int(rng.integers(0, 3))
        if rng.random() < 0.8:
            lo = int(rng.integers(0, KEYSPACE - 40_000))
            tid = TxnId.create(1, 10 + i, TxnKind.Read, Domain.Range, node)
            keys = Ranges.of(Range(lo, lo + int(rng.integers(1, 40_000))))
        else:
            tid = TxnId.create(1, 10 + i, TxnKind.Write, Domain.Key, node)
            keys = Keys([IntKey(int(rng.integers(0, KEYSPACE)))])
        rows.append((tid, tid, keys))
    return rows


def _queries(seed, n_hist, count=48):
    rng = np.random.default_rng(seed + 1)
    qs = []
    for j in range(count):
        hlc = 10 + n_hist + 1 + j
        if j % 4:
            lo = int(rng.integers(0, KEYSPACE - 40_000))
            bound = TxnId.create(1, hlc, TxnKind.Read, Domain.Range, 1)
            toks, rngs = [], [Range(lo, lo + int(rng.integers(1, 40_000)))]
        else:
            bound = TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1)
            toks, rngs = [int(rng.integers(0, KEYSPACE))], []
        qs.append((bound, bound, bound.kind().witnesses(), toks, rngs))
    return qs


def _store_with_cfks(rows, mesh):
    """A DeviceState whose store's CommandsForKey hold the rows' inserts,
    applied (what CommandStore.install_history writes before the mirror)."""
    store, dev, safe = make_device_state(mesh=mesh)
    for tid, ex, keys in rows:
        if not isinstance(keys, Ranges):
            for key in keys:
                cfk = store.commands_for_key.setdefault(
                    key.token(), CommandsForKey(key.token()))
                cfk.update(tid, InternalStatus.APPLIED, ex)
    return store, dev, safe


def _one_by_one(rows, mesh):
    store, dev, safe = _store_with_cfks(rows, mesh)
    for tid, ex, keys in rows:           # what commands._register_txn does
        dev.register(tid, APPLIED, keys)
        dev.update_status(tid, APPLIED, ex)
    return store, dev, safe


def _in_bulk(rows, mesh):
    store, dev, safe = _store_with_cfks(rows, mesh)
    assert dev.register_history(rows, APPLIED) == len(rows)
    return store, dev, safe


@pytest.mark.parametrize("mesh", [None, "auto"])
@pytest.mark.parametrize("seed", [1, 8])
def test_bulk_install_leaves_the_mirror_per_txn_registration_leaves(seed,
                                                                    mesh):
    rows = _history(seed, 2000)
    _s1, one, safe1 = _one_by_one(rows, mesh)
    _s2, bulk, safe2 = _in_bulk(rows, mesh)
    a, b = one.deps, bulk.deps
    assert (a.capacity, a.max_intervals) == (b.capacity, b.max_intervals)
    for col in COLUMNS:
        assert np.array_equal(getattr(a, col), getattr(b, col)), col
    assert a.slot_of == b.slot_of and a.id_of == b.id_of
    assert a.free_slots == b.free_slots
    assert a.n_live == b.n_live == bulk.n_live_slots == 2000
    assert a.wide_entries == b.wide_entries and a.wide_entries
    assert a.bucket_row == b.bucket_row and a._blen == b._blen
    assert np.array_equal(a._brec, b._brec)
    assert bulk.n_history_registered == 2000 and one.n_history_registered == 0
    qs = _queries(seed, len(rows))
    answers = []
    for dev, safe in ((one, safe1), (bulk, safe2)):
        for route in ("host", "device"):
            dev.route_override = route
            answers.append(_unpack_builders(
                deps_oracle.flush_builders(dev, safe, qs)))
    assert all(ans == answers[0] for ans in answers[1:])
    # the inserts are the attribution index's pivots, read from the same
    # CommandsForKey either way
    assert one._aidx.toks.tolist() == bulk._aidx.toks.tolist()
    assert one._aidx.n_execs == bulk._aidx.n_execs > 0
    # the history is what they answer: the scans name the inserts in their
    # ranges, the inserts the scans over their keys
    assert sum(len(k) + len(r) for k, r in answers[0]) > 0
    assert answers[0] == _unpack_builders(
        deps_oracle.reference_builders(bulk, safe2, qs))


def test_bulk_install_sizes_the_capacity_once(monkeypatch):
    rows = _history(3, 2000)

    def doubling(self):
        raise AssertionError("a doubling during a bulk install")

    monkeypatch.setattr(_DepsMirror, "_grow_capacity", doubling)
    _store, dev, _safe = _in_bulk(rows, None)
    assert dev.deps.capacity == 2048 and dev.n_live_slots == 2000
    assert dev.deps.n_interval_grows == 0
    # a second batch on top grows it once more, to the next power of two
    more = [(TxnId.create(1, 5000 + i, tid.kind(), tid.domain(), tid.node),
             TxnId.create(1, 5000 + i, tid.kind(), tid.domain(), tid.node),
             keys) for i, (tid, _e, keys) in enumerate(_history(4, 300))]
    dev.register_history(more, APPLIED)
    assert dev.deps.capacity == 4096 and dev.n_live_slots == 2300
    # a txn the mirror holds already takes the per-txn path, counted once
    again = dev.register_history(rows[:5], APPLIED)
    assert again == 5 and dev.n_live_slots == 2300
    assert dev.n_history_registered == 2300


@pytest.mark.parametrize("seed", [2, 5])
def test_a_footprint_is_sliced_to_the_store_s_ranges(seed):
    """CommandStore.install_history keeps the rows' slices of its own
    ranges: footprints inside, outside and across its bounds."""
    from accord_tpu.local.command_store import CommandStore

    class _Node:
        device_mode = False
        journal = None

        def progress_log_factory(self, store):
            return None

    store = CommandStore(0, _Node())
    store.ranges_for_epoch.snapshot(1, Ranges.of(Range(100, 200),
                                                 Range(300, 400)))
    rng = random.Random(seed)
    rows, want = [], {}
    for i in range(200):
        tid = TxnId.create(1, 10 + i, TxnKind.Read, Domain.Range, 1)
        lo = rng.randrange(0, 480)
        keys = Ranges.of(Range(lo, lo + rng.randrange(1, 120)))
        if rng.random() < 0.3:
            tid = TxnId.create(1, 10 + i, TxnKind.Write, Domain.Key, 1)
            keys = Keys([IntKey(t) for t in sorted(rng.sample(range(500),
                                                              3))])
        rows.append((tid, tid, keys))
        sliced = keys.slice(store.ranges_for_epoch.all())
        if not sliced.is_empty():
            want[tid] = sliced
    assert store.install_history(rows) == len(want)
    assert set(store.commands) == set(want)
    assert all(c.is_applied() for c in store.commands.values())
    for tid, keys in want.items():
        if isinstance(keys, Ranges):
            assert store.range_commands[tid] == keys
        else:
            for key in keys:
                assert store.commands_for_key[key.token()].get(tid).status \
                    is InternalStatus.APPLIED


# -- a served cluster over an installed history ----------------------------

NODES, LOADED, HISTORY, CLIENTS, OPS, ADMIT = 3, 300, 512, 4, 15, 8
TOKEN_SPACE = 1 << 32


def _datum(n):
    return f"record-{n}-" + "y" * 30


async def _serve(journal_root):
    from accord_tpu.net.client import ClusterClient
    from accord_tpu.net.server import NodeServer
    names = [f"n{i}" for i in range(1, NODES + 1)]
    addrs = {n: ("127.0.0.1", p) for n, p in zip(names, free_ports(NODES))}
    # admit_max 8 a node: the stores load their programs for flushes of
    # up to 24 queries (three gates' worth), 32 padded
    servers = [NodeServer(n, *addrs[n], dict(addrs), device_mode=True,
                          durability=False, admit_max=ADMIT,
                          journal_dir=str(journal_root / n),
                          journal_sync="client", wire_codec_name="binary")
               for n in names]
    client = ClusterClient([(n, *addrs[n]) for n in names], timeout=60.0,
                           codec="binary")
    rng = random.Random(23)
    tokens = rng.sample(range(TOKEN_SPACE), LOADED + CLIENTS * OPS + 1)
    loaded = {t: (_datum(n),) for n, t in enumerate(tokens[:LOADED])}
    fresh = list(enumerate(tokens[LOADED:], start=LOADED))
    known = sorted(loaded)
    verifier = CompositeVerifier(StrictSerializabilityVerifier(),
                                 ListAppendCycleChecker())
    answered, fetched, sent = [], [], collections.Counter()
    # the history: scans over 1-20 loaded keys and inserts of loaded
    # records (their writes are in the load), one store in two each
    history = []
    for i in range(HISTORY * 2):
        node = 2 + rng.randrange(NODES)        # "n1" .. "n3" are ids 2 .. 4
        if rng.random() < 0.9:
            at = rng.randrange(len(known) - 21)
            tid = TxnId.create(1, 2 + i, TxnKind.Read, Domain.Range, node)
            keys = Ranges.of(Range(known[at],
                                   known[at + rng.randint(1, 20)]))
        else:
            tid = TxnId.create(1, 2 + i, TxnKind.Write, Domain.Key, node)
            keys = Keys([IntKey(known[rng.randrange(len(known))])])
        history.append((tid, tid, keys))
    hist_ids = {tid for tid, _e, _k in history}
    # the first insert lands inside the first history scan's range
    target, scanned = next((t, r[0]) for t, _e, r in history
                           if isinstance(r, Ranges))
    inside_token = scanned.start + 1
    assert inside_token not in loaded

    async def scan(lo, hi, node=None):
        op_id, start = verifier.begin(), time.monotonic_ns() // 1000
        in_range = known[bisect.bisect_left(known, lo):
                         bisect.bisect_left(known, hi)]
        body = await client.submit_retry([["scan", [lo, hi], None]],
                                         node=node)
        end = time.monotonic_ns() // 1000
        rows = [(k, tuple(v)) for k, v in body["txn"][0][2]]
        verifier.on_result(op_id, start, end,
                           {k: () for k in in_range} | dict(rows), {})
        answered.append((start, end, {}, {}, [((lo, hi), rows)]))
        return rows

    async def insert(n, token, node=None):
        bisect.insort(known, token)
        op_id, start = verifier.begin(), time.monotonic_ns() // 1000
        await client.submit_retry([["append", token, _datum(n)]], node=node)
        end = time.monotonic_ns() // 1000
        writes = {token: (_datum(n),)}
        verifier.on_result(op_id, start, end, {}, writes)
        answered.append((start, end, {}, writes))

    async def one_client(crng):
        while fresh:
            node = names[crng.randrange(NODES)]
            if crng.random() < 0.3:
                await insert(*fresh.pop(), node)
            else:
                at = crng.randrange(len(known))
                hi = known[min(at + crng.randint(1, 20), len(known) - 1)] + 1
                await scan(known[at], hi, node)

    try:
        for s in servers:
            await s.start()
        load_id = TxnId.create(1, 1, TxnKind.Write, Domain.Key, 1)
        held = []
        for s in servers:
            s.proc.node.data_store.install_snapshot(
                {t: [(v, load_id, load_id)] for t, v in loaded.items()})
            held.append(s.install_history(history))
            node = s.proc.node
            real = node.send

            def send(to, request, callback=None, _real=real):
                sent[type(request).__name__] += 1
                if type(request).__name__ == "CheckStatus":
                    fetched.append(request.txn_id)
                return _real(to, request, callback)

            node.send = send
        live = [st.device.n_live_slots for s in servers
                for st in s.proc.node.command_stores.stores]
        loaded_batch = {st.device.loaded_batch for s in servers
                        for st in s.proc.node.command_stores.stores}
        await client.connect()
        for n in names:
            await client.ping(n, timeout=60.0)
        inside_n = LOADED + CLIENTS * OPS
        await insert(inside_n, inside_token)
        await asyncio.gather(*[one_client(random.Random(300 + i))
                               for i in range(CLIENTS)])
        finals = {}
        for lo in range(0, TOKEN_SPACE, TOKEN_SPACE // 4):
            finals.update(await scan(lo, lo + TOKEN_SPACE // 4))
        # the insert inside the history scan, at every replica
        inside = []
        for s in servers:
            for st in s.proc.node.command_stores.stores:
                if not st.owned_current().contains_token(inside_token):
                    continue
                cmd = next(c for t, c in st.commands.items()
                           if t.domain() == Domain.Key and c.writes
                           is not None and inside_token in
                           c.writes.keys.tokens())
                deps = cmd.partial_deps
                inside.append((cmd.is_applied(),
                               set(deps.range_deps.txn_ids) & hist_ids,
                               st.commands[target].is_applied(),
                               target in st.device.drain.slot_of))
        failures = sum(len(s.proc.failures) for s in servers)
    finally:
        await client.close()
        for s in servers:
            for link in s.links.values():
                await link.close()
        for s in servers:
            if s.frame_server is not None:
                await asyncio.wait_for(s.close(), 30.0)
    return dict(verifier=verifier, answered=answered, finals=finals,
                loaded=loaded, known=known, fetched=fetched, sent=sent,
                hist_ids=hist_ids, held=held, live=live, inside=inside,
                loaded_batch=loaded_batch,
                failures=failures, target=target)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from accord_tpu.local.device_index import DeviceState
    threshold = gc.get_threshold()
    calib = DeviceState._CALIB
    DeviceState._CALIB = None
    try:
        return asyncio.run(_serve(tmp_path_factory.mktemp("wal")))
    finally:
        DeviceState._CALIB = calib
        gc.unfreeze()
        gc.set_threshold(*threshold)


def test_every_store_holds_its_share_of_the_history(served):
    # each node's two stores split the history about evenly between them
    assert all(h >= 2 * HISTORY for h in served["held"])
    assert all(HISTORY // 2 < n < 2 * HISTORY for n in served["live"])
    # every store loaded its flush programs for what the gates admit
    assert served["loaded_batch"] == {32}


def test_ycsb_e_over_an_installed_history_is_serial_and_fetches_nothing(
        served):
    assert served["failures"] == 0
    finals, loaded, known = served["finals"], served["loaded"], \
        served["known"]
    assert sorted(finals) == known
    assert all(finals[t] == v for t, v in loaded.items())
    verifier = served["verifier"]
    for token, final in finals.items():
        verifier.set_final(token, final)
    verifier.verify()
    order = serial_kv.replay(served["answered"], [], finals, initial=loaded)
    assert len(order) == len(served["answered"])
    # a later txn that depends on a history txn finds it applied: no
    # CheckStatus is ever sent for one (the nodes' sends are all seen)
    assert served["sent"]["PreAccept"] > 0
    assert not set(served["fetched"]) & served["hist_ids"]


def test_an_insert_into_a_history_scan_executes_without_waiting_on_it(
        served):
    inside = served["inside"]
    assert len(inside) == 3          # rf 3: one store a node holds the key
    for applied, hist_deps, target_applied, drained in inside:
        assert applied and target_applied
        assert served["target"] in hist_deps
        # never an edge of the drain graph: cleared at Stable, as applied
        assert not drained


# -- the programs a serving store loads before traffic ---------------------

def _loaded_store(max_flush=16):
    """A store holding a history, its device flush programs loaded
    (warm_flush) for flushes of up to ``max_flush`` queries, on the
    single-device path."""
    from accord_tpu.local.device_index import flush_batches
    rows = _history(11, 1500)
    _store, dev, safe = _in_bulk(rows, None)
    dev.warm_flush(flush_batches(max_flush))
    return dev, safe, _queries(11, len(rows), count=32)


def test_warm_flush_loads_the_flush_programs_and_fixes_the_budgets():
    from accord_tpu.local import device_index as di
    from accord_tpu.ops import deps_kernel as dk
    dev, safe, qs = _loaded_store()
    assert dev.loaded_budgets == (dev._batch_flat, dev._batch_k)
    assert dev.loaded_batch == 16
    kernels = dk.calculate_deps_flat_attr._cache_size()
    syncs = di._sync_tables._cache_size()
    dev.route_override = "device"
    for b in (1, 2, 3, 5, 8, 13, 16):          # pads to a loaded size
        for at in range(0, len(qs) - b + 1, 2 * b):
            deps_oracle.flush_builders(dev, safe, qs[at:at + b])
            # a scan registered between flushes: a dirty row to sync (an
            # interval too wide for the buckets, so no bucket cell)
            lo = 5_000 * at + b
            dev.register(TxnId.create(1, 90_000 + at + b, TxnKind.Read,
                                      Domain.Range, 1), APPLIED,
                         Ranges.of(Range(lo, lo + 30_000)))
    # no new program: the batch sizes and the dirty rows' syncs were loaded
    assert dk.calculate_deps_flat_attr._cache_size() == kernels
    assert di._sync_tables._cache_size() == syncs
    assert dev.loaded_budgets == (dev._batch_flat, dev._batch_k)


def test_flush_batches_are_the_powers_of_two_up_to_the_largest_flush():
    from accord_tpu.local.device_index import flush_batches
    assert flush_batches(1) == (1,)
    assert flush_batches(16) == (1, 2, 4, 8, 16)
    assert flush_batches(192) == (1, 2, 4, 8, 16, 32, 64, 128, 256)


def _assert_host_answered(dev, n, n_range):
    """Every one of a flush's ``n`` queries counted as answered by the host
    route past the loaded programs, none by a device route."""
    assert dev.n_budget_host_queries == n
    assert dev.n_host_queries == n
    assert dev.n_dense_queries == dev.n_bucketed_queries == 0
    assert dev.n_fused_queries == dev.n_mesh_queries == 0
    assert dev.n_range_queries == n_range > 0
    assert dev.n_range_device_queries == 0
    assert dev.n_fallback_queries == 0


def _n_range(qs):
    return sum(1 for q in qs if q[0].domain() == Domain.Range)


@pytest.mark.parametrize("seed", [4, 9])
def test_a_flush_past_the_loaded_budgets_is_answered_by_the_host(seed):
    rows = _history(seed, 1500)
    qs = _queries(seed, len(rows), count=24)
    want = _unpack_builders(deps_oracle.reference_builders(
        *_in_bulk(rows, None)[1:], qs))
    # tiny loaded budgets: every serving flush launches at them and
    # overflows them; the learned budgets are not what it launches at
    _s, dev, safe = _in_bulk(rows, None)
    learned = (dev._batch_flat, dev._batch_k)
    dev.loaded_budgets, dev.loaded_batch = (16, 2), 32
    dev.route_override = "device"
    got = _unpack_builders(deps_oracle.flush_builders(dev, safe, qs))
    assert got == want
    assert (dev._batch_flat, dev._batch_k) == learned   # nothing learned
    _assert_host_answered(dev, len(qs), _n_range(qs))
    # without loaded budgets the same flush re-runs larger, as ever
    _s, free, safe2 = _in_bulk(rows, None)
    free._batch_flat, free._batch_k = 16, 2
    free.route_override = "device"
    assert _unpack_builders(deps_oracle.flush_builders(free, safe2, qs)) \
        == want
    assert free._batch_k > 2 and free.n_budget_host_queries == 0
    assert free.n_dense_queries + free.n_bucketed_queries == len(qs)
    assert free.n_range_device_queries == _n_range(qs)


@pytest.mark.parametrize("seed", [4, 9])
def test_a_fused_member_past_the_loaded_budgets_is_answered_by_the_host(
        seed):
    from tests.conftest import DispatchTestNode, DispatchTestStoreShim
    node = DispatchTestNode(fusion=True)
    members, want = [], []
    for i in range(2):
        rows = _history(seed + 10 * i, 1500)
        qs = _queries(seed + 10 * i, len(rows), count=8)
        want.append(_unpack_builders(deps_oracle.reference_builders(
            *_in_bulk(rows, None)[1:], qs)))
        store, dev, safe = _in_bulk(rows, None)
        dev.store = DispatchTestStoreShim(store, node, i)
        dev.loaded_budgets, dev.loaded_batch = (16, 2), 32
        dev.route_override = "device"
        members.append((dev, safe, qs))
    hints, errors = [], []
    for dev, _safe, qs in members:
        h = dev.fused_eligible(qs)
        h["batch"] = [(q, DepsBuilder(), lambda e, _s: errors.append(e))
                      for q in qs]
        hints.append(h)
    launch = node.dispatcher._launch_fused_flush(hints)
    assert launch is not None
    for (dev, safe, qs), h, expect in zip(members, hints, want):
        dev.fused_harvest(safe, h, launch)
        assert errors == [None] * len(qs)
        errors.clear()
        assert _unpack_builders([b for _q, b, _d in h["batch"]]) == expect
        _assert_host_answered(dev, len(qs), _n_range(qs))


def test_a_flush_larger_than_any_loaded_batch_is_answered_by_the_host():
    from accord_tpu.ops import deps_kernel as dk
    dev, safe, qs = _loaded_store(max_flush=4)
    assert dev.loaded_batch == 4
    kernels = dk.calculate_deps_flat_attr._cache_size()
    dev.route_override = "device"
    big = qs[:6]
    want = _unpack_builders(deps_oracle.reference_builders(dev, safe, big))
    budget_host, host = dev.n_budget_host_queries, dev.n_host_queries
    dense = dev.n_dense_queries
    assert dev.fused_eligible(big) is None        # it runs solo, on the host
    assert _unpack_builders(deps_oracle.flush_builders(dev, safe, big)) \
        == want
    assert dev.n_budget_host_queries - budget_host == len(big)
    assert dev.n_host_queries - host == len(big)
    assert dev.n_dense_queries == dense
    assert dk.calculate_deps_flat_attr._cache_size() == kernels
