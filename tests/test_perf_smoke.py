"""Perf smoke: the low-live-set regime must route to the host path.

Guards against silently re-pessimizing BASELINE config 3 (hot-128 keys,
90% of the table below the durable floor): with the round-trip cost of
a high-round-trip host-device link injected into the calibration, the
router must serve the scan from the host tail — and the result must
still be bit-identical to the device kernels.  Fast (-m 'not slow'): a 2k
txn store, one flush per route.

The r18 section pins the per-op protocol path's allocation behavior
(tracemalloc/gc deltas, seeded inputs): the serving profile puts
``Command.updated`` at ~33 calls/txn and the commit/apply quorum merges
on every reply — these must not silently regress to per-call dict or
literal rebuilds."""

import gc
import tracemalloc

import numpy as np

from accord_tpu.local.commands_for_key import InternalStatus
from accord_tpu.local.device_index import DeviceState
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind

from tests.conftest import make_device_state
from tests.test_routing import _attributed, _reference

HOT = 128
N = 2_000


def _hot_store():
    rng = np.random.default_rng(13)
    store, dev, safe = make_device_state()
    hlcs = np.sort(rng.choice(np.arange(1, 20 * N), size=N, replace=False))
    floor_hlc = int(hlcs[int(N * 0.9)])
    for i in range(N):
        status = InternalStatus.APPLIED if int(hlcs[i]) < floor_hlc \
            else InternalStatus.PREACCEPTED
        tid = TxnId.create(1, int(hlcs[i]), TxnKind.Write, Domain.Key,
                           1 + i % 5)
        toks = [int(t) for t in rng.integers(0, HOT, rng.integers(1, 4))]
        dev.register(tid, int(status), Keys([IntKey(t) for t in toks]))
    floor_id = TxnId.create(1, floor_hlc, TxnKind.ExclusiveSyncPoint,
                            Domain.Range, 1)
    store.redundant_before.add_redundant(Ranges.of(Range(0, HOT)), floor_id)
    qs = []
    for _ in range(64):
        bound = TxnId.create(1, int(rng.integers(20 * N, 40 * N)),
                             TxnKind.Write, Domain.Key, 1)
        toks = [int(t) for t in rng.integers(0, HOT, rng.integers(1, 4))]
        qs.append((bound, bound, bound.kind().witnesses(), toks, []))
    return dev, safe, qs


def test_router_picks_host_in_low_live_set_regime():
    saved = DeviceState._CALIB
    # a high-round-trip host-device link (the regime config 3 runs in); the
    # host/device per-element costs are this machine's own measurements
    meas = DeviceState._measure_route_calibration()
    DeviceState.set_route_calibration(rtt=2e-3, c_host=meas["c_host"],
                                      c_dev=meas["c_dev"])
    try:
        dev, safe, qs = _hot_store()
        routes = []
        dev.on_route = lambda route, nq: routes.append((route, nq))
        host_out = _attributed(dev, safe, qs)
        assert routes and routes[0][0] == "host", routes
        assert dev.n_host_queries == len(qs)
        assert host_out == _reference(dev, safe, qs)
        assert any(k for k, _r in host_out), "no dep to compare"
        # identical to the pinned device kernels on the same store
        for route in ("device", "dense"):
            dev.route_override = route
            assert _attributed(dev, safe, qs) == host_out, route
        # route counters are disjoint and complete
        assert dev.n_host_queries + dev.n_bucketed_queries \
            + dev.n_dense_queries + dev.n_mesh_queries == dev.n_queries
    finally:
        DeviceState._CALIB = saved


def test_at_scale_shape_routes_to_device():
    """The inverse guard: with the same high-RTT calibration, a query
    batch whose modeled host scan dwarfs two round trips (large live range
    set x many query intervals) must stay on the device kernels."""
    saved = DeviceState._CALIB
    meas = DeviceState._measure_route_calibration()
    DeviceState.set_route_calibration(rtt=2e-3, c_host=meas["c_host"],
                                      c_dev=meas["c_dev"])
    try:
        rng = np.random.default_rng(17)
        store, dev, safe = make_device_state()
        keyspace = 500_000
        hlcs = rng.choice(np.arange(1, 500_000), size=4_000, replace=False)
        for i in range(4_000):
            s = int(rng.integers(0, keyspace - 64))
            tid = TxnId.create(1, int(hlcs[i]), TxnKind.Write, Domain.Range,
                               1 + i % 5)
            dev.register(tid, int(InternalStatus.PREACCEPTED),
                         Ranges.of(Range(s, s + int(rng.integers(1, 64)))))
        qs = []
        for _ in range(256):
            bound = TxnId.create(1, int(rng.integers(600_000, 700_000)),
                                 TxnKind.Write, Domain.Key, 1)
            ivs = [Range(int(s), int(s) + 64) for s in
                   rng.integers(0, keyspace - 64, 4)]
            qs.append((bound, bound, bound.kind().witnesses(), [], ivs))
        routes = []
        dev.on_route = lambda route, nq: routes.append(route)
        _attributed(dev, safe, qs)
        assert routes == ["device"], routes
        assert dev.n_host_queries == 0
    finally:
        DeviceState._CALIB = saved


# -- r18: per-op protocol microbenches (seeded, allocation-pinned) ----------

def _gc_objects_per_call(fn, n=256):
    """(new GC-tracked objects per call, [results]) with the results held
    alive so every call's retained allocations are attributable to it."""
    out = [None] * n
    fn(); fn()                 # warm lazy memos (hash caches, starts tuple)
    gc.collect()
    gc.disable()
    try:
        before = len(gc.get_objects())
        for i in range(n):
            out[i] = fn()
        after = len(gc.get_objects())
    finally:
        gc.enable()
        gc.collect()
    return (after - before) / n, out


def _retained_bytes_per_call(fn, n=256):
    out = [None] * n
    fn(); fn()
    gc.collect()
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        for i in range(n):
            out[i] = fn()
        cur = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    return (cur - base) / n, out


def _seeded_command():
    from accord_tpu.local.command import Command, WaitingOn
    from accord_tpu.local.status import SaveStatus
    from accord_tpu.primitives.keys import RoutingKeys, Route
    from accord_tpu.primitives.timestamp import (Ballot, Domain, TxnId,
                                                 TxnKind)
    txn_id = TxnId.create(1, 1234, TxnKind.Write, Domain.Key, 1)
    route = Route(7, RoutingKeys([3, 7, 11]), True,
                  Ranges.of(Range(0, 16)))
    deps = [TxnId.create(1, h, TxnKind.Write, Domain.Key, 2)
            for h in (100, 200, 300)]
    return Command(txn_id, save_status=SaveStatus.PreAccepted, route=route,
                   progress_key=7, promised=Ballot.ZERO,
                   accepted=Ballot.ZERO, execute_at=txn_id,
                   waiting_on=WaitingOn.all_of(deps))


def test_command_updated_allocates_one_object():
    """The slot-copy fast path of Command.updated (the top allocator on
    the serving profile) retains exactly ONE new GC-tracked object per
    call — the Command itself, no field dict — and stays field-for-field
    identical to the constructor path."""
    from accord_tpu.local import command as command_mod
    from accord_tpu.local.command import Command
    from accord_tpu.local.status import SaveStatus
    cmd = _seeded_command()
    per_call, cmds = _gc_objects_per_call(
        lambda: cmd.updated(save_status=SaveStatus.Stable))
    assert per_call <= 1.05, f"{per_call} objects/call (expected 1)"
    # bit-identical to the ungated constructor path, field by field
    saved = command_mod._FASTPATH
    command_mod._FASTPATH = False
    try:
        ref = cmd.updated(save_status=SaveStatus.Stable)
    finally:
        command_mod._FASTPATH = saved
    for slot in Command.__slots__:
        assert getattr(cmds[0], slot) == getattr(ref, slot), slot
    # and the record itself stays small: one slotted object, no dict
    bytes_per, _held = _retained_bytes_per_call(
        lambda: cmd.updated(save_status=SaveStatus.Stable))
    assert bytes_per <= 512, f"{bytes_per} retained bytes/call"


def test_quorum_merge_tables_allocate_nothing():
    """The commit/apply per-reply merge paths probe module-level tables
    and return PREEXISTING enum members: zero retained objects per op."""
    from accord_tpu.local.commands import ApplyOutcome, CommitOutcome
    from accord_tpu.messages.apply import _APPLY_OUTCOME_KIND, ApplyReplyKind
    from accord_tpu.messages.commit import _COMMIT_RANK
    # totality + identity: every outcome maps to a cached member
    assert set(_COMMIT_RANK) == set(CommitOutcome)
    assert set(_APPLY_OUTCOME_KIND) == set(ApplyOutcome)
    assert _APPLY_OUTCOME_KIND[ApplyOutcome.Success] is ApplyReplyKind.Applied
    # worst-outcome-wins precedence is what the reducers rank by
    co = CommitOutcome
    assert sorted(co, key=_COMMIT_RANK.__getitem__) == [
        co.Insufficient, co.Rejected, co.Redundant, co.Success]
    assert max(ApplyReplyKind) is ApplyReplyKind.Insufficient
    pairs = [(a, b) for a in co for b in co]

    def merge_all():
        acc = co.Success
        for a, b in pairs:
            acc = a if _COMMIT_RANK[a] < _COMMIT_RANK[b] else b
        return acc
    per_call, _out = _gc_objects_per_call(merge_all, n=64)
    assert per_call == 0, f"{per_call} objects per 16-pair merge"


def test_timestamp_hash_cache_is_value_identical():
    """Timestamp.__hash__ memoizes but must return the exact same value
    as the uncached tuple hash (set iteration order / byte determinism
    ride on it), and cost nothing after the first call."""
    from accord_tpu.primitives.timestamp import Timestamp
    rng = np.random.default_rng(29)
    stamps = [Timestamp(int(m), int(l), int(n)) for m, l, n in
              rng.integers(0, 1 << 48, (64, 3))]
    for ts in stamps:
        assert hash(ts) == hash((ts.msb, ts.lsb, ts.node))
    per_call, _out = _gc_objects_per_call(
        lambda: [hash(ts) for ts in stamps] and None, n=64)
    assert per_call <= 1.05, f"{per_call} objects per 64-hash sweep"


def test_ranges_token_probe_allocates_nothing_after_warm():
    """index_containing rides the memoized starts tuple: zero retained
    GC objects per probe once warm, same answers as a linear scan."""
    rng = np.random.default_rng(31)
    bounds = np.sort(rng.choice(np.arange(0, 10_000), 64, replace=False))
    ranges = Ranges([Range(int(bounds[i]), int(bounds[i + 1]))
                     for i in range(0, 64, 2)])
    tokens = [int(t) for t in rng.integers(0, 10_000, 128)]
    for t in tokens:
        linear = next((i for i, r in enumerate(ranges)
                       if r.contains_token(t)), -1)
        assert ranges.index_containing(t) == linear, t
    per_call, _out = _gc_objects_per_call(
        lambda: sum(ranges.index_containing(t) for t in tokens), n=64)
    assert per_call == 0, f"{per_call} objects per 128-probe sweep"
