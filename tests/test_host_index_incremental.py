"""The host route's index is MAINTAINED by appends (_DepsMirror.host_index):
after every step of a random history of registrations, bulk loads, frees
with slot reuse, invalidations, resizes, width doublings and floor moves,
the maintained index probes to exactly the entries a from-scratch
``_host_index_of`` over the same mirror yields, and the Deps a host flush
builds from it are byte-equal to those of a whole rebuild.  A host flush
after a registration folds and does not rebuild; a mirror whose host route
never ran records nothing; the router prices the fold, and a whole build
only where one is due."""

import numpy as np
import pytest

from accord_tpu.local.commands_for_key import InternalStatus
from accord_tpu.local.device_index import _DepsMirror, _pow2_at_least
from accord_tpu.ops import deps_kernel as dk
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind

from tests import deps_oracle
from tests.conftest import make_device_state
from tests.test_routing import _reference, _unpack_builders

KEYSPACE = 4_000
PRE = int(InternalStatus.PREACCEPTED)


def _tid(rng, hlc, dom):
    kind = TxnKind.Write if rng.random() < 0.7 else TxnKind.Read
    return TxnId.create(1, hlc, kind, dom, 1 + int(rng.integers(0, 4)))


def _footprint(rng, wide=False):
    """(tokens, [(lo, hi)] ranges inclusive): points, ranges or both."""
    toks = [int(t) for t in rng.integers(0, KEYSPACE,
                                         int(rng.integers(0, 3)))]
    rngs = []
    for _ in range(int(rng.integers(0 if toks else 1, 3))):
        lo = int(rng.integers(0, KEYSPACE - 200))
        rngs.append((lo, lo + int(rng.integers(0, 1500 if wide else 150))))
    return toks, rngs


def _queries(rng, hlc):
    qs = []
    for _ in range(int(rng.integers(1, 6))):
        bound = _tid(rng, hlc + int(rng.integers(-40, 40)), Domain.Key)
        toks, rngs = _footprint(rng, wide=rng.random() < 0.2)
        qs.append((bound, bound.kind().witnesses(), toks,
                   [Range(lo, hi + 1) for lo, hi in rngs], bound))
    q_m = _pow2_at_least(max(len(q[2]) + len(q[3]) for q in qs), 1)
    return dk.pack_query_matrix(qs, q_m), q_m


def _sorted_entries(ent):
    return sorted(zip(*(a.tolist() for a in ent)))


def _scratch(m, qnp, q_m, floor):
    """host_pairs over a from-scratch _host_index_of of the same mirror."""
    return m.host_pairs(qnp, q_m, floor, snapshot=(
        m.msb, m.lsb, m.node, m.kind, m.status, m.lo, m.hi))


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6, 7, 8])
def test_the_maintained_index_probes_as_a_fresh_build(seed):
    rng = np.random.default_rng(seed)
    m = _DepsMirror(capacity=16, max_intervals=2)
    held, hlc, probes = [], 100, 0
    for step in range(220):
        hlc += int(rng.integers(1, 4))
        op = rng.random()
        if op < 0.35 or not held:
            tid = _tid(rng, hlc, Domain.Key)
            toks, rngs = _footprint(rng, wide=rng.random() < 0.1)
            slot = m.alloc(tid)
            m.add_intervals(slot, toks,
                            [Range(lo, hi + 1) for lo, hi in rngs])
            held.append(tid)
        elif op < 0.45:
            tid = held[int(rng.integers(0, len(held)))]
            toks, rngs = _footprint(rng)
            m.add_intervals(m.slot_of[tid], toks,
                            [Range(lo, hi + 1) for lo, hi in rngs])
        elif op < 0.52:
            ids, fps = [], []
            for _ in range(int(rng.integers(1, 12))):
                hlc += 1
                ids.append(_tid(rng, hlc, Domain.Range))
                toks, rngs = _footprint(rng)
                # none inside another, as a store's sliced footprint is
                fps.append(sorted(set([(t, t) for t in toks] + rngs)))
                fps[-1] = [iv for iv in fps[-1]
                           if not any(o != iv and o[0] <= iv[0]
                                      and iv[1] <= o[1] for o in fps[-1])]
            m.alloc_bulk(ids, fps, int(dk.SLOT_APPLIED), [None] * len(ids))
            held.extend(ids)
        elif op < 0.75:
            # free: alloc pops the freed slot next, so reuse follows
            m.free(held.pop(int(rng.integers(0, len(held)))))
        elif op < 0.85:
            tid = held[int(rng.integers(0, len(held)))]
            m.set_status(m.slot_of[tid], dk.SLOT_INVALIDATED)
        elif op < 0.88:
            m._resize(m.capacity * 2)
        elif op < 0.91:
            m._grow_intervals()
        if rng.random() < 0.6:
            floor = None if rng.random() < 0.4 else TxnId.create(
                1, int(rng.integers(100, hlc + 1)), TxnKind.Write,
                Domain.Key, int(rng.integers(1, 5)))
            qnp, q_m = _queries(rng, hlc)
            got = m.host_pairs(qnp, q_m, floor)
            want = _scratch(m, qnp, q_m, floor)
            assert _sorted_entries(got) == _sorted_entries(want), step
            assert m.n_host_index_pending == 0
            probes += 1
    assert probes > 100 and m.n_host_index_folds > 20
    # the first read, each resize / doubling after it, compactions
    assert m.n_host_index_builds >= 2


def _served_store(seed, n=300):
    rng = np.random.default_rng(seed)
    store, dev, safe = make_device_state(mesh=None)
    for i in range(n):
        _register(dev, rng, 10 + i)
    return rng, store, dev, safe


def _register(dev, rng, hlc):
    if rng.random() < 0.5:
        lo = int(rng.integers(0, KEYSPACE - 200))
        tid = _tid(rng, hlc, Domain.Range)
        dev.register(tid, PRE, Ranges.of(Range(lo, lo + int(
            rng.integers(1, 200)))))
    else:
        tid = _tid(rng, hlc, Domain.Key)
        dev.register(tid, PRE, Keys([IntKey(int(t)) for t in rng.integers(
            0, KEYSPACE, int(rng.integers(1, 4)))]))
    return tid


def _flush_queries(rng, hlc):
    qs = []
    for _ in range(int(rng.integers(1, 4))):
        bound = _tid(rng, hlc, Domain.Key)
        toks, rngs = _footprint(rng)
        qs.append((bound, bound, bound.kind().witnesses(), toks,
                   [Range(lo, hi + 1) for lo, hi in rngs]))
    return qs


@pytest.mark.parametrize("seed", [3, 9])
def test_a_host_flush_builds_the_deps_a_whole_rebuild_builds(seed):
    """Registrations, frees and invalidations between host flushes: every
    flush's Deps equal, byte for byte, those built after the index is
    dropped and rebuilt whole, and the reference's; each flush after the
    first rebuild folds."""
    rng, _store, dev, safe = _served_store(seed)
    dev.route_override = "host"
    hlc, held = 400, list(dev.deps.slot_of)
    for k in range(12):
        for _ in range(int(rng.integers(1, 4))):
            hlc += 1
            held.append(_register(dev, rng, hlc))
        if k % 3 == 1:
            dev.free(held.pop(int(rng.integers(0, len(held)))))
        if k % 4 == 2:
            dev.update_status(held[int(rng.integers(0, len(held)))],
                              int(InternalStatus.INVALIDATED))
        qs = _flush_queries(rng, hlc + 1)
        folded = _unpack_builders(deps_oracle.flush_builders(dev, safe, qs))
        dev.deps._hidx = None
        whole = _unpack_builders(deps_oracle.flush_builders(dev, safe, qs))
        assert folded == whole == _reference(dev, safe, qs), k
    assert dev.n_host_index_folds == 11 and dev.n_host_index_builds == 13


def test_a_host_flush_after_a_registration_folds():
    rng, _store, dev, safe = _served_store(4)
    dev.route_override = "host"
    qs = _flush_queries(rng, 1000)
    deps_oracle.flush_builders(dev, safe, qs)
    assert (dev.n_host_index_builds, dev.n_host_index_folds) == (1, 0)
    dev.register(_tid(rng, 900, Domain.Range), PRE,
                 Ranges.of(Range(100, 300)))
    assert dev.deps.n_host_index_pending == 1
    deps_oracle.flush_builders(dev, safe, qs)
    assert (dev.n_host_index_builds, dev.n_host_index_folds) == (1, 1)
    # nothing written since: the next read neither builds nor folds
    deps_oracle.flush_builders(dev, safe, qs)
    assert (dev.n_host_index_builds, dev.n_host_index_folds) == (1, 1)
    # both inside dispatch_host, in kernel_times, once each
    kt = dev.kernel_times
    assert kt["host_index_build"][0] == 1 and kt["host_index_fold"][0] == 1
    assert kt["dispatch_host"][0] == 3
    from accord_tpu.obs.metrics import INDEX_COUNTERS
    assert ("host_index_builds", "n_host_index_builds") in INDEX_COUNTERS
    assert ("host_index_folds", "n_host_index_folds") in INDEX_COUNTERS


def test_dead_entries_past_the_live_ones_rebuild_the_index():
    rng, _store, dev, safe = _served_store(6, n=200)
    dev.route_override = "host"
    qs = _flush_queries(rng, 1000)
    deps_oracle.flush_builders(dev, safe, qs)
    held = list(dev.deps.slot_of)
    for tid in held[:90]:
        dev.free(tid)
    assert not dev.deps.host_index_rebuild_due()
    for tid in held[90:120]:
        dev.free(tid)
    assert dev.deps.host_index_rebuild_due()
    assert _unpack_builders(deps_oracle.flush_builders(dev, safe, qs)) \
        == _reference(dev, safe, qs)
    assert dev.n_host_index_builds == 2
    assert not dev.deps.host_index_rebuild_due()


def test_a_mirror_never_read_by_the_host_route_records_nothing():
    """The store cells' path: registrations, a bulk load and device
    flushes leave no pending entries and no index behind."""
    rng, _store, dev, safe = _served_store(5)
    rows = []
    for i in range(50):
        tid = _tid(rng, 2000 + i, Domain.Range)
        lo = int(rng.integers(0, KEYSPACE - 200))
        rows.append((tid, None, Ranges.of(Range(lo, lo + 50))))
    dev.register_history(rows, int(InternalStatus.APPLIED))
    dev.route_override = "device"
    deps_oracle.flush_builders(dev, safe, _flush_queries(rng, 3000))
    _register(dev, rng, 2500)
    assert dev.deps._hidx is None and dev.deps.n_host_index_pending == 0
    assert dev.n_host_index_builds == dev.n_host_index_folds == 0
    assert "host_index_build" not in dev.kernel_times


def test_the_router_prices_a_fold_and_a_whole_build_only_when_due():
    rng, _store, dev, safe = _served_store(7)
    st = dev.deps.floor_stats(None)
    whole = 4.0 * (st["n_above"] + st["n_pt"] + st["n_rng"])
    assert dev._host_index_price(st, 1.0) == whole > 0
    dev.route_override = "host"
    deps_oracle.flush_builders(dev, safe, _flush_queries(rng, 1000))
    assert dev._host_index_price(st, 1.0) == 0.0
    _register(dev, rng, 900)
    pend = dev.deps.n_host_index_pending
    assert pend >= 1 and dev._host_index_price(st, 1.0) == 4.0 * pend
    dev.deps._grow_intervals()
    assert dev._host_index_price(st, 1.0) == whole
