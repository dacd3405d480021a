"""Exact-geometry kernels + two-stage compacted downloads (r10), and the
device-resident attribution they feed (r15).

Every device kernel emits the exact overlap TRIPLES as sorted composite
integer codes, already floored / elided / key-deduped, and the collect
fetches the scalar header first and only the live entry prefix after.
The references live in tests/deps_oracle.py.  These tests pin the contract
on two stores — INTERVAL-GAP tables (``gap``: multi-interval slots whose
gaps a coarse bounding-box mask would falsely admit) and ELISION-ACTIVE
tables (``elision``: floors, committed-write pivots, transitive entries):

- property: every kernel's shipped entries equal the reference geometry
  (``deps_oracle.attributed_entries``) over its own pair list;
- the int32/int64 entry-width crossover is byte-invisible;
- the two-stage download composes with the r07 fault ladder: a header
  fetched followed by a faulted prefix fetch fails the whole flush over
  to the host route;
- overflow -> exact-header-sized re-run -> compaction interleavings keep
  the begin-time snapshot answer.
"""

import numpy as np
import pytest

from accord_tpu.local.commands_for_key import CommandsForKey, InternalStatus
from accord_tpu.local.device_index import _decode_triples, _prefix_len
from accord_tpu.ops import deps_kernel as dk
from accord_tpu.primitives.deps import DepsBuilder
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
from accord_tpu.utils import faults
from accord_tpu.utils.random_source import RandomSource

from tests import deps_oracle
from tests.conftest import make_device_state
from tests.test_routing import _attributed, _unpack_builders
from tests.test_routing import _reference as _reference_with


def _build_gap_store(seed, n=160, keyspace=4_000, mesh=None):
    """Slots with MULTIPLE disjoint intervals (gaps between them) — the
    shape where a bounding-box mask would admit a query probing inside a
    slot's gap.  Queries deliberately target gap interiors, interval
    interiors, and boundaries."""
    rng = np.random.default_rng(seed)
    store, dev, safe = make_device_state(mesh=mesh)
    hlcs = rng.choice(np.arange(1, 50 * n), size=n, replace=False)
    for i in range(n):
        kind = TxnKind.Write if rng.random() < 0.7 else TxnKind.Read
        # 2-4 narrow intervals separated by wide gaps
        n_iv = int(rng.integers(2, 5))
        base = int(rng.integers(0, keyspace // 2))
        rngs, toks = [], []
        for v in range(n_iv):
            s = base + v * (keyspace // 8) + int(rng.integers(0, 40))
            if rng.random() < 0.3:
                toks.append(s)
            else:
                rngs.append(Range(s, s + int(rng.integers(1, 12))))
        dom = Domain.Range if rngs else Domain.Key
        tid = TxnId.create(1, int(hlcs[i]), kind, dom,
                           1 + int(rng.integers(0, 5)))
        keys = Ranges.of(*rngs) if rngs else Keys([IntKey(t) for t in toks])
        dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
        if rng.random() < 0.06:
            dev.update_status(tid, int(InternalStatus.INVALIDATED))
    qs = []
    for _ in range(24):
        bound = TxnId.create(1, int(rng.integers(50 * n, 99 * n)),
                             TxnKind.Write, Domain.Key, 1)
        toks, rngs = [], []
        for _ in range(int(rng.integers(1, 4))):
            r = rng.random()
            # probe gap interiors (base + half-gap offsets) as often as
            # interval interiors
            s = int(rng.integers(0, keyspace - 80))
            if r < 0.4:
                toks.append(s + keyspace // 16)     # likely inside a gap
            elif r < 0.7:
                toks.append(s)
            else:
                rngs.append(Range(s, s + int(rng.integers(1, 80))))
        qs.append((bound, bound, bound.kind().witnesses(), toks, rngs))
    return dev, safe, qs


def _build_attr_store(rs, mesh=None, n=90, hot=24):
    """Randomized ELISION-ACTIVE store from one RandomSource: a hot token
    set dense enough that committed-write pivots, transitive entries and
    floor positions all land, with the CFK state co-registered (the sync
    invariant the elision registry leans on).  Returns (dev, safe, qs)."""
    from accord_tpu.primitives.timestamp import Timestamp
    store, dev, safe = make_device_state(mesh=mesh)
    floor_pos = rs.next_int(60 * n)
    floor_id = TxnId.create(1, 1 + floor_pos, TxnKind.ExclusiveSyncPoint,
                            Domain.Range, 1)
    span = 1 + rs.next_int(2 * hot)
    store.redundant_before.add_redundant(
        Ranges.of(Range(0, span)), floor_id)
    seen = set()
    for _ in range(n):
        hlc = 1 + rs.next_int(60 * n)
        while hlc in seen:
            hlc = 1 + rs.next_int(60 * n)
        seen.add(hlc)
        kind = TxnKind.Write if rs.next_int(10) < 7 else TxnKind.Read
        domain = Domain.Key if rs.next_int(10) < 8 else Domain.Range
        if domain == Domain.Key:
            toks = [rs.next_int(hot) for _ in range(1 + rs.next_int(3))]
            keys = Keys([IntKey(t) for t in toks])
            rngs = []
        else:
            s0 = rs.next_int(hot)
            rngs = [Range(s0, s0 + 1 + rs.next_int(6))]
            keys = Ranges.of(*rngs)
            toks = []
        tid = TxnId.create(1, hlc, kind, domain, 1 + rs.next_int(5))
        draw = rs.next_int(10)
        if draw < 4:
            status = InternalStatus.PREACCEPTED
        elif draw < 8:
            status = InternalStatus.COMMITTED
        elif draw < 9:
            status = InternalStatus.TRANSITIVELY_KNOWN
        else:
            status = InternalStatus.APPLIED
        dev.register(tid, int(status), keys)
        exec_at = None
        if status >= InternalStatus.COMMITTED:
            # executeAt sometimes moved off the id (recovery-proposed)
            exec_at = tid if rs.next_int(4) else Timestamp(
                tid.msb, tid.lsb + 1 + rs.next_int(50), tid.node)
            dev.update_status(tid, int(status), execute_at=exec_at)
        for t in toks:
            cfk = store.commands_for_key.get(t)
            if cfk is None:
                cfk = store.commands_for_key[t] = CommandsForKey(t)
            cfk.update(tid, status, execute_at=exec_at)
    qs = []
    for _ in range(10):
        bound = TxnId.create(1, 60 * n + rs.next_int(40 * n),
                             TxnKind.Write, Domain.Key, 1)
        toks, rngs = [], []
        for _ in range(1 + rs.next_int(3)):
            if rs.next_int(10) < 7:
                toks.append(rs.next_int(hot))
            else:
                s0 = rs.next_int(hot)
                rngs.append(Range(s0, s0 + 1 + rs.next_int(8)))
        qs.append((bound, bound, bound.kind().witnesses(), toks, rngs))
    return dev, safe, qs


def _store(kind, seed, mesh=None, **kw):
    """(dev, safe, qs) of one of the two store shapes the contract is
    pinned on."""
    if kind == "gap":
        return _build_gap_store(seed, mesh=mesh, **kw)
    return _build_attr_store(RandomSource(seed), mesh=mesh, **kw)


def _flush(dev, safe, qs, route=None):
    """The product flush on ``route``, unpacked."""
    if route is not None:
        dev.route_override = route
    return _attributed(dev, safe, qs)


def _reference(dev, safe, qs):
    """The reference answer (tests/deps_oracle.py), unpacked — the same
    with and without the batch-global floor in its candidate scan."""
    want = _reference_with(dev, safe, qs, prune=True)
    assert want == _reference_with(dev, safe, qs, prune=False)
    return want


def _shipped_entries(dev, qs, route):
    """The entries route ``route`` hands the finalize, + the snapshot."""
    dev.route_override = route
    tb, tj, tm, tq, ids, ivs, qnp, q_m, _q = dev._batch_collect_attr(
        dev.deps_query_batch_begin(qs, immediate=True))
    return (tb, tj, tm, tq), ids, ivs, qnp, q_m


def _reference_entries(ent, ivs, qnp, q_m):
    """deps_oracle.attributed_entries over the entries' own pair list."""
    b_d, j_d, _p = deps_oracle.entry_pairs(ent[0], ent[1])
    return deps_oracle.attributed_entries(b_d.copy(), j_d.copy(), ivs,
                                          qnp, q_m)


@pytest.mark.parametrize("seed", [3, 17, 59])
@pytest.mark.parametrize("route", ["device", "dense"])
def test_exact_kernel_triples_match_host_geometry(seed, route):
    """Device-route entries == the reference geometry applied to the
    device's own pair list (exact array equality — same order), and the
    pair list == the host route's (no false positives survive)."""
    dev, safe, qs = _build_gap_store(seed)
    ent, ids, ivs, qnp, q_m = _shipped_entries(dev, qs, route)
    # no pair may be dropped by the reference (exactness) and the entries
    # must match in VALUE AND ORDER (the kernels' code sort is
    # np.nonzero's (pair, m, q) order)
    for got, ref in zip(ent, _reference_entries(ent, ivs, qnp, q_m)):
        np.testing.assert_array_equal(got, ref)
    # pair set == host route's pair set
    ent_h, ids_h, _ivs, _qnp, _qm = _shipped_entries(dev, qs, "host")
    dep_d = sorted(set(zip(ent[0].tolist(), [ids[3][j] for j in ent[1]])))
    dep_h = sorted(set(zip(ent_h[0].tolist(),
                           [ids_h[3][j] for j in ent_h[1]])))
    assert dep_d == dep_h, f"seed={seed} route={route}"


@pytest.mark.parametrize("kind,seed", [("gap", 31), ("elision", 0x51AB)])
def test_mesh_routes_match_oracle(kind, seed):
    """The mesh-sharded kernels — slot-sharded dense and row-sharded
    bucketed, with the cross-shard merge ON DEVICE — build byte-equal Deps
    to the reference, and on the gap store ship the reference geometry's
    entry SET (cross-shard dedupe included)."""
    dev, safe, qs = _store(kind, seed, mesh="auto")
    if dev.mesh is None:
        pytest.skip("virtual mesh unavailable")
    oracle = _reference(dev, safe, qs)
    for route in ("host", "dense", "bucketed"):
        assert _flush(dev, safe, qs, route) == oracle, \
            f"mesh route={route}"
        if kind == "gap" and route != "host":
            ent, _ids, ivs, qnp, q_m = _shipped_entries(dev, qs, route)
            got = list(zip(*(a.tolist() for a in ent)))
            ref = set(zip(*(a.tolist() for a in _reference_entries(
                ent, ivs, qnp, q_m))))
            assert len(got) == len(set(got)) and set(got) == ref, route
    assert dev.n_mesh_queries > 0 and dev.n_mesh_bucketed_queries > 0


@pytest.mark.parametrize("kind,seed,cap", [("gap", 7, 0),
                                           ("elision", 0xC0DE, 16)])
def test_int32_int64_crossover(monkeypatch, kind, seed, cap):
    """Lowering INT32_CODE_MAX forces int64 entry buffers on every kernel;
    results must be byte-identical to the int32 run (the width is a
    transport detail, never a semantic)."""
    dev, safe, qs = _store(kind, seed)
    dev.mesh = None
    oracle = _reference(dev, safe, qs)
    for wide, code_max in ((False, dk.INT32_CODE_MAX), (True, cap)):
        monkeypatch.setattr(dk, "INT32_CODE_MAX", code_max)
        assert dk.wide_codes(dev.deps.capacity, dev.deps.max_intervals,
                             4) == wide
        for route in ("bucketed", "dense"):
            dev.route_override = route
            builders = [DepsBuilder() for _ in qs]
            h = dev.deps_query_batch_begin(qs, immediate=True)
            for part in h[0]:
                assert part["wide"] == wide
                assert np.dtype(part["box"]["ent"].dtype) == (
                    np.int64 if wide else np.int32)
            dev.deps_query_batch_end_attributed(safe, h, builders)
            assert _unpack_builders(builders) == oracle, (wide, route)


def test_header_then_faulted_prefix_fails_over_to_host():
    """The r07 ladder composes with the two-stage download: the header
    fetch succeeds, the entry-prefix fetch faults, and the WHOLE flush
    fails over to the host route — same bytes, one quarantine."""
    dev, safe, qs = _build_gap_store(11)
    dev.mesh = None
    want = _reference(dev, safe, qs)
    dev.route_override = "device"
    builders = [DepsBuilder() for _ in qs]
    h = dev.deps_query_batch_begin(qs, immediate=True)
    assert all(p["kind"].startswith("attr_") for p in h[0])
    orig_check = faults.check
    stages = []

    def entry_stage_only(kind, detail=""):
        stages.append((kind, detail))
        if kind == "transfer" and detail == "entry download":
            raise faults.TransferFault("injected entry-stage fault")
        return orig_check(kind, detail)

    n_faults = dev.n_device_faults
    try:
        faults.check = entry_stage_only
        dev.deps_query_batch_end_attributed(safe, h, builders)
    finally:
        faults.check = orig_check
    # the header stage was consulted (and passed) before the entry stage
    assert stages[:2] == [("transfer", "header download"),
                          ("transfer", "entry download")]
    assert _unpack_builders(builders) == want
    assert dev.n_device_faults == n_faults + 1
    assert dev.n_fallback_queries >= len(qs)
    assert dev._dev_quar_flushes > 0          # quarantined, as a real fault


def test_whole_transfer_fault_fails_over_to_host():
    """Armed transfer faults at collect (header stage) also fail the
    flush over — the pre-r10 behavior is preserved stage-wise."""
    dev, safe, qs = _build_gap_store(13)
    dev.mesh = None
    want = _reference(dev, safe, qs)
    dev.route_override = "device"
    builders = [DepsBuilder() for _ in qs]
    h = dev.deps_query_batch_begin(qs, immediate=True)
    n_faults = dev.n_device_faults
    with faults.device_fault("transfer", 1.0, RandomSource(5)):
        dev.deps_query_batch_end_attributed(safe, h, builders)
    assert _unpack_builders(builders) == want
    assert dev.n_device_faults == n_faults + 1
    assert dev.n_fallback_queries >= len(qs)


def _interleave_gap(dev):
    """Register fresh txns (bucket index + mirror mutate) and free a live
    one, then squeeze the table under a budget so the next grow compacts."""
    for i in range(40):
        tid = TxnId.create(1, 900_000 + i, TxnKind.Write, Domain.Key, 1)
        dev.register(tid, int(InternalStatus.PREACCEPTED),
                     Keys([IntKey((i * 97) % 4_000)]))
    dev.free(next(iter(dev.deps.slot_of)))
    dev.device_budget_slots = dev.deps.capacity
    dev._compact_below_floor()


def _interleave_elision(dev):
    """One late registration on a hot token of the elision-active store
    (freed again: the next route's reference is the same store)."""
    late = TxnId.create(1, 7, TxnKind.Write, Domain.Key, 3)
    if late in dev.deps.slot_of:
        dev.free(late)
    dev.register(late, int(InternalStatus.PREACCEPTED), Keys([IntKey(1)]))


@pytest.mark.parametrize("kind,seed,kw,routes,interleave", [
    ("gap", 23, {"n": 220}, ("bucketed",), _interleave_gap),
    ("elision", 0x0F10, {}, ("dense", "bucketed"), _interleave_elision)])
def test_overflow_rerun_interleaving(kind, seed, kw, routes, interleave):
    """Overflow -> exact-header-sized re-run -> interleaved mutation (+
    floor compaction on the gap store): the deferred collect must answer
    for the BEGIN-time snapshot, sized from the header it already
    downloaded (never the full padded buffer), byte-equal to the
    reference computed at begin, regardless of what lands in between."""
    dev, safe, qs = _store(kind, seed, **kw)
    dev.mesh = None
    for route in routes:
        oracle = _reference(dev, safe, qs)
        dev.route_override = route
        dev._batch_flat, dev._batch_k = 16, 2     # guaranteed overflow
        builders = [DepsBuilder() for _ in qs]
        h = dev.deps_query_batch_begin(qs)
        interleave(dev)   # none of it may leak into the in-flight collect
        dev.deps_query_batch_end_attributed(safe, h, builders)
        assert dev._batch_k > 2, "overflow re-run never happened"
        assert _unpack_builders(builders) == oracle, route


def test_prefix_len_and_decode_edges():
    """Unit edges of the download helpers: zero totals fetch nothing,
    granularity bounds the slice-shape count, decode round-trips codes."""
    assert _prefix_len(0, 4096) == 0
    assert _prefix_len(1, 4096) == 256          # gran = max(128, s>>4)
    assert _prefix_len(4096, 4096) == 4096
    assert _prefix_len(100, 65536) == 4096      # gran = s>>4
    # decode round-trip: attributed header = 5 scalars + row_end[3]; the
    # pow2-padded tail past ``total`` never decodes
    m_t, q_m = 4, 8
    mq = m_t * q_m
    hdr = np.array([[4, 4, 3, 0, 0, 1, 4, 4]], np.int32)
    ent = np.array([[5 * mq + 2 * q_m + 7, 9 * mq, 9 * mq + 3,
                     101 * mq + 1 * q_m + 1, -1, -1]], np.int64)
    b, j, m_i, q_i = _decode_triples(hdr, ent, 3, mq, q_m)
    np.testing.assert_array_equal(b, [0, 1, 1, 1])
    np.testing.assert_array_equal(j, [5, 9, 9, 101])
    np.testing.assert_array_equal(m_i, [2, 0, 0, 1])
    np.testing.assert_array_equal(q_i, [7, 0, 3, 1])


def test_download_byte_counters_and_compaction_ratio():
    """The two-stage transfer counts what it actually moved; the padded
    counter records what the old full-buffer download would have moved.
    On a spread keyspace the ratio must show real compaction."""
    dev, safe, qs = _build_gap_store(41)
    dev.mesh = None
    dev.route_override = "device"
    for _ in range(3):
        dev.deps_query_batch_attributed(safe, qs,
                                        [DepsBuilder() for _ in qs])
    assert dev.download_bytes > 0
    assert dev.download_bytes < dev.download_bytes_padded


# -- r15: device-resident attribution + elision -------------------------------
#
# The kernels fold per-token RedundantBefore floors, CFK transitive elision
# and the key dedupe INTO the device program and emit pre-attributed CSR
# blocks.  The host pass they replaced is the reference
# (deps_oracle.attribute_batch) these sweeps compare every route against,
# byte-for-byte at the builder level.

def test_attributed_blocks_match_oracle_property():
    """Seeded property sweep (tests/proptest.py run_property): on a
    randomized elision-active store — random floor positions, committed
    writes with moved executeAts, transitive entries, point AND range
    queries — every route's flush builds byte-equal Deps to the
    reference."""
    from tests.proptest import case_budget, run_property

    def make_case(rs):
        return rs.seed()

    def check(seed):
        dev, safe, qs = _store("elision", seed)
        oracle = _reference(dev, safe, qs)
        for route in ("host", "dense", "bucketed"):
            assert _flush(dev, safe, qs, route) == oracle, f"route={route}"

    run_property(case_budget(25), 0xA77B, make_case, check,
                 replay_hint="tests/test_exact_collect.py "
                             "test_attributed_blocks_match_oracle_property")


def test_attributed_elision_counters_count():
    """The elided-row counters (eknown/emsb legs) move on a store where
    elision provably fires, on the kernel routes AND the host route, and
    attributed downloads are accounted."""
    dev, safe, qs = _store("elision", 0xE11D)
    base_t, base_d = dev.n_elided_transitive, dev.n_elided_decided
    _flush(dev, safe, qs, "host")
    host_moved = (dev.n_elided_transitive + dev.n_elided_decided
                  - base_t - base_d)
    _flush(dev, safe, qs, "dense")
    dense_moved = (dev.n_elided_transitive + dev.n_elided_decided
                   - base_t - base_d - host_moved)
    assert host_moved > 0 and dense_moved > 0
    assert dev.attr_download_bytes > 0
