"""Bucketed interval index (ops.deps_kernel.bucketed_flat + the
_DepsMirror bucket maintenance): the single-device fast path the real-chip
bench runs.  The suite's virtual mesh forces the sharded kernel everywhere
else, so these tests pin mesh=None and drive the bucketed path directly,
checking it against the dense kernel and a host brute force — identical
results through every footprint shape: points, narrow ranges, wide
(straggler) ranges, hot-bucket overflow spill, frees, and wide queries
(dense sub-batch fallback)."""

import numpy as np
import pytest

from accord_tpu.local.commands_for_key import InternalStatus
from accord_tpu.local.device_index import _DepsMirror
from accord_tpu.primitives.deps import DepsBuilder
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind


from tests import deps_oracle
from tests.conftest import make_device_state


def _mk_state():
    # pin the single-device path under the test mesh; these tests target
    # the device kernels — host-route equivalence lives in test_routing.py
    store, dev, safe = make_device_state(mesh=None)
    dev.route_override = "device"
    return store, dev, safe


def _workload(rng, n, keyspace, hot_frac=0.0, wide_frac=0.0):
    hlcs = rng.choice(np.arange(1, 10 * n + 10), size=n, replace=False)
    out = []
    for i in range(n):
        r = rng.random()
        kind = TxnKind.Write if rng.random() < 0.7 else TxnKind.Read
        if r < wide_frac:
            # straggler: interval spanning many buckets
            s = int(rng.integers(0, keyspace // 2))
            toks, rngs = [], [Range(s, s + int(rng.integers(
                _DepsMirror.SPAN * (1 << _DepsMirror.BSHIFT) + 1,
                keyspace // 2)))]
            dom = Domain.Range
        elif r < wide_frac + hot_frac:
            # hot bucket: tokens from one 64-token window (overflow spill)
            toks = [int(t) for t in rng.integers(0, 1 << _DepsMirror.BSHIFT,
                                                 rng.integers(1, 3))]
            rngs = []
            dom = Domain.Key
        elif rng.random() < 0.5:
            toks = [int(t) for t in rng.integers(0, keyspace,
                                                 rng.integers(1, 4))]
            rngs = []
            dom = Domain.Key
        else:
            toks = []
            rngs = []
            for _ in range(int(rng.integers(1, 3))):
                s = int(rng.integers(0, keyspace - 80))
                rngs.append(Range(s, s + int(rng.integers(1, 80))))
            dom = Domain.Range
        tid = TxnId.create(1, int(hlcs[i]), kind, dom,
                           1 + int(rng.integers(0, 5)))
        out.append((tid, toks, rngs))
    return out


def _queries(rng, nq, keyspace, n, wide_q_frac=0.0):
    qs = []
    for _ in range(nq):
        bound = TxnId.create(1, int(rng.integers(10 * n + 10, 20 * n + 20)),
                             TxnKind.Write, Domain.Key, 1)
        toks, rngs = [], []
        for _ in range(int(rng.integers(1, 4))):
            if rng.random() < wide_q_frac:
                s = int(rng.integers(0, keyspace // 2))
                rngs.append(Range(s, s + keyspace // 3))
            elif rng.random() < 0.5:
                toks.append(int(rng.integers(0, keyspace)))
            else:
                s = int(rng.integers(0, keyspace - 80))
                rngs.append(Range(s, s + int(rng.integers(1, 80))))
        qs.append((bound, bound, bound.kind().witnesses(), toks, rngs))
    return qs


def _brute(entries, q):
    """(bound, self, witnesses, toks, rngs) -> sorted dep TxnId list."""
    bound, _self_id, witnesses, toks, rngs = q
    out = set()
    for tid, etoks, erngs in entries:
        if not (tid < bound) or tid == bound:
            continue
        if not witnesses.test(tid.kind()):
            continue
        hit = False
        for t in toks:
            if t in etoks or any(r.contains_token(t) for r in erngs):
                hit = True
        for r in rngs:
            for t in etoks:
                if r.contains_token(t):
                    hit = True
            for er in erngs:
                if er.start < r.end and r.start < er.end:
                    hit = True
        if hit:
            out.add(tid)
    return sorted(out)


def _raw_deps(dev, qs):
    """Per query, the sorted TxnIds the flush's built Deps name (these
    stores hold no floor, no CommandsForKey and no TRANSITIVE slot, so
    that is every overlapping earlier witnessed txn)."""
    return deps_oracle.dep_ids(deps_oracle.flush_builders(dev, None, qs))


def _check_rows(deps):
    """The in-place bucket rows against a from-scratch rebuild from the
    mirror's lo/hi/status columns: per bucket row the same multiset of
    entry records (an entry a full bucket refused sits in the wide set
    instead), lengths that match, padding beyond each length, and a
    per-slot cell record that points at the slot's own entries."""
    from collections import Counter
    from accord_tpu.local.device_index import _BUCKET_PAD
    from accord_tpu.ops import deps_kernel as dk
    k = deps.BUCKET_K
    want, want_wide = {}, set()
    for slot in np.nonzero((deps.status >= 0)
                           & (deps.status != dk.SLOT_INVALIDATED))[0]:
        slot = int(slot)
        for m in range(deps.max_intervals):
            lo, hi = int(deps.lo[slot, m]), int(deps.hi[slot, m])
            if lo > hi:
                continue
            blo, bhi = lo >> deps.BSHIFT, hi >> deps.BSHIFT
            if bhi - blo + 1 > deps.SPAN:
                want_wide.add((lo, hi, slot, m))
                continue
            rec = (lo, hi, slot, m, int(deps.msb[slot]),
                   int(deps.lsb[slot]), int(deps.node[slot]),
                   int(deps.kind[slot]))
            for bid in range(blo, bhi + 1):
                want.setdefault(bid, Counter())[rec] += 1
    assert set(want) <= set(deps.bucket_row)
    assert sorted(deps.bucket_row.values()) == list(range(len(deps._blen)))
    assert len(deps._blen) <= deps._g_cap == deps._brec.shape[0]
    spilled = set()
    for bid, row in deps.bucket_row.items():
        n = deps._blen[row]
        got = Counter(tuple(int(v) for v in rec)
                      for rec in deps._brec[row, :n])
        missing = want.get(bid, Counter()) - got
        assert not got - want.get(bid, Counter()), (bid, got)
        spilled.update(rec[:4] for rec in missing)
        assert (deps._brec[row, n:] == _BUCKET_PAD).all(), (bid, n)
    assert (deps._brec[len(deps._blen):] == _BUCKET_PAD).all()
    assert deps.wide_entries == want_wide | spilled
    assert deps.bucket_max_len >= max(deps._blen, default=0)
    cells = [c for cs in deps._bcells.values() for c in cs]
    assert len(cells) == len(set(cells)) == sum(deps._blen)
    for slot, cs in deps._bcells.items():
        assert cs and all(deps._bflat[c]["slot"] == slot for c in cs)
        assert all(c % k < deps._blen[c // k] for c in cs)


def _check_device(deps):
    """bucket_device() leaves the device arrays equal to the host rows."""
    btable = deps.bucket_device()
    assert not deps._bpend
    for dev_a, host_a in zip(btable[:8], deps._bhost):
        assert dev_a.dtype == host_a.dtype
        assert np.array_equal(np.asarray(dev_a), host_a)


@pytest.mark.parametrize("seed", [41, 42])
def test_bucket_rows_kept_in_place_property(seed):
    """A random register / invalidate / free sequence with a hot bucket
    driven past BUCKET_K into the wide spill and back, and enough distinct
    buckets to grow the row arrays twice: the in-place rows always equal a
    from-scratch rebuild, on the host and (after a sync) on the device."""
    rng = np.random.default_rng(seed)
    keyspace = 20_000                   # 313 buckets: g_cap 64 -> 256
    store, dev, safe = _mk_state()
    deps = dev.deps
    live, hlc, high_water, hot_lens = [], 1, 0, []
    g_caps = {deps._g_cap}
    for step in range(900):
        filling = (step // 300) % 2 == 0        # fill, drain, fill
        if rng.random() < (0.85 if filling else 0.15) or not live:
            (tid, toks, rngs), = _workload(
                rng, 1, keyspace, wide_frac=0.1,
                hot_frac=0.6 if filling else 0.0)
            tid = TxnId.create(1, hlc, tid.kind(), tid.domain(),
                               1 + int(rng.integers(0, 5)))
            hlc += 1
            keys = Ranges.of(*rngs) if rngs else \
                Keys([IntKey(t) for t in toks])
            dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
            live.append(tid)
        else:
            tid = live.pop(int(rng.integers(0, len(live))))
            if rng.random() < 0.3:
                dev.update_status(tid, int(InternalStatus.INVALIDATED))
            if rng.random() < 0.8:
                dev.free(tid)       # an invalidated slot may stay
        assert deps.bucket_max_len >= high_water
        high_water = deps.bucket_max_len
        g_caps.add(deps._g_cap)
        hot_row = deps.bucket_row.get(0)
        if hot_row is not None:
            hot_lens.append(deps._blen[hot_row])
        if step % 25 == 24:
            _check_rows(deps)
        if step % 75 == 74:
            _check_device(deps)
    _check_rows(deps)
    _check_device(deps)
    assert g_caps >= {64, 128, 256}
    full = hot_lens.index(deps.BUCKET_K)        # into the spill ...
    assert min(hot_lens[full:]) < deps.BUCKET_K // 2        # ... and back
    assert deps.bucket_max_len == deps.BUCKET_K
    kt = dev.kernel_times
    assert kt["sync_bucket_cells"][0] + kt["sync_bucket_full"][0] == 12
    assert kt["sync_bucket_full"][0] >= 3       # first sync + two grows
    # same answers as the dense kernel on the state all of that left
    qs = _queries(rng, 16, keyspace, 10_000)
    got = _raw_deps(dev, qs)
    dev.BUCKETED = False
    assert got == _raw_deps(dev, qs)


@pytest.mark.parametrize("path", ["cells", "full"])
def test_bucket_device_uploads_cells_or_the_whole_table(path):
    """After the first (whole-table) upload a sync carries the pending
    cells alone — or the table again once the cells would cost as many
    bytes; either way device == host, and the counters say which."""
    from accord_tpu.local.device_index import _CELL_BYTES, _MIN_CELLS
    rng = np.random.default_rng(5)
    store, dev, safe = _mk_state()
    deps = dev.deps
    hlc = iter(range(1, 1 << 20))

    def register(n_txns, n_keys):
        # 64 buckets only: the row arrays never grow
        for _ in range(n_txns):
            tid = TxnId.create(1, next(hlc), TxnKind.Write, Domain.Key, 1)
            toks = rng.choice(64 << deps.BSHIFT, n_keys, replace=False)
            dev.register(tid, int(InternalStatus.PREACCEPTED),
                         Keys([IntKey(int(t)) for t in toks]))
            yield tid

    first = list(register(20, 4))
    _check_device(deps)
    table_bytes = deps._brec.nbytes
    assert table_bytes == 64 * deps.BUCKET_K * 48
    assert {k: c for k, (c, _s) in dev.kernel_times.items()} == \
        {"sync_bucket_full": 1, "sync_tables": 1, "register": 20}
    assert (dev.n_bucket_cells_uploaded, dev.bucket_upload_bytes) == \
        (0, table_bytes)
    # the first sync sent every column of the three tables, by no program
    assert (dev.n_sync_launches, dev.n_sync_uploads) == (0, 7 + 9 + 8)
    _check_device(deps)                 # nothing pending: no sync at all
    assert dev.bucket_upload_bytes == table_bytes
    assert dev.kernel_times["sync_tables"][0] == 1
    if path == "cells":
        for tid in first[:5]:
            dev.free(tid)
        list(register(3, 4))
        n = len(deps._bpend)
        assert 12 <= n <= 12 + 2 * 20
        _check_device(deps)
        assert dev.kernel_times["sync_bucket_cells"][0] == 1
        assert dev.kernel_times["sync_bucket_full"][0] == 1
        # ... with the dirty slot and attribution rows, as one staging
        # buffer into one program
        assert (dev.n_sync_launches, dev.n_sync_uploads) == (1, 24 + 1)
        assert dev.n_bucket_cells_uploaded == n
        assert dev.bucket_upload_bytes == \
            table_bytes + _MIN_CELLS * _CELL_BYTES
    else:
        list(register(1100, 4))
        assert deps._g_cap == 64 and len(deps._bpend) == 4400
        assert 8192 * _CELL_BYTES >= table_bytes > 4096 * _CELL_BYTES
        _check_device(deps)
        assert "sync_bucket_cells" not in dev.kernel_times
        assert dev.kernel_times["sync_bucket_full"][0] == 2
        # 1,100 dirty rows of the 2,048 are mostly dirty too: no program
        assert (dev.n_sync_launches, dev.n_sync_uploads) == (0, 2 * 24)
        assert (dev.n_bucket_cells_uploaded, dev.bucket_upload_bytes) == \
            (0, 2 * table_bytes)
    _check_rows(deps)


_ACCESSORS = ("device_table", "device_attr_cols", "bucket_device")


def _check_synced(dev, first):
    """Ask the three accessors, ``first`` first: at most one program is
    launched for them all, nothing stays dirty, and the device's slot
    table, attribution columns and bucket arrays equal the host's."""
    deps = dev.deps
    launches = dev.n_sync_launches
    got = {a: getattr(deps, a)()
           for a in (first, *(a for a in _ACCESSORS if a != first))}
    assert dev.n_sync_launches - launches <= 1
    assert not (deps._dirty or deps._attr_dirty or deps._bpend)
    for dev_cols, host_cols in (
            (got["device_table"], deps._slot_host_cols()),
            (got["device_attr_cols"], deps._attr_host_cols()),
            (got["bucket_device"][:8], deps._bhost)):
        assert len(dev_cols) == len(host_cols)
        for dev_a, host_a in zip(dev_cols, host_cols):
            assert dev_a.dtype == host_a.dtype
            assert np.array_equal(np.asarray(dev_a), host_a)


def _mutate(rng, dev, live, hlc, keyspace, max_keys):
    """One random mutation of the store: register, widen a footprint
    (add_intervals on a held slot), status move, executeAt write,
    invalidate, free."""
    from accord_tpu.primitives.timestamp import Timestamp
    r = rng.random()
    if r < 0.45 or len(live) < 8:
        kind = TxnKind.Write if rng.random() < 0.7 else TxnKind.Read
        node = 1 + int(rng.integers(0, 5))
        if rng.random() < 0.5:
            tid = TxnId.create(1, hlc, kind, Domain.Key, node)
            keys = Keys([IntKey(int(t)) for t in rng.choice(
                keyspace, int(rng.integers(1, max_keys + 1)), replace=False)])
        else:
            tid = TxnId.create(1, hlc, kind, Domain.Range, node)
            s = int(rng.integers(0, keyspace - 80))
            keys = Ranges.of(Range(s, s + int(rng.integers(1, 80))))
        dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
        live.append(tid)
        return
    tid = live[int(rng.integers(0, len(live)))]
    if r < 0.55 and tid.domain() == Domain.Key:
        # the same txn witnessed on more keys: its footprint is the union
        dev.register(tid, int(InternalStatus.PREACCEPTED),
                     Keys([IntKey(int(t)) for t in rng.choice(
                         keyspace, 2, replace=False)]))
    elif r < 0.65:
        dev.update_status(tid, int(InternalStatus.ACCEPTED))
    elif r < 0.78:
        dev.update_status(
            tid, int(InternalStatus.COMMITTED),
            Timestamp.from_values(1, hlc + int(rng.integers(0, 50)),
                                  1 + int(rng.integers(0, 5))))
    elif r < 0.84:
        dev.update_status(tid, int(InternalStatus.INVALIDATED))
    else:
        live.remove(tid)
        dev.free(tid)


@pytest.mark.parametrize("path", ["cells", "full"])
@pytest.mark.parametrize("first", _ACCESSORS)
def test_one_sync_keeps_every_device_copy_level(first, path):
    """Random register / add_intervals / status move / executeAt write /
    invalidate / free sequences, through a capacity grow (64 -> 128 slots
    and on) and an interval grow (4 -> 8 and on): after EVERY sync the device
    copies equal the host truth, whichever accessor asks first.  ``cells``
    syncs every few mutations (dirty rows and pending cells through the one
    program), ``full`` after bursts that leave every table mostly dirty
    (whole uploads, no program)."""
    rng = np.random.default_rng(7)
    keyspace = 64 << _DepsMirror.BSHIFT         # 64 buckets: no row grow
    store, dev, safe = _mk_state()
    deps = dev.deps
    live, hlc = [], 1
    rounds, burst, max_keys = (70, 9, 6) if path == "cells" else (5, 700, 8)
    rising = [InternalStatus.ACCEPTED, InternalStatus.COMMITTED,
              InternalStatus.STABLE, InternalStatus.APPLIED]
    caps, widths = {deps.capacity}, {deps.max_intervals}
    whole = 0
    for r in range(rounds):
        for _ in range(burst):
            _mutate(rng, dev, live, hlc, keyspace, max_keys)
            hlc += 1
        if path == "full":
            # every held txn moves, and the next round's keys reach twice
            # the buckets (the row arrays grow): all three tables go whole
            for tid in live:
                dev.update_status(tid, int(rising[min(r, 3)]))
            keyspace *= 2
        before = (dev.n_sync_launches, dev.n_sync_uploads)
        _check_synced(dev, first)
        whole += (dev.n_sync_launches, dev.n_sync_uploads) == \
            (before[0], before[1] + 7 + 9 + 8)
        caps.add(deps.capacity)
        widths.add(deps.max_intervals)
    assert len(caps) >= 2 and len(widths) >= 2
    _check_rows(deps)
    if path == "cells":
        # one program a round but for the first sync and the grows
        assert dev.n_sync_launches >= rounds - 6
        assert dev.kernel_times["sync_bucket_cells"][0] >= rounds - 6
        assert rounds <= dev.kernel_times["sync_tables"][0] <= rounds + 3
    else:
        assert whole >= rounds - 1 and dev.n_sync_launches <= 1
    # the state all of that left answers as the dense kernel does
    qs = _queries(rng, 16, keyspace, hlc)
    got = _raw_deps(dev, qs)
    dev.BUCKETED = False
    assert got == _raw_deps(dev, qs)


def test_each_table_sync_draws_its_transfer_fault():
    """The slot, attribution and bucket uploads are three fault points of
    the one sync: each fires when its turn in the draw comes, a sync that
    faulted has sent nothing, and a flush that meets any of them fails over
    to the host route and quarantines."""
    from accord_tpu.utils import faults

    class Nth:
        """A fault source that fires on its n-th draw only."""

        def __init__(self, n):
            self.left = n

        def decide(self, _p):
            self.left -= 1
            return self.left == 0

    rng = np.random.default_rng(11)
    keyspace = 64 << _DepsMirror.BSHIFT
    store, dev, safe = _mk_state()
    deps = dev.deps
    live = []
    for hlc in range(1, 40):
        _mutate(rng, dev, live, hlc, keyspace, 3)
    _check_synced(dev, "bucket_device")
    qs = _queries(rng, 8, keyspace, 100)
    for n, point in enumerate(("slot upload", "attr column upload",
                               "bucket upload"), 1):
        for hlc in range(100 * n, 100 * n + 6):
            _mutate(rng, dev, live, hlc, keyspace, 3)
        tid = TxnId.create(1, 100 * n + 50, TxnKind.Write, Domain.Key, 1)
        dev.register(tid, int(InternalStatus.PREACCEPTED),
                     Keys([IntKey(3), IntKey(700)]))
        live.append(tid)
        assert deps._dirty and deps._attr_dirty and deps._bpend
        dirty = (set(deps._dirty), set(deps._attr_dirty), set(deps._bpend))
        uploads = dev.n_sync_uploads
        with faults.device_fault("transfer", 1.0, Nth(n)):
            with pytest.raises(faults.TransferFault, match=point):
                deps.device_table()
        assert dirty == (deps._dirty, deps._attr_dirty, deps._bpend)
        assert dev.n_sync_uploads == uploads
        # the same draw inside a flush: host answer, one quarantine
        faulted = dev.n_device_faults
        with faults.device_fault("transfer", 1.0, Nth(n)):
            got = _raw_deps(dev, qs)
        assert dev.n_device_faults == faulted + 1
        assert dev.n_fallback_queries == n * len(qs)
        dev._dev_quar_flushes = dev._dev_backoff = 0    # lift the quarantine
        _check_synced(dev, "device_table")
        assert got == _raw_deps(dev, qs)


@pytest.mark.parametrize("shape", ["spread", "hot", "wide", "mixed"])
def test_bucketed_matches_bruteforce_and_dense(shape):
    rng = np.random.default_rng({"spread": 1, "hot": 2, "wide": 3,
                                 "mixed": 4}[shape])
    hot = 0.6 if shape == "hot" else (0.2 if shape == "mixed" else 0.0)
    wide = 0.3 if shape == "wide" else (0.2 if shape == "mixed" else 0.0)
    keyspace = 20_000
    entries = _workload(rng, 300, keyspace, hot_frac=hot, wide_frac=wide)
    store, dev, safe = _mk_state()
    for tid, toks, rngs in entries:
        keys = Ranges.of(*rngs) if rngs else Keys([IntKey(t) for t in toks])
        dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
    qs = _queries(rng, 40, keyspace, 300,
                  wide_q_frac=0.2 if shape in ("wide", "mixed") else 0.0)
    got = _raw_deps(dev, qs)
    assert dev.n_bucketed_queries > 0, "bucketed path never ran"
    # identical to brute force
    for q, g in zip(qs, got):
        assert g == _brute(entries, q)
    # identical to the dense kernel on the same store
    dev.BUCKETED = False
    dense = _raw_deps(dev, qs)
    assert got == dense


def test_bucketed_survives_frees_and_requery():
    rng = np.random.default_rng(9)
    keyspace = 5_000
    entries = _workload(rng, 200, keyspace, wide_frac=0.15)
    store, dev, safe = _mk_state()
    for tid, toks, rngs in entries:
        keys = Ranges.of(*rngs) if rngs else Keys([IntKey(t) for t in toks])
        dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
    drop = entries[::3]
    for tid, _t, _r in drop:
        dev.free(tid)
    kept = [e for i, e in enumerate(entries) if i % 3 != 0]
    qs = _queries(rng, 30, keyspace, 200)
    got = _raw_deps(dev, qs)
    for q, g in zip(qs, got):
        assert g == _brute(kept, q)
    # the freed slots must be fully de-indexed: no stale bucket entries
    bslot = dev.deps._bhost[2]
    live = set(bslot[bslot >= 0].tolist())
    live.update(s for (_l, _h, s, _c) in dev.deps.wide_entries)
    assert live and all(dev.deps.id_of.get(s) is not None for s in live)
    _check_rows(dev.deps)


def test_bucketed_attributed_matches_dense_attributed():
    """The protocol-complete path (floors + elision + attribution) must be
    byte-identical between the bucketed and dense kernels."""
    rng = np.random.default_rng(11)
    keyspace = 8_000
    entries = _workload(rng, 250, keyspace, hot_frac=0.2, wide_frac=0.1)
    store, dev, safe = _mk_state()
    floor_id = TxnId.create(1, 50, TxnKind.ExclusiveSyncPoint, Domain.Range, 1)
    store.redundant_before.add_redundant(
        Ranges.of(Range(0, keyspace // 3)), floor_id)
    for tid, toks, rngs in entries:
        keys = Ranges.of(*rngs) if rngs else Keys([IntKey(t) for t in toks])
        dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
    qs = _queries(rng, 32, keyspace, 250, wide_q_frac=0.1)

    def run():
        builders = [DepsBuilder() for _ in qs]
        dev.deps_query_batch_attributed(safe, qs, builders)
        out = []
        for b in builders:
            deps = b.build()
            out.append(([(k, tuple(deps.key_deps.txn_ids_for(k)))
                         for k in deps.key_deps.keys.tokens()],
                        [(r.start, r.end, tuple(deps.range_deps.txn_ids[j]
                                                for j in row))
                         for r, row in zip(deps.range_deps.ranges,
                                           deps.range_deps._per_range)]))
        return out

    got = run()
    dev.BUCKETED = False
    want = run()
    assert got == want


def test_device_floor_prune_matches_host_floors():
    """A floor covering the whole queried window makes the batch-global
    DEVICE prune engage (min_floor_over > NONE); results must still be
    exactly the host-floored ones, on both kernels."""
    rng = np.random.default_rng(21)
    keyspace = 4_000
    entries = _workload(rng, 220, keyspace, wide_frac=0.1)
    store, dev, safe = _mk_state()
    floor_id = TxnId.create(1, 1_000, TxnKind.ExclusiveSyncPoint,
                            Domain.Range, 1)
    store.redundant_before.add_redundant(
        Ranges.of(Range(-(1 << 60), 1 << 60)), floor_id)
    assert store.redundant_before.min_floor_over(0, keyspace) == floor_id
    for tid, toks, rngs in entries:
        keys = Ranges.of(*rngs) if rngs else Keys([IntKey(t) for t in toks])
        dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
    qs = _queries(rng, 24, keyspace, 220)

    def run():
        builders = [DepsBuilder() for _ in qs]
        dev.deps_query_batch_attributed(safe, qs, builders)
        return [sorted(set(b.build().key_deps.txn_ids)
                       | set(b.build().range_deps.txn_ids))
                for b in builders]

    got = run()
    dev.BUCKETED = False
    assert got == run()
    # floors applied: every brute-force dep below the floor is gone, every
    # one at/above it survives
    for q, g in zip(qs, got):
        want = [t for t in _brute(entries, q) if t >= floor_id]
        assert g == want


def test_bucketed_random_lifecycle_interleaving():
    """Property run over random register / invalidate / free / query
    interleavings: the bucket index (incl. invalidation de-indexing and
    straggler spill) must agree with the dense kernel and with a host
    brute force that drops invalidated entries, at every step."""
    from accord_tpu.ops import deps_kernel as dk
    rng = np.random.default_rng(31)
    keyspace = 3_000
    store, dev, safe = _mk_state()
    live = {}         # tid -> (toks, rngs)
    all_entries = []
    hlc = 1
    for step in range(300):
        roll = rng.random()
        if roll < 0.55 or not live:
            tid_entries = _workload(rng, 1, keyspace, wide_frac=0.15,
                                    hot_frac=0.15)
            (tid, toks, rngs) = tid_entries[0]
            tid = TxnId.create(1, hlc, tid.kind(), tid.domain(),
                               1 + int(rng.integers(0, 5)))
            hlc += int(rng.integers(1, 4))
            keys = Ranges.of(*rngs) if rngs else \
                Keys([IntKey(t) for t in toks])
            dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
            live[tid] = (toks, rngs)
            all_entries.append((tid, toks, rngs))
        elif roll < 0.75:
            tid = list(live)[int(rng.integers(0, len(live)))]
            dev.update_status(tid, int(InternalStatus.INVALIDATED))
            del live[tid]
            all_entries = [e for e in all_entries if e[0] != tid]
        else:
            tid = list(live)[int(rng.integers(0, len(live)))]
            dev.free(tid)
            del live[tid]
            all_entries = [e for e in all_entries if e[0] != tid]
        if step % 60 == 59:
            qs = _queries(rng, 12, keyspace, 10_000, wide_q_frac=0.1)
            got = _raw_deps(dev, qs)
            for q, g in zip(qs, got):
                assert g == _brute(all_entries, q), f"step {step}"
    # final cross-check vs the dense kernel
    qs = _queries(rng, 20, keyspace, 10_000)
    got = _raw_deps(dev, qs)
    dev.BUCKETED = False
    assert got == _raw_deps(dev, qs)
