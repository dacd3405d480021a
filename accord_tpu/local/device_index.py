"""Device-backed conflict index + execution drain for a CommandStore.

This is the live protocol wiring of the two TPU kernels (SURVEY.md §7
stages 3-4): every globally-visible transaction a store witnesses is
registered in a struct-of-arrays DepsTable slot kept incrementally in sync
with the host command state, PreAccept/Accept/BeginRecovery dependency scans
run through ONE flush path (deps_query_batch_begin ->
deps_query_batch_end_attributed, the attributed kernels of
ops.deps_kernel), and the executeAt-gated
execution drain is driven by ops.drain_kernel.ready_frontier over a live
adjacency graph instead of per-dependency listener fan-out.

Ref semantics preserved:
 - deps scan: accord-core/src/main/java/accord/local/CommandsForKey.java:614-650
   (mapReduceActive) + InMemoryCommandStore.java:863-877 (range scan) +
   messages/PreAccept.java:245-265 (calculatePartialDeps)
 - drain: local/Commands.java:656-857 (maybeExecute /
   updateDependencyAndMaybeExecute / NotifyWaitingOn)

Host numpy mirrors are the source of truth (the sim mutates them in place,
deterministically, under the store's single-threaded task queue).  The deps
table's device buffers are refreshed by scatter-updating only dirty rows, so
on TPU the table stays HBM-resident between queries and only deltas cross
the PCIe/ICI boundary; the drain graph is uploaded whole per tick — it is
bounded by the in-flight (stable-but-unapplied) set, which sweep_free keeps
small.  The host command records remain authoritative for execution: the
kernel proposes the ready frontier, and each candidate is re-validated
against its WaitingOn bitset before executing — any mirror divergence
degrades to a no-op, never a wrong execution.

Regime-adaptive dispatch: every batched deps scan is routed per flush to
the cheapest of THREE routes, all of which hand the shared finalize the
same ATTRIBUTED entry set over the same snapshot — the protocol never
sees which route ran (results are bit-identical by construction).  The
device kernels answer EXACTLY (sorted composite overlap-triple codes;
ops.deps_kernel module docstring) with the batch-global RedundantBefore
prune, the per-token floors, elision and the key dedupe applied
in-kernel, and the result download is two-stage and compacted: the
scalar header first, then only the live entry prefix — the host-side
collect is a pure vectorized decode:

 - **host**: a vectorized numpy interval scan over only the LIVE TAIL
   (slots above the batch-global RedundantBefore floor): token-sorted point
   entries probed with searchsorted, flat range entries stabbed with one
   broadcast.  Wins when the live working set is small relative to a device
   round trip (the hot-key / durable-prefix-dominated regime, where 90%+ of
   the table sits below the floor and RTTs dominate a ~10k-entry scan).
 - **bucketed** (device): the CINTIA-analogue bucket index
   (ops.deps_kernel.bucketed_attr_jit) — O(candidates) per query.  Under
   a mesh the bucket rows are row-sharded (parallel.sharded.
   sharded_bucketed_attr) and the shard blocks merge on device.
 - **dense** (device): the exact O(N) kernel (calculate_deps_flat_attr) —
   the fallback when footprint distributions defeat bucketing (straggler
   spill, wide queries).  Under a mesh it row-shards the slot table
   (sharded_flat_attr).

Device-fault tolerance (the degradation ladder): the accelerator is a
FAILURE DOMAIN, not a trusted coprocessor.  Every device-boundary operation
(kernel launch, upload, result download, capacity grow) can fail — really
(XlaRuntimeError / transfer error / HBM OOM) or injected (utils.faults'
seedable device-fault registry, the accelerator-side analogue of the sim's
network nemesis).  Because all routes are bit-identical, failure handling
is CORRECTNESS-PRESERVING by construction:

    device route -> quarantine -> host route -> compaction -> backpressure

 - any device-boundary exception during a flush quarantines the device
   routes and FAILS THE IN-FLIGHT FLUSH OVER to the host route — the
   protocol sees the same bytes, one flush later than the kernel would
   have delivered them;
 - while quarantined every flush (and drain tick) is pinned to host; the
   quarantine expires after an exponential-backoff flush count with
   deterministic jitter, then ONE probe flush re-tries the device route —
   success restores it, failure re-quarantines deeper (a drain tick that
   the ROUTER sweeps on the host, because its live set is too small to
   pay for the round trip, is no rung of this ladder: it is counted in
   ``n_priced_host_ticks`` and kernel_times ``drain_tick_host``, never in
   ``n_host_ticks`` or a fault counter; so that such a store still meets
   this ladder, its first tick and then one priced tick every
   ``TICK_AUDIT_MICROS`` of the node's clock go to the device all the
   same: ``n_audit_ticks``; a serving node asks every store for that
   tick at start and once a period, ``audit_route``, so that a store whose
   traffic arms nothing is audited too);
 - paranoia mode (utils.faults.PARANOIA or DeviceState.paranoia)
   shadow-verifies every device flush against the host route and treats a
   mismatch as a device fault — the detector for silent result corruption
   (the stale_result fault class);
 - a configurable device-memory budget (``device_budget_slots``, also env
   ACCORD_TPU_DEVICE_BUDGET_SLOTS) backpressures ``_grow_capacity``: at
   the budget the mirror COMPACTS (frees slots wholly below the global
   RedundantBefore floor — exactly the entries every attributed scan
   would drop) instead of doubling, and if compaction cannot make room the
   store degrades PINNED-TO-HOST (degraded-but-live) with a loud one-shot
   event rather than dying.

Quarantine/fallback/compaction counters ride the bench ``# index:`` line,
``Cluster.stats`` (DeviceFault.*) and the structured trace
(utils.trace record_fault / record_quarantine).

The crossover is NOT hard-coded: a once-per-process micro-probe measures
the device round-trip cost, the device per-element kernel cost and the
host per-element scan cost (DeviceState._measure_route_calibration); the
router compares a modeled host scan cost (live-above-floor working set,
estimated O(1) per dispatch from _DepsMirror's incremental counters +
RedundantBefore.version) against the modeled device cost and picks the
cheaper side.  The drain tick is priced the same way (_host_tick_pays: the
Python sweep over the driven rows and the mirror's dep edges, ``c_sweep``
each, against the round trip plus the frontier program over the padded state).
``DeviceState.route_override`` pins a route for tests and
benches; per-route dispatch counters (n_host_queries / n_bucketed_queries /
n_dense_queries / n_mesh_queries) make routing regressions visible in
every BENCH artifact.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import devprof
from ..ops import deps_kernel as dk
from ..ops import drain_kernel as drk
from ..ops.packing import to_i64
from ..primitives.keys import Range, Ranges
from ..primitives.timestamp import Domain, Kinds, Timestamp, TxnId
from ..utils import faults
from ..utils.random_source import RandomSource

_MIN_CAPACITY = 64
_MIN_INTERVALS = 4


def _pow2_at_least(n: int, floor: int = _MIN_INTERVALS) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


_PZ = None


def _prune_zeros():
    """Replicated zero floor for the always-pruned sharded kernels: under
    the unsigned ts_lt order nothing sorts below (0, 0, 0), so a zero
    triple prunes nothing (same convention as calculate_deps' default)."""
    global _PZ
    if _PZ is None:
        _PZ = (jnp.asarray(np.int64(0)), jnp.asarray(np.int64(0)),
               jnp.asarray(np.int32(0)))
    return _PZ


def _grow(arr: np.ndarray, new_len: int, fill) -> np.ndarray:
    out = np.full((new_len,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


_FETCH_POOL = None


def _fetch_pool():
    """Shared two-worker pool for the two-stage download prefetch: the
    pipelined path keeps at most two flushes in flight, and spawning a
    fresh thread per flush measured ~2ms/batch of pure start_new_thread
    on the 2-core box — a fifth of the whole headline batch budget."""
    global _FETCH_POOL
    if _FETCH_POOL is None:
        from concurrent.futures import ThreadPoolExecutor
        _FETCH_POOL = ThreadPoolExecutor(max_workers=2,
                                         thread_name_prefix="accord-fetch")
    return _FETCH_POOL


def _prefix_len(maxtot: int, s: int) -> int:
    """Length of the live entry prefix to transfer, rounded up to a coarse
    granularity so the device-side slice compiles a bounded number of
    shapes (at most ~16 per learned ``s``) instead of one per total."""
    gran = max(128, s >> 4)
    return min(s, -(-maxtot // gran) * gran)


def _fetch_entry_prefix(ent_dev, s: int, maxtot: int) -> np.ndarray:
    """Stage-2 of the compacted download: transfer ONLY the live prefix of
    the entry block (the pow2-padded tail never crosses the wire).
    Returns host [1, L]."""
    length = _prefix_len(maxtot, s)
    if length == 0:
        return np.zeros((1, 0), np.dtype(ent_dev.dtype))
    return np.asarray(ent_dev[:length]).reshape(1, length)


# scalar prefix of the attributed header (ops.deps_kernel: total, the two
# overflow watermarks, the two elision tallies), before row_end[B]
_HOFF = 5


def _decode_triples(hdr: np.ndarray, ent: np.ndarray, nq: int,
                    mq: int, q_m: int):
    """Vectorized parse of one attributed CSR download (header [1, 5+nq],
    entries [1, L]; a mesh route's shard blocks arrive merged on device).
    Returns per-TRIPLE arrays (b, slot, dep_col, q_col); slot ids are
    GLOBAL on every route."""
    counts = np.diff(hdr[0, _HOFF:].astype(np.int64), prepend=0)
    b = np.repeat(np.arange(nq, dtype=np.int64), counts)
    j, m_i, q_i = dk.decode_triples(ent[0, :int(hdr[0, 0])], mq // q_m,
                                    q_m)
    return b, j, m_i, q_i


# one bucket-index entry as the host keeps it: the BucketTable's eight
# per-entry columns, in its field order, as ONE record — an add is one store,
# and the eight [g_cap, BUCKET_K] host arrays are field views of one buffer
_BUCKET_REC = np.dtype([("lo", np.int64), ("hi", np.int64),
                        ("slot", np.int32), ("col", np.int32),
                        ("msb", np.int64), ("lsb", np.int64),
                        ("node", np.int32), ("kind", np.int32)])
_BUCKET_PAD = np.array((dk.PAD_LO, dk.PAD_HI, -1, 0, 0, 0, 0, 0),
                       _BUCKET_REC)
# a pending cell on its way to the device: the record + its flat cell index
_CELL_BYTES = _BUCKET_REC.itemsize + 8
# the record as the staging buffer carries it: six int64 words, the int32
# pairs (slot, col) and (node, kind) each in one word, low half first
_CELL_WORDS = _BUCKET_REC.itemsize // 8
# floor of the padded pending-cell count: a served store's few-cell syncs
# and a 64-txn flush's ~1k cells share ONE compiled scatter shape
_MIN_CELLS = 2048


@functools.partial(jax.jit, static_argnames=("n_rows", "n_cells"))
def _sync_tables(table, acols, bdev, stage, n_rows, n_cells):
    """The single-device table sync as ONE program over ONE staging buffer
    (``_DepsMirror.sync_device`` packs it): int64 words, field-major —
    ``n_rows`` dirty slot rows (their index, then msb, lsb, node, status;
    kind, lo[M], hi[M] when the slot ``table`` takes them; domain and the
    executeAt columns when ``acols`` does), then ``n_cells`` bucket cells
    (flat index, then the _BUCKET_REC words).  A table that takes nothing
    is passed as None and comes back as None."""
    at = 0

    def take(n):
        nonlocal at
        at += n
        return stage[at - n:at]

    def i32(a):
        return a.astype(jnp.int32)

    if n_rows:
        idx = i32(take(n_rows))
        msb, lsb = take(n_rows), take(n_rows)
        node, status = i32(take(n_rows)), i32(take(n_rows))
        if table is not None:
            m = table.lo.shape[1]
            kind = i32(take(n_rows))
            lo = take(m * n_rows).reshape(m, n_rows).T
            hi = take(m * n_rows).reshape(m, n_rows).T
            table = dk.DepsTable(*(
                a.at[idx].set(v) for a, v in zip(
                    table, (msb, lsb, node, kind, status, lo, hi))))
        if acols is not None:
            dom = i32(take(n_rows))
            emsb, elsb = take(n_rows), take(n_rows)
            enode, eknown = i32(take(n_rows)), take(n_rows) != 0
            acols = dk.AttrCols(*(
                a.at[idx].set(v) for a, v in zip(
                    acols, (dom, status, msb, lsb, node, emsb, elsb, enode,
                            eknown))))
    if n_cells:
        cell = i32(take(n_cells))
        k = bdev[0].shape[1]
        lo, hi, slot_col, msb, lsb, node_kind = \
            take(_CELL_WORDS * n_cells).reshape(_CELL_WORDS, n_cells)
        vals = (lo, hi, i32(slot_col), i32(slot_col >> 32), msb, lsb,
                i32(node_kind), i32(node_kind >> 32))
        bdev = tuple(a.at[cell // k, cell % k].set(v)
                     for a, v in zip(bdev, vals))
    return table, acols, bdev


def _host_index_of(status, lo_a, hi_a, msb, lsb, node, fkey):
    """Build the host-route index (see _DepsMirror.host_index) from
    explicit arrays — shared by the live cached path and the snapshot-based
    fused fallback/shadow path.  ``fkey`` is the normalized floor (None =
    no floor)."""
    live = (status >= 0) & (status != dk.SLOT_INVALIDATED)
    if fkey is not None:
        from ..ops.packing import to_u64
        fm = np.uint64(to_u64(to_i64(fkey.msb)))
        fl = np.uint64(to_u64(to_i64(fkey.lsb)))
        fn = np.int32(fkey.node)
        um = msb.astype(np.uint64)
        ul = lsb.astype(np.uint64)
        live &= ((um > fm) | ((um == fm)
                             & ((ul > fl) | ((ul == fl) & (node >= fn)))))
    j = np.nonzero(live)[0]
    lo, hi = lo_a[j], hi_a[j]
    used = lo <= hi
    pt = used & (lo == hi)
    rr, cc = np.nonzero(pt)
    ptok = lo[rr, cc]
    order = np.argsort(ptok, kind="stable")
    rr2, cc2 = np.nonzero(used & ~pt)
    return (ptok[order], j[rr][order], cc[order],
            lo[rr2, cc2], hi[rr2, cc2], j[rr2], cc2)


class _DepsMirror:
    """Host mirror of one store's DepsTable, with dirty-row tracking, plus
    the host half of the bucketed interval index (the CINTIA-analogue in
    ops.deps_kernel.bucketed_flat): per-bucket rows of entry records kept
    IN PLACE, entry by entry, wide/overflow entries in a straggler set, and
    the cells each mutation wrote scatter-updated to the device alongside
    the slot table."""

    # bucket width = 2^BSHIFT tokens; intervals (and query probes) touching
    # more than SPAN buckets go to the wide/straggler path
    BSHIFT = 6
    SPAN = 4
    BUCKET_K = 128        # entries per bucket before spilling wide
    WIDE_MAX = 4096       # beyond this many stragglers the dense scan wins

    def __init__(self, capacity: int = _MIN_CAPACITY,
                 max_intervals: int = _MIN_INTERVALS):
        self.capacity = capacity
        self.max_intervals = max_intervals
        # owning DeviceState (set by DeviceState.__init__): consulted before
        # any capacity grow so the HBM budget can compact-instead-of-double
        # (see DeviceState._approve_grow)
        self.owner = None
        self.msb = np.zeros(capacity, np.int64)
        self.lsb = np.zeros(capacity, np.int64)
        self.node = np.zeros(capacity, np.int32)
        self.kind = np.zeros(capacity, np.int32)
        self.domain = np.zeros(capacity, np.int8)   # Domain enum value
        self.status = np.full(capacity, dk.SLOT_FREE, np.int32)
        self.lo = np.full((capacity, max_intervals), dk.PAD_LO, np.int64)
        self.hi = np.full((capacity, max_intervals), dk.PAD_HI, np.int64)
        # decided executeAt per slot (host-only; drives the VECTORIZED
        # transitive-elision check in attribution)
        self.emsb = np.zeros(capacity, np.int64)
        self.elsb = np.zeros(capacity, np.int64)
        self.enode = np.zeros(capacity, np.int32)
        self.eknown = np.zeros(capacity, bool)
        self.slot_of: Dict[TxnId, int] = {}
        self.id_of: Dict[int, TxnId] = {}
        # parallel object column: obj[slot] is the TxnId living in the slot
        # (None when free) — snapshot with the packed columns at batch
        # begin, so result attribution is a pure C-level take instead of a
        # per-slot dict lookup + verification
        self.obj = np.full(capacity, None, object)
        self.free_slots: List[int] = list(range(capacity - 1, -1, -1))
        self._dirty: Set[int] = set()
        self._device: Optional[dk.DepsTable] = None
        # r21 store-shard residency (parallel.store_shard.StoreShards, set
        # by the owner's spill rung): while active, the sharded table /
        # attr uploads route through per-slice resident buffers with their
        # OWN dirty sets — device_table() consumes and clears ``_dirty``,
        # so the sliced consumer must not share it
        self.shards = None
        self._dirty_sh: Set[int] = set()
        self._attr_dirty_sh: Set[int] = set()
        # mesh-sharded slot-table copy, cached SEPARATELY from the
        # single-device one (r08 satellite: the router alternating
        # single-device and mesh routes between flushes used to clobber
        # one consumer's copy with the other's placement and re-upload —
        # or worse, implicitly reshard — on every switch).  Keyed on the
        # mutation version: the dep mask reads only liveness from the
        # status column, and every mutation it can observe (alloc/free,
        # invalidate, footprint growth) bumps ``version``
        self._device_sh: Optional[dk.DepsTable] = None
        self._device_sh_key = None
        # -- bucket index (host truth): ``_brec[row, :_blen[row]]`` are the
        # bucket's entries, in no order (the kernels sort the candidate
        # codes), every cell beyond is _BUCKET_PAD; wide entries are
        # (lo, hi, slot, col).  col is the interval's column in its slot
        # row — the third leg of the exact overlap triple the kernels emit
        self.bucket_row: Dict[int, int] = {}     # bucket id -> dense row
        self._blen: List[int] = []               # entries per dense row
        # slot -> the flat cells (row * BUCKET_K + pos) its entries sit in
        self._bcells: Dict[int, List[int]] = {}
        self.wide_entries: Set[Tuple[int, int, int, int]] = set()
        # live-occupancy high-water across bucket rows (monotonic, like
        # capacity): the kernels slice the entry axis to its pow2 — the
        # [G, BUCKET_K] rows are ~95% padding on spread keyspaces and the
        # candidate matrix (and kernel wall) shrinks proportionally
        self.bucket_max_len = 0
        self._bdev = None                         # jnp 8-tuple
        self._bpend: Set[int] = set()             # flat cells _bdev lacks
        self._alloc_bucket_rows(_MIN_CAPACITY)
        # wide/straggler host arrays cached PER PADDED WIDTH (r08): the
        # single-device and mesh consumers may ask for different pow2
        # floors, and alternating routes between flushes must not rebuild
        # (and re-upload) the wide list on every switch — each width keeps
        # its own copy keyed on the wide version counter
        self._whost_cache: Dict[int, tuple] = {}  # w -> (wide_version, arrs)
        self._wdev = None                         # (wlo, whi, wslot...) jnp
        self._wdev_key = None
        self._bsh = None                          # mesh-sharded BucketTable
        self._bsh_key = None
        self._sorted_bids = np.zeros(0, np.int64)
        self._row_of_sorted = np.zeros(0, np.int32)
        self._bids_stale = False
        # -- routing state (see module docstring): incremental mutation /
        # liveness counters + the cached floor stats and host-route index.
        # ``version`` bumps on every slot mutation, ``bucket_version`` /
        # ``wide_version`` on bucket-index mutations (they key the sharded
        # bucket upload), ``n_live`` counts non-free non-invalidated slots
        # exactly — together they make the live-above-floor estimate O(1)
        # amortized per dispatch.
        self.version = 0
        self.bucket_version = 0
        self.wide_version = 0
        self.n_live = 0
        # ``mut_version`` bumps on EVERY column write (unlike ``version``,
        # which skips live->live status moves the kernels cannot observe):
        # it keys the deferred-collect snapshot cache, whose columns the
        # host attribution DOES read in full
        self.mut_version = 0
        self._snap = None
        self._fstats = None                       # cached floor stats
        self._hidx = None                         # cached host-route index
        self._hidx_key = None
        # -- device attribution columns (r15): domain / fresh status /
        # decided executeAt, scatter-updated alongside the slot table so
        # the ATTRIBUTED kernels can apply elision in-kernel.  They get
        # their own dirty set and version: unlike the dep mask, the
        # attribution pass DOES observe live->live status moves and
        # executeAt writes, so the sharded (full-reupload) caches key on
        # ``attr_version``, not ``version``
        self.attr_version = 0
        self._attr_dirty: Set[int] = set()
        self._attr_dev = None                     # dk.AttrCols (1 device)
        self._attr_repl = None                    # replicated under a mesh
        self._attr_repl_key = None
        self._attr_sh = None                      # slot-sharded under a mesh
        self._attr_sh_key = None

    # -- bucket index maintenance -------------------------------------------
    def bucket_keff(self) -> int:
        """Static entry-axis slice for the bucketed kernels: the pow2 of
        the live-occupancy high-water (floor 8, cap BUCKET_K)."""
        return min(self.BUCKET_K,
                   _pow2_at_least(max(self.bucket_max_len, 1), 8))

    def _alloc_bucket_rows(self, g_cap: int, old=None) -> None:
        """Host rows for ``g_cap`` buckets: ``old``'s, then _BUCKET_PAD.  The
        device copy is absent or of another shape: full upload next."""
        rec = np.zeros((g_cap, self.BUCKET_K), _BUCKET_REC)
        rec["lo"], rec["hi"], rec["slot"] = dk.PAD_LO, dk.PAD_HI, -1
        if old is not None:
            rec[: len(old)] = old
        self._brec, self._bflat, self._g_cap = rec, rec.reshape(-1), g_cap
        self._bhost = tuple(rec[f] for f in _BUCKET_REC.names)
        self._bdev = None
        self._bpend.clear()

    def _bucket_add(self, slot: int, lo: int, hi: int, col: int) -> None:
        if self.status[slot] == dk.SLOT_INVALIDATED:
            return   # structurally excluded (de-indexed on invalidation)
        self.bucket_version += 1
        blo, bhi = lo >> self.BSHIFT, hi >> self.BSHIFT
        if bhi - blo + 1 > self.SPAN:
            self.wide_entries.add((lo, hi, slot, col))
            self.wide_version += 1
            return
        # the slot is live, so its immutable id/kind columns are current
        ent = (lo, hi, slot, col, self.msb[slot], self.lsb[slot],
               self.node[slot], self.kind[slot])
        blen, k = self._blen, self.BUCKET_K
        for bid in range(blo, bhi + 1):
            row = self.bucket_row.get(bid)
            if row is None:
                row = len(blen)
                if row == self._g_cap:
                    self._alloc_bucket_rows(2 * row, self._brec)
                self.bucket_row[bid] = row
                blen.append(0)
                self._bids_stale = True
            n = blen[row]
            if n >= k:
                # overflow spill: the straggler list absorbs hot buckets
                self.wide_entries.add((lo, hi, slot, col))
                self.wide_version += 1
            else:
                cell = row * k + n
                self._bflat[cell] = ent
                blen[row] = n + 1
                self._bcells.setdefault(slot, []).append(cell)
                if self._bdev is not None:
                    self._bpend.add(cell)
                if n >= self.bucket_max_len:
                    self.bucket_max_len = n + 1

    def _bucket_remove(self, slot: int) -> None:
        """De-index every interval of ``slot`` (called before the row's
        lo/hi are cleared on free): each of its cells takes its row's last
        entry, and the vacated last cell is cleared."""
        self.bucket_version += 1
        if self.wide_entries:
            row_lo, row_hi = self.lo[slot], self.hi[slot]
            for m in range(self.max_intervals):
                ent = (int(row_lo[m]), int(row_hi[m]), slot, m)
                if ent in self.wide_entries:
                    self.wide_entries.discard(ent)
                    self.wide_version += 1
        cells = self._bcells.pop(slot, None)
        if cells is None:
            return
        flat, blen, k = self._bflat, self._blen, self.BUCKET_K
        wrote = []
        for i, cell in enumerate(cells):
            cells[i] = -1      # done: a later same-slot move must not match
            row = cell // k
            blen[row] -= 1
            last = row * k + blen[row]
            if cell != last:
                moved = flat[last]
                flat[cell] = moved
                owner = int(moved["slot"])
                own = cells if owner == slot else self._bcells[owner]
                own[own.index(last)] = cell
                wrote.append(cell)
            flat[last] = _BUCKET_PAD
            wrote.append(last)
        if self._bdev is not None:
            self._bpend.update(wrote)

    def bid_rows(self) -> Tuple[np.ndarray, np.ndarray]:
        """(sorted bucket ids, dense row per id) for vectorized query->row
        mapping via searchsorted."""
        if self._bids_stale or len(self._sorted_bids) != len(self.bucket_row):
            n = len(self.bucket_row)
            bids = np.fromiter(self.bucket_row.keys(), np.int64, n)
            rows = np.fromiter(self.bucket_row.values(), np.int32, n)
            order = np.argsort(bids)
            self._sorted_bids = bids[order]
            self._row_of_sorted = rows[order]
            self._bids_stale = False
        return self._sorted_bids, self._row_of_sorted

    def _sync_wide_host(self, floor: int):
        """Host arrays for the wide/straggler entries, padded to a pow2 of
        at least ``floor`` (the mesh caller passes its device count so the
        wide dimension row-shards evenly).  Cached per padded width and
        keyed on the wide version counter, so single-device and mesh
        consumers asking for different widths never invalidate each
        other's copy."""
        w = _pow2_at_least(max(len(self.wide_entries), 1), floor)
        hit = self._whost_cache.get(w)
        if hit is None or hit[0] != self.wide_version:
            wlo = np.full(w, dk.PAD_LO, np.int64)
            whi = np.full(w, dk.PAD_HI, np.int64)
            wslot = np.full(w, -1, np.int32)
            wcol = np.zeros(w, np.int32)
            wmsb = np.zeros(w, np.int64)
            wlsb = np.zeros(w, np.int64)
            wnode = np.zeros(w, np.int32)
            wkind = np.zeros(w, np.int32)
            for i, (lo, hi, s, col) in enumerate(self.wide_entries):
                wlo[i] = lo
                whi[i] = hi
                wslot[i] = s
                wcol[i] = col
                wmsb[i] = self.msb[s]
                wlsb[i] = self.lsb[s]
                wnode[i] = self.node[s]
                wkind[i] = self.kind[s]
            hit = (self.wide_version,
                   (wlo, whi, wslot, wcol, wmsb, wlsb, wnode, wkind))
            self._whost_cache[w] = hit
            if len(self._whost_cache) > 4:   # widths only grow; drop stale
                for stale_w in sorted(self._whost_cache)[:-4]:
                    del self._whost_cache[stale_w]
        return hit[1]

    def bucket_device(self) -> "dk.BucketTable":
        """The BucketTable on the (single) device, after sync_device has
        brought it (and the slot table and the attribution columns) level
        with the host."""
        self.sync_device(bucket=True)
        whost = self._sync_wide_host(16)
        wkey = (self.wide_version, whost[0].shape[0])
        if self._wdev is None or self._wdev_key != wkey:
            faults.check("transfer", "wide upload")
            self._wdev = tuple(jnp.asarray(a) for a in whost)
            self._wdev_key = wkey
        return dk.BucketTable(*self._bdev, *self._wdev)

    def bucket_device_sharded(self, mesh) -> "dk.BucketTable":
        """Mesh placement of the bucket index: bucket ROWS and the wide list
        row-sharded across the mesh (the per-shard slices feed
        parallel.sharded.sharded_bucketed_attr).  Any mutation triggers a
        full sharded re-upload, keyed on the bucket/wide version counters —
        same policy as device_table_sharded."""
        d = int(np.prod(list(mesh.shape.values())))
        whost = self._sync_wide_host(max(16, d))
        key = (self.bucket_version, self.wide_version, self._g_cap,
               whost[0].shape[0], tuple(dev.id for dev in mesh.devices.flat))
        if self._bsh is not None and self._bsh_key == key:
            return self._bsh
        from ..parallel.sharded import shard_bucket_table
        self._bsh = shard_bucket_table(
            mesh, dk.BucketTable(*self._bhost, *whost))
        self._bsh_key = key
        return self._bsh

    # -- slot management ----------------------------------------------------
    def alloc(self, txn_id: TxnId) -> int:
        slot = self.slot_of.get(txn_id)
        if slot is not None:
            return slot
        if not self.free_slots:
            self._grow_capacity()
        slot = self.free_slots.pop()
        self.slot_of[txn_id] = slot
        self.id_of[slot] = txn_id
        self.obj[slot] = txn_id
        self.eknown[slot] = False
        self.msb[slot] = to_i64(txn_id.msb)
        self.lsb[slot] = to_i64(txn_id.lsb)
        self.node[slot] = txn_id.node
        self.kind[slot] = int(txn_id.kind())
        self.domain[slot] = int(txn_id.domain())
        self.status[slot] = dk.SLOT_TRANSITIVE
        self.lo[slot] = dk.PAD_LO
        self.hi[slot] = dk.PAD_HI
        self._dirty.add(slot)
        if self.shards is not None:
            self._dirty_sh.add(slot)
        self._mark_attr(slot)
        self.version += 1
        self.mut_version += 1
        self.n_live += 1
        return slot

    def free(self, txn_id: TxnId) -> None:
        slot = self.slot_of.pop(txn_id, None)
        if slot is None:
            return
        self.id_of.pop(slot, None)
        self.obj[slot] = None
        self.eknown[slot] = False
        self._bucket_remove(slot)
        if self.status[slot] != dk.SLOT_INVALIDATED:
            self.n_live -= 1
        self.status[slot] = dk.SLOT_FREE
        self.lo[slot] = dk.PAD_LO
        self.hi[slot] = dk.PAD_HI
        self.free_slots.append(slot)
        self._dirty.add(slot)
        if self.shards is not None:
            self._dirty_sh.add(slot)
        self._mark_attr(slot)
        self.version += 1
        self.mut_version += 1

    def _grow_capacity(self) -> None:
        if self.owner is not None and not self.owner._approve_grow(self):
            # HBM backpressure: compaction made room under the budget —
            # the caller's free_slots.pop() proceeds without doubling
            return
        old = self.capacity
        new = old * 2
        self.msb = _grow(self.msb, new, 0)
        self.lsb = _grow(self.lsb, new, 0)
        self.node = _grow(self.node, new, 0)
        self.kind = _grow(self.kind, new, 0)
        self.domain = _grow(self.domain, new, 0)
        self.status = _grow(self.status, new, dk.SLOT_FREE)
        self.lo = _grow(self.lo, new, dk.PAD_LO)
        self.hi = _grow(self.hi, new, dk.PAD_HI)
        self.obj = _grow(self.obj, new, None)
        self.emsb = _grow(self.emsb, new, 0)
        self.elsb = _grow(self.elsb, new, 0)
        self.enode = _grow(self.enode, new, 0)
        self.eknown = _grow(self.eknown, new, False)
        self.free_slots.extend(range(new - 1, old - 1, -1))
        self.capacity = new
        self.mut_version += 1
        self._snap = None
        self._device = None  # shape changed: full re-upload
        self._device_sh = None
        self._attr_dev = None
        self._attr_repl = None
        self._attr_sh = None
        self.attr_version += 1

    def _grow_intervals(self) -> None:
        new_m = self.max_intervals * 2
        lo = np.full((self.capacity, new_m), dk.PAD_LO, np.int64)
        hi = np.full((self.capacity, new_m), dk.PAD_HI, np.int64)
        lo[:, : self.max_intervals] = self.lo
        hi[:, : self.max_intervals] = self.hi
        self.lo, self.hi = lo, hi
        self.max_intervals = new_m
        self.mut_version += 1
        self._snap = None
        self._device = None
        self._device_sh = None

    def add_intervals(self, slot: int, tokens: Sequence[int],
                      ranges: Sequence[Range]) -> None:
        """Union new intervals into the slot's footprint (idempotent)."""
        row_lo, row_hi = self.lo[slot], self.hi[slot]
        used = int(np.sum(row_lo <= row_hi))
        new: List[Tuple[int, int]] = []
        for t in tokens:
            new.append((t, t))
        for r in ranges:
            new.append((r.start, r.end - 1))
        for lo_v, hi_v in new:
            present = False
            for m in range(used):
                if row_lo[m] <= lo_v and hi_v <= row_hi[m]:
                    present = True
                    break
            if present:
                continue
            while used >= self.max_intervals:
                self._grow_intervals()
                row_lo, row_hi = self.lo[slot], self.hi[slot]
            row_lo[used] = lo_v
            row_hi[used] = hi_v
            self._dirty.add(slot)
            if self.shards is not None:
                self._dirty_sh.add(slot)
            self.version += 1
            self.mut_version += 1
            self._bucket_add(slot, lo_v, hi_v, used)
            used += 1

    def set_status(self, slot: int, status: int) -> None:
        cur = int(self.status[slot])
        if cur != status:
            if status == dk.SLOT_INVALIDATED and cur != dk.SLOT_FREE:
                self.n_live -= 1
                # liveness changed: the host-route index (which excludes
                # dead slots STRUCTURALLY) is stale.  Live->live status
                # moves deliberately do NOT bump: the index carries only
                # geometry + liveness, and commit/apply churn between
                # flushes would otherwise rebuild it every flush in
                # exactly the hot regime the host route serves
                self.version += 1
            self.status[slot] = status
            self._dirty.add(slot)
            if self.shards is not None:
                self._dirty_sh.add(slot)
            self._mark_attr(slot)
            self.mut_version += 1

    # -- device attribution columns (r15) -----------------------------------
    def _mark_attr(self, slot: int) -> None:
        self._attr_dirty.add(slot)
        if self.shards is not None:
            self._attr_dirty_sh.add(slot)
        self.attr_version += 1

    def mark_exec(self, slot: int) -> None:
        """An executeAt landed on ``slot`` (emsb/elsb/enode/eknown written
        by DeviceState._advance_status): the device attribution columns
        must see it before the next attributed launch."""
        self._attr_dirty.add(slot)
        if self.shards is not None:
            self._attr_dirty_sh.add(slot)
        self.attr_version += 1
        self.mut_version += 1   # snapshot columns changed too

    def _attr_host_cols(self):
        return (self.domain.astype(np.int32), self.status,
                self.msb, self.lsb, self.node,
                self.emsb, self.elsb, self.enode, self.eknown)

    def device_attr_cols(self) -> "dk.AttrCols":
        """Single-device attribution columns, synced in lockstep with
        device_table() (sync_device)."""
        self.sync_device()
        return self._attr_dev

    def device_attr_cols_replicated(self, mesh) -> "dk.AttrCols":
        """Fully-replicated attribution columns for the mesh-sharded
        BUCKETED kernel (entries carry global slot ids, so every shard
        grades every slot).  Keyed on attr_version: any status/executeAt
        write re-replicates — these columns are O(N) scalars, small next
        to the interval table the mesh exists to split."""
        key = (self.attr_version, self.capacity,
               tuple(dev.id for dev in mesh.devices.flat))
        if self._attr_repl is not None and self._attr_repl_key == key:
            return self._attr_repl
        faults.check("transfer", "attr replicated upload")
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        sr = NamedSharding(mesh, P())
        self._attr_repl = dk.AttrCols(
            *(jax.device_put(a, sr) for a in self._attr_host_cols()))
        self._attr_repl_key = key
        return self._attr_repl

    def device_attr_cols_sharded(self, mesh) -> "dk.AttrCols":
        """Slot-sharded attribution columns for the mesh-sharded DENSE
        kernel (each shard grades only its own slice), keyed on
        attr_version (NOT ``version``: elision observes live->live status
        moves and executeAt writes the dep mask never reads)."""
        if self.shards is not None and self.shards.active:
            return self.shards.attr_cols()
        key = (self.attr_version, self.capacity,
               tuple(dev.id for dev in mesh.devices.flat))
        if self._attr_sh is not None and self._attr_sh_key == key:
            return self._attr_sh
        faults.check("transfer", "attr sharded upload")
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from ..parallel.sharded import STORE_AXIS
        s1 = NamedSharding(mesh, P(STORE_AXIS))
        self._attr_sh = dk.AttrCols(
            *(jax.device_put(a, s1) for a in self._attr_host_cols()))
        self._attr_sh_key = key
        return self._attr_sh

    # -- host route (the third dispatch target; see module docstring) -------
    def _above_floor_mask(self, floor_id) -> np.ndarray:
        """bool[capacity]: packed id >= floor, under EXACTLY the kernel's
        ts_lt order (unsigned on the two int64 words, then signed node)."""
        from ..ops.packing import to_u64
        fm = np.uint64(to_u64(to_i64(floor_id.msb)))
        fl = np.uint64(to_u64(to_i64(floor_id.lsb)))
        fn = np.int32(floor_id.node)
        um = self.msb.astype(np.uint64)
        ul = self.lsb.astype(np.uint64)
        return ((um > fm) | ((um == fm)
                            & ((ul > fl) | ((ul == fl) & (self.node >= fn)))))

    def floor_stats(self, floor_id) -> Dict[str, float]:
        """Estimated shape of the LIVE-above-floor working set: slot count,
        point/range interval-entry counts and the point-token span.  Cached;
        recomputed (one vectorized pass) only when the floor changes or the
        mutation version drifts past 1/8 of the live set — between
        recomputes the slot-count delta (``n_live`` is exact) scales the
        entry estimates, so the router's read is O(1) per dispatch."""
        fkey = floor_id if floor_id is not None and floor_id > TxnId.NONE \
            else None
        st = self._fstats
        if st is None or st["floor"] != fkey or \
                self.version - st["version"] > max(64, st["n_at"] >> 3):
            live = (self.status >= 0) & (self.status != dk.SLOT_INVALIDATED)
            if fkey is not None:
                live &= self._above_floor_mask(fkey)
            j = np.nonzero(live)[0]
            lo, hi = self.lo[j], self.hi[j]
            used = lo <= hi
            pt = used & (lo == hi)
            n_pt = int(pt.sum())
            toks = lo[pt]
            st = self._fstats = {
                "floor": fkey, "version": self.version, "n_at": self.n_live,
                "n_above": len(j), "n_pt": n_pt,
                "n_rng": int(used.sum()) - n_pt,
                "tok_lo": int(toks.min()) if n_pt else 0,
                "tok_hi": int(toks.max()) if n_pt else 0}
        grown = max(self.n_live - st["n_at"], 0)
        per = (st["n_pt"] + st["n_rng"]) / max(st["n_at"], 1)
        frac_pt = st["n_pt"] / max(st["n_pt"] + st["n_rng"], 1)
        return {"n_above": st["n_above"] + grown,
                "n_pt": st["n_pt"] + grown * per * frac_pt,
                "n_rng": st["n_rng"] + grown * per * (1.0 - frac_pt),
                "tok_lo": st["tok_lo"], "tok_hi": st["tok_hi"]}

    def host_index(self, floor_id):
        """(ptok, pslot, pcol, rlo, rhi, rslot, rcol): the live-above-floor
        tail as a token-SORTED point-entry array plus a flat range-entry
        table — the reference's own scan shape (CommandsForKey sorted
        arrays + rangeCommands, ref: local/CommandsForKey.java:614-650),
        rebuilt from the mirror whenever a mutation lands and cached
        between flushes.  ``pcol``/``rcol`` record each entry's interval
        column in its slot row, so probes yield exact emit triples and the
        collect pass never rebuilds the overlap geometry."""
        fkey = floor_id if floor_id is not None and floor_id > TxnId.NONE \
            else None
        key = (fkey, self.version)
        if self._hidx is not None and self._hidx_key == key:
            return self._hidx
        self._hidx = _host_index_of(self.status, self.lo, self.hi,
                                    self.msb, self.lsb, self.node, fkey)
        self._hidx_key = key
        return self._hidx

    def host_pairs(self, qnp: np.ndarray, q_m: int, floor_id,
                   snapshot=None):
        """The host route's candidate generation: per-ENTRY arrays
        (query row, slot, entry interval column, query interval column)
        satisfying the EXACT kernel predicate (liveness + floor structurally
        via the index; witness / earlier / not-self as vectorized compares
        identical to the device ts_lt) — the same overlap triples the
        device routes' raw compaction yields, so the attribution filter
        sees identical inputs and results are bit-identical by
        construction.

        ``snapshot`` = (msb, lsb, node, kind, status, lo, hi) computes the
        scan against a begin-time copy of the mirror instead of the live
        arrays (no caching): the fused harvest path runs a store task
        AFTER dispatch, and its host fallback / shadow verify must answer
        for the snapshot the device kernel scanned, not for mutations that
        landed in between."""
        if snapshot is not None:
            s_msb, s_lsb, s_node, s_kind, s_status, s_lo, s_hi = snapshot
            fkey = floor_id if floor_id is not None \
                and floor_id > TxnId.NONE else None
            idx = _host_index_of(s_status, s_lo, s_hi, s_msb, s_lsb,
                                 s_node, fkey)
        else:
            s_msb, s_lsb, s_node, s_kind = (self.msb, self.lsb, self.node,
                                            self.kind)
            idx = self.host_index(floor_id)
        ptok, pslot, pcol, rlo, rhi, rslot, rcol = idx
        lo = qnp[:, 7:7 + q_m]
        hi = qnp[:, 7 + q_m:7 + 2 * q_m]
        used = lo <= hi
        # duplicate query intervals (same (lo, hi) as an earlier column of
        # the same row) probe identical slices and emit identical entries
        # the finalize would dedupe anyway — drop them at the probe (the
        # kernels' first-q dedupe is the device analogue)
        for m_i_ in range(1, q_m):
            dup = np.zeros(qnp.shape[0], bool)
            for m_j_ in range(m_i_):
                dup |= ((lo[:, m_i_] == lo[:, m_j_])
                        & (hi[:, m_i_] == hi[:, m_j_]) & used[:, m_j_])
            used[:, m_i_] &= ~dup
        qi, mi = np.nonzero(used)
        flo = lo[qi, mi]
        fhi = hi[qi, mi]
        parts_b: List[np.ndarray] = []
        parts_j: List[np.ndarray] = []
        parts_m: List[np.ndarray] = []
        parts_q: List[np.ndarray] = []
        if len(ptok):
            # token-sorted probe: every query interval (point OR range)
            # selects the contiguous token slice it covers
            l = np.searchsorted(ptok, flo, side="left")
            r = np.searchsorted(ptok, fhi, side="right")
            cnt = r - l
            tot = int(cnt.sum())
            if tot:
                owner = np.repeat(np.arange(len(qi)), cnt)
                # pos = per-probe slice start + within-slice offset, with
                # ONE repeat: arange(tot) already walks each slice 0..cnt
                # after subtracting the repeated running base
                pos = np.arange(tot) + np.repeat(l - (np.cumsum(cnt) - cnt),
                                                 cnt)
                parts_b.append(qi[owner])
                parts_j.append(pslot[pos])
                parts_m.append(pcol[pos])
                parts_q.append(mi[owner])
        if len(rlo) and len(qi):
            ov = (rlo[None, :] <= fhi[:, None]) & (flo[:, None] <= rhi[None, :])
            ii, jj = np.nonzero(ov)
            parts_b.append(qi[ii])
            parts_j.append(rslot[jj])
            parts_m.append(rcol[jj])
            parts_q.append(mi[ii])
        if not parts_b:
            return (np.zeros(0, np.int64),) * 4
        cb = np.concatenate(parts_b).astype(np.int64)
        cj = np.concatenate(parts_j).astype(np.int64)
        cm = np.concatenate(parts_m).astype(np.int64)
        cq = np.concatenate(parts_q).astype(np.int64)
        em, el, en = s_msb[cj], s_lsb[cj], s_node[cj]
        keep = (qnp[cb, 3] >> s_kind[cj]) & 1 > 0
        uem, ubm = em.view(np.uint64), qnp[cb, 0].view(np.uint64)
        uel, ubl = el.view(np.uint64), qnp[cb, 1].view(np.uint64)
        bn = qnp[cb, 2]
        keep &= ((uem < ubm) | ((uem == ubm)
                               & ((uel < ubl) | ((uel == ubl) & (en < bn)))))
        keep &= ~((em == qnp[cb, 4]) & (el == qnp[cb, 5])
                  & (en == qnp[cb, 6]))
        if not keep.all():
            cb, cj, cm, cq = cb[keep], cj[keep], cm[keep], cq[keep]
        return cb, cj, cm, cq

    def snapshot_cols(self):
        """(ids 9-tuple, ivs 3-tuple, kind) copies of every column the
        deferred collect + attribution path reads, cached on
        ``mut_version`` — back-to-back deferred flushes (pipelined bench
        batches, fused-harvest members) over an unmutated mirror share ONE
        copy instead of re-copying O(capacity x intervals) bytes per
        flush.  Consumers must treat the arrays as frozen."""
        s = self._snap
        if s is None or s[0] != self.mut_version:
            with self._span("snapshot_cols"):
                ids = (self.msb.copy(), self.lsb.copy(), self.node.copy(),
                       self.obj.copy(), self.status.copy(),
                       self.emsb.copy(), self.elsb.copy(),
                       self.enode.copy(), self.eknown.copy())
                ivs = (self.lo.copy(), self.hi.copy(), self.domain.copy())
                s = self._snap = (self.mut_version, ids, ivs,
                                  self.kind.copy())
        return s[1], s[2], s[3]

    def _span(self, kind: str):
        """A host span of the owning DeviceState's (its ``kernel_times``),
        or a bare one on a mirror nobody owns."""
        owner = self.owner
        return owner._span(kind) if owner is not None \
            else devprof.span(kind)

    # -- device sync --------------------------------------------------------
    def device_table_sharded(self, mesh) -> dk.DepsTable:
        """Mesh placement: the slot dimension sharded across the mesh,
        cached SEPARATELY from the single-device copy and keyed on the
        mutation version counter — the router alternating single-device
        and mesh routes between flushes keeps BOTH copies live instead of
        invalidating one whenever the other syncs (pre-r08 this clobbered
        the shared cache and paid an implicit reshard per alternation).
        Any version drift triggers a full sharded re-upload (the
        incremental scatter path is single-device; on the virtual CPU mesh
        correctness is the point, and a real multi-chip deployment would
        shard the scatter too).  Live->live status moves don't bump the
        version: the dep mask reads only liveness from the status column,
        so a stale live status byte cannot change any answer."""
        if self.shards is not None and self.shards.active:
            # r21 sliced residency: per-slice scatter sync + zero-copy
            # assembly (with quarantined slices' status masked) replaces
            # the monolithic full re-upload
            return self.shards.table()
        key = (self.version, self.capacity, self.max_intervals,
               tuple(dev.id for dev in mesh.devices.flat))
        if self._device_sh is not None and self._device_sh_key == key:
            return self._device_sh
        faults.check("transfer", "sharded slot upload")
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from ..parallel.sharded import STORE_AXIS
        s1 = NamedSharding(mesh, P(STORE_AXIS))
        s2 = NamedSharding(mesh, P(STORE_AXIS, None))
        self._device_sh = dk.DepsTable(
            jax.device_put(self.msb, s1), jax.device_put(self.lsb, s1),
            jax.device_put(self.node, s1), jax.device_put(self.kind, s1),
            jax.device_put(self.status, s1), jax.device_put(self.lo, s2),
            jax.device_put(self.hi, s2))
        self._device_sh_key = key
        return self._device_sh

    def _slot_host_cols(self):
        return (self.msb, self.lsb, self.node, self.kind, self.status,
                self.lo, self.hi)

    def device_table(self) -> dk.DepsTable:
        self.sync_device()
        return self._device

    def sync_device(self, bucket: bool = False) -> None:
        """Bring the single-device copies level with the host: the slot
        table, the attribution columns and the bucket index (``bucket``
        asks for its first upload; once resident, every sync keeps it
        level).  Per table, as ever: a copy that is absent, of another
        shape or mostly dirty goes up whole (``jnp.asarray`` of the columns;
        the bucket index when its pending cells would cost as many bytes as
        the table).  Whatever else is dirty crosses as ONE staging buffer
        into ONE launch of _sync_tables: the union of the dirty slot and
        attribution rows (padded to one pow2 for both tables by repeating
        the last row, an idempotent scatter) and the pending cells (padded
        likewise, floor _MIN_CELLS).  device_table(), device_attr_cols()
        and bucket_device() all come here, so the first of them a flush
        asks leaves the others nothing to do."""
        full_slots = self._device is None \
            or len(self._dirty) * 2 >= self.capacity
        full_attrs = self._attr_dev is None \
            or len(self._attr_dirty) * 2 >= self.capacity
        n_pend = len(self._bpend)     # empty while the index is not resident
        n_cells = _pow2_at_least(n_pend, _MIN_CELLS) if n_pend else 0
        full_cells = bucket if self._bdev is None \
            else n_cells * _CELL_BYTES >= self._brec.nbytes
        slots = full_slots or bool(self._dirty)
        attrs = full_attrs or bool(self._attr_dirty)
        cells = full_cells or n_pend > 0
        if not (slots or attrs or cells):
            return
        if slots:
            faults.check("transfer", "slot upload")
        if attrs:
            faults.check("transfer", "attr column upload")
        if cells:
            faults.check("transfer", "bucket upload")
        owner, uploads = self.owner, 0
        with self._span("sync_tables"):
            if full_slots:
                self._device = dk.DepsTable(
                    *(jnp.asarray(a) for a in self._slot_host_cols()))
                self._dirty.clear()
                uploads += 7
            if full_attrs:
                self._attr_dev = dk.AttrCols(
                    *(jnp.asarray(a) for a in self._attr_host_cols()))
                self._attr_dirty.clear()
                uploads += 9
            if full_cells:
                with self._span("sync_bucket_full"):
                    self._bdev = tuple(jnp.asarray(a) for a in self._bhost)
                self._bpend.clear()
                n_pend = n_cells = 0
                uploads += 8
                if owner is not None:
                    owner.bucket_upload_bytes += self._brec.nbytes
            # the staging buffer: int64 words, field-major (_sync_tables)
            pieces = []
            table = acols = bdev = None
            rows = self._dirty | self._attr_dirty
            n_rows = _pow2_at_least(len(rows), 8) if rows else 0
            if rows:
                # a resident table rides along even when only the other has
                # dirty rows (its rows are rewritten with what they hold): the
                # program family stays one per (rows, cells) shape
                table = None if full_slots else self._device
                acols = None if full_attrs else self._attr_dev
                rows = np.sort(np.fromiter(rows, np.int64, len(rows)))
                rows = np.concatenate(
                    [rows, np.full(n_rows - len(rows), rows[-1])])
                pieces += [rows, self.msb[rows], self.lsb[rows],
                           self.node[rows], self.status[rows]]
                if table is not None:
                    pieces += [self.kind[rows], self.lo[rows].T.ravel(),
                               self.hi[rows].T.ravel()]
                if acols is not None:
                    pieces += [self.domain[rows], self.emsb[rows],
                               self.elsb[rows], self.enode[rows],
                               self.eknown[rows]]
            if n_pend:
                with self._span("sync_bucket_cells"):
                    bdev = self._bdev
                    at = np.fromiter(self._bpend, np.int64, n_pend)
                    at = np.concatenate(
                        [at, np.full(n_cells - n_pend, at[-1])])
                    pieces += [at, self._bflat[at].view(np.int64).reshape(
                        n_cells, _CELL_WORDS).T.ravel()]
                if owner is not None:
                    owner.n_bucket_cells_uploaded += n_pend
                    owner.bucket_upload_bytes += n_cells * _CELL_BYTES
            if pieces:
                table, acols, bdev = _sync_tables(
                    table, acols, bdev, np.concatenate(pieces, dtype=np.int64),
                    n_rows=n_rows, n_cells=n_cells)
                uploads += 1
                if table is not None:
                    self._device = table
                if acols is not None:
                    self._attr_dev = acols
                if bdev is not None:
                    self._bdev = bdev
                self._dirty.clear()
                self._attr_dirty.clear()
                self._bpend.clear()
        if owner is not None:
            owner.n_sync_uploads += uploads
            owner.n_sync_launches += bool(pieces)


@jax.jit
def _scatter_drain_scalars(status, em, el, en, idx, s_new, em_new, el_new,
                           en_new):
    """One fused dirty-row update for the drain state's scalar columns —
    the delta-upload path that replaced the r07 whole-graph upload per
    tick (the adjacency re-uploads only when edges or membership change)."""
    return (status.at[idx].set(s_new), em.at[idx].set(em_new),
            el.at[idx].set(el_new), en.at[idx].set(en_new))


class _DrainMirror:
    """Host mirror of the execution drain graph: SPARSE adjacency over the
    store's in-flight (stable-but-unapplied) txns and their direct
    dependencies — per-slot dep/waiter sets, the host analogue of the
    reference's WaitingOn bitset-over-txnIds (ref: local/Command.java:
    1295-1332).  The r04 dense bool[capacity, capacity] matrix needed
    O(N^2) host memory (10^10 entries at the 100k-in-flight spec); edge
    count here is bounded by the live waiting sets.

    r08 delta uploads: the compacted device state is CACHED between ticks.
    ``version`` bumps on any device-visible mutation, ``membership_version``
    on alloc/free (the live set — and therefore the compaction mapping —
    changed), ``edge_version`` on adjacency changes; status/executeAt moves
    land in ``_dirty_scalars``.  A tick whose membership and edges are
    unchanged scatter-updates only the dirty scalar rows of the cached
    device state instead of rebuilding and re-uploading the whole graph —
    exactly the dirty-row policy the deps table already uses."""

    def __init__(self, capacity: int = _MIN_CAPACITY):
        self.capacity = capacity
        self.deps_of: List[Set[int]] = [set() for _ in range(capacity)]
        self.waiters_of: List[Set[int]] = [set() for _ in range(capacity)]
        self.status = np.full(capacity, dk.SLOT_FREE, np.int32)
        self.exec_msb = np.zeros(capacity, np.int64)
        self.exec_lsb = np.zeros(capacity, np.int64)
        self.exec_node = np.zeros(capacity, np.int32)
        self.awaits_all = np.zeros(capacity, bool)
        self.active = np.zeros(capacity, bool)   # rows being driven to execution
        self.slot_of: Dict[TxnId, int] = {}
        self.id_of: Dict[int, TxnId] = {}
        self.free_slots: List[int] = list(range(capacity - 1, -1, -1))
        self.version = 0
        self.membership_version = 0
        self.edge_version = 0
        self.n_edges = 0        # sum of len(deps_of[.]): what a tick prices
        self._dirty_scalars: Set[int] = set()
        self._state_cache: Optional[Dict[str, object]] = None

    # -- edge maintenance ---------------------------------------------------
    def add_edge(self, waiter: int, dep: int) -> None:
        if dep not in self.deps_of[waiter]:
            self.n_edges += 1
        self.deps_of[waiter].add(dep)
        self.waiters_of[dep].add(waiter)
        self.edge_version += 1
        self.version += 1

    def clear_deps(self, slot: int) -> None:
        if self.deps_of[slot]:
            self.edge_version += 1
            self.version += 1
        for dep in self.deps_of[slot]:
            self.waiters_of[dep].discard(slot)
        self.n_edges -= len(self.deps_of[slot])
        self.deps_of[slot].clear()

    def _clear_edges(self, slot: int) -> None:
        self.clear_deps(slot)
        if self.waiters_of[slot]:
            self.edge_version += 1
            self.version += 1
        for w in self.waiters_of[slot]:
            self.deps_of[w].discard(slot)
        self.n_edges -= len(self.waiters_of[slot])
        self.waiters_of[slot].clear()

    def alloc(self, txn_id: TxnId) -> int:
        slot = self.slot_of.get(txn_id)
        if slot is not None:
            return slot
        if not self.free_slots:
            self._grow_capacity()
        slot = self.free_slots.pop()
        self.slot_of[txn_id] = slot
        self.id_of[slot] = txn_id
        self.status[slot] = dk.SLOT_TRANSITIVE
        self.exec_msb[slot] = 0
        self.exec_lsb[slot] = 0
        self.exec_node[slot] = 0
        self.awaits_all[slot] = txn_id.kind().awaits_only_deps()
        self._clear_edges(slot)
        self.active[slot] = False
        self.membership_version += 1
        self.version += 1
        return slot

    def free(self, slot: int) -> None:
        txn_id = self.id_of.pop(slot, None)
        if txn_id is not None:
            del self.slot_of[txn_id]
        self.status[slot] = dk.SLOT_FREE
        self._clear_edges(slot)
        self.active[slot] = False
        self.free_slots.append(slot)
        self.membership_version += 1
        self.version += 1

    def _grow_capacity(self) -> None:
        old = self.capacity
        new = old * 2
        self.deps_of.extend(set() for _ in range(new - old))
        self.waiters_of.extend(set() for _ in range(new - old))
        self.status = _grow(self.status, new, dk.SLOT_FREE)
        self.exec_msb = _grow(self.exec_msb, new, 0)
        self.exec_lsb = _grow(self.exec_lsb, new, 0)
        self.exec_node = _grow(self.exec_node, new, 0)
        self.awaits_all = _grow(self.awaits_all, new, False)
        self.active = _grow(self.active, new, False)
        self.free_slots.extend(range(new - 1, old - 1, -1))
        self.capacity = new

    def set_status(self, slot: int, status: int,
                   execute_at: Optional[Timestamp]) -> None:
        changed = int(self.status[slot]) != status
        self.status[slot] = status
        if execute_at is not None:
            em, el = to_i64(execute_at.msb), to_i64(execute_at.lsb)
            en = execute_at.node
            changed |= (int(self.exec_msb[slot]) != em
                        or int(self.exec_lsb[slot]) != el
                        or int(self.exec_node[slot]) != en)
            self.exec_msb[slot] = em
            self.exec_lsb[slot] = el
            self.exec_node[slot] = en
        if changed:
            self.version += 1
            self._dirty_scalars.add(slot)

    # above this live count the drain ships the ELL (padded row-index)
    # adjacency instead of the dense matrix: dense [n, n] at 100k in-flight
    # is 10GB of bools; ELL is n x max_degree
    DENSE_MAX = 8192
    # the smallest compacted state, and the smallest dirty-row scatter into
    # a state that large.  Every (state size) and (state size, scatter size)
    # is a separately compiled program, and so is every tuple of state sizes
    # that a fused tick stacks.  A served store's live set sits below 64
    # slots, so with this floor a node meets ONE program of each kind, in
    # its first ticks; with 16 it met 16, then now and then 32 and the
    # mixed pairs, and compiled each on the serving loop when it first did
    MIN_STATE_SLOTS = 64

    def state(self):
        """Compacted drain state over LIVE slots only (padded to a power-of-
        two bucket so jit caches per bucket): the kernel cost scales with the
        in-flight set, not the high-water capacity.  Returns (state,
        live_slot_index); ``state`` is a dense DrainState below DENSE_MAX
        live slots (MXU matvec fixpoint) and an EllDrainState above it
        (gather fixpoint — no O(N^2) anywhere).

        The device state is cached between ticks (r08): an unchanged
        mirror re-ticks with ZERO upload; membership- and edge-stable
        mutations (status / executeAt moves — the common tick-to-tick
        churn) scatter only the dirty rows of the scalar columns into the
        cached state; only a changed live set or adjacency rebuilds."""
        c = self._state_cache
        if c is not None and c["version"] == self.version:
            return c["state"], c["live"]
        if (c is not None and c["membership"] == self.membership_version
                and c["edges"] == self.edge_version):
            # scalar delta: the live set and adjacency are exactly the
            # cached upload's — scatter the dirty status/executeAt rows
            rows = np.array(sorted(self._dirty_scalars), np.int64)
            li = c["local"][rows]
            ok = li >= 0
            rows, li = rows[ok], li[ok].astype(np.int32)
            st = c["state"]
            if len(li):
                padded = _pow2_at_least(
                    len(li), min(len(st.status), self.MIN_STATE_SLOTS))
                idx = np.concatenate(
                    [li, np.full(padded - len(li), li[-1], np.int32)])
                rws = np.concatenate(
                    [rows, np.full(padded - len(rows), rows[-1], np.int64)])
                new_s, new_em, new_el, new_en = _scatter_drain_scalars(
                    st.status, st.exec_msb, st.exec_lsb, st.exec_node,
                    jnp.asarray(idx), self.status[rws].astype(np.int32),
                    self.exec_msb[rws], self.exec_lsb[rws],
                    self.exec_node[rws].astype(np.int32))
                st = st._replace(status=new_s, exec_msb=new_em,
                                 exec_lsb=new_el, exec_node=new_en)
                c["state"] = st
            c["version"] = self.version
            self._dirty_scalars.clear()
            return st, c["live"]
        live = np.nonzero(self.status != dk.SLOT_FREE)[0]
        n = _pow2_at_least(len(live), self.MIN_STATE_SLOTS)
        local = np.full(self.capacity, -1, np.int32)
        local[live] = np.arange(len(live), dtype=np.int32)
        status = np.full(n, dk.SLOT_FREE, np.int32)
        status[: len(live)] = self.status[live]
        em = np.zeros(n, np.int64)
        el = np.zeros(n, np.int64)
        en = np.zeros(n, np.int32)
        aw = np.zeros(n, bool)
        em[: len(live)] = self.exec_msb[live]
        el[: len(live)] = self.exec_lsb[live]
        en[: len(live)] = self.exec_node[live]
        aw[: len(live)] = self.awaits_all[live]
        if n <= self.DENSE_MAX:
            adj = np.zeros((n, n), bool)
            ris, rjs = [], []
            for i in live:
                row = self.deps_of[int(i)]
                if row:
                    ris.extend([int(local[i])] * len(row))
                    rjs.extend(row)
            if ris:
                li = np.array(ris, np.int64)
                lj = local[np.array(rjs, np.int64)]
                ok = lj >= 0
                adj[li[ok], lj[ok]] = True
            state = drk.DrainState(jnp.asarray(adj), jnp.asarray(status),
                                   jnp.asarray(em), jnp.asarray(el),
                                   jnp.asarray(en), jnp.asarray(aw))
            return self._cache_state(state, live, local)
        max_deg = max((len(self.deps_of[int(i)]) for i in live), default=0)
        d = _pow2_at_least(max(max_deg, 1), 4)
        adj_idx = np.full((n, d), -1, np.int32)
        for i in live:
            row = self.deps_of[int(i)]
            if row:
                li = local[i]
                cols = local[np.fromiter(row, np.int64, len(row))]
                cols = cols[cols >= 0]
                adj_idx[li, : len(cols)] = cols
        state = drk.EllDrainState(jnp.asarray(adj_idx), jnp.asarray(status),
                                  jnp.asarray(em), jnp.asarray(el),
                                  jnp.asarray(en), jnp.asarray(aw))
        return self._cache_state(state, live, local)

    def _cache_state(self, state, live, local):
        self._state_cache = {"state": state, "live": live, "local": local,
                             "version": self.version,
                             "membership": self.membership_version,
                             "edges": self.edge_version}
        self._dirty_scalars.clear()
        return state, live

    def sweep_free(self) -> None:
        """Release slots that can no longer gate anything: terminal status,
        not being driven, and no waiter edge pointing at them."""
        terminal = (self.status == dk.SLOT_APPLIED) | \
                   (self.status == dk.SLOT_INVALIDATED)
        for slot in np.nonzero(terminal & ~self.active)[0]:
            s = int(slot)
            if not self.waiters_of[s] and self.id_of.get(s) is not None:
                self.free(s)

    def host_ready_slots(self) -> np.ndarray:
        """The drain frontier sweep on the host — EXACTLY
        drain_kernel.ready_frontier's rule over the sparse adjacency: a
        driven Stable row is ready unless some dep is live, non-applied,
        and gating (undecided, executing earlier, or the row awaits all
        deps).  A Python loop over the driven rows and the dep edges they
        visit, so its cost is theirs (``c_sweep`` of the route
        calibration): the route of every tick whose live set is too small
        to pay for a device round trip (DeviceState._host_tick_pays), and
        the bottom rung of the degradation ladder for the rest."""
        m64 = (1 << 64) - 1
        out = []
        for i in np.nonzero((self.status == dk.SLOT_STABLE) & self.active)[0]:
            i = int(i)
            ei = (int(self.exec_msb[i]) & m64, int(self.exec_lsb[i]) & m64,
                  int(self.exec_node[i]))
            awaits = bool(self.awaits_all[i])
            blocked = False
            for j in self.deps_of[i]:
                stj = int(self.status[j])
                if stj in (dk.SLOT_FREE, dk.SLOT_INVALIDATED,
                           dk.SLOT_APPLIED):
                    continue
                if stj < dk.SLOT_COMMITTED or awaits:
                    blocked = True      # undecided always gates
                    break
                ej = (int(self.exec_msb[j]) & m64, int(self.exec_lsb[j]) & m64,
                      int(self.exec_node[j]))
                if ej < ei:             # executes before i: gates
                    blocked = True
                    break
            if not blocked:
                out.append(i)
        return np.array(out, np.int64)


def _group_dedupe(cols):
    """lexsort by ``cols`` (last array = primary key) + shift-compare
    dedupe; returns (order, first_mask) — the tiny-array-friendly
    replacement for np.unique(axis=0), whose void-view machinery costs
    ~0.2ms per call."""
    order = np.lexsort(cols)
    first = np.ones(len(order), bool)
    acc = None
    for c in cols:
        cs = c[order]
        d = cs[1:] != cs[:-1]
        acc = d if acc is None else (acc | d)
    first[1:] = acc
    return order, first


def _finalize_key_batch(builders, bb, tt, trank, ntok, dkey, ndep,
                        objs) -> None:
    """Construct every builder's KeyDeps in ONE vectorized pass over the
    batch's key emits — integer-composite-key sorts + shift-compares;
    per-builder Python touches only group boundaries (the CSR freeze the
    reference does per reply in KeyDeps.Builder, done batch-wide).

    ``trank``/``dkey`` are dense ranks of the token and of the dep's packed
    id (caller-computed over the batch's unique tokens/slots), so the
    (builder, token, dep) dedupe and the per-builder dep ordering are
    single int64 argsorts instead of 5-column lexsorts — the r05 profile
    put ~40% of hot-regime attribution in those lexsorts."""
    from ..primitives.deps import KeyDeps
    from ..primitives.keys import RoutingKeys
    nb = int(bb.max()) + 1 if len(bb) else 1
    if nb * ntok * ndep >= (1 << 62):    # composite would overflow int64
        key1 = None                      # fall back to column lexsort
    else:
        key1 = (bb * ntok + trank) * np.int64(ndep) + dkey
    if key1 is None:
        o = np.lexsort((dkey, tt, bb))
        first = np.ones(len(o), bool)
        first[1:] = _changed((dkey, tt, bb), o)[1:]
    else:
        o = np.argsort(key1, kind="stable")
        k1 = key1[o]
        first = np.ones(len(o), bool)
        first[1:] = k1[1:] != k1[:-1]
    if not first.all():
        o = o[first]
    bb, tt, dkey, objs = bb[o], tt[o], dkey[o], objs[o]
    n = len(bb)
    # per-builder unique deps, ordered by packed id (== TxnId order; dkey
    # ranks preserve it)
    key2 = bb * np.int64(ndep) + dkey
    o2 = np.argsort(key2, kind="stable")
    k2 = key2[o2]
    b2 = bb[o2]
    newb = np.ones(n, bool)
    newb[1:] = b2[1:] != b2[:-1]
    newd = np.ones(n, bool)
    newd[1:] = k2[1:] != k2[:-1]
    gid = np.cumsum(newd) - 1
    base = np.maximum.accumulate(np.where(newb, gid, 0))
    inv = np.empty(n, np.int64)
    inv[o2] = gid - base
    dep_rows = o2[newd]
    dep_bs = bb[dep_rows]
    dep_objs = objs[dep_rows]
    dstart = np.nonzero(newb[newd])[0]
    dbounds = np.append(dstart, len(dep_rows))
    txn_lists = {int(b): dep_objs[dbounds[i]:dbounds[i + 1]].tolist()
                 for i, b in enumerate(dep_bs[dstart].tolist())}
    # (b, token) groups over the (b, tok, dep)-ordered arrays
    # (b, token) groups over the (b, tok, dep)-ordered rows, then one
    # COLUMNAR KeyDeps per builder: np slices only, no per-group Python
    newg = np.ones(n, bool)
    newg[1:] = (bb[1:] != bb[:-1]) | (tt[1:] != tt[:-1])
    gstart = np.nonzero(newg)[0]
    g_b = bb[gstart]
    g_t = tt[gstart]
    gbounds = np.append(gstart, n)
    newb_g = np.ones(len(gstart), bool)
    newb_g[1:] = g_b[1:] != g_b[:-1]
    bstart_g = np.nonzero(newb_g)[0]
    bbounds_g = np.append(bstart_g, len(gstart))
    for k_i in range(len(bstart_g)):
        s0, s1 = bstart_g[k_i], bbounds_g[k_i + 1]
        b = int(g_b[s0])
        row_ptr = gbounds[s0:s1 + 1] - gbounds[s0]
        dep_idx = inv[gbounds[s0]:gbounds[s1]]
        builders[b].key.set_prebuilt(KeyDeps.from_columns(
            RoutingKeys(g_t[s0:s1].tolist(), _presorted=True),
            txn_lists[b], row_ptr, dep_idx))


def _finalize_range_batch(builders, bb, lo, hi, dm, dl, dn, objs) -> None:
    """Range-domain analogue of _finalize_key_batch: the group key is the
    (lo, hi) clip instead of the token."""
    from ..primitives.deps import RangeDeps
    o, first = _group_dedupe((dn, dl, dm, hi, lo, bb))
    o = o[first]
    bb, lo, hi, dm, dl, dn, objs = (bb[o], lo[o], hi[o], dm[o], dl[o],
                                    dn[o], objs[o])
    n = len(bb)
    o2 = np.lexsort((dn, dl, dm, bb))
    b2 = bb[o2]
    newb = np.ones(n, bool)
    newb[1:] = b2[1:] != b2[:-1]
    newd = newb | _changed((dm, dl, dn), o2)
    gid = np.cumsum(newd) - 1
    base = np.maximum.accumulate(np.where(newb, gid, 0))
    inv = np.empty(n, np.int64)
    inv[o2] = gid - base
    dep_rows = o2[newd]
    dep_bs = bb[dep_rows]
    dep_objs = objs[dep_rows]
    dstart = np.nonzero(newb[newd])[0]
    dbounds = np.append(dstart, len(dep_rows))
    txn_lists = {int(b): dep_objs[dbounds[i]:dbounds[i + 1]].tolist()
                 for i, b in enumerate(dep_bs[dstart].tolist())}
    newg = np.ones(n, bool)
    newg[1:] = ((bb[1:] != bb[:-1]) | (lo[1:] != lo[:-1])
                | (hi[1:] != hi[:-1]))
    gstart = np.nonzero(newg)[0]
    g_b = bb[gstart]
    gbounds = np.append(gstart, n)
    newb_g = np.ones(len(gstart), bool)
    newb_g[1:] = g_b[1:] != g_b[:-1]
    bstart_g = np.nonzero(newb_g)[0]
    bbounds_g = np.append(bstart_g, len(gstart))
    for k_i in range(len(bstart_g)):
        s0, s1 = bstart_g[k_i], bbounds_g[k_i + 1]
        b = int(g_b[s0])
        row_ptr = gbounds[s0:s1 + 1] - gbounds[s0]
        dep_idx = inv[gbounds[s0]:gbounds[s1]]
        builders[b].range.set_prebuilt(RangeDeps.from_columns(
            lo[gstart[s0:s1]], hi[gstart[s0:s1]], txn_lists[b],
            row_ptr, dep_idx))


def _changed(cols, order) -> np.ndarray:
    """Shift-compare over reordered columns: True where any column differs
    from the previous row (first row excluded — callers OR with their own
    leading mask)."""
    acc = None
    for c in cols:
        cs = c[order]
        d = cs[1:] != cs[:-1]
        acc = d if acc is None else (acc | d)
    out = np.zeros(len(order), bool)
    out[1:] = acc
    return out


# -- device-resident attribution index (r15) ----------------------------------

def _ts_byte_keys(msb, lsb, node) -> np.ndarray:
    """Pack (msb int64, lsb int64, node int32) columns into V20 byte keys
    whose memcmp order IS the timestamp order (ts_lt): UNSIGNED on the two
    packed words (their bits as they are, big-endian), then the node id
    (signed: its sign bit flipped).  One np.searchsorted over these keys
    replaces a three-level lexicographic refinement — the host half of
    the in-kernel rank trick (the device compares precomputed integer
    RANKS instead)."""
    n = len(msb)
    out = np.empty((n, 20), np.uint8)
    out[:, 0:8] = np.asarray(msb, np.int64).astype(">u8")[:, None] \
        .view(np.uint8).reshape(n, 8)
    out[:, 8:16] = np.asarray(lsb, np.int64).astype(">u8")[:, None] \
        .view(np.uint8).reshape(n, 8)
    out[:, 16:20] = (np.asarray(node, np.int64).astype(np.int64)
                     .astype(np.uint32, casting="unsafe")
                     ^ np.uint32(1 << 31)).astype(">u4")[:, None] \
        .view(np.uint8).reshape(n, 4)
    return np.ascontiguousarray(out).view("V20").ravel()


_I64_INF = np.int64(np.iinfo(np.int64).max)


def _exact_ranks(sorted_unique: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """Ranks of ``keys`` within ``sorted_unique`` when every key IS a
    member: a scatter-map + gather (O(span) memory, one pass) replaces the
    n-log-n searchsorted whenever the value span is modest — the hot-key
    regime's tokens and the snapshot's slot ids are both dense."""
    n = len(sorted_unique)
    if n == 0:
        return np.zeros(len(keys), np.int64)
    lo = int(sorted_unique[0])
    span = int(sorted_unique[-1]) - lo + 1
    if span > max(4 * n, 1 << 16):
        return np.searchsorted(sorted_unique, keys)
    rmap = np.zeros(span, np.int64)
    rmap[sorted_unique - lo] = np.arange(n, dtype=np.int64)
    return rmap[keys - lo]


class _AttrIndexHost:
    """One store's floor + elision index as it stood at ONE flush's begin:
    the packed RedundantBefore segment floors, and the committed-write
    pivot list (CommandsForKey.packed_committed_execs) of every token that
    has one, by token.  DeviceState._attr_index MAINTAINS it: a token is
    marked dirty by the store's own notification (DeviceState.
    _advance_status, a decided key-domain write on a point) and by every
    CommandsForKey pivot mutation (CommandsForKey._cw_mutated, through the
    sink _attr_index attaches); a flush re-reads the dirty tokens' lists
    and nothing else, and hands out a NEW index that shares every
    untouched list with its predecessor — an index is never written after
    it was handed out, so a deferred collect or a fused harvest reads the
    begin-time state whatever was committed since.

    The host route's readers (floors_match, keep_floor, elide_decided)
    answer from the floors and from the lists of the tokens the batch's
    entries name, comparing the 128-bit executeAt triples directly.  What
    only a device route needs — dense ranks over the unique executeAts of
    ALL tokens, the concatenated CSR, the pow2-padded arrays that upload
    as ops.deps_kernel.AttrIndex (padding bounds the jit shape count) — is
    assembled from the same lists when a device route first asks (pad,
    rank_bounds, device, device_replicated) and kept with the index.  The
    rank compare is an order isomorphism of the direct compare, so every
    route elides the same entries."""

    __slots__ = ("fbnd", "fmsb", "flsb", "fnode", "rb_version", "toks",
                 "packs", "n_execs", "seq", "_owner", "_image", "_dev",
                 "_repl", "_repl_key")

    _SEQ = [0]

    def __init__(self, owner, floors, rb_version, toks, packs, n_execs):
        # monotone build id: cache keys over index IDENTITY must never
        # use id() (a rebuilt index can reuse a freed predecessor's
        # address and alias a stale cache entry)
        _AttrIndexHost._SEQ[0] += 1
        self.seq = _AttrIndexHost._SEQ[0]
        self._owner = owner
        self.fbnd, self.fmsb, self.flsb, self.fnode = floors
        self.rb_version = rb_version
        # sorted tokens with a non-empty pivot list; packs[i] is toks[i]'s
        # (msb, lsb int64; node int32) columns, ascending — the CFK's own
        # cached arrays, never written in place
        self.toks = toks
        self.packs = packs
        self.n_execs = n_execs
        self._image = None
        self._dev = None
        self._repl = None
        self._repl_key = None

    @property
    def floors(self):
        return self.fbnd, self.fmsb, self.flsb, self.fnode

    # -- the device image: assembled when a device route asks -------------
    def _device_image(self):
        """(uqkeys, pad): the UNIQUE exec triples, sorted — dense ranks
        over them turn exec < bound compares into integer rank compares on
        device — and the pow2-padded arrays (floors pad +INF / zero rows;
        elidable tokens pad +INF; padded eptr segments are empty)."""
        if self._image is not None:
            return self._image
        self._owner.n_attr_device_builds += 1
        etok, packs = self.toks, self.packs
        eptr = np.zeros(len(packs) + 1, np.int32)
        if packs:
            np.cumsum([len(p[0]) for p in packs], out=eptr[1:])
            exm = np.concatenate([p[0] for p in packs])
            exl = np.concatenate([p[1] for p in packs])
            exn = np.concatenate([p[2] for p in packs])
        else:
            exm = np.zeros(0, np.int64)
            exl = np.zeros(0, np.int64)
            exn = np.zeros(0, np.int32)
        keys = _ts_byte_keys(exm, exl, exn)
        uqkeys = np.unique(keys)
        u = len(uqkeys)
        rank = np.searchsorted(uqkeys, keys).astype(np.int64)
        seg = np.repeat(np.arange(len(etok), dtype=np.int64),
                        np.diff(eptr))
        erank = seg * np.int64(u + 1) + rank
        fp = _pow2_at_least(max(len(self.fbnd), 1), 1)
        tp = _pow2_at_least(max(len(etok), 1), 1)
        lp = _pow2_at_least(max(len(erank), 1), 1)
        l_real = len(erank)

        def tail(a, n, fill, dtype):
            out = np.full(n, fill, dtype)
            out[: len(a)] = a
            return out

        pad = (
            tail(self.fbnd, fp, _I64_INF, np.int64),
            tail(self.fmsb, fp + 1, 0, np.int64),
            tail(self.flsb, fp + 1, 0, np.int64),
            tail(self.fnode, fp + 1, 0, np.int32),
            tail(etok, tp, _I64_INF, np.int64),
            tail(eptr, tp + 1, l_real, np.int32),
            tail(erank, lp, _I64_INF, np.int64),
            tail(exm, lp, 0, np.int64),
            tail(exl, lp, 0, np.int64),
            tail(exn, lp, 0, np.int32),
            np.int64(u + 1))
        self._image = (uqkeys, pad)
        return self._image

    @property
    def pad(self):
        return self._device_image()[1]

    def rank_bounds(self, qnp: np.ndarray) -> np.ndarray:
        """Per-query rank of the started-before bound among the index's
        unique committed-write executeAts — the ``rankb`` column the
        kernels compare in place of 128-bit timestamps."""
        if self.n_execs == 0:
            return np.zeros(qnp.shape[0], np.int64)
        uqkeys = self._device_image()[0]
        keys = _ts_byte_keys(qnp[:, 0], qnp[:, 1], qnp[:, 2])
        return np.searchsorted(uqkeys, keys).astype(np.int64)

    def device(self) -> "dk.AttrIndex":
        if self._dev is None:
            faults.check("transfer", "attr index upload")
            self._dev = dk.AttrIndex(*(jnp.asarray(a) for a in self.pad))
        return self._dev

    def device_replicated(self, mesh) -> "dk.AttrIndex":
        key = tuple(dev.id for dev in mesh.devices.flat)
        if self._repl is None or self._repl_key != key:
            faults.check("transfer", "attr index upload")
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            sr = NamedSharding(mesh, P())
            self._repl = dk.AttrIndex(
                *(jax.device_put(a, sr) for a in self.pad))
            self._repl_key = key
        return self._repl

    # -- host-route mirror of the in-kernel attribution predicate ---------
    def keep_floor(self, tok, dmsb, dlsb, dnode) -> np.ndarray:
        """Per-entry exact-floor keep mask: dep >= deps_floor(token), the
        numpy twin of the kernel's floor leg."""
        fi = np.searchsorted(self.fbnd, tok, side="right")
        fm, fl, fn = self.fmsb[fi], self.flsb[fi], self.fnode[fi]
        um, ufm = dmsb.view(np.uint64), fm.view(np.uint64)
        ul, ufl = dlsb.view(np.uint64), fl.view(np.uint64)
        return ((um > ufm) | ((um == ufm)
                             & ((ul > ufl)
                                | ((ul == ufl) & (dnode >= fn)))))

    def floors_match(self, qnp: np.ndarray, q_m: int, floor_id) -> bool:
        """True when every floor segment the batch window touches equals
        the batch-global floor the host index already applied
        STRUCTURALLY — the per-entry floor leg is then a no-op the host
        route skips wholesale (the hot-key regime: one watermark over the
        hot range)."""
        from ..ops.packing import to_i64 as _ti
        lo = qnp[:, 7:7 + q_m]
        hi = qnp[:, 7 + q_m:7 + 2 * q_m]
        used = lo <= hi
        if not used.any():
            return True
        i0 = int(np.searchsorted(self.fbnd, int(lo[used].min()),
                                 side="right"))
        i1 = int(np.searchsorted(self.fbnd, int(hi[used].max()),
                                 side="right"))
        fm = self.fmsb[i0:i1 + 1]
        fl = self.flsb[i0:i1 + 1]
        fn = self.fnode[i0:i1 + 1]
        if floor_id is not None and floor_id > TxnId.NONE:
            t = (_ti(floor_id.msb), _ti(floor_id.lsb), floor_id.node)
        else:
            t = (0, 0, 0)
        return bool((fm == t[0]).all() and (fl == t[1]).all()
                    and (fn == t[2]).all())

    def elide_decided(self, tok, emsb, elsb, enode, tb, qnp) -> np.ndarray:
        """Per-entry decided-elision mask for candidates ALREADY known to
        be decided (Committed..Applied with executeAt): does a committed
        write on the token execute strictly between the dep and the
        bound of the entry's query (``qnp[tb]``)?  Reads only the lists of
        the tokens the entries name; the pivot search — a lower bound of
        the query's bound inside the token's ascending list — collapses to
        the UNIQUE (token, query) pairs and runs for all of them at once
        (the hot regime has a handful of hot tokens and bounds against
        tens of thousands of entries)."""
        out = np.zeros(len(tok), bool)
        if self.n_execs == 0:
            return out
        at = np.minimum(np.searchsorted(self.toks, tok), len(self.toks) - 1)
        ent = np.nonzero(self.toks[at] == tok)[0]
        if not len(ent):
            return out
        nq = np.int64(qnp.shape[0])
        uc, cinv = np.unique(at[ent] * nq + tb[ent], return_inverse=True)
        tix, qb = uc // nq, uc % nq
        # the touched tokens' lists, concatenated: the pairs are token-
        # major, so pair i searches segment seg[i] of ``ptr``
        first = np.ones(len(uc), bool)
        first[1:] = tix[1:] != tix[:-1]
        seg = np.cumsum(first) - 1
        live = [self.packs[i] for i in tix[first].tolist()]
        ptr = np.zeros(len(live) + 1, np.int64)
        np.cumsum([len(p[0]) for p in live], out=ptr[1:])
        xm = np.concatenate([p[0] for p in live]).view(np.uint64)
        xl = np.concatenate([p[1] for p in live]).view(np.uint64)
        xn = np.concatenate([p[2] for p in live])
        bm = qnp[qb, 0].view(np.uint64)
        bl = qnp[qb, 1].view(np.uint64)
        bn = qnp[qb, 2]
        base = ptr[seg]
        lo, hi = base, ptr[seg + 1]
        # binary search, all pairs at once; the first probe is the list's
        # LAST pivot (a query's bound mostly lies above every pivot, and
        # the search is then over)
        probe = hi - 1
        while True:
            open_ = lo < hi
            if not open_.any():
                break
            km, kl = xm[probe], xl[probe]
            lt = open_ & ((km < bm) | ((km == bm) & (
                (kl < bl) | ((kl == bl) & (xn[probe] < bn)))))
            lo = np.where(lt, probe + 1, lo)
            hi = np.where(open_ & ~lt, probe, hi)
            probe = (lo + hi) >> 1
            np.minimum(probe, len(xm) - 1, out=probe)
        cnt = (lo - base)[cinv]
        piv = np.maximum(lo - 1, 0)[cinv]
        pm, pl, pn = xm[piv], xl[piv], xn[piv]
        uem, uel = emsb[ent].view(np.uint64), elsb[ent].view(np.uint64)
        below = ((uem < pm) | ((uem == pm)
                              & ((uel < pl)
                                 | ((uel == pl) & (enode[ent] < pn)))))
        out[ent] = (cnt > 0) & below
        return out


class DeviceState:
    """Per-CommandStore device wiring: the deps index + drain graph, kept in
    sync by the Commands transition functions."""

    def __init__(self, store):
        self.store = store
        self.deps = _DepsMirror()
        self.deps.owner = self
        self.drain = _DrainMirror()
        self._tick_scheduled = False
        # mesh mode: with >1 jax device (the virtual 8-device CPU test mesh,
        # or a real multi-chip slice), the deps table's slot dimension is
        # sharded across the mesh and every scan runs as a shard_map with
        # per-shard CSR compaction (ref: the CommandStores scatter-gather,
        # CommandStores.java:575-643; cross-shard Deps.merge, Deps.java:256)
        self.mesh = None
        import jax as _jax
        n_dev = len(_jax.devices())
        if n_dev > 1:
            d = 1
            while d * 2 <= n_dev:
                d *= 2
            from ..parallel.sharded import make_mesh
            self.mesh = make_mesh(d)
        # learned compaction width for batched queries (sticky across
        # batches; see deps_query_batch)
        self._batch_k = 64
        # learned flat-compaction capacity (coarse pairs per batch)
        self._batch_flat = 4096
        # counters surfaced through sim stats / bench
        self.n_queries = 0
        self.n_ticks = 0
        self.n_kernel_deps = 0
        self.n_mesh_queries = 0
        self.n_bucketed_queries = 0
        self.n_dense_queries = 0
        self.n_host_queries = 0
        self.n_mesh_bucketed_queries = 0
        # queries of range-domain txns among n_queries, and those of them
        # that a device route answered (the rest went to the host route)
        self.n_range_queries = 0
        self.n_range_device_queries = 0
        self.n_dispatches = 0       # kernel dispatches: n_queries /
        #                             n_dispatches = mean lived batch size
        # r08 launch coalescing (local.dispatch.DeviceDispatcher): flushes
        # and drain ticks of THIS store that rode a fused, store-tagged
        # launch shared with sibling stores (launch counts live on the
        # dispatcher — one fused launch serves many store flushes)
        self.n_fused_flushes = 0
        self.n_fused_queries = 0
        self.n_fused_ticks = 0
        # routing controls (see module docstring): None = adaptive;
        # "host" / "dense" pin a route; "device" = adaptive kernels but
        # never the host route (the pre-routing behavior, used by kernel
        # equivalence tests).  on_route(route, nq) observes every decision
        # (utils.trace.Trace.record_route is the sim-side consumer).
        self.route_override: Optional[str] = None
        self.on_route = None
        # store-level coalescing queue (enqueue_query/_flush_queries)
        self._q_pending: List[tuple] = []
        # batch-floor memo keyed on (RedundantBefore.version, window):
        # repeated flushes over a stable watermark map resolve the prune
        # floor with one dict hit instead of a segment walk
        self._floor_memo: Optional[tuple] = None
        # -- device-resident attribution (r15) --
        # the attribution index (_attr_index) and the tokens whose pivot
        # list it must re-read at the next flush: _advance_status marks a
        # point token when a decided key-domain write is driven on it,
        # and each CommandsForKey the index has read marks its own token
        # on every pivot mutation (this very set is its _elide_sink)
        self._attr_dirty: Set[int] = set()
        self._aidx: Optional[_AttrIndexHost] = None
        # flushes that found a dirty token, the tokens they re-read, and
        # the times a device route had the device image assembled
        self.n_attr_refreshes = 0
        self.n_attr_tokens_refreshed = 0
        self.n_attr_device_builds = 0
        # attributed-path counters (bench ``# index:`` line)
        self.n_elided_transitive = 0
        self.n_elided_decided = 0
        self.attr_download_bytes = 0
        # per-kernel wall timing (SURVEY §5: structured per-kernel timing):
        # kind -> [calls, seconds], written by _span alone; dispatch_*
        # covers host pack + upload + enqueue, wait_* the download join,
        # host_* the host-side passes
        self.kernel_times: Dict[str, List[float]] = {}
        # (node, store): the Chrome-trace row of this store's spans
        self._span_ids = (
            getattr(getattr(store, "node", None), "node_id", 0) or 0,
            getattr(store, "store_id", 0) or 0)
        # _DepsMirror.sync_device's bucket index: kernel_times'
        # sync_bucket_cells / sync_bucket_full count its syncs by path (the
        # cells' packing, the whole upload: both inside sync_tables); these
        # the pending cells the cells path carried and the bytes either sent
        self.n_bucket_cells_uploaded = 0
        self.bucket_upload_bytes = 0
        # _DepsMirror.sync_device (kernel_times' sync_tables is its HOST
        # clock): programs its table syncs launched and arrays they handed
        # to the device; a steady flush reads 1 and 1, a whole upload 0 and
        # one per column
        self.n_sync_launches = 0
        self.n_sync_uploads = 0
        # -- device-fault tolerance (module docstring: degradation ladder) --
        # shadow-verify every device flush against the host route when True
        # (or when utils.faults.PARANOIA is set process-wide)
        self.paranoia = False
        # OOM backpressure terminal state: all flushes/ticks pinned to host
        self.host_pinned = False
        # device-memory budget in table slots (None = unbounded); at the
        # budget _grow_capacity compacts below the RedundantBefore floor
        # instead of doubling, then degrades to host_pinned if still full
        import os as _os
        self.device_budget_slots: Optional[int] = (
            int(_os.environ.get("ACCORD_TPU_DEVICE_BUDGET_SLOTS", "0"))
            or None)
        # quarantine state machine: consecutive device-boundary failures
        # (the backoff exponent) and remaining quarantined flushes; jitter
        # is seeded from (node, store) so the backoff schedule is
        # deterministic yet desynchronized across the replicas of a shard
        # — a cluster-wide device fault must not re-probe in lockstep
        self._dev_backoff = 0
        self._dev_quar_flushes = 0
        node_id = getattr(getattr(store, "node", None), "node_id", 0)
        self._jitter = RandomSource(
            0xFA17 ^ (node_id << 16) ^ getattr(store, "store_id", 0))
        # fault observability: on_fault(event, detail) if set, else the
        # node-level observer the sim cluster wires (node.fault_observer)
        self.on_fault = None
        self.n_device_faults = 0
        self.n_quarantines = 0
        self.n_fallback_queries = 0    # queries served by host fallback/pin
        self.n_reprobes = 0
        self.n_restores = 0
        self.n_shadow_checks = 0
        self.n_shadow_mismatches = 0
        self.n_compactions = 0
        self.n_compacted_slots = 0
        self.n_oom_degraded = 0
        # drain ticks swept on the host: as the ladder's fallback
        # (quarantined, host-pinned or a fault mid-tick), and because the
        # router priced the device round trip dearer (_host_tick_pays) —
        # a choice, never counted with the faults
        self.n_host_ticks = 0
        self.n_priced_host_ticks = 0
        # priced to the host and sent to the device all the same
        # (_audit_tick), and the node's clock at the last device tick
        self.n_audit_ticks = 0
        self._tick_dev_micros: Optional[int] = None
        # audit_route() asked: the next tick audits, even if it finds
        # nothing to drive
        self._audit_asked = False
        # r21 store-sharded residency (parallel.store_shard): the spill
        # rung's StoreShards instance (None until the ladder activates it),
        # flush/byte counters, the per-slice quarantine tallies, and the
        # host-pin recovery state — ``_pin_recheck`` pinned flushes between
        # compaction-and-re-probe attempts (doubling to a cap, the same
        # backoff shape the quarantine ladder uses)
        self.store_shards = None
        self.n_store_sharded_flushes = 0
        self.n_slice_quarantines = 0
        self.n_slice_restores = 0
        self.n_shard_merge_bytes = 0
        self.n_oom_recovered = 0
        self._pin_flushes = 0
        self._pin_recheck = 64
        # r19 adaptive drain wavefront: W=1 ticks run the plain frontier
        # sweep (byte-identical to pre-r19 behavior); W grows x2 only when
        # a tick's ENTIRE candidate set synchronously reached Applied (the
        # PreApplied cascade regime, where a serial chain would otherwise
        # pay one tick per link), letting the log-depth level kernel
        # harvest the next W executeAt antichains in one launch.  Any
        # candidate that does not execute resets W to 1 — protocol-flow
        # ticks never see a widened sweep.
        self._drain_wavefront = 1
        self.n_wavefront_ticks = 0     # ticks swept with W > 1
        # two-stage compacted downloads (r10): bytes actually transferred
        # (headers + live entry prefixes) vs what the old full padded
        # flat-buffer download would have moved — the compaction ratio on
        # every bench ``# index:`` line
        self.download_bytes = 0
        self.download_bytes_padded = 0

    # ------------------------------------------------------------------
    # registration hooks (called from local.commands transitions)
    # ------------------------------------------------------------------
    def register(self, txn_id: TxnId, status: int, keys) -> None:
        """Witness/advance a txn in the deps index.  ``keys`` is the txn's
        sliced participation (Keys or Ranges) — its conflict footprint."""
        with self._span("register"):
            if isinstance(keys, Ranges):
                # the mirror's interval index is what answers a range query
                # on this path: keeping it is the timed kind
                # range_index_sync (as CommandStore.put_range_command's,
                # once that index has a reader)
                with self._span("range_index_sync"):
                    slot = self.deps.alloc(txn_id)
                    self.deps.add_intervals(slot, (), list(keys))
            else:
                slot = self.deps.alloc(txn_id)
                if keys is not None:
                    self.deps.add_intervals(
                        slot, [k.token() for k in keys], ())
            self._advance_status(txn_id, slot, status, None)

    def update_status(self, txn_id: TxnId, status: int,
                      execute_at: Optional[Timestamp] = None) -> None:
        slot = self.deps.slot_of.get(txn_id)
        if slot is None:
            slot = self.deps.alloc(txn_id)
        self._advance_status(txn_id, slot, status, execute_at)

    def _advance_status(self, txn_id: TxnId, slot: int, status: int,
                        execute_at: Optional[Timestamp]) -> None:
        cur = int(self.deps.status[slot])
        if status == dk.SLOT_INVALIDATED:
            new = dk.SLOT_INVALIDATED
        else:
            new = max(cur, status)
        self.deps.set_status(slot, new)
        if execute_at is not None:
            self.deps.emsb[slot] = to_i64(execute_at.msb)
            self.deps.elsb[slot] = to_i64(execute_at.lsb)
            self.deps.enode[slot] = execute_at.node
            self.deps.eknown[slot] = True
            self.deps.mark_exec(slot)    # device attr columns + snapshot
        # attribution index (r15): a decided (executeAt-known) key-domain
        # WRITE is a potential elision pivot on each of its footprint
        # points — mark the tokens dirty so the next flush reads their
        # CommandsForKey pivot lists into the index.  Superset semantics:
        # the refresh reads the CFK truth per token; a token marked here
        # whose CFK has no committed writes simply contributes nothing
        if dk.SLOT_COMMITTED <= new <= dk.SLOT_APPLIED \
                and self.deps.eknown[slot] and txn_id.kind().is_write() \
                and txn_id.domain() == Domain.Key:
            row_lo, row_hi = self.deps.lo[slot], self.deps.hi[slot]
            pts = row_lo[(row_lo <= row_hi) & (row_lo == row_hi)]
            if len(pts):
                self._attr_dirty.update(pts.tolist())
        if new == dk.SLOT_INVALIDATED and cur != dk.SLOT_INVALIDATED:
            # de-index: the bucket path excludes invalidated entries
            # structurally (the dense path excludes them by status)
            self.deps._bucket_remove(slot)
        dslot = self.drain.slot_of.get(txn_id)
        if dslot is not None:
            self.drain.set_status(dslot, new, execute_at)
        # a dependency becoming decided (executeAt known) or terminal can
        # unblock waiters: re-evaluate the frontier
        if new >= dk.SLOT_COMMITTED and self.drain.active.any():
            self.schedule_tick()

    def free(self, txn_id: TxnId) -> None:
        """Truncation/erasure: drop the txn from the deps index (its effect
        is covered by the RedundantBefore watermark from now on)."""
        self.deps.free(txn_id)

    def index_size(self) -> int:
        return len(self.deps.slot_of)

    # ------------------------------------------------------------------
    # device-fault tolerance: quarantine state machine + HBM backpressure
    # (module docstring: the degradation ladder)
    # ------------------------------------------------------------------
    _BACKOFF_BASE = 4      # flushes quarantined after the first failure
    _BACKOFF_MAX = 256     # quarantine ceiling (flushes)

    def _paranoid(self) -> bool:
        return self.paranoia or faults.PARANOIA

    def _fault_event(self, event: str, detail: str = "") -> None:
        obs = self.on_fault
        if obs is None:
            obs = getattr(getattr(self.store, "node", None),
                          "fault_observer", None)
        if obs is not None:
            obs(self.store, event, detail)

    def _device_fault(self, exc_or_kind, detail: str = "",
                      sliced: bool = False) -> None:
        """Record one device-boundary failure and quarantine the device
        routes: exponential backoff in FLUSHES (deterministic per-store
        jitter so co-faulted stores don't re-probe in lockstep).

        ``sliced=True`` (the flush dispatch/collect call sites) composes
        the ladder per slice when the store-sharded residency is active:
        the failure quarantines the SLICE it touched — its slots answer
        from the host twin while healthy slices stay on device — instead
        of the whole node.  Drain-tick faults keep the whole-device
        quarantine (the drain state is not sliced)."""
        kind = exc_or_kind if isinstance(exc_or_kind, str) \
            else faults.kind_of(exc_or_kind)
        self.n_device_faults += 1
        self._fault_event("fault." + kind, detail)
        sh = self.store_shards
        if sliced and sh is not None and sh.active:
            sh.slice_fault(kind, detail)
            return
        self.n_quarantines += 1
        self._dev_backoff = min(self._dev_backoff + 1, 8)
        base = min(self._BACKOFF_BASE << (self._dev_backoff - 1),
                   self._BACKOFF_MAX)
        self._dev_quar_flushes = base + self._jitter.next_int(
            max(base // 2, 1))
        self._fault_event(
            "quarantine", f"{kind} backoff={self._dev_quar_flushes}")

    def _restore_device(self) -> None:
        """A probe flush succeeded end-to-end: the device routes are
        healthy again."""
        self._dev_backoff = 0
        self._dev_quar_flushes = 0
        self.n_restores += 1
        self._fault_event("restore")

    def _flush_gate(self, nq: int):
        """The degradation-ladder gate shared by the solo and fused flush
        paths: (forced, may_probe).  ``forced`` pins this flush to the host
        route ("host-pinned" / "host-fallback", consuming one quarantined
        flush); ``may_probe`` marks that a device-bound flush would be the
        quarantine probe (the caller records the probe only if it actually
        takes a device route)."""
        if self.host_pinned:
            # r21: the OOM degrade is no longer terminal — every
            # _pin_recheck pinned flushes, compact and re-check whether
            # the table fits the device (or the sharded mesh) again; on
            # success the NEXT flush is the recovery probe
            self._pin_flushes += 1
            if self._pin_flushes >= self._pin_recheck:
                self._pin_flushes = 0
                self._pin_recheck = min(self._pin_recheck * 2, 1024)
                if self._try_oom_recover():
                    return None, True
            self.n_fallback_queries += nq
            return "host-pinned", False
        if self._dev_quar_flushes > 0:
            self._dev_quar_flushes -= 1
            self.n_fallback_queries += nq
            return "host-fallback", False
        return None, self._dev_backoff > 0

    def _approve_grow(self, mirror: _DepsMirror) -> bool:
        """HBM capacity backpressure: called by _DepsMirror._grow_capacity
        before doubling.  True = grow as usual; False = compaction made
        room under the budget (free_slots is non-empty), don't grow.

        The r21 ladder: breach -> compact -> SPILL TO SHARDED (when a mesh
        is available and the grown table fits d x the per-chip budget —
        one store's slots split across d devices) -> host-pinned.  When
        every rung fails the store degrades PINNED-TO-HOST (loud one-shot
        event) and the HOST arrays still grow — the protocol stays live,
        the device stops receiving uploads."""
        new = mirror.capacity * 2
        budget = self.device_budget_slots
        sh = self.store_shards
        sharded = sh is not None and sh.active
        # while sharded, the effective budget is the MESH's: d slices
        eff = None if budget is None else (budget * sh.d if sharded
                                           else budget)
        breach = eff is not None and new > eff
        if not breach and faults.should_fire("hbm_oom"):
            self.n_device_faults += 1
            self._fault_event("fault.hbm_oom", f"grow to {new}")
            breach = True
        if not breach:
            return True
        freed = self._compact_below_floor()
        self.n_compactions += 1
        self.n_compacted_slots += freed
        self._fault_event("oom.compact",
                          f"freed={freed} capacity={mirror.capacity}")
        if mirror.free_slots:
            return False
        if not self.host_pinned and not sharded and self.mesh is not None:
            from ..parallel.store_shard import store_shard_enabled
            d = max(len(self.mesh.devices.flat), 1)
            if store_shard_enabled() and (budget is None
                                          or new <= budget * d):
                self._activate_store_shards(f"capacity={mirror.capacity}"
                                            f" -> {new}")
                return True
        if not self.host_pinned:
            # the one-shot loud degrade: host route only from here on
            self.host_pinned = True
            self.n_oom_degraded += 1
            self._fault_event("oom.degrade",
                              f"capacity={mirror.capacity} -> {new}")
        return True

    def _activate_store_shards(self, detail: str = "") -> None:
        """Turn on the r21 sliced residency for this store (the spill rung
        and the sharded leg of OOM recovery): from here the sharded table
        and attr uploads route through per-slice resident buffers."""
        if self.store_shards is None:
            from ..parallel.store_shard import StoreShards
            self.store_shards = StoreShards(self, self.deps, self.mesh)
        self.store_shards.activate()
        self._fault_event("oom.spill", detail)

    def _try_oom_recover(self) -> bool:
        """Un-terminal the OOM degrade (r21): compact, then re-check the
        budget — a raised budget (or a mesh whose d slices now cover the
        table) lets a host-pinned store re-probe the device route.  Loud
        one-shot recovery, counted in ``oom_recovered``; mirrors the
        quarantine -> probe -> restore cycle of the device ladder."""
        mirror = self.deps
        freed = self._compact_below_floor()
        if freed:
            self.n_compactions += 1
            self.n_compacted_slots += freed
            self._fault_event("oom.compact",
                              f"freed={freed} capacity={mirror.capacity}")
        budget = self.device_budget_slots
        cap = mirror.capacity
        if budget is not None and cap > budget:
            from ..parallel.store_shard import store_shard_enabled
            sh_ok = (self.mesh is not None and store_shard_enabled())
            d = max(len(self.mesh.devices.flat), 1) if sh_ok else 1
            if not sh_ok or cap > budget * d:
                return False
            self._activate_store_shards(f"recover capacity={cap}")
        self.host_pinned = False
        self.n_oom_recovered += 1
        self._fault_event("oom.recover",
                          f"capacity={cap} budget={budget}")
        return True

    def _compact_below_floor(self) -> int:
        """Floor-driven compaction: free every live slot whose TxnId sits
        below the RedundantBefore floor over EVERY interval of its own
        footprint.  Safe by the same contract as free(): the attributed
        scan drops a dep below the floor of every token it could emit at,
        on every route — its effect is covered by the watermark.  A Python
        sweep: this is the rare emergency path (budget breach / OOM), not
        a hot path."""
        rb = getattr(self.store, "redundant_before", None)
        if rb is None:
            return 0
        d = self.deps
        freed = 0
        for s in np.nonzero(d.status != dk.SLOT_FREE)[0].tolist():
            tid = d.id_of.get(s)
            if tid is None:
                continue
            row_lo, row_hi = d.lo[s], d.hi[s]
            covered = False
            for m in range(d.max_intervals):
                lo_v, hi_v = int(row_lo[m]), int(row_hi[m])
                if lo_v > hi_v:
                    continue
                if tid < rb.min_floor_over(lo_v, hi_v):
                    covered = True
                else:
                    covered = False
                    break
            if covered:
                d.free(tid)
                freed += 1
        return freed

    # ------------------------------------------------------------------
    # the deps query (device replacement of map_reduce_active fold)
    # ------------------------------------------------------------------
    def deps_query(self, safe, txn_id: TxnId, keys, started_before: Timestamp,
                   witnesses: Kinds, builder) -> None:
        """Run the PreAccept/Accept/Recover dependency scan on device and
        fold the result into ``builder`` with the same per-key semantics as
        the host CommandsForKey path (full ownership history, matching
        SafeCommandStore.map_reduce_active — a dual-quorum scan at a
        dropped prior-epoch owner must still see its old-range witnesses).

        This is the batch path with B=1: the per-message and batched code
        are ONE path (same kernel dispatch, same floors/elision/attribution)
        so the benched path is exactly the path the protocol runs."""
        query = self.build_query(safe, txn_id, keys, started_before,
                                 witnesses)
        if query is None:
            return
        handle = self.deps_query_batch_begin([query], immediate=True)
        self.deps_query_batch_end_attributed(safe, handle, [builder])

    def build_query(self, safe, txn_id: TxnId, keys,
                    started_before: Timestamp, witnesses: Kinds):
        """Slice a scan's keys to the store's full ownership history and
        package them as one batch-query tuple (None if nothing owned)."""
        owned = safe.store.ranges_for_epoch.all()
        if isinstance(keys, Ranges):
            q_toks: List[int] = []
            q_rngs = list(keys.slice(owned))
        else:
            q_toks = [k.token() for k in keys
                      if owned.contains_token(k.token())]
            q_rngs = []
        if not q_toks and not q_rngs:
            return None
        return (txn_id, started_before, witnesses, q_toks, q_rngs)

    # ------------------------------------------------------------------
    # store-level coalescing (the lived batched path): queries arriving
    # within one scheduler quantum fold into ONE kernel dispatch
    # ------------------------------------------------------------------
    def enqueue_query(self, query, builder, done) -> None:
        """Queue one deps query for the next flush; ``done(failure, safe)``
        fires after the builder is filled (``safe`` is the flush task's
        exclusive store handle, live only within the callback).  All queries enqueued before the flush
        task runs (i.e. during the same scheduler quantum — message bursts
        land as same-timestamp tasks) share one kernel dispatch, so the
        benched batched shape IS the lived shape (mean batch size =
        n_queries / n_dispatches)."""
        self._q_pending.append((query, builder, done))
        if len(self._q_pending) == 1:
            node = self.store.node
            # node-level dispatch scheduler (r08): all stores of this node
            # whose flushes become runnable in the same event-loop step
            # register with ONE dispatcher event, which coalesces their
            # device launches when the cost model says fusion wins
            disp = getattr(node, "dispatcher", None)
            if disp is not None:
                disp.register_flush(self)
                return
            from .command_store import PreLoadContext
            # one scheduler hop (zero sim-time) so every same-instant
            # message's store task enqueues BEFORE the flush runs
            node.scheduler.now(lambda: self.store.execute(
                PreLoadContext.empty(), self._flush_queries))

    def _flush_queries(self, safe) -> None:
        batch = self._q_pending
        self._q_pending = []
        self._flush_batch(safe, batch)

    def _flush_batch(self, safe, batch) -> None:
        """Serve one claimed batch of enqueued queries solo: the classic
        atomic begin+collect+attribute within this store task (the
        dispatcher routes a store here when fusion does not pay)."""
        if not batch:
            return
        try:
            # the flush itself, the kernel_times kinds its children; the
            # callbacks below are the handlers' continuations
            with self._flush_span():
                handle = self.deps_query_batch_begin(
                    [q for q, _b, _d in batch], immediate=True)
                self.deps_query_batch_end_attributed(
                    safe, handle, [b for _q, b, _d in batch])
        except BaseException as e:  # noqa: BLE001
            for _q, _b, d in batch:
                d(e, None)
            return
        for _q, _b, d in batch:
            d(None, safe)

    def deps_query_batch_attributed(self, safe, queries, builders):
        """The correctness-complete batched scan: one kernel dispatch for B
        queries, then the full host-path semantics (floors, elision,
        key/range attribution) folded into each query's builder.  This is
        the exact code deps_query runs (B=1) — and what the bench times."""
        if not queries:
            return
        handle = self.deps_query_batch_begin(queries)
        self.deps_query_batch_end_attributed(safe, handle, builders)

    # below this many stragglers the bucketed path is used for narrow
    # queries on a single device; above it (hot/adversarial footprints) the
    # dense scan is the better kernel anyway
    BUCKETED = True

    # process-wide route calibration: {"rtt": s, "c_dev": s/elem,
    # "c_host": s/elem, "c_sweep": s per row or edge of the host drain
    # sweep, ...}, measured once by a micro-probe (or injected by tests via
    # set_route_calibration)
    _CALIB = None

    @classmethod
    def set_route_calibration(cls, rtt: float, c_host: float,
                              c_dev: float,
                              rtt_mesh: Optional[float] = None,
                              c_xfer: float = 0.0,
                              c_attr: float = 0.0,
                              c_shard: float = 0.0,
                              c_sweep: float = 1e-6) -> None:
        cls._CALIB = {"rtt": rtt, "c_host": c_host, "c_dev": c_dev,
                      "rtt_mesh": rtt_mesh if rtt_mesh is not None else rtt,
                      "c_xfer": c_xfer, "c_attr": c_attr,
                      "c_shard": c_shard, "c_sweep": c_sweep}

    @staticmethod
    def _measure_route_calibration():
        """The once-per-process micro-probe behind the routing crossover:
        measures (a) the device round-trip cost (tiny dispatch + download —
        on a high-round-trip host-device link this dominates small scans),
        (b) the device per-element kernel cost (a mid-size dense scan minus
        the round trip), (c) the host per-element cost of the vectorized
        numpy predicate the host route runs, and the coefficients of the
        later routes (copy, transfer, attribution, the host drain sweep).
        No hard-coded thresholds: the crossovers ARE these numbers."""
        import statistics as _st
        import time as _time
        x = jnp.arange(256, dtype=jnp.int64)
        tiny = jax.jit(lambda a: a + 1)
        np.asarray(tiny(x))                      # warm + compile
        rtts = []
        for _ in range(5):
            t0 = _time.perf_counter()
            np.asarray(tiny(x))
            rtts.append(_time.perf_counter() - t0)
        rtt = _st.median(rtts)
        # device per-element: dense flat kernel over a 8192x4 table, B=16
        cap, b, m = 8192, 16, 4
        table = dk.empty_table(cap, m)
        qmat = jnp.asarray(np.zeros((b, 7 + 2 * m), np.int64))
        jax.block_until_ready(dk.calculate_deps_flat(table, qmat, m,
                                                     256, 64))
        runs = []
        for _ in range(3):
            t0 = _time.perf_counter()
            jax.block_until_ready(dk.calculate_deps_flat(table, qmat, m,
                                                         256, 64))
            runs.append(_time.perf_counter() - t0)
        elems = b * cap * m * m
        c_dev = max(_st.median(runs) - rtt, 1e-9) / elems
        # host per-element: the predicate compare chain over 64k entries
        n = 1 << 16
        a = np.arange(n, dtype=np.int64)
        c = a[::-1].copy()
        _ = ((a < c) | ((a == c) & (c < a))).sum()   # warm
        t0 = _time.perf_counter()
        reps = 4
        for _ in range(reps):
            _ = ((a < c) | ((a == c) & (c < a))).sum()
        c_host = max((_time.perf_counter() - t0) / (reps * n), 1e-11)
        # host per-element column-copy cost (the deferred-harvest mirror
        # snapshot the fused pricing charges) — memcpy, ~20x cheaper per
        # element than the compare chain
        _ = a.copy()
        t0 = _time.perf_counter()
        for _ in range(8):
            _ = a.copy()
        c_copy = max((_time.perf_counter() - t0) / (8 * n), 1e-12)
        # device->host transfer cost per BYTE (the r10 prefix-fetch model:
        # an immediate flush slices the entry buffer only when the bytes
        # it saves cost more than the extra slice dispatch ~ one rtt; on
        # a local CPU device bytes are ~free and the full fetch wins, on
        # a slow MB/s-scale link the prefix wins from ~100KB saved)
        # each timed conversion must see a FRESH device buffer: jax.Array
        # caches its host copy after the first np.asarray, so re-converting
        # one array times a cache hit (~ns) and c_xfer would collapse to
        # the floor, pricing the prefix fetch off on exactly the slow
        # link it exists for
        mk = jax.jit(lambda i: jnp.zeros(1 << 16, jnp.int64) + i)
        bufs = [jax.block_until_ready(mk(i)) for i in range(4)]   # 512KB ea
        np.asarray(bufs[0])                      # warm the conversion path
        xfers = []
        for buf in bufs[1:]:
            t0 = _time.perf_counter()
            np.asarray(buf)
            xfers.append(_time.perf_counter() - t0)
        c_xfer = max((_st.median(xfers) - rtt) / float(8 << 16), 1e-13)
        # r15: the attributed kernels run the post-compaction attribution
        # stage over the [s]-long entry buffer — price its per-entry-slot
        # cost from a direct A/B of the attributed vs raw dense kernel at
        # a wide s (the stage is O(s), so the slope IS the coefficient)
        s_probe = 4096
        zeros3 = (jnp.asarray(np.int64(0)), jnp.asarray(np.int64(0)),
                  jnp.asarray(np.int32(0)))
        attr = dk.AttrCols(jnp.zeros(cap, jnp.int32),
                           jnp.full(cap, dk.SLOT_FREE, jnp.int32),
                           jnp.zeros(cap, jnp.int64),
                           jnp.zeros(cap, jnp.int64),
                           jnp.zeros(cap, jnp.int32),
                           jnp.zeros(cap, jnp.int64),
                           jnp.zeros(cap, jnp.int64),
                           jnp.zeros(cap, jnp.int32),
                           jnp.zeros(cap, bool))
        inf64 = np.int64(np.iinfo(np.int64).max)
        aidx = dk.AttrIndex(jnp.full(1, inf64), jnp.zeros(2, jnp.int64),
                            jnp.zeros(2, jnp.int64), jnp.zeros(2, jnp.int32),
                            jnp.full(1, inf64), jnp.zeros(2, jnp.int32),
                            jnp.full(1, inf64), jnp.zeros(1, jnp.int64),
                            jnp.zeros(1, jnp.int64), jnp.zeros(1, jnp.int32),
                            jnp.asarray(np.int64(1)))
        rb0 = jnp.zeros(b, jnp.int64)
        jax.block_until_ready(dk.calculate_deps_flat(table, qmat, m,
                                                     s_probe, 64))
        jax.block_until_ready(dk.calculate_deps_flat_attr(
            table, attr, aidx, qmat, rb0, *zeros3, m, s_probe, 64))
        t0 = _time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(dk.calculate_deps_flat(table, qmat, m,
                                                         s_probe, 64))
        t_raw = (_time.perf_counter() - t0) / 3
        t0 = _time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(dk.calculate_deps_flat_attr(
                table, attr, aidx, qmat, rb0, *zeros3, m, s_probe, 64))
        t_attr = (_time.perf_counter() - t0) / 3
        c_attr = max(t_attr - t_raw, 0.0) / s_probe
        # the host drain sweep is a Python loop, not a numpy pass, so
        # c_host is the wrong price for it: time the sweep itself on a
        # mirror of Stable rows whose deps are all decided and execute
        # later (nothing gates, so every edge is visited), and divide by
        # the rows and edges it walked
        rows, deg = 256, 4
        mirror = _DrainMirror(2 * rows)
        mirror.status[:rows] = dk.SLOT_STABLE
        mirror.status[rows:] = dk.SLOT_COMMITTED
        mirror.exec_msb[rows:] = 1
        mirror.active[:rows] = True
        for i in range(rows):
            mirror.deps_of[i] = {rows + (i + j) % rows for j in range(deg)}
        mirror.host_ready_slots()                # warm
        sweeps = []
        for _ in range(3):
            t0 = _time.perf_counter()
            mirror.host_ready_slots()
            sweeps.append(_time.perf_counter() - t0)
        c_sweep = max(_st.median(sweeps), 1e-9) / (rows * (1 + deg))
        return {"rtt": rtt, "c_dev": c_dev, "c_host": c_host,
                "c_copy": c_copy, "c_xfer": c_xfer, "c_attr": c_attr,
                "c_sweep": c_sweep}

    @staticmethod
    def _measure_mesh_rtt(mesh) -> float:
        """Round-trip cost of ONE tiny shard_map dispatch over ``mesh`` —
        the mesh analogue of the single-device rtt probe.  A shard_map
        launch costs far more than a plain dispatch (per-device program
        launches + collectives plumbing; on the virtual CPU test mesh it is
        100x+ a single-device call), so pricing mesh routes with the
        single-device rtt would send tiny sim scans to the mesh the model
        claims is cheap."""
        import statistics as _st
        import time as _time
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from ..parallel.sharded import STORE_AXIS, _shard_map
        d = int(np.prod(list(mesh.shape.values())))
        arr = jax.device_put(np.zeros(8 * d, np.int64),
                             NamedSharding(mesh, P(STORE_AXIS)))
        fn = jax.jit(_shard_map(lambda a: a + 1, mesh,
                                (P(STORE_AXIS),), P(STORE_AXIS)))
        np.asarray(fn(arr))                      # warm + compile
        rtts = []
        for _ in range(3):
            t0 = _time.perf_counter()
            np.asarray(fn(arr))
            rtts.append(_time.perf_counter() - t0)
        return _st.median(rtts)

    @staticmethod
    def _measure_shard_coeff(mesh) -> float:
        """Per-element cost of the cross-slice merge collective the
        sharded-store route adds (all-gather + replicated-block shuffle):
        an A/B slope over two buffer sizes, so the fixed launch overhead
        cancels and what remains is the collective's marginal cost.  A
        COEFFICIENT, never a device-count threshold — the router prices
        the sharded route with it like every other term."""
        import statistics as _st
        import time as _time
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from ..parallel.sharded import STORE_AXIS, _shard_map
        d = int(np.prod(list(mesh.shape.values())))

        def timed(n):
            arr = jax.device_put(np.zeros(n * d, np.int64),
                                 NamedSharding(mesh, P(STORE_AXIS)))

            def body(a):
                g = jax.lax.all_gather(a, STORE_AXIS, tiled=True)
                return jnp.sort(g)

            fn = jax.jit(_shard_map(body, mesh, (P(STORE_AXIS),),
                                    P(STORE_AXIS)))
            np.asarray(fn(arr))                  # warm + compile
            runs = []
            for _ in range(3):
                t0 = _time.perf_counter()
                np.asarray(fn(arr))
                runs.append(_time.perf_counter() - t0)
            return _st.median(runs)

        n1, n2 = 1024, 8192
        t1, t2 = timed(n1), timed(n2)
        return max(t2 - t1, 0.0) / ((n2 - n1) * d) + 1e-12

    def _calibration(self):
        if DeviceState._CALIB is None:
            DeviceState._CALIB = self._measure_route_calibration()
        calib = DeviceState._CALIB
        if self.mesh is not None and "rtt_mesh" not in calib:
            calib["rtt_mesh"] = self._measure_mesh_rtt(self.mesh)
        if self.mesh is not None and "c_shard" not in calib:
            calib["c_shard"] = self._measure_shard_coeff(self.mesh)
        return calib

    def _choose_route(self, qnp: np.ndarray, q_m: int, floor_id) -> str:
        """Pick "host" or "device" for this flush by comparing the modeled
        host-scan cost (live-above-floor working set from the mirror's
        incremental stats) against the modeled device cost (round trips +
        the cheaper kernel's element count).  Models, not thresholds: both
        sides are priced in seconds from the calibration probe."""
        calib = self._calibration()
        st = self.deps.floor_stats(floor_id)
        lo = qnp[:, 7:7 + q_m]
        hi = qnp[:, 7 + q_m:7 + 2 * q_m]
        used = lo <= hi
        n_iv = int(used.sum())
        nq = qnp.shape[0]
        # host model: point candidates ~ covered-token-width x density,
        # plus the [query-interval x range-entry] stab broadcast
        span = max(st["tok_hi"] - st["tok_lo"] + 1, 1)
        density = st["n_pt"] / span
        w = np.where(used,
                     np.minimum(hi, st["tok_hi"])
                     - np.maximum(lo, st["tok_lo"]) + 1, 0)
        est_pt = float(np.clip(w, 0, None).sum()) * density + n_iv * 8.0
        est_host = est_pt + float(n_iv) * st["n_rng"]
        # ~5 vectorized passes per candidate (probe + attr filter + the
        # thin finalize — r15 replaced the attribute re-sort), plus a
        # fixed per-flush overhead (probe setup, index builds, snapshots)
        host_cost = calib["c_host"] * (5.0 * est_host + 40_000.0)
        if self.deps._hidx_key != ((floor_id if floor_id is not None
                                    and floor_id > TxnId.NONE else None),
                                   self.deps.version):
            # index rebuild: one vectorized pass over the live tail
            host_cost += calib["c_host"] * 4.0 * (st["n_above"]
                                                  + st["n_pt"] + st["n_rng"])
        # device model: the cheaper kernel's PER-SHARD element count (wall
        # clock of a parallel launch = the per-shard work).  The slot table
        # row-shards, so dense work divides by d; the bucket probe matrix
        # does NOT — every shard evaluates all nq x (q_m*span*K) bucket
        # candidates against its row slice, only the wide list splits
        rtt = calib["rtt"]
        d = 1
        if self.mesh is not None:
            d = max(len(self.mesh.devices.flat), 1)
            rtt = calib.get("rtt_mesh", rtt)
        dense_elems = nq * self.deps.capacity * q_m \
            * self.deps.max_intervals // d
        if self.BUCKETED and \
                len(self.deps.wide_entries) <= self.deps.WIDE_MAX:
            # the candidate matrix is sliced to the live bucket-occupancy
            # high-water (not BUCKET_K) and the wide list crosses every
            # query interval (exact triples) — price what actually runs
            buck_elems = nq * (q_m * self.deps.SPAN
                               * self.deps.bucket_keff()
                               + q_m * len(self.deps.wide_entries) // d)
            dev_elems = min(dense_elems, buck_elems)
        else:
            dev_elems = dense_elems
        # the attributed launch additionally runs the post-compaction
        # attribution stage over the learned [s] entry buffer — collect
        # got cheaper (pre-attributed prefix), launch slightly heavier;
        # both priced, never thresholded
        s_attr = min(self._batch_flat, dev_elems)
        dev_cost = 2.0 * rtt + calib["c_dev"] * dev_elems \
            + calib.get("c_attr", 0.0) * s_attr
        if d > 1:
            # the mesh routes pay the cross-slice merge collective over
            # the (up to) d x s merged entry block — priced from its own
            # A/B micro-probe slope (r21), never a device-count threshold
            dev_cost += calib.get("c_shard", 0.0) * d * s_attr
        return "host" if host_cost < dev_cost else "device"

    def _count_range_queries(self, queries) -> int:
        """Count a flush's queries of range-domain txns into
        n_range_queries; the caller adds them to n_range_device_queries
        where a device route answers the flush."""
        n = sum(1 for q in queries if q[0].domain() == Domain.Range)
        self.n_range_queries += n
        return n

    def _batch_floor(self, qnp: np.ndarray, q_m: int):
        """(floor_id, np prune triple) for a batch: the conservative
        batch-global RedundantBefore floor with the (rb.version, window)
        memo — shared by the solo begin path and the fused dispatcher
        prep.  (None, None) when no floor applies."""
        rb = getattr(self.store, "redundant_before", None)
        if rb is None:
            return None, None
        lo_cols = qnp[:, 7:7 + q_m]
        hi_cols = qnp[:, 7 + q_m:7 + 2 * q_m]
        used = lo_cols <= hi_cols
        if not used.any():
            return None, None
        window = (rb.version, int(lo_cols[used].min()),
                  int(hi_cols[used].max()))
        if self._floor_memo is not None and self._floor_memo[0] == window:
            f = self._floor_memo[1]
        else:
            f = rb.min_floor_over(window[1], window[2])
            self._floor_memo = (window, f)
        if f > TxnId.NONE:
            return f, (to_i64(f.msb), to_i64(f.lsb), np.int32(f.node))
        return None, None

    # ------------------------------------------------------------------
    # device-resident attribution (r15): the per-store floor + elision
    # index every attributed route (kernels AND host) applies
    # ------------------------------------------------------------------
    def _attr_index(self) -> _AttrIndexHost:
        """The store's attribution index as of now: the packed
        RedundantBefore segment floors plus, per token, the CFK
        committed-write pivot list (_AttrIndexHost).  Maintained, not
        rebuilt: a flush with no dirty token and an unmoved
        RedundantBefore.version hands out the index it handed out last
        time; otherwise the dirty tokens' lists are re-read from their
        CommandsForKey (whose pivot mutations mark the token from then
        on) into a new index sharing everything else.  Who marks a token:
        _advance_status (the store drives a decided key-domain write on
        the point) and CommandsForKey._cw_mutated (every content change
        of the pivot list).  A marked token whose CommandsForKey the
        store does not hold yet stays dirty until it does.  The device
        image is no part of this: _AttrIndexHost assembles it when a
        device route asks."""
        with self._span("host_attr_index"):
            rb = getattr(self.store, "redundant_before", None)
            rb_version = rb.version if rb is not None else -1
            cur = self._aidx
            if cur is None or cur.rb_version != rb_version:
                if rb is not None:
                    floors = rb.packed_floor_index()
                else:
                    floors = (np.zeros(0, np.int64), np.zeros(1, np.int64),
                              np.zeros(1, np.int64), np.zeros(1, np.int32))
                lists = (np.zeros(0, np.int64), [], 0) if cur is None \
                    else (cur.toks, cur.packs, cur.n_execs)
                cur = self._aidx = _AttrIndexHost(self, floors, rb_version,
                                                  *lists)
            if self._attr_dirty:
                cur = self._aidx = self._attr_refresh(cur)
        return cur

    @property
    def n_attr_tokens(self) -> int:
        """Tokens the attribution index holds a pivot list for."""
        return 0 if self._aidx is None else len(self._aidx.toks)

    def _attr_refresh(self, cur: _AttrIndexHost) -> _AttrIndexHost:
        """Re-read the dirty tokens' pivot lists: ``cur`` itself when none
        of them moved (a token is marked again at Stable and Applied, its
        list as it was), else a new index that shares every other list
        with ``cur`` — O(dirty) reads, whatever the index holds."""
        dirty = self._attr_dirty
        self.n_attr_refreshes += 1
        self.n_attr_tokens_refreshed += len(dirty)
        cfk_map = getattr(self.store, "commands_for_key", None) or {}
        toks, packs, n_execs = cur.toks, cur.packs, cur.n_execs
        marked = sorted(dirty)
        at = np.searchsorted(toks, marked)
        held = np.zeros(len(marked), bool)
        if len(toks):
            held = toks[np.minimum(at, len(toks) - 1)] == marked
        dirty.clear()
        moved = False
        left, joined = [], {}
        for t, i, h in zip(marked, at.tolist(), held.tolist()):
            c = cfk_map.get(t)
            p = None
            if c is None:
                dirty.add(t)          # asked again at the next flush
            else:
                c._elide_sink = dirty
                p = c.packed_committed_execs()
                if not len(p[0]):
                    p = None
            if not h:
                if p is not None:
                    joined[t] = p
            elif packs[i] is not p:
                if not moved:
                    packs = list(packs)
                    moved = True
                n_execs -= len(packs[i][0])
                if p is None:
                    left.append(i)
                else:
                    packs[i] = p
                    n_execs += len(p[0])
        if left:
            toks = np.delete(toks, left)
            for i in reversed(left):
                del packs[i]
        if joined:
            new = np.fromiter(joined, np.int64, len(joined))
            at = np.searchsorted(toks, new)
            toks = np.sort(np.concatenate([toks, new]))
            if not moved:
                packs = list(packs)
                moved = True
            for k, (i, p) in enumerate(zip(at.tolist(), joined.values())):
                packs.insert(i + k, p)
                n_execs += len(p[0])
        if not moved:
            return cur
        return _AttrIndexHost(self, cur.floors, cur.rb_version, toks, packs,
                              n_execs)

    def _attr_filter_entries(self, tb, tj, tm, tq, ids, ivs, aidx,
                             qnp, floor_skip: bool = False) -> tuple:
        """Apply the attributed kernels' in-kernel drops to a HOST-derived
        entry set (host route, fault fallback, shadow verify): per-token
        floors + elision on key-domain entries, over the flush's snapshot
        columns.  Duplicate (row, token, dep) emits survive — the shared
        finalize dedupes, so bytes match the kernel routes that dropped
        them in-kernel.  ``floor_skip`` (precomputed per flush by
        floors_match) elides the whole floor leg when the exact per-token
        floors equal the structurally-applied batch floor; the decided-
        elision pivot search runs only over the decided subset."""
        if len(tj) == 0:
            return tb, tj, tm, tq, 0, 0
        (msb_a, lsb_a, node_a, _obj, status_a, xm_a, xl_a, xn_a,
         xk_a) = ids
        lo, _hi, dom = ivs
        key_dep = dom[tj] == int(Domain.Key)
        if not key_dep.any():
            return tb, tj, tm, tq, 0, 0
        status = status_a[tj]
        el_trans = key_dep & (status == dk.SLOT_TRANSITIVE)
        tok = None
        keep_floor = None
        if not floor_skip:
            tok = lo[tj, tm]
            keep_floor = aidx.keep_floor(tok, msb_a[tj], lsb_a[tj],
                                         node_a[tj])
        el_dec = np.zeros(len(tj), bool)
        if aidx.n_execs:
            dec = (key_dep & (status >= dk.SLOT_COMMITTED)
                   & (status <= dk.SLOT_APPLIED) & xk_a[tj])
            di = np.nonzero(dec)[0]
            if len(di):
                tji = tj[di]
                tok_d = tok[di] if tok is not None else lo[tji, tm[di]]
                el_dec[di] = aidx.elide_decided(
                    tok_d, xm_a[tji], xl_a[tji], xn_a[tji], tb[di], qnp)
        if keep_floor is None:
            keep = ~(el_trans | el_dec)
            n_trans = int(el_trans.sum())
            n_dec = int(el_dec.sum())
        else:
            keep = ~key_dep | (keep_floor & ~el_trans & ~el_dec)
            n_trans = int(np.sum(keep_floor & el_trans))
            n_dec = int(np.sum(keep_floor & ~el_trans & el_dec))
        if keep.all():
            return tb, tj, tm, tq, n_trans, n_dec
        return tb[keep], tj[keep], tm[keep], tq[keep], n_trans, n_dec

    def deps_query_batch_begin(self, queries, immediate: bool = False,
                               prune_floors: bool = True,
                               attributed: bool = True):
        """Dispatch a batched deps scan WITHOUT waiting: one fused query
        upload per kernel part + enqueue; returns an opaque handle for
        deps_query_batch_end_attributed.

        There is ONE flush: every device kind launches its ATTRIBUTED
        kernel — the batch-global RedundantBefore prune, the per-token
        floors, elision and the key dedupe run in-kernel against the
        device-resident attribution columns + the packed floor/elision
        index, and the CSR that comes back holds exactly the entries the
        builders keep — the host side is a pure decode + finalize.  Mesh
        routes additionally merge their shard blocks ON DEVICE (one
        replicated download).  ``prune_floors`` / ``attributed`` are
        accepted only as True (ROADMAP D11: the benchmark's store driver
        still passes them).  Callers overlap the next batch's dispatch
        with the previous batch's result download (double-buffering) — on a
        high-round-trip host-device link the round trips dominate the
        kernel, so the pipeline nearly doubles sustained throughput.

        Dispatch is adaptive: under a mesh the scan fans over the sharded
        dense kernel; on a single device queries whose intervals are narrow
        probe the bucketed index (O(candidates) instead of O(N)), wide
        queries — and everything, when the straggler list says the
        footprint distribution defeats bucketing — take the dense kernel.
        All parts share one mirror snapshot and one finalize, so every
        path yields identical protocol results."""
        if not (prune_floors and attributed):
            raise TypeError("deps_query_batch_begin has one flush path: "
                            "prune_floors and attributed are always on")
        nq = len(queries)
        q_m, qnp = self._pack_queries(queries)
        parts: List[Dict[str, object]] = []
        # conservative batch-global RedundantBefore floor, applied ON
        # DEVICE (the exact floors still run in attribution): in durable-
        # prefix-dominated stores this keeps the CSR to the live tail
        # instead of shipping redundant history — on EVERY device route,
        # sharded included
        prune = None
        floor_id, prune_np = self._batch_floor(qnp, q_m)
        if floor_id is not None:
            prune = (jnp.asarray(prune_np[0]), jnp.asarray(prune_np[1]),
                     jnp.asarray(prune_np[2]))
        aidx = self._attr_index()
        # the bounds' ranks among ALL the index's executeAts: a device
        # kind's ``rankb`` column, computed (and the index's device image
        # assembled) by the first device part of the flush
        rankb_np = None
        # when the exact per-token floors equal the structurally applied
        # batch floor everywhere the batch reaches, the per-entry floor leg
        # is provably a no-op — on the host route AND in the kernels (the
        # mask's batch-global prune is that same floor); an empty elision
        # index likewise drops the whole pivot leg from the traced program
        # (static flags)
        floor_skip = aidx.floors_match(qnp, q_m, floor_id)
        k_floors = not floor_skip
        k_elide = aidx.n_execs > 0

        def dispatch(kind, rows, qcols=None):
            """rows: np int64 array of query indices for this part, padded
            to a pow2 batch by repeating the last row (pads map to -1).
            Every device kind launches its ATTRIBUTED kernel (``attr_`` +
            kind in the spans' names and kernel_times); mesh kinds come
            back as ONE merged replicated block (d=1, entry buffer
            d_mesh * s)."""
            nonlocal rankb_np
            kname = kind if kind in ("host", "host_slice") \
                else "attr_" + kind
            with self._span("dispatch_" + kname):
                if kind == "host":
                    # the host route computes its exact emit entries right
                    # here — no device box, no download thread; the
                    # floor/elision drops run at collect over the same
                    # snapshot the builders read
                    parts.append({"kind": "host",
                                  "ent": self.deps.host_pairs(qnp, q_m,
                                                              floor_id)})
                    self.n_host_queries += len(rows)
                    self.n_dispatches += 1
                    return
                if kind == "host_slice":
                    # r21 hybrid twin part: while slices are quarantined the
                    # assembled sharded table masks their slots to SLOT_FREE,
                    # and this part answers for EXACTLY those slots from the
                    # host mirror — disjoint from the device part's slot set
                    # by construction, so the concatenated entries finalize
                    # byte-identically to an all-device answer
                    cb, cj, cm, cq = self.deps.host_pairs(qnp, q_m, floor_id)
                    keep = self.store_shards.quarantined_slot_mask(cj)
                    parts.append({"kind": "host_slice",
                                  "ent": (cb[keep], cj[keep], cm[keep],
                                          cq[keep])})
                    self.n_dispatches += 1
                    return
                dk.launch_check(kind)
                b_pad = _pow2_at_least(len(rows), 1)
                rows_p = np.concatenate(
                    [rows, np.full(b_pad - len(rows), rows[-1], np.int64)])
                gmap = np.concatenate(
                    [rows, np.full(b_pad - len(rows), -1, np.int64)])
                m_t = self.deps.max_intervals
                part: Dict[str, object] = {"kind": kname, "gmap": gmap,
                                           "nq": b_pad, "q_m": q_m,
                                           "mq": m_t * q_m, "d_ent": 1,
                                           "immediate": immediate}
                if rankb_np is None:
                    rankb_np = aidx.rank_bounds(qnp)
                rankb = jnp.asarray(rankb_np[rows_p])
                pz = prune if prune is not None else _prune_zeros()
                if kind == "sharded":
                    table = self.deps.device_table_sharded(self.mesh)
                    d = int(np.prod(list(self.mesh.shape.values())))
                    n = table.capacity
                    s = min(self._batch_flat, b_pad * (n // d) * m_t * q_m)
                    k = min(self._batch_k, (n // d) * m_t * q_m)
                    qmat = jnp.asarray(qnp[rows_p])
                    mesh = self.mesh
                    # merged replicated block with GLOBAL slot codes: the
                    # cross-shard Deps.merge happens on device
                    wide = dk.wide_codes(n, m_t, q_m)
                    from ..parallel.sharded import sharded_flat_attr
                    acols = self.deps.device_attr_cols_sharded(mesh)
                    ai = aidx.device_replicated(mesh)

                    def relaunch(s2, k2, _m=mesh, _t=table, _q=qmat,
                                 _a=acols, _i=ai, _r=rankb, _p=pz):
                        return sharded_flat_attr(
                            _m, q_m, s2, k2, wide, k_floors,
                            k_elide)(_t, _a, _i, _q, _r, *_p)

                    part.update(d_ent=d, s=s, k=k, wide=wide,
                                s_cap=b_pad * (n // d) * m_t * q_m,
                                k_cap=(n // d) * m_t * q_m)
                    self.n_mesh_queries += len(rows)
                elif kind == "sharded_bucketed":
                    btable = self.deps.bucket_device_sharded(self.mesh)
                    d = int(np.prod(list(self.mesh.shape.values())))
                    span = self.deps.SPAN
                    keff = self.deps.bucket_keff()
                    wide = dk.wide_codes(self.deps.capacity, m_t, q_m)
                    # per-shard candidate ceiling: every touched bucket's live
                    # entry slice plus this shard's slice of the wide list
                    # crossed with the query intervals (exact triples)
                    c = (q_m * span * keff
                         + q_m * (btable.wlo.shape[0] // d))
                    s = min(self._batch_flat, b_pad * c)
                    k = min(self._batch_k, c)
                    qb = qcols[rows_p].reshape(b_pad, q_m * span)
                    qmat = jnp.asarray(np.concatenate(
                        [qnp[rows_p], qb], axis=1))
                    mesh = self.mesh
                    from ..parallel.sharded import sharded_bucketed_attr
                    acols = self.deps.device_attr_cols_replicated(mesh)
                    ai = aidx.device_replicated(mesh)
                    tsh = self.deps.device_table_sharded(mesh)

                    def relaunch(s2, k2, _m=mesh, _b=btable, _t=tsh,
                                 _q=qmat, _a=acols, _i=ai, _r=rankb,
                                 _p=pz):
                        return sharded_bucketed_attr(
                            _m, q_m, span, s2, k2, m_t, keff, wide,
                            k_floors, k_elide)(_b, _t, _a, _i, _q, _r,
                                               *_p)

                    part.update(d_ent=d, s=s, k=k, wide=wide,
                                s_cap=b_pad * c, k_cap=c)
                    self.n_mesh_queries += len(rows)
                    self.n_mesh_bucketed_queries += len(rows)
                elif kind == "dense":
                    table = self.deps.device_table()
                    n = table.capacity
                    wide = dk.wide_codes(n, m_t, q_m)
                    s = min(self._batch_flat, b_pad * n * m_t * q_m)
                    k = min(self._batch_k, n * m_t * q_m)
                    qmat = jnp.asarray(qnp[rows_p])
                    acols = self.deps.device_attr_cols()
                    ai = aidx.device()

                    def relaunch(s2, k2, _t=table, _q=qmat, _a=acols,
                                 _i=ai, _r=rankb, _p=pz):
                        return dk.calculate_deps_flat_attr(
                            _t, _a, _i, _q, _r, *_p, q_m, s2, k2, wide,
                            k_floors, k_elide)

                    self.n_dense_queries += len(rows)
                    part.update(s=s, k=k, wide=wide,
                                s_cap=b_pad * n * m_t * q_m,
                                k_cap=n * m_t * q_m)
                else:   # bucketed
                    table = self.deps.device_table()
                    btable = self.deps.bucket_device()
                    span = self.deps.SPAN
                    keff = self.deps.bucket_keff()
                    wide = dk.wide_codes(table.capacity, m_t, q_m)
                    c = (q_m * span * keff + q_m * btable.wlo.shape[0])
                    s = min(self._batch_flat, b_pad * c)
                    k = min(self._batch_k, c)
                    qb = qcols[rows_p].reshape(b_pad, q_m * span)
                    qmat = jnp.asarray(np.concatenate(
                        [qnp[rows_p], qb], axis=1))
                    acols = self.deps.device_attr_cols()
                    ai = aidx.device()

                    def relaunch(s2, k2, _t=table, _b=btable, _q=qmat,
                                 _a=acols, _i=ai, _r=rankb, _p=pz):
                        return dk.bucketed_attr_jit(
                            _t, _a, _i, _b, _q, _r, q_m, span, s2, k2,
                            _p, keff=keff, wide=wide, floors=k_floors,
                            elide=k_elide)

                    self.n_bucketed_queries += len(rows)
                    part.update(s=s, k=k, wide=wide, s_cap=b_pad * c,
                                k_cap=c)
                hdr_dev, ent_dev = relaunch(s, k)
                part["relaunch"] = relaunch
                self.n_dispatches += 1
            box: Dict[str, object] = {"hdr": hdr_dev, "ent": ent_dev}
            part["box"] = box
            if not immediate:
                # two-stage prefetch on a worker thread: the header join
                # blocks on the kernel (GIL released), then ONLY the live
                # entry prefix crosses the wire — a pipelined caller
                # finalizes batch i while batch i+1 computes AND
                # downloads.  No faults.check here: injection draws stay
                # on the deterministic store-task thread (_collect_part
                # re-checks before consuming each stage)
                nq_, s_, k_, de_ = b_pad, s, k, part["d_ent"]
                # the worker's spans: a table of its own, folded into
                # kernel_times by the collector once it has joined
                times = box["times"] = {}
                ids = self._span_ids

                def _fetch():
                    try:
                        with devprof.span("wait_header_" + kname, times,
                                          *ids):
                            hdr = np.asarray(hdr_dev).reshape(
                                1, _HOFF + nq_)
                        box["hdr_np"] = hdr
                        if int(hdr[0, 1]) > s_ or int(hdr[0, 2]) > k_:
                            return    # overflowed: collector re-runs
                        with devprof.span("wait_entries_" + kname, times,
                                          *ids):
                            box["ent_np"] = _fetch_entry_prefix(
                                ent_dev, de_ * s_, int(hdr[0, 0]))
                    except BaseException as e:     # surfaced after join
                        box["err"] = e

                part["th"] = _fetch_pool().submit(_fetch)
            parts.append(part)

        all_rows = np.arange(nq, dtype=np.int64)
        # -- route health gating (module docstring: degradation ladder) --
        # while OOM-degraded or quarantined, every flush is pinned to the
        # host route (the route choice isn't even priced); when a
        # quarantine expires, the next device-bound flush is the PROBE —
        # its success restores the device routes, its failure re-
        # quarantines deeper
        probing = False
        forced, may_probe = self._flush_gate(nq)
        if forced is not None:
            route = "host"
        else:
            route = self.route_override
            if route is None:
                with self._span("choose_route"):
                    route = self._choose_route(qnp, q_m, floor_id)
            if route != "host" and may_probe:
                probing = True
                self.n_reprobes += 1
                self._fault_event("reprobe", f"route={route}")
        # -- r21 store-sharded residency gating --
        sh = self.store_shards
        hybrid = False
        if (sh is not None and sh.active and self.mesh is not None
                and forced is None and route != "host"):
            sh.tick_flush()
            # hybrid: healthy slices answer on device, the sick slices'
            # slots from the host twin (a host_slice part)
            hybrid = sh.any_quarantined()
            self.n_store_sharded_flushes += 1
        n_range = self._count_range_queries(queries)
        observed = forced or route
        if self.on_route is not None:
            self.on_route(observed, nq)
        else:
            obs = getattr(self.store.node, "route_observer", None)
            if obs is not None:
                # the query txn-ids ride along so the observer can stamp
                # the route onto each txn's span tree (obs.spans)
                obs(self.store, observed, nq, [q[0] for q in queries])
        degenerate = not self.BUCKETED or \
            len(self.deps.wide_entries) > self.deps.WIDE_MAX
        try:
            if route == "host":
                dispatch("host", all_rows)
            elif self.mesh is not None:
                if hybrid:
                    # quarantined slices pin the flush to the DENSE
                    # sharded kind: the bucketed kernels read entries
                    # structurally (no status column), so only the dense
                    # mask can exclude a masked slice
                    dispatch("sharded", all_rows)
                    dispatch("host_slice", all_rows)
                elif route == "dense" or degenerate:
                    dispatch("sharded", all_rows)
                else:
                    qcols, wide_q = self._bucket_query_cols(qnp, q_m)
                    narrow = np.nonzero(~wide_q)[0].astype(np.int64)
                    wide = np.nonzero(wide_q)[0].astype(np.int64)
                    if len(narrow):
                        dispatch("sharded_bucketed", narrow, qcols)
                    if len(wide):
                        dispatch("sharded", wide)
            elif route == "dense" or degenerate:
                dispatch("dense", all_rows)
            else:
                qcols, wide_q = self._bucket_query_cols(qnp, q_m)
                narrow = np.nonzero(~wide_q)[0].astype(np.int64)
                wide = np.nonzero(wide_q)[0].astype(np.int64)
                if len(narrow):
                    dispatch("bucketed", narrow, qcols)
                if len(wide):
                    dispatch("dense", wide)
        except faults.DEVICE_EXCEPTIONS as e:
            # device-boundary failure at dispatch: quarantine (the slice
            # it touched, under store-shards; else the device) and fail
            # the WHOLE flush over to the always-correct host route
            parts.clear()
            self._device_fault(e, f"dispatch: {e}", sliced=True)
            self.n_fallback_queries += nq
            route = "host"
            probing = False
            dispatch("host", all_rows)
        if route != "host":
            self.n_range_device_queries += n_range
        if immediate:
            # synchronous caller (deps_query, B=1): collect follows on the
            # next line with no interleaved mutation, so skip the snapshot
            # copies and the prefetch thread — the live mirror IS the
            # snapshot
            ids = (self.deps.msb, self.deps.lsb, self.deps.node,
                   self.deps.obj, self.deps.status, self.deps.emsb,
                   self.deps.elsb, self.deps.enode, self.deps.eknown)
            ivs = (self.deps.lo, self.deps.hi, self.deps.domain)
        elif len(parts) == 1 and parts[0]["kind"] == "host":
            # host route: the entries are already known, so snapshot ONLY
            # the referenced slots (a gather of ~live-tail rows instead of
            # a full-capacity copy) and remap the slot indices onto the
            # compact snapshot.  The remap is monotonic, so the entries'
            # ascending-slot order — and therefore every downstream byte —
            # is unchanged
            part = parts[0]
            d = self.deps
            cb, cj, cm, cq = part["ent"]
            flag = np.zeros(d.capacity, bool)
            flag[cj] = True
            u = np.nonzero(flag)[0]
            remap = np.empty(d.capacity, np.int64)
            remap[u] = np.arange(len(u), dtype=np.int64)
            part["ent"] = (cb, remap[cj], cm, cq)
            ids = (d.msb[u], d.lsb[u], d.node[u], d.obj[u], d.status[u],
                   d.emsb[u], d.elsb[u], d.enode[u], d.eknown[u])
            ivs = (d.lo[u], d.hi[u], d.domain[u])
        else:
            # snapshot the mirror's id + interval columns: the mirror
            # mutates in place, and a slot freed+reallocated between begin
            # and end would otherwise resolve this batch's indices to the
            # WRONG TxnId (or footprint).  The copy is version-cached:
            # pipelined batches over an unmutated mirror share one
            ids, ivs, _kind = self.deps.snapshot_cols()
        fmeta = {"floor_id": floor_id, "probing": probing,
                 "immediate": immediate, "aidx": aidx,
                 "floor_skip": floor_skip}
        return (parts, ids, ivs, qnp, q_m, list(queries), fmeta)

    def _pack_queries(self, queries):
        """(q_m, qnp): a flush's queries as one int64 matrix, each row
        padded to ``q_m`` intervals (dk.pack_query_matrix)."""
        with self._span("pack_queries"):
            q_m = _pow2_at_least(
                max(len(t[3]) + len(t[4]) for t in queries))
            packed = [(sb, wit, toks, rngs, tid)
                      for (tid, sb, wit, toks, rngs) in queries]
            return q_m, dk.pack_query_matrix(packed, q_m)

    def _bucket_query_cols(self, qnp: np.ndarray, q_m: int):
        """Vectorized query->bucket-row mapping: int64[NQ, q_m, SPAN] dense
        rows (-1 = no/empty bucket) and the wide-query mask (any interval
        spanning more than SPAN buckets — those take the dense kernel)."""
        with self._span("pack_queries"):
            shift = self.deps.BSHIFT
            span = self.deps.SPAN
            lo = qnp[:, 7:7 + q_m]
            hi = qnp[:, 7 + q_m:7 + 2 * q_m]
            used = lo <= hi
            blo = lo >> shift
            bhi = hi >> shift
            wide_q = np.any(used & (bhi - blo + 1 > span), axis=1)
            sorted_bids, row_of = self.deps.bid_rows()
            cols = np.full((qnp.shape[0], q_m, span), -1, np.int64)
            if len(sorted_bids):
                for off in range(span):
                    bid = blo + off
                    ok = used & (bid <= bhi)
                    idx = np.searchsorted(sorted_bids, bid)
                    idxc = np.minimum(idx, len(sorted_bids) - 1)
                    found = ok & (sorted_bids[idxc] == bid)
                    cols[:, :, off] = np.where(found, row_of[idxc], -1)
            return cols, wide_q

    def _flush_span(self):
        """One deps flush of a serving node, in its loop's table (none in a
        sim): the kernel_times kinds are its children."""
        return devprof.span(
            "srv.deps_flush",
            getattr(getattr(self.store, "node", None), "loop_times", None))

    def _span(self, kind: str):
        """One host span into ``kernel_times`` (obs.devprof.span): every
        launch boundary and host pass of the store is one — dispatch_* =
        host pack + upload + enqueue, wait_header_* = header join,
        wait_entries_* = entry-prefix transfer, host_* = host passes —
        and with a profiler armed a Chrome-trace slice too: pid = node,
        tid = store, the launch timeline and not just a counter."""
        return devprof.span(kind, self.kernel_times, *self._span_ids)

    def _overflow_resize(self, total: int, maxc: int, s: int, k: int,
                         s_cap: int, k_cap: int, runs: int):
        """ONE overflow re-sizing policy for the solo and fused re-run
        loops: size the flat capacity to the exact observed total (+25%
        headroom, 16k granularity) and the row width with 2x headroom
        (every distinct (s, k) is a fresh jit compilation; a mid-run
        recompile costs seconds on TPU); after the first re-run escalate
        geometrically — a truncated-past-k dense row under-counts its
        triples in the header (flat_csr_local docstring) — so the loop
        terminates at the caps; sticky-learn the result so subsequent
        batches dispatch right-sized."""
        s2 = -(-int(total * 1.25) // 16384) * 16384
        k2 = _pow2_at_least(2 * maxc)
        if runs:
            s2, k2 = max(s2, 2 * s), max(k2, 2 * k)
        s = min(max(s2, s), s_cap)
        k = min(max(k2, k), k_cap)
        self._batch_flat = max(self._batch_flat, s)
        self._batch_k = max(self._batch_k, k)
        return s, k

    def _prefix_pays(self, s: int, maxtot: int, itemsize: int) -> bool:
        """Stage-2 transfer model for a SYNCHRONOUS fetch: slicing the
        live prefix costs one extra device dispatch (~an rtt) and saves
        the padded tail's bytes — a model over the calibrated per-byte
        transfer cost, not a threshold.  On a local CPU device bytes are
        ~free and the single full fetch wins; on a slow MB/s link the
        prefix wins from ~100KB of tail."""
        saved = (s - _prefix_len(maxtot, s)) * itemsize
        if saved <= 0:
            return False
        calib = self._calibration()
        return saved * calib.get("c_xfer", 0.0) > calib["rtt"]

    def _collect_part(self, part):
        """Two-stage download + decode of one kernel part's attributed CSR.
        Stage 1 fetches the scalar header (total / overflow watermarks /
        elision tallies / row_end) — a few hundred int32s whose join also
        absorbs the kernel wait; stage 2 transfers ONLY the live prefix of
        the entry buffer.
        When the learned flat capacity or row width overflowed, the re-run
        is sized from the exact header already downloaded and rides the
        same compacted transfer — the full pow2-padded buffer is never
        materialized on the host.  Returns per-triple (b, j, m, q) global
        arrays (codes decoded, pad rows dropped)."""
        box = part["box"]
        th = part.get("th")
        nq, d_ent = part["nq"], part["d_ent"]
        s, k = part["s"], part["k"]
        itemsize = 8 if part["wide"] else 4
        faults.check("transfer", "header download")
        if th is not None:
            # the worker's wait_header_* / wait_entries_* spans are its
            # own clocks on its own thread; they count here, at the join
            th.result()
            devprof.merge(self.kernel_times, box["times"])
            err = box.get("err")
            if err is not None:
                raise err           # the real device/transfer failure
            hdr = box["hdr_np"]
        else:
            with self._span("wait_header_" + part["kind"]):
                hdr = np.asarray(box["hdr"]).reshape(1, _HOFF + nq)
        self.download_bytes += hdr.nbytes
        self.download_bytes_padded += hdr.nbytes + d_ent * s * itemsize
        runs = 0
        while int(hdr[0, 1]) > s or int(hdr[0, 2]) > k:
            # overflow: re-size from the exact header (shared policy,
            # _overflow_resize), then re-dispatch against the same
            # snapshot tables via the part's relaunch closure —
            # registrations interleaved between begin and end must not
            # shift the queried snapshot
            s, k = self._overflow_resize(
                int(hdr[0, 1]), int(hdr[0, 2]), s, k,
                part["s_cap"], part["k_cap"], runs)
            dk.launch_check(part["kind"])
            hdr_dev, ent_dev = part["relaunch"](s, k)
            box = {"hdr": hdr_dev, "ent": ent_dev}
            th = None
            faults.check("transfer", "header download")
            with self._span("wait_header_" + part["kind"]):
                hdr = np.asarray(hdr_dev).reshape(1, _HOFF + nq)
            self.download_bytes += hdr.nbytes
            self.download_bytes_padded += hdr.nbytes \
                + d_ent * s * itemsize
            runs += 1
        faults.check("transfer", "entry download")
        if th is not None and "ent_np" in box:
            ent = box["ent_np"]
        else:
            # synchronous fetch (immediate flush or post-overflow): slice
            # the live prefix only when the modeled byte saving beats the
            # extra slice dispatch — on the pipelined path the prefix
            # fetch rides the prefetch thread and overlaps compute, so it
            # never asks
            with self._span("wait_entries_" + part["kind"]):
                maxtot = int(hdr[0, 0])
                if self._prefix_pays(d_ent * s, maxtot, itemsize):
                    ent = _fetch_entry_prefix(box["ent"], d_ent * s,
                                              maxtot)
                else:
                    ent = np.asarray(box["ent"]).reshape(1, d_ent * s)
        self.download_bytes += ent.nbytes
        if self.store_shards is not None and self.store_shards.active \
                and "sharded" in part["kind"]:
            # bytes the sharded-store merge shipped home (header + merged
            # entry block) — the ``shard_merge_bytes`` index counter
            self.n_shard_merge_bytes += hdr.nbytes + ent.nbytes
        # the attributed header carries the in-kernel elision tallies
        # (eknown-graded transitive rows vs decided-below-pivot rows) and
        # the download is the post-attribution entry set
        self.n_elided_transitive += int(hdr[0, 3])
        self.n_elided_decided += int(hdr[0, 4])
        self.attr_download_bytes += hdr.nbytes + ent.nbytes
        tb, tj, tm, tq = _decode_triples(hdr, ent, nq, part["mq"],
                                         part["q_m"])
        # stale/corrupted-result injection: perturb the slot indices the
        # kernel answered with.  Only where the detector actually runs —
        # paranoia shadow-verify on an IMMEDIATE flush (the protocol path);
        # injecting silent corruption with no detector would just be
        # breaking the program, not testing it.
        if part.get("immediate") and self._paranoid() and len(tj) \
                and faults.should_fire("stale_result"):
            tj = (tj + np.int64(1)) % np.int64(self.deps.capacity)
        gmap = part["gmap"]
        b_global = gmap[tb]
        keep = b_global >= 0                      # drop pad rows
        return b_global[keep], tj[keep], tm[keep], tq[keep]

    def _host_attr_triples(self, handle, part=None, snapshot=None):
        """Entry-level host answer for a flush: the host
        route's exact probes + the same floor/elision drops the kernels
        fold in, over the flush's snapshot columns.  Serves the host
        route itself, the device-fault failover and the paranoia shadow.
        Returns (tb, tj, tm, tq)."""
        (_parts, ids, ivs, qnp, q_m, _queries, fmeta) = handle
        if part is not None:
            tb, tj, cm, cq = part["ent"]
        else:
            tb, tj, cm, cq = self.deps.host_pairs(
                qnp, q_m, fmeta["floor_id"], snapshot=snapshot)
        tb, tj, tm, tq, n_t, n_d = self._attr_filter_entries(
            tb, tj, cm, cq, ids, ivs, fmeta["aidx"], qnp,
            fmeta["floor_skip"])
        self.n_elided_transitive += n_t
        self.n_elided_decided += n_d
        return tb, tj, tm, tq

    def _batch_collect_attr(self, handle):
        """Collect a dispatched batch: the kernels already
        applied floors/elision/dedupe, so the download IS the final entry
        set and this is a pure decode.  The host route (and any device
        failover / paranoia shadow) applies the identical drops through
        _attr_filter_entries over the same snapshot — every route hands
        the shared finalize the same entries.  Returns (tb, tj, tm, tq,
        ids, ivs, qnp, q_m, queries)."""
        (parts, ids, ivs, qnp, q_m, queries, fmeta) = handle
        nq = len(queries)
        if len(parts) == 1 and parts[0]["kind"] == "host":
            with self._span("host_attr_filter"):
                tb, tj, tm, tq = self._host_attr_triples(handle,
                                                         part=parts[0])
                self.n_queries += nq
                self.n_kernel_deps += len(tj)
            return tb, tj, tm, tq, ids, ivs, qnp, q_m, queries
        try:
            # host_slice twin parts (the r21 hybrid) answer from the host
            # mirror through the same attr filter the host route uses;
            # device parts download as usual
            outs = [self._host_attr_triples(handle, part=p)
                    if p["kind"] == "host_slice" else self._collect_part(p)
                    for p in parts]
        except faults.DEVICE_EXCEPTIONS as e:
            self._device_fault(e, f"collect: {e}", sliced=True)
            self.n_host_queries += nq
            self.n_fallback_queries += nq
            self.n_dispatches += 1
            self.n_queries += nq
            tb, tj, tm, tq = self._host_attr_triples(handle)
            self.n_kernel_deps += len(tj)
            return tb, tj, tm, tq, ids, ivs, qnp, q_m, queries
        if len(outs) == 1:
            tb, tj, tm, tq = outs[0]
        else:
            tb = np.concatenate([o[0] for o in outs])
            tj = np.concatenate([o[1] for o in outs])
            tm = np.concatenate([o[2] for o in outs])
            tq = np.concatenate([o[3] for o in outs])
        if self._paranoid() and fmeta["immediate"]:
            # shadow-verify the ATTRIBUTED answer: the surviving
            # (query, slot) pair set must equal the host route's answer
            # run through the same floor/elision drops
            self.n_shadow_checks += 1
            hb, hj, hm, hq = self._host_attr_triples(handle)
            cap = np.int64(max(self.deps.capacity, 1))
            if not np.array_equal(np.unique(tb * cap + tj),
                                  np.unique(hb * cap + hj)):
                self.n_shadow_mismatches += 1
                self._device_fault("stale_result", "attr shadow mismatch",
                                   sliced=True)
                self.n_fallback_queries += nq
                self.n_queries += nq
                self.n_kernel_deps += len(hj)
                return hb, hj, hm, hq, ids, ivs, qnp, q_m, queries
        sh = self.store_shards
        if sh is not None and sh.active:
            sh.note_success()   # probing suspect slices are healthy again
        if fmeta["probing"]:
            self._restore_device()   # the probe flush succeeded end-to-end
        self.n_queries += nq
        self.n_kernel_deps += len(tj)
        return tb, tj, tm, tq, ids, ivs, qnp, q_m, queries

    def _finalize_attr_entries(self, tb, tj, tm, tq, ids, ivs, qnp, q_m,
                               builders) -> None:
        """The thin shared finalize: attributed entries -> builder CSRs.
        Every floor/elision decision already happened (in-kernel on device
        routes, _attr_filter_entries on the host route), so what remains
        is pure shaping: token gathers, dense id ranks, and the two
        columnar batch finalizes.  The (query, token, dep) dedupe built
        into _finalize_key_batch covers the duplicate emits host probes
        keep (the kernels drop them in-kernel only to shrink the wire)."""
        (msb_a, lsb_a, node_a, obj_a, _status, _xm, _xl, _xn, _xk) = ids
        lo, hi, dom = ivs
        if len(tj) == 0:
            return
        key_dep = dom[tj] == int(Domain.Key)
        all_key = key_dep.all()              # the hot-key regime: skip the
        if all_key:                          # split gathers wholesale
            kp = None
        else:
            kp = np.nonzero(key_dep)[0]
        if all_key or len(kp):
            if all_key:
                bb, jj, km = tb, tj, tm
            else:
                bb, jj, km = tb[kp], tj[kp], tm[kp]
            tt = lo[jj, km]                  # key-domain footprint = point
            # token ranks: when every used query interval is a POINT the
            # emitted tokens are a subset of the query tokens — rank
            # against that tiny sorted set instead of sorting the emits
            # (extra never-emitted ranks only stretch the composite)
            q_lo = qnp[:, 7:7 + q_m]
            q_hi = qnp[:, 7 + q_m:7 + 2 * q_m]
            used = q_lo <= q_hi
            if (q_lo[used] == q_hi[used]).all():
                uniq_t2 = np.unique(q_lo[used])
                inv_t2 = _exact_ranks(uniq_t2, tt)
            else:
                uniq_t2, inv_t2 = np.unique(tt, return_inverse=True)
            # unique dep slots: presence flags + an inverse-map gather
            # beat a sort once the emit set outgrows the snapshot's slot
            # space (slot ids are dense by construction)
            cap_s = len(msb_a)
            if len(jj) > cap_s // 4:
                flag = np.zeros(cap_s, bool)
                flag[jj] = True
                u_slots = np.nonzero(flag)[0]
                remap = np.empty(cap_s, np.int64)
                remap[u_slots] = np.arange(len(u_slots), dtype=np.int64)
                slot_inv = remap[jj]
            else:
                u_slots, slot_inv = np.unique(jj, return_inverse=True)
            ordr = np.lexsort((node_a[u_slots],
                               lsb_a[u_slots].astype(np.uint64),
                               msb_a[u_slots].astype(np.uint64)))
            rank = np.empty(len(u_slots), np.int64)
            rank[ordr] = np.arange(len(u_slots))
            _finalize_key_batch(builders, bb, tt, inv_t2, len(uniq_t2),
                                rank[slot_inv], len(u_slots), obj_a[jj])
        rp = np.zeros(0, np.int64) if all_key else np.nonzero(~key_dep)[0]
        if len(rp):
            jj_r, bb_r, rm, rq = tj[rp], tb[rp], tm[rp], tq[rp]
            ilo = np.maximum(lo[jj_r, rm], qnp[bb_r, 7 + rq])
            ihi = np.minimum(hi[jj_r, rm], qnp[bb_r, 7 + q_m + rq]) + 1
            _finalize_range_batch(builders, bb_r, ilo, ihi,
                                  msb_a[jj_r], lsb_a[jj_r],
                                  node_a[jj_r], obj_a[jj_r])

    def deps_query_batch_end_attributed(self, safe, handle, builders) -> None:
        """Collect a dispatched batch and fold each query's deps into its
        builder with full host-path semantics: the entries arrive
        pre-floored/pre-elided (in-kernel on the device routes,
        _attr_filter_entries on the host route) and take the thin shared
        finalize."""
        tb, tj, tm, tq, ids, ivs, qnp, q_m, _queries = \
            self._batch_collect_attr(handle)
        with self._span("host_attr_finalize"):
            self._finalize_attr_entries(tb, tj, tm, tq, ids, ivs, qnp,
                                        q_m, builders)

    # ------------------------------------------------------------------
    # fused cross-store dispatch (r08; driven by local.dispatch's
    # per-node DeviceDispatcher)
    # ------------------------------------------------------------------
    def fused_eligible(self, queries):
        """Dispatcher phase A (PURE — mutates nothing): can this store's
        pending flush join a fused device launch?  None when the flush
        must (or would) run the host route — a host flush has no device
        launch to coalesce; else a hint dict carrying the packed queries
        and the modeled solo device element count the dispatcher's
        fused-vs-solo pricing consumes.  A store that ends up NOT fused
        runs the classic solo flush, which applies the gate/probe/route
        bookkeeping itself."""
        if self.host_pinned or self._dev_quar_flushes > 0 \
                or self.route_override == "host":
            return None
        sh = self.store_shards
        if sh is not None and sh.active and sh.any_quarantined():
            # hybrid (device + host-twin) flushes run solo: a fused
            # member's block is all-device, with no twin part to graft
            return None
        q_m, qnp = self._pack_queries(queries)
        floor_id, prune_np = self._batch_floor(qnp, q_m)
        route = self.route_override
        if route is None:
            with self._span("choose_route"):
                route = self._choose_route(qnp, q_m, floor_id)
        if route == "host":
            return None
        nq = qnp.shape[0]
        b_pad = _pow2_at_least(nq, 1)
        cap = self.deps.capacity
        d = 1 if self.mesh is None else max(len(self.mesh.devices.flat), 1)
        solo_elems = b_pad * cap * q_m * self.deps.max_intervals // d
        degenerate = not self.BUCKETED or \
            len(self.deps.wide_entries) > self.deps.WIDE_MAX
        if route != "dense" and not degenerate:
            # the adaptive solo dispatch would probe the bucket index for
            # narrow queries — price solo with the cheaper kernel
            buck = b_pad * (q_m * self.deps.SPAN * self.deps.bucket_keff()
                            + q_m * len(self.deps.wide_entries) // d)
            solo_elems = min(solo_elems, buck)
        # snapshot cost the fused pricing charges: zero when the cached
        # copy is still fresh, one full-column memcpy's worth otherwise
        dm = self.deps
        snap_stale = dm._snap is None or dm._snap[0] != dm.mut_version
        snap_elems = cap * (2 * dm.max_intervals + 10) if snap_stale else 0
        # r15: fused launches run the ATTRIBUTED kernels — take this
        # store's floor/elision index and the per-query bound ranks (which
        # assemble its device image) now, while the mirror is the
        # begin-time state
        aidx = self._attr_index()
        return {"dev": self, "queries": list(queries), "qnp": qnp,
                "q_m": q_m, "floor_id": floor_id, "prune": prune_np,
                "nq": nq, "b_pad": b_pad, "cap": cap,
                "m_iv": self.deps.max_intervals, "solo_elems": solo_elems,
                "snap_elems": snap_elems, "aidx": aidx,
                "rankb_np": aidx.rank_bounds(qnp),
                "floor_skip": aidx.floors_match(qnp, q_m, floor_id)}

    def fused_table(self):
        """The (cached, device-resident) table the fused launch consumes —
        mesh-sharded under a mesh, single-device otherwise."""
        if self.mesh is not None:
            return self.deps.device_table_sharded(self.mesh)
        return self.deps.device_table()

    def fused_commit(self, hint) -> None:
        """Dispatcher phase B for a chosen fused member: apply the
        flush-gate bookkeeping the solo path would have applied (probe
        accounting), snapshot the mirror columns the deferred harvest
        needs (mutations may land between dispatch and the harvest task),
        and surface the routing decision."""
        probing = False
        if self._dev_backoff > 0:
            probing = True
            self.n_reprobes += 1
            self._fault_event("reprobe", "route=fused")
        hint["ids"], hint["ivs"], hint["kind_col"] = \
            self.deps.snapshot_cols()
        hint["probing"] = probing
        if self.on_route is not None:
            self.on_route("fused", hint["nq"])
        else:
            obs = getattr(self.store.node, "route_observer", None)
            if obs is not None:
                batch = hint.get("batch") or ()
                obs(self.store, "fused", hint["nq"],
                    [q[0] for q, _b, _d in batch])

    def fused_fail_to_host(self, hint, exc) -> None:
        """A device fault inside the fused LAUNCH fails the whole batch
        over to the host route: quarantine this member and compute its
        host pairs right now (still inside the dispatcher event, so the
        live mirror IS the prep-time state)."""
        self._device_fault(exc, f"fused dispatch: {exc}", sliced=True)
        self.n_fallback_queries += hint["nq"]
        hint["probing"] = False
        hint["host"] = self.deps.host_pairs(hint["qnp"], hint["q_m"],
                                            hint["floor_id"])

    def _hint_attr_entries(self, hint, ent4) -> tuple:
        """Turn a fused hint's host-route per-entry answer into the
        attributed entry set: the same floor/elision drops the fused
        kernel applies, over the hint's begin-time snapshot columns."""
        cb, cj, cm, cq = ent4
        tb, tj, tm, tq, n_t, n_d = self._attr_filter_entries(
            cb, cj, cm, cq, hint["ids"], hint["ivs"],
            hint["aidx"], hint["qnp"], hint.get("floor_skip", False))
        self.n_elided_transitive += n_t
        self.n_elided_decided += n_d
        return tb, tj, tm, tq

    def _fused_snapshot(self, hint):
        return (hint["ids"][0], hint["ids"][1], hint["ids"][2],
                hint["kind_col"], hint["ids"][4], hint["ivs"][0],
                hint["ivs"][1])

    def _fused_collect(self, hint, launch):
        """Download + decode of this store's block of the fused ATTRIBUTED
        result, with the solo path's full semantics: overflow re-run
        (solo attributed, escalated s/k from the exact header, same
        snapshot table + attr inputs), stale-result injection point,
        paranoia shadow-verify against the attr-filtered SNAPSHOT host
        scan, probe restore, and whole-batch host failover on any
        device-boundary failure.  Returns attributed ENTRY arrays
        (tb, tj, tm, tq)."""
        with self._span("wait_attr_fused"):
            nq = hint["nq"]
            n_range = self._count_range_queries(hint["queries"])
            if "host" in hint:           # launch already failed over to host
                self.n_host_queries += nq
                self.n_dispatches += 1
                return self._hint_attr_entries(hint, hint["host"])
            qnp, q_m = hint["qnp"], hint["q_m"]
            shard_n = hint["shard_n"]
            b_pad = hint["b_pad_c"]
            mq, qmc = hint["mq"], hint["q_m_c"]
            pad_stride = hint.get("pad_shard_n")   # mesh: padded shard stride
            try:
                hdr_all, ent_all = launch.materialize()
                hdr = hdr_all[hint["row"]].reshape(1, 5 + b_pad)
                ent = ent_all[hint["row"]]
                s_, k_ = launch.s, launch.k
                runs = 0
                while int(hdr[:, 1].max()) > s_ or int(hdr[:, 2].max()) > k_:
                    # overflow: escalate EXACTLY like the solo path — re-run
                    # this store alone against the same cached table + attr
                    # inputs, sized from the exact header
                    cap_k = shard_n * hint["m_iv"] * qmc
                    s_, k_ = self._overflow_resize(
                        int(hdr[:, 1].max()), int(hdr[:, 2].max()), s_, k_,
                        b_pad * cap_k, cap_k, runs)
                    qmat = jnp.asarray(hint["qmat_np"])
                    rankb = jnp.asarray(hint["rankb_pad"])
                    pnp = hint["prune"]
                    pz = _prune_zeros() if pnp is None else \
                        (jnp.asarray(pnp[0]), jnp.asarray(pnp[1]),
                         jnp.asarray(pnp[2]))
                    wide = hint["wide"]
                    fl_, el_ = (not hint.get("floor_skip", False),
                                hint["aidx"].n_execs > 0)
                    if self.mesh is not None:
                        from ..parallel.sharded import sharded_flat_attr
                        hdr_dev, ent_dev = sharded_flat_attr(
                            self.mesh, qmc, s_, k_, wide, fl_, el_)(
                            hint["table"],
                            self.deps.device_attr_cols_sharded(self.mesh),
                            hint["aidx"].device_replicated(self.mesh),
                            qmat, rankb, *pz)
                        d_ent = len(self.mesh.devices.flat)
                    else:
                        hdr_dev, ent_dev = dk.calculate_deps_flat_attr(
                            hint["table"], self.deps.device_attr_cols(),
                            hint["aidx"].device(), qmat, rankb, *pz,
                            qmc, s_, k_, wide, fl_, el_)
                        d_ent = 1
                    faults.check("transfer", "header download")
                    hdr = np.asarray(hdr_dev).reshape(1, 5 + b_pad)
                    itemsize = 8 if wide else 4
                    self.download_bytes += hdr.nbytes
                    self.download_bytes_padded += hdr.nbytes \
                        + d_ent * s_ * itemsize
                    if int(hdr[:, 1].max()) <= s_ \
                            and int(hdr[:, 2].max()) <= k_:
                        faults.check("transfer", "entry download")
                        ent = _fetch_entry_prefix(ent_dev, d_ent * s_,
                                                  int(hdr[:, 0].max()))
                        self.download_bytes += ent.nbytes
                    runs += 1
                if runs:
                    # the re-run scanned the store's OWN table solo, so its
                    # codes scale on the store's interval width and its slot
                    # ids are contiguous-global (no fused pad stride)
                    mq = hint["m_iv"] * qmc
                    pad_stride = None
                if ent.ndim == 1:
                    ent = ent.reshape(1, -1)
            except faults.DEVICE_EXCEPTIONS as e:
                # whole-batch failover: quarantine every member, serve this
                # flush from the SNAPSHOT host scan (begin-time bytes)
                launch.poison(e)
                self.n_fallback_queries += nq
                self.n_host_queries += nq
                self.n_dispatches += 1
                return self._hint_attr_entries(
                    hint, self.deps.host_pairs(
                        qnp, q_m, hint["floor_id"],
                        snapshot=self._fused_snapshot(hint)))
            self.n_elided_transitive += int(hdr[:, 3].sum())
            self.n_elided_decided += int(hdr[:, 4].sum())
            self.attr_download_bytes += hdr.nbytes + ent.nbytes
            tb, tj, tm, tq = _decode_triples(hdr, ent, b_pad, mq, qmc)
            if pad_stride is not None:
                # mesh fused codes number slots on the PADDED per-shard
                # stride (every member padded to the group's largest slice):
                # fold back onto this store's contiguous slot ids
                tj = (tj // pad_stride) * np.int64(hint["cap"]
                                                   // hint["d_mesh"]) \
                    + tj % pad_stride
            if self._paranoid() and len(tj) \
                    and faults.should_fire("stale_result"):
                tj = (tj + np.int64(1)) % np.int64(len(hint["ids"][0]))
            gmap = hint["gmap"]
            b_global = gmap[tb]
            keep = b_global >= 0
            tb, tj, tm, tq = b_global[keep], tj[keep], tm[keep], tq[keep]
            if self._paranoid():
                self.n_shadow_checks += 1
                hb, hj, hm, hq = self._hint_attr_entries(
                    hint, self.deps.host_pairs(
                        qnp, q_m, hint["floor_id"],
                        snapshot=self._fused_snapshot(hint)))
                cap = np.int64(len(hint["ids"][0]))
                if not np.array_equal(np.unique(tb * cap + tj),
                                      np.unique(hb * cap + hj)):
                    self.n_shadow_mismatches += 1
                    self._device_fault("stale_result", "fused shadow mismatch")
                    self.n_fallback_queries += nq
                    self.n_dispatches += 1
                    return hb, hj, hm, hq
            sh = self.store_shards
            if sh is not None and sh.active:
                sh.note_success()
            if hint.get("probing"):
                self._restore_device()
            self.n_dispatches += 1
            self.n_fused_flushes += 1
            self.n_fused_queries += nq
            self.n_range_device_queries += n_range
            if self.mesh is not None:
                self.n_mesh_queries += nq
            else:
                self.n_dense_queries += nq
            return tb, tj, tm, tq

    def fused_harvest(self, safe, hint, launch) -> None:
        """Store-task leg of a fused flush: parse this store's block of
        the fused ATTRIBUTED result (the shared download happens at the
        first member's harvest — jax's async dispatch overlapped the
        device work with whatever host processing ran since the launch)
        and hand the pre-attributed entries straight to the shared
        finalize over the prep-time snapshot — the same bytes the solo
        launch would have produced, harvested at the next event-loop
        boundary in deterministic store order."""
        batch = hint["batch"]
        try:
            with self._flush_span():
                tb, tj, tm, tq = self._fused_collect(hint, launch)
                self.n_queries += hint["nq"]
                self.n_kernel_deps += len(tj)
                self._finalize_attr_entries(tb, tj, tm, tq, hint["ids"],
                                            hint["ivs"], hint["qnp"],
                                            hint["q_m"],
                                            [b for _q, b, _d in batch])
        except BaseException as e:  # noqa: BLE001
            for _q, _b, done in batch:
                done(e, None)
            return
        for _q, _b, done in batch:
            done(None, safe)

    # ------------------------------------------------------------------
    # the drain (device replacement of listener fan-out)
    # ------------------------------------------------------------------
    def arm(self, safe, txn_id: TxnId) -> None:
        """Register a Stable/PreApplied txn's remaining waiting set as a
        drain row; the next tick will re-evaluate it."""
        cmd = safe.if_present(txn_id)
        if cmd is None or cmd.waiting_on is None:
            return
        slot = self.drain.alloc(txn_id)
        self.drain.set_status(slot, dk.SLOT_STABLE, cmd.execute_at)
        self.drain.clear_deps(slot)
        for dep in cmd.waiting_on.waiting_ids():
            dslot = self._dep_drain_slot(safe, dep)
            self.drain.add_edge(slot, dslot)
        self.drain.active[slot] = True
        self.schedule_tick()

    def _dep_drain_slot(self, safe, dep: TxnId) -> int:
        slot = self.drain.slot_of.get(dep)
        if slot is not None:
            return slot
        slot = self.drain.alloc(dep)
        cmd = safe.if_present(dep)
        status, exec_at = _drain_status_of(cmd)
        self.drain.set_status(slot, status, exec_at)
        return slot

    def on_terminal(self, txn_id: TxnId) -> None:
        """Truncation/erasure: the txn can never gate execution again
        (ref: _dep_clearance treats truncated as done).  Mark its drain row
        terminal and re-evaluate waiters — without this, truncating a dep
        whose record Cleanup then drops is a lost wakeup in device mode
        (no listeners exist to carry the erase notification)."""
        dslot = self.drain.slot_of.get(txn_id)
        if dslot is not None:
            self.drain.set_status(dslot, dk.SLOT_INVALIDATED, None)
            if self.drain.active.any():
                self.schedule_tick()

    def on_driven(self, txn_id: TxnId) -> None:
        """The txn reached ReadyToExecute/Applying — stop driving it (its
        slot lives on as a dependency of others until terminal + unreferenced)."""
        slot = self.drain.slot_of.get(txn_id)
        if slot is not None:
            self.drain.active[slot] = False
            self.drain.clear_deps(slot)

    def _mesh_tick_pays(self, n: int) -> bool:
        """Regime-adaptive drain tick: row-shard the frontier sweep only
        when the modeled per-shard matvec saving (n^2 work split d ways)
        beats the extra shard_map launch cost — the same calibration the
        deps router uses.  Tiny in-flight sets (the common sim/tick shape)
        otherwise pay a 100x launch premium per tick on the virtual CPU
        mesh; at-scale dense drains still shard."""
        calib = self._calibration()
        d = max(len(self.mesh.devices.flat), 1)
        single = 2.0 * calib["rtt"] + calib["c_dev"] * float(n) * n
        mesh = 2.0 * calib.get("rtt_mesh", calib["rtt"]) \
            + calib["c_dev"] * float(n) * n / d
        return mesh < single

    def _host_tick_pays(self) -> bool:
        """Priced drain tick: sweep the frontier on the host when the
        Python loop over the driven Stable rows and the dep edges is
        modeled cheaper than the device round trip — models, not
        thresholds, from the calibration the deps router uses.  The host
        side counts EVERY edge of the mirror (``n_edges``, kept O(1)), the
        undriven rows' too: an upper bound on what the sweep visits, so
        the error is toward the device.  The device side is the
        single-device frontier program over the padded state (dense
        [n, n], or ELL [n, degree] above DENSE_MAX with the mean degree
        per driven row, padded as state() pads it, standing in for the
        maximum state() would have to walk the live set to find); the
        Python rebuild and upload state() makes after an edge moved are
        not in it, which errs toward the device as well.  A served store's
        few dozen live slots price to the host; a deep or wide drain
        prices to the device.  ``route_override`` pins either way
        ("host", else the device)."""
        if self.route_override is not None:
            return self.route_override == "host"
        dr = self.drain
        calib = self._calibration()
        rows = int(np.count_nonzero((dr.status == dk.SLOT_STABLE)
                                    & dr.active))
        host = calib["c_sweep"] * (rows + dr.n_edges)
        n = _pow2_at_least(len(dr.id_of), dr.MIN_STATE_SLOTS)
        if n <= dr.DENSE_MAX:
            elems = float(n) * n
        else:
            elems = float(n) * _pow2_at_least(-(-dr.n_edges // max(rows, 1)))
        return host < 2.0 * calib["rtt"] + calib["c_dev"] * elems

    # A store whose every tick prices to the host would never cross the
    # device boundary again after start-up: a device route that broke
    # meanwhile would be found by the first deep drain that needs it and
    # not by the ladder while the host sweep can still carry the load, and
    # no run would hold the device tick's cost beside the host sweep's.
    # So the device route of a tick is never left alone for longer than
    # this, on the node's clock (simulated in a sim: replays stay exact).
    # 3 s lies under the 4 s slice the served benchmark cells trace, which
    # has to hold a device operation (PERF.md §7); nothing else sizes it
    TICK_AUDIT_MICROS = 3_000_000

    def _node_micros(self) -> Optional[int]:
        now = getattr(getattr(self.store, "node", None), "now_micros", None)
        return None if now is None else now()

    def _can_audit(self) -> bool:
        """Never under a pin or the ladder's hold, nor on a store without
        a clock."""
        return self.route_override is None and not self.host_pinned \
            and self._dev_quar_flushes <= 0 \
            and self._node_micros() is not None

    def _audit_tick(self, asked: bool) -> bool:
        """Asked by _tick of a tick the router priced to the host: True,
        and counted, when this store's last device tick is
        ``TICK_AUDIT_MICROS`` old or it never had one (so a store meets the
        tick's program in its first tick, where a served node starts, and
        not seconds into its traffic), or when the serving node's timer
        ``asked`` (audit_route), so this tick is the store's audit of the
        device route: the whole tick on the device, solo, with the ladder
        under it as under any device tick."""
        if not self._can_audit():
            return False
        if not asked and self._tick_dev_micros is not None \
                and self._node_micros() - self._tick_dev_micros \
                < self.TICK_AUDIT_MICROS:
            return False
        self.n_audit_ticks += 1
        return True

    def audit_route(self) -> None:
        """A serving node's timer (NodeServer.start: at start, then every
        ``TICK_AUDIT_MICROS``): this store's next tick is its audit of the
        device route, whether or not it finds a row to drive.  The audit
        otherwise rides the ticks that traffic schedules, and traffic whose
        txns do not wait on each other (range scans over rare inserts)
        schedules almost none: such a store would load the tick's program
        on the serving loop whenever its first tick came, and the ladder
        would not hear of a device that died until the first deep drain
        needed it.  A tick that drives nothing sweeps the empty frontier:
        launch, download and the ladder are what it exercises."""
        self._audit_asked = True
        self.schedule_tick()

    # Coalescing quantum for drain ticks (simulated/real micros): many dep
    # transitions land per tick, so the per-tick adjacency upload + kernel
    # sweep amortizes across a whole antichain instead of firing per event.
    TICK_DELAY_MICROS = 2_000

    def schedule_tick(self) -> None:
        if self._tick_scheduled:
            return
        self._tick_scheduled = True
        disp = getattr(self.store.node, "dispatcher", None)
        if disp is not None:
            # node-level coalescing (r08): ticks landing in the same
            # window share one dispatcher event — and, when the cost model
            # says it pays, one fused frontier launch
            disp.register_tick(self)
            return
        from .command_store import PreLoadContext

        def run():
            self.store.execute(PreLoadContext.empty(), self._tick)

        self.store.node.scheduler.once(self.TICK_DELAY_MICROS, run)

    def _tick(self, safe, fused=None) -> None:
        from . import commands
        self._tick_scheduled = False
        self.n_ticks += 1
        sweep_due = self.n_ticks % 8 == 0
        asked, self._audit_asked = self._audit_asked, False
        if not self.drain.active.any() \
                and not (asked and self._can_audit()):
            if sweep_due:
                self.drain.sweep_free()
            return
        # the drain is a device boundary too: while quarantined/degraded
        # the frontier sweeps on host, and a device failure mid-tick
        # quarantines + falls back to the host sweep (same rule, same
        # candidates — the per-candidate WaitingOn re-validation below
        # makes any residual divergence a no-op, never a wrong execution).
        # A fused sweep (dispatcher-precomputed, shared with sibling
        # stores) serves the same candidates; a device failure harvesting
        # it quarantines the WHOLE fused batch, and every member's sweep
        # fails over to host.
        cand_slots = None
        used_fused = False
        mode = None
        if not (self.host_pinned or self._dev_quar_flushes > 0):
            if fused is not None and fused.serves(self):
                try:
                    cand_slots = fused.result_for(self)
                    self.n_fused_ticks += 1
                    used_fused = True
                    mode = "fused"
                    self._tick_dev_micros = self._node_micros()
                except faults.DEVICE_EXCEPTIONS as e:
                    fused.poison(e)
            elif self._drain_wavefront <= 1 and self._host_tick_pays() \
                    and not self._audit_tick(asked):
                # priced to the host: nothing is uploaded or launched.  A
                # choice of the router and no fault, so it counts in no
                # ladder counter and not in n_host_ticks.  A widened
                # wavefront (mid-cascade) is not re-priced: one antichain is
                # what the host sweep finds, the level kernel finds W
                with self._span("drain_tick_host"):
                    cand_slots = self.drain.host_ready_slots()
                self.n_priced_host_ticks += 1
                mode = "host-priced"
            else:
                self._tick_dev_micros = self._node_micros()
                try:
                    # drain forensics: the sweep is split at the async-
                    # dispatch boundary, upload + enqueue against the
                    # result join, so a drain-bound regime shows WHERE
                    # the tick pays
                    with self._span("drain_tick_dispatch"):
                        dk.launch_check("drain")
                        state, live = self.drain.state()
                        faults.check("transfer", "drain download")
                        wave = self._drain_wavefront
                        fut = None
                        if wave > 1 and drk.drain_logdepth_enabled():
                            # widened sweep: the log-depth level pass
                            # prices one launch for the next `wave`
                            # executeAt antichains.  Candidates beyond the
                            # true frontier are safe — the per-candidate
                            # host re-validation below makes a not-
                            # actually-ready candidate a no-op — and any
                            # that fail to execute reset the wavefront
                            try:
                                if isinstance(state, drk.EllDrainState):
                                    mode = "ell-wave"
                                    lv, _r = drk.level_assign_ell(state)
                                else:
                                    mode = "wave"
                                    lv, _r = drk.level_assign_dense(state)
                                fut = (lv >= 1) & (lv <= wave)
                                self.n_wavefront_ticks += 1
                            except faults.DEVICE_EXCEPTIONS:
                                # fail the widened launch over to the plain
                                # frontier route, byte-identically (the W=1
                                # candidate set); leave the outer handler to
                                # the frontier's own faults
                                self._drain_wavefront = wave = 1
                                mode = None
                                fut = None
                        if wave > 1 and fut is not None:
                            pass
                        elif isinstance(state, drk.EllDrainState):
                            # large in-flight set: sparse gather sweep
                            # (no [N, N])
                            mode = "ell"
                            fut = drk.ready_frontier_ell(state)
                        elif self.mesh is not None and \
                                state.status.shape[0] % \
                                len(self.mesh.devices.flat) == 0 \
                                and self._mesh_tick_pays(
                                    state.status.shape[0]):
                            # live mesh path: the frontier sweep row-shards
                            # across devices (fixpoint analogue:
                            # parallel.sharded.sharded_drain)
                            from ..parallel.sharded import \
                                sharded_ready_frontier
                            mode = "mesh"
                            fut = sharded_ready_frontier(self.mesh)(state)
                        else:
                            mode = "device"
                            fut = drk.ready_frontier(state)
                    with self._span("drain_tick_wait"):
                        ready = np.asarray(fut)[: len(live)]
                    cand_slots = live[ready & self.drain.active[live]]
                except faults.DEVICE_EXCEPTIONS as e:
                    self._device_fault(e, f"drain tick: {e}")
        if cand_slots is None:
            self.n_host_ticks += 1
            cand_slots = self.drain.host_ready_slots()
            mode = "host"
        obs = getattr(getattr(self.store, "node", None),
                      "drain_observer", None)
        if obs is not None:
            obs(self.store, mode, int(len(cand_slots)))
        if len(cand_slots) != 0:
            cands = sorted(
                (self.drain.id_of[int(s)] for s in cand_slots
                 if int(s) in self.drain.id_of),
                key=_exec_order_key(safe))
            for txn_id in cands:
                commands.refresh_waiting_and_maybe_execute(safe, txn_id)
        # adaptive wavefront control (r19): widen only in the synchronous-
        # cascade regime — every candidate this tick reached Applied before
        # the tick returned (a serial chain drains in O(log depth) ticks
        # instead of one tick per link).  A tick the router swept on the
        # host widens like the device sweep it stands for, or a store whose
        # one-antichain tick prices to the host could never start the
        # cascade and would drain a chain one link a tick; the widened tick
        # then runs the level kernel.  Anything else (async execution, the
        # ladder's host fallback, fused/mesh route, empty sweep, escape
        # hatch) pins W back to 1, so protocol-flow ticks run the exact
        # pre-r19 frontier sweep.
        if mode in ("device", "ell", "wave", "ell-wave", "host-priced") \
                and len(cand_slots) != 0 and drk.drain_logdepth_enabled() \
                and all(int(self.drain.status[int(s)]) == dk.SLOT_APPLIED
                        for s in cand_slots):
            self._drain_wavefront = min(self._drain_wavefront * 2, 8192)
        else:
            self._drain_wavefront = 1
        if sweep_due:
            self.drain.sweep_free()
        if used_fused and self.drain.version != fused.version_for(self) \
                and self.drain.active.any():
            # the fused sweep was computed at dispatch time; mutations that
            # landed between dispatch and this harvest (earlier tasks in
            # this store's queue) could otherwise be a lost wakeup —
            # re-evaluate with a fresh tick
            self.schedule_tick()


def _exec_order_key(safe):
    def key(txn_id: TxnId):
        cmd = safe.if_present(txn_id)
        exec_at = cmd.execute_at if cmd is not None and cmd.execute_at \
            is not None else txn_id
        return (exec_at, txn_id)
    return key


def _drain_status_of(cmd) -> Tuple[int, Optional[Timestamp]]:
    from .status import Status
    if cmd is None:
        return dk.SLOT_TRANSITIVE, None
    if cmd.is_invalidated():
        return dk.SLOT_INVALIDATED, None
    if cmd.is_truncated():
        # truncated == locally done; never gates execution
        return dk.SLOT_INVALIDATED, None
    exec_at = cmd.execute_at_if_known()
    if cmd.has_been(Status.Applied):
        return dk.SLOT_APPLIED, exec_at
    if cmd.has_been(Status.Stable):
        return dk.SLOT_STABLE, exec_at
    if cmd.has_been(Status.Committed):
        return dk.SLOT_COMMITTED, exec_at
    if cmd.has_been(Status.Accepted):
        return dk.SLOT_ACCEPTED, exec_at
    return dk.SLOT_PREACCEPTED, None
