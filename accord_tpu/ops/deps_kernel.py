"""Batched PreAccept dependency calculation — the #1 hot loop, on device.

Rebuild of ref: accord-core/src/main/java/accord/local/CommandsForKey.java:614-650
(mapReduceActive) + messages/PreAccept.java:245-265 (calculatePartialDeps) +
utils/CheckpointIntervalArray.java (range stabbing), redesigned as one fused
TPU kernel instead of a per-key tree scan.

Design (SURVEY.md §7 stage 3): a command store's conflict index is a
struct-of-arrays table of up to N in-flight transactions.  Every slot stores
the packed TxnId, its kind, per-key status, and up to M touched *intervals*
``[lo, hi]`` (inclusive; a point key token t is stored as [t, t]; a range
[s, e) as [s, e-1]).  Unifying keys and ranges as intervals lets ONE kernel
answer both the KeyDeps scan and the RangeDeps stabbing query — the
reference needs two structures (CommandsForKey + SearchableRangeList) for
the same job.

The kernel computes, for a batch of B queries (in-flight PreAccepts):

    dep[b, j] = slot j live
              & witness_mask[b] admits kind[j]            (Txn.Kind.witnesses)
              & txn_id[j] < started_before[b]             (deps = strictly earlier)
              & intervals overlap (any of MxM pairs)
              & txn_id[j] != self[b]
              & txn_id[j] >= prune floor                  (RedundantBefore)

plus the per-query max-conflict timestamp over ALL overlapping live slots
(the MaxConflicts floor used to propose executeAt, ref:
local/MaxConflicts.java:32).  Everything is elementwise compares + reduces
over a [B, N, M, M] broadcast — embarrassingly parallel, static shapes,
fuses to a handful of VPU loops under jit.  B and N are padded to lane
multiples by the host packer.

Exact-geometry CSR (r10): the batched flat kernels no longer answer with
coarse (query, slot) pairs the host re-filters — every entry that leaves
the device is an exact overlap TRIPLE, encoded as one sorted composite
integer key::

    code = slot * (M_t * Q) + dep_interval_col * Q + query_interval_col

where ``M_t`` is the table's interval width and ``Q`` the query's.  Codes
ascend (slot-major, then dep column, then query column) within each CSR
row, which is exactly the (pair, m, q) order the host's old
``np.nonzero(overlap)`` geometry pass produced (tests/deps_oracle.py
keeps that pass as the reference) — so the device answer plugs straight
into the attribution stage.  The result ships as TWO buffers, ``(header,
entries)``: the header (scalars, then row_end[B]) is a few hundred
int32s the host fetches first; only the LIVE PREFIX of the entry buffer
crosses the wire after it (int32 entries whenever
``capacity * M_t * Q <= INT32_CODE_MAX``, int64 past that crossover).
The store's flush launches only the ATTRIBUTED variants (the r15 section
below: ``calculate_deps_flat_attr``, ``bucketed_attr_jit``,
``fused_flat_attr`` and their mesh twins in parallel.sharded), which run
the raw phases here (``flat_csr_local``, ``bucketed_flat``) and then the
attribution stage over the compacted entries.
"""

from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..primitives.timestamp import Kinds, Timestamp, TxnId
from .packing import (ensure_x64, masked_ts_max, to_i64, ts_eq, ts_lt,
                      unpack_txn_id)

def launch_check(what: str = "") -> None:
    """Device-boundary fault hook for every (un-jitted) kernel dispatch
    wrapper: raises utils.faults.KernelLaunchFault when a kernel-launch
    fault is armed.  Lives here — next to the kernels — so the injection
    surface and the thing it simulates stay in one place; a production
    process with nothing armed pays one dict miss."""
    from ..utils import faults
    faults.check("kernel_launch", what)


PAD_LO = np.int64(np.iinfo(np.int64).max)   # empty interval: lo > hi
PAD_HI = np.int64(np.iinfo(np.int64).min)

# widest triple code an int32 entry buffer can carry; codes are
# slot * M_t * Q + col * Q + q, so the crossover is capacity * M_t * Q.
# Module attribute (not inlined) so the int64 crossover is testable on
# tables that fit in memory — tests lower it and assert both widths agree.
INT32_CODE_MAX = 2**31 - 1


def wide_codes(capacity: int, m_t: int, q_m: int) -> bool:
    """True when triple codes for this (table, query) shape need int64
    entries.  Callers thread the result into the kernels as a STATIC
    argument (the dtype is part of the traced program, and the jit cache
    key must see it)."""
    return capacity * m_t * q_m > INT32_CODE_MAX


def _code_dtype(wide: bool):
    return jnp.int64 if wide else jnp.int32


def _code_sentinel(wide: bool):
    return (np.int64(np.iinfo(np.int64).max) if wide
            else np.int32(np.iinfo(np.int32).max))

# slot liveness/status codes (device view of CommandsForKey.InternalStatus)
SLOT_FREE = -1
SLOT_TRANSITIVE = 0
SLOT_PREACCEPTED = 1
SLOT_ACCEPTED = 2
SLOT_COMMITTED = 3
SLOT_STABLE = 4
SLOT_APPLIED = 5
SLOT_INVALIDATED = 6


class DepsTable(NamedTuple):
    """SoA conflict index: N slots x M intervals.  A pytree of device arrays;
    the device-format equivalent of one store's CommandsForKey map."""

    msb: jnp.ndarray        # int64[N]  packed TxnId
    lsb: jnp.ndarray        # int64[N]
    node: jnp.ndarray       # int32[N]
    kind: jnp.ndarray       # int32[N]  TxnKind ordinal
    status: jnp.ndarray     # int32[N]  SLOT_* (FREE/INVALIDATED excluded from deps)
    lo: jnp.ndarray         # int64[N, M]  inclusive interval starts (PAD_LO if unused)
    hi: jnp.ndarray         # int64[N, M]  inclusive interval ends   (PAD_HI if unused)

    @property
    def capacity(self) -> int:
        return self.msb.shape[0]


class DepsQuery(NamedTuple):
    """Batch of B dependency queries (one per PreAccept-ing txn)."""

    msb: jnp.ndarray          # int64[B]  started-before bound (usually the TxnId)
    lsb: jnp.ndarray          # int64[B]
    node: jnp.ndarray         # int32[B]
    witness_mask: jnp.ndarray  # int32[B]  bitmask over TxnKind ordinals
    lo: jnp.ndarray           # int64[B, M]
    hi: jnp.ndarray           # int64[B, M]
    self_msb: jnp.ndarray     # int64[B]  the querying TxnId itself — excluded
    self_lsb: jnp.ndarray     # int64[B]  from the dep set even when the bound
    self_node: jnp.ndarray    # int32[B]  exceeds it (Accept-phase executeAt)


def empty_table(capacity: int, max_intervals: int) -> DepsTable:
    ensure_x64()
    return DepsTable(
        msb=jnp.zeros(capacity, jnp.int64),
        lsb=jnp.zeros(capacity, jnp.int64),
        node=jnp.zeros(capacity, jnp.int32),
        kind=jnp.zeros(capacity, jnp.int32),
        status=jnp.full(capacity, SLOT_FREE, jnp.int32),
        lo=jnp.full((capacity, max_intervals), PAD_LO, jnp.int64),
        hi=jnp.full((capacity, max_intervals), PAD_HI, jnp.int64),
    )


@jax.jit
def scatter_table_rows(table: DepsTable, idx, msb, lsb, node, kind, status,
                       lo, hi) -> DepsTable:
    """One fused dirty-row update for all seven table arrays (a single jit
    dispatch instead of seven eager scatters — the update-in-place path
    that keeps the table device-resident between queries).  Placement
    follows the committed ``table`` arrays, so the r21 store-shard path
    runs the same program once per slice device."""
    return DepsTable(
        table.msb.at[idx].set(msb),
        table.lsb.at[idx].set(lsb),
        table.node.at[idx].set(node),
        table.kind.at[idx].set(kind),
        table.status.at[idx].set(status),
        table.lo.at[idx].set(lo),
        table.hi.at[idx].set(hi))


def _dep_mask_and_conflict(table: DepsTable, query: DepsQuery,
                           prune_msb=None, prune_lsb=None, prune_node=None):
    """Traceable core shared by calculate_deps (mask + max_conflict) and
    the flat-CSR path (mask only; XLA dead-code-eliminates the unused
    conflict reduce there).  ``prune_* = None`` means no floor."""
    if prune_msb is None:
        prune_msb = jnp.zeros((), jnp.int64)
        prune_lsb = jnp.zeros((), jnp.int64)
        prune_node = jnp.zeros((), jnp.int32)
    live = table.status >= SLOT_TRANSITIVE                     # [N]
    not_invalidated = table.status != SLOT_INVALIDATED         # [N]

    # interval overlap: any (query interval m) x (slot interval m') pair
    # q.lo[b,m] <= t.hi[j,m'] and t.lo[j,m'] <= q.hi[b,m]
    qlo = query.lo[:, None, :, None]                           # [B,1,M,1]
    qhi = query.hi[:, None, :, None]
    tlo = table.lo[None, :, None, :]                           # [1,N,1,M]
    thi = table.hi[None, :, None, :]
    overlap = jnp.any((qlo <= thi) & (tlo <= qhi), axis=(2, 3))  # [B,N]

    conflict = overlap & (live & not_invalidated)[None, :]

    # witness predicate: does this query's kind witness slot j's kind?
    witnessed = (query.witness_mask[:, None] >> table.kind[None, :]) & 1 > 0

    # strictly-earlier TxnId than the started-before bound
    earlier = ts_lt(table.msb[None, :], table.lsb[None, :], table.node[None, :],
                    query.msb[:, None], query.lsb[:, None], query.node[:, None])

    # never depend on yourself: the Accept-phase bound is executeAt, which
    # exceeds the txn's own id, so the strict compare alone is not enough
    not_self = ~ts_eq(table.msb[None, :], table.lsb[None, :], table.node[None, :],
                      query.self_msb[:, None], query.self_lsb[:, None],
                      query.self_node[:, None])

    # prune floor: exclude ids below the RedundantBefore watermark
    above_floor = ~ts_lt(table.msb, table.lsb, table.node,
                         prune_msb, prune_lsb, prune_node)

    dep_mask = conflict & witnessed & earlier & not_self & above_floor[None, :]
    return dep_mask, conflict


@jax.jit
def calculate_deps(table: DepsTable, query: DepsQuery,
                   prune_msb: jnp.ndarray = None, prune_lsb: jnp.ndarray = None,
                   prune_node: jnp.ndarray = None
                   ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """Returns (dep_mask bool[B, N], max_conflict (msb, lsb, node)[B]).

    max_conflict covers every live overlapping slot regardless of TxnId order
    or kind — it is the executeAt floor, not the dep set.
    """
    dep_mask, conflict = _dep_mask_and_conflict(table, query, prune_msb,
                                                prune_lsb, prune_node)
    # [1, N] inputs broadcast against the [B, N] mask inside masked_ts_max
    max_conflict = masked_ts_max(table.msb[None, :], table.lsb[None, :],
                                 table.node[None, :], conflict)
    return dep_mask, max_conflict


from functools import partial


def _compact_topk(dep_mask: jnp.ndarray, k: int):
    """Mask -> (idx int32[B, k] ascending slot indices padded with -1,
    counts int32[B]) — the compaction shared by every indices path.

    One implementation on every backend: sort the set-bit columns
    ascending (unset -> n sorts last) and keep the first k — the same
    program in the tests, the AOT compiles and on the chip (the name
    predates the removal of the TPU-only lax.top_k branch)."""
    n = dep_mask.shape[1]
    col = jnp.arange(n, dtype=jnp.int32)
    counts = jnp.sum(dep_mask, axis=1, dtype=jnp.int32)
    cols = jnp.where(dep_mask, col, jnp.int32(n))
    cols = jax.lax.slice_in_dim(jnp.sort(cols, axis=1), 0, min(k, n), axis=1)
    idx = jnp.where(cols < n, cols, -1)
    return idx, counts


# NOT a route: no flush launches this program.  It is the route
# calibration's (DeviceState._measure_route_calibration): the probe times it
# for ``c_dev`` and, against calculate_deps_flat_attr, for ``c_attr`` — the
# prices that place every route crossover.
@partial(jax.jit, static_argnames=("m", "s", "k", "wide"))
def calculate_deps_flat(table: DepsTable, qmat: jnp.ndarray,
                        m: int, s: int, k: int, wide: bool = False):
    """The download-optimal batched scan: the EXACT dep-triple set compacted
    into a packed CSR on device, so the download is the sparse result alone
    — and a two-stage one: ``(header, entries)``, where the host fetches
    the tiny header first and then only the live entry prefix.

    On a high-round-trip host-device link the wire dominates: the dense
    [B, 1+k] compaction ships megabytes while the true dep sets are tens of
    entries per query.  Entries are the sorted composite
    overlap codes (module docstring) — no false-positive pair and no
    host-side geometry pass remain.
    """
    return flat_csr_local(table, qmat, m, s, k, wide=wide)


def query_from_qmat(qmat: jnp.ndarray, m: int) -> DepsQuery:
    return DepsQuery(
        qmat[:, 0], qmat[:, 1], qmat[:, 2].astype(jnp.int32),
        qmat[:, 3].astype(jnp.int32),
        qmat[:, 7:7 + m], qmat[:, 7 + m:7 + 2 * m],
        qmat[:, 4], qmat[:, 5], qmat[:, 6].astype(jnp.int32))


def _compact_rows(valid: jnp.ndarray, codes: jnp.ndarray, s: int, k: int):
    """Shared row compaction: pack each row's valid ``codes`` (already in
    their final per-row order) into the first ``counts[b]`` cells of a flat
    entry buffer.  Returns (counts int32[B], row_end int32[B], ent[s]).

    The pack is a POSITION sort (ascending column index of valid cells,
    invalid -> C sorts last) followed by a B*k scatter — scattering all B*C
    candidate positions directly is pathologically slow on TPU.  Every
    backend compacts through this one sort (no backend test at trace
    time), so the program the chip runs is the one the tests run."""
    b, c = codes.shape
    counts = jnp.sum(valid, axis=1, dtype=jnp.int32)
    row_end = jnp.cumsum(counts)
    starts = row_end - counts
    k = min(k, c)
    col = jnp.arange(c, dtype=jnp.int32)
    cols = jnp.where(valid, col, jnp.int32(c))
    cols = jax.lax.slice_in_dim(jnp.sort(cols, axis=1), 0, k, axis=1)
    vals = jnp.take_along_axis(codes, jnp.minimum(cols, c - 1), axis=1)
    ok = cols < c
    pos = starts[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    pos = jnp.where(ok & (pos < s), pos, s)                    # s = dropped
    ent = jnp.full(s + 1, -1, codes.dtype).at[pos.reshape(-1)] \
        .set(vals.reshape(-1), mode="drop")[:s]
    return counts, row_end, ent


def _entry_rows(row_end: jnp.ndarray, s: int) -> jnp.ndarray:
    """The query row of each cell of a compacted entry buffer, DERIVED from
    the rows' ends (``row_end`` [B], non-decreasing: the prefix sum of the
    rows' counts) instead of searched for.  Returns int32[s].

    Identity: ``searchsorted(row_end, p, side="right")`` is the number of
    rows whose end is <= p, so one mark per row at its end (an empty row
    shares its end with its predecessor: the marks add up, which is what
    ``side="right"`` means) and a prefix sum over the cells give every
    cell's row at once — a B-point scatter-add and one int32 scan, where
    the search is a log2(B)-deep loop of s-wide gathers out of an
    emulated-int64 table (28 ms against 0.2 ms on a v5e at B = 2048,
    s = 163,840: PERF.md, PR 34).  Ends at or beyond the buffer's (an
    exactly full or overflowed budget) mark no cell."""
    marks = jnp.zeros(s, jnp.int32).at[row_end.astype(jnp.int32)].add(
        1, mode="drop")
    return jnp.cumsum(marks)


def _flat_phase1(table: DepsTable, qmat: jnp.ndarray, m: int, k: int,
                 prune=None):
    """Shared phase 1 of the dense flat kernels: exact mask -> per-row
    compacted slot indices -> overlap-triple expansion.  Returns
    (query, idx, pair_counts, sel, tlo, valid[B,kp,M,Q])."""
    query = query_from_qmat(qmat, m)
    if prune is None:
        mask, _conflict = _dep_mask_and_conflict(table, query)
    else:
        mask, _conflict = _dep_mask_and_conflict(table, query, *prune)
    n = mask.shape[1]
    kp = min(k, n)
    idx, pair_counts = _compact_topk(mask, kp)                 # [B,kp],[B]
    sel = jnp.clip(idx, 0)
    tlo = table.lo[sel]                                        # [B,kp,M]
    thi = table.hi[sel]
    qlo = query.lo[:, None, None, :]                           # [B,1,1,Q]
    qhi = query.hi[:, None, None, :]
    ov = (qlo <= thi[:, :, :, None]) & (tlo[:, :, :, None] <= qhi)
    valid = ov & (idx >= 0)[:, :, None, None]                  # [B,kp,M,Q]
    return query, idx, pair_counts, sel, tlo, valid


def _triple_codes(sel, m_t: int, m: int, wide: bool):
    dt = _code_dtype(wide)
    mq = m_t * m
    return (sel.astype(dt)[:, :, None, None] * mq
            + jnp.arange(m_t, dtype=dt)[None, None, :, None] * m
            + jnp.arange(m, dtype=dt)[None, None, None, :])


def flat_csr_local(table: DepsTable, qmat: jnp.ndarray,
                   m: int, s: int, k: int, prune=None, wide: bool = False):
    """The traceable body of calculate_deps_flat: exact mask over THIS
    table (a full table, or one mesh shard's slice under shard_map), then
    the EXACT overlap-triple expansion compacted into a two-buffer CSR —
    (header (total, maxc, row_end[B]) int32, entries[s] composite codes).

    Two phases keep it memory-safe: (1) the per-row slot indices compact
    through the mask exactly as before (no [B, N, M, Q] expansion of the
    full table); (2) only the <= k selected slots' interval rows are
    gathered (row gathers — effectively free on TPU) and expanded against
    the query intervals into sorted codes.  ``k`` caps the widest TRIPLE
    row, ``s`` the batch triple total; both sticky-learned by the caller
    from the header.  Overflow stays detectable: the reported maxc is the
    exact per-row triple count when every pair fit phase 1, and at least
    the (truncated-past-k) pair count otherwise — either way overflow
    reads as ``maxc > k`` and the caller re-runs escalated."""
    _query, idx, pair_counts, sel, _tlo, valid = \
        _flat_phase1(table, qmat, m, k, prune)
    m_t = table.lo.shape[1]
    codes = _triple_codes(sel, m_t, m, wide)
    b = valid.shape[0]
    valid_f = valid.reshape(b, -1)
    codes_f = codes.reshape(b, -1)   # ascending: slot-major, then col, q
    counts, row_end, ent = _compact_rows(valid_f, codes_f, s, k)
    maxc = jnp.maximum(jnp.max(counts), jnp.max(pair_counts))
    header = jnp.concatenate(
        [jnp.stack([row_end[-1], maxc]).astype(jnp.int32),
         row_end.astype(jnp.int32)])
    return header, ent


# -- bucketed index kernel ----------------------------------------------------
#
# The CINTIA-style device index (ref: utils/CheckpointIntervalArray.java:40-60,
# CheckpointIntervalArrayBuilder.java — the reference's checkpointed interval
# stabbing structure), redesigned for static shapes: the token space is cut
# into width-2^shift buckets; every NARROW slot interval is registered as an
# (lo, hi, slot) entry in each bucket it touches; intervals spanning many
# buckets — and bucket-overflow spill — live in a separate WIDE list that
# every query always checks (the reference's straggler/checkpoint split).
# A query probes only the K entries of the <= SPAN buckets each of its
# intervals touches, so the scan is O(candidates), not O(N): the exact
# predicate (overlap, earlier-TxnId, witness, liveness) runs per candidate,
# duplicates (one slot reachable via several buckets/intervals) are removed
# by an in-row sort, and the surviving slot ids compact into the same packed
# CSR the dense kernel ships.


class BucketTable(NamedTuple):
    """Device half of the bucket index: G buckets x K interval entries plus
    the wide/straggler entries (-1 slot = empty).

    Every IMMUTABLE per-slot column the predicate needs (packed TxnId,
    kind) is embedded in the entry: TPU gathers of scalar columns at
    arbitrary candidate indices lower to slow per-element loops (~140ms
    per gathered column at B=2048, C=4k over the VPU), while row gathers
    of whole bucket lines are effectively free.  Liveness needs no status
    column: entries are de-indexed on invalidate/free, so candidates are
    live by construction (the exact status/floor semantics are re-applied
    by the attribution pass either way).  ``bcol``/``wcol`` record each
    entry's interval COLUMN in its owning slot row — the third leg of the
    exact overlap triple the kernel emits (module docstring), so the host
    never rebuilds the geometry."""

    blo: jnp.ndarray     # int64[G, K] entry interval starts (PAD_LO empty)
    bhi: jnp.ndarray     # int64[G, K]
    bslot: jnp.ndarray   # int32[G, K] owning slot (-1 empty)
    bcol: jnp.ndarray    # int32[G, K] entry's interval column in its slot
    bmsb: jnp.ndarray    # int64[G, K] owning TxnId packed
    blsb: jnp.ndarray    # int64[G, K]
    bnode: jnp.ndarray   # int32[G, K]
    bkind: jnp.ndarray   # int32[G, K]
    wlo: jnp.ndarray     # int64[W] wide/straggler entries
    whi: jnp.ndarray     # int64[W]
    wslot: jnp.ndarray   # int32[W]
    wcol: jnp.ndarray    # int32[W]
    wmsb: jnp.ndarray    # int64[W]
    wlsb: jnp.ndarray    # int64[W]
    wnode: jnp.ndarray   # int32[W]
    wkind: jnp.ndarray   # int32[W]


def _entry_pred(query: DepsQuery, ov, slot, emsb, elsb, enode, ekind,
                extra_dims: int):
    """Exact per-entry predicate on embedded entry columns; ``extra_dims``
    broadcasts the per-query scalars over the candidate axes."""
    idx = (slice(None),) + (None,) * extra_dims
    valid = slot >= 0
    witnessed = (query.witness_mask[idx] >> ekind) & 1 > 0
    earlier = ts_lt(emsb, elsb, enode,
                    query.msb[idx], query.lsb[idx], query.node[idx])
    not_self = ~ts_eq(emsb, elsb, enode, query.self_msb[idx],
                      query.self_lsb[idx], query.self_node[idx])
    return valid & ov & witnessed & earlier & not_self


def bucketed_flat(table: DepsTable, buckets: BucketTable, qmat: jnp.ndarray,
                  m: int, span: int, s: int, k: int, prune=None,
                  row_offset=None, keff: int = None, wide: bool = False,
                  m_t: int = None):
    """Bucket-indexed batched deps scan -> two-buffer exact CSR
    (header(total, maxc, row_end[B]) int32, entries[s] composite overlap
    codes) — same layout as flat_csr_local, d=1.

    ``qmat`` carries the standard query columns plus m*span bucket-row
    columns (int64, -1 = no bucket) appended by the host packer.  ``table``
    is unused on the device (kept in the signature so dispatch snapshots
    stay uniform across kernels; may be None) except for its interval
    width, which scales the codes; all predicate data rides in ``buckets``.
    ``row_offset`` translates GLOBAL bucket rows to this shard's local rows
    under a row-sharded BucketTable (shard_map passes ``axis_index *
    local_rows``): rows outside the local slice become -1 (no bucket here)
    — the union over shards covers every global row.  ``keff`` slices the
    bucket entry axis to the mirror's live high-water occupancy (static, so
    XLA slices the operand before the gather): the [G, BUCKET_K] rows are
    mostly padding on spread keyspaces, and at the measured 18-entry
    high-water this cuts the candidate matrix — and the kernel wall — ~4x."""
    query = query_from_qmat(qmat, m)
    b = qmat.shape[0]
    if m_t is None:
        m_t = table.lo.shape[1]      # mesh locals pass m_t (table is None)
    mq = m_t * m
    dt = _code_dtype(wide)
    sent = _code_sentinel(wide)
    if keff is None:
        keff = buckets.blo.shape[1]
    keff = min(keff, buckets.blo.shape[1])
    blo, bhi = buckets.blo[:, :keff], buckets.bhi[:, :keff]
    bslot, bcol = buckets.bslot[:, :keff], buckets.bcol[:, :keff]
    bmsb, blsb = buckets.bmsb[:, :keff], buckets.blsb[:, :keff]
    bnode, bkind = buckets.bnode[:, :keff], buckets.bkind[:, :keff]
    qbuck = qmat[:, 7 + 2 * m:].astype(jnp.int32)          # [B, m*span]
    if row_offset is not None:
        n_local = blo.shape[0]
        local = qbuck - row_offset
        qbuck = jnp.where((qbuck >= 0) & (local >= 0) & (local < n_local),
                          local, -1)
    g = jnp.clip(qbuck, 0)
    has = qbuck >= 0                                        # [B, m*span]
    # bucket candidates: every entry of every touched bucket, each checked
    # against the query interval that touched the bucket (row gathers only)
    elo = blo[g]                                            # [B, m*span, K]
    ehi = bhi[g]
    qlo = jnp.repeat(query.lo, span, axis=1)[:, :, None]    # [B, m*span, 1]
    qhi = jnp.repeat(query.hi, span, axis=1)[:, :, None]
    ov = (elo <= qhi) & (qlo <= ehi) & has[:, :, None]      # [B, m*span, K]
    pred_b = _entry_pred(query, ov, bslot[g], bmsb[g],
                         blsb[g], bnode[g], bkind[g], 2)
    # the exact overlap triple is inherent in each candidate: the entry IS
    # one (slot, interval-column) and the probe axis IS the query interval
    q_of = jnp.repeat(jnp.arange(m, dtype=dt), span)[None, :, None]
    cand = (bslot[g].astype(dt) * mq + bcol[g].astype(dt) * m
            + q_of).reshape(b, -1)
    pred_b = pred_b.reshape(b, -1)
    # wide/straggler candidates: each entry crossed with every query
    # interval (the old any-reduce collapsed the triple; exact emission
    # keeps the [B, Q, W] cross — W is straggler-bounded by construction)
    w = buckets.wlo.shape[0]
    ov_w = ((buckets.wlo[None, None, :] <= query.hi[:, :, None])
            & (query.lo[:, :, None] <= buckets.whi[None, None, :]))
    pred_w = _entry_pred(query, ov_w, buckets.wslot[None, None, :],
                         buckets.wmsb[None, None, :],
                         buckets.wlsb[None, None, :],
                         buckets.wnode[None, None, :],
                         buckets.wkind[None, None, :], 2)   # [B, Q, W]
    cand_w = (buckets.wslot[None, None, :].astype(dt) * mq
              + buckets.wcol[None, None, :].astype(dt) * m
              + jnp.arange(m, dtype=dt)[None, :, None])
    cand = jnp.concatenate(
        [cand, jnp.broadcast_to(cand_w, (b, m, w)).reshape(b, -1)], axis=1)
    pred = jnp.concatenate([pred_b, pred_w.reshape(b, -1)], axis=1)
    if prune is not None:
        pmsb, plsb, pnode = prune
        above_b = ~ts_lt(bmsb[g], blsb[g], bnode[g],
                         pmsb, plsb, pnode).reshape(b, -1)
        above_w = ~ts_lt(buckets.wmsb[None, None, :],
                         buckets.wlsb[None, None, :],
                         buckets.wnode[None, None, :], pmsb, plsb, pnode)
        pred = pred & jnp.concatenate(
            [above_b,
             jnp.broadcast_to(above_w, (b, m, w)).reshape(b, -1)], axis=1)
    # dedupe (a triple is reachable via several buckets): sort the
    # surviving codes per row — which ALSO establishes the canonical
    # (slot, dep-col, query-col) ascending emit order — then mark adjacent
    # repeats; rejected candidates carry the sentinel and sort last
    hit = jnp.where(pred, cand, sent)
    hit = jnp.sort(hit, axis=1)
    uniq = (hit != sent) & jnp.concatenate(
        [jnp.ones((b, 1), bool), hit[:, 1:] != hit[:, :-1]], axis=1)
    counts, row_end, ent = _compact_rows(uniq, hit, s, k)
    header = jnp.concatenate(
        [jnp.stack([row_end[-1], jnp.max(counts)]).astype(jnp.int32),
         row_end.astype(jnp.int32)])
    return header, ent


def decode_triples(codes: np.ndarray, m_t: int, q_m: int):
    """Host decode of composite overlap codes -> (slot, dep_col, q_col)
    int64 triples (the inverse of the kernel-side encoding)."""
    codes = codes.astype(np.int64)
    mq = np.int64(m_t * q_m)
    j = codes // mq
    rem = codes - j * mq
    m_i = rem // q_m
    return j, m_i, rem - m_i * q_m


# -- fused (batched-over-stores) dispatch ------------------------------------
#
# Launch coalescing (r08; the entry point is fused_flat_attr below): one
# device dispatch answers the deps flushes of SEVERAL CommandStores that
# became runnable in the same event-loop step.  Each store's table is padded
# (free slots / PAD intervals prune themselves out of the mask, so padding
# never changes a store's answer) to the group maximum and stacked on a
# leading store axis; the per-store scan is the solo trace vmapped over that
# axis — integer compares/sorts/cumsums vmap losslessly, so every store's
# CSR block is bit-identical to the solo launch it replaces.  The per-store
# prune floors ride as [S] triples (zeros = prune nothing, the ts_lt
# convention).


def _pad_table_cols(cols, n, m):
    """Pad one store's seven table columns to (n, m): appended slots are
    FREE and appended interval columns are PAD (lo > hi) — structurally
    excluded from the dep mask, so the padded scan answers exactly what the
    unpadded one does."""
    msb, lsb, node, kind, status, lo, hi = cols
    dn = n - msb.shape[0]
    dm = m - lo.shape[1]
    pad1 = lambda a, fill: jnp.pad(a, (0, dn), constant_values=fill)  # noqa: E731
    pad2 = lambda a, fill: jnp.pad(a, ((0, dn), (0, dm)),             # noqa: E731
                                   constant_values=fill)
    return (pad1(msb, 0), pad1(lsb, 0), pad1(node, 0), pad1(kind, 0),
            pad1(status, SLOT_FREE), pad2(lo, PAD_LO), pad2(hi, PAD_HI))


def pack_query_matrix(queries: Sequence[tuple], max_intervals: int) -> np.ndarray:
    """Host packer for the flat/attributed kernels: one int64 matrix
    instead of nine arrays (single device upload).  queries as in
    build_query."""
    b = len(queries)
    m = max_intervals
    q = np.empty((b, 7 + 2 * m), np.int64)
    q[:, 7:7 + m] = PAD_LO
    q[:, 7 + m:] = PAD_HI
    cols = ([], [], [], [], [], [], [])
    for i, item in enumerate(queries):
        (bound, witnesses, toks, rngs), self_id = \
            item[:4], (item[4] if len(item) > 4 else item[0])
        cols[0].append(to_i64(bound.msb))
        cols[1].append(to_i64(bound.lsb))
        cols[2].append(bound.node)
        cols[3].append(witnesses.mask())
        cols[4].append(to_i64(self_id.msb))
        cols[5].append(to_i64(self_id.lsb))
        cols[6].append(self_id.node)
        if len(toks) + len(rngs) > m:
            raise ValueError(f"txn touches > {m} intervals")
        j = 0
        for t in toks:
            q[i, 7 + j] = t
            q[i, 7 + m + j] = t
            j += 1
        for r in rngs:
            q[i, 7 + j] = r.start
            q[i, 7 + m + j] = r.end - 1
            j += 1
    for c in range(7):
        q[:, c] = cols[c]
    return q


# -- host bridge --------------------------------------------------------------

def _intervals_of(txn_keys, txn_ranges, max_intervals: int):
    """(tokens, ranges) -> padded [lo...], [hi...] rows."""
    lo = [PAD_LO] * max_intervals
    hi = [PAD_HI] * max_intervals
    i = 0
    for t in txn_keys:
        if i >= max_intervals:
            raise ValueError(f"txn touches > {max_intervals} intervals")
        lo[i], hi[i] = t, t
        i += 1
    for r in txn_ranges:
        if i >= max_intervals:
            raise ValueError(f"txn touches > {max_intervals} intervals")
        lo[i], hi[i] = r.start, r.end - 1
        i += 1
    return lo, hi


def build_table(entries: Sequence[Tuple[TxnId, int, list, list]],
                capacity: int, max_intervals: int) -> DepsTable:
    """Host packer: entries = [(txn_id, status, key_tokens, ranges)].

    Capacity is padded; callers should size it to a static bucket so jit
    caches one compilation per bucket.
    """
    ensure_x64()
    n = len(entries)
    if n > capacity:
        raise ValueError(f"{n} entries > capacity {capacity}")
    msb = np.zeros(capacity, np.int64)
    lsb = np.zeros(capacity, np.int64)
    node = np.zeros(capacity, np.int32)
    kind = np.zeros(capacity, np.int32)
    status = np.full(capacity, SLOT_FREE, np.int32)
    lo = np.full((capacity, max_intervals), PAD_LO, np.int64)
    hi = np.full((capacity, max_intervals), PAD_HI, np.int64)
    for i, (tid, st, toks, rngs) in enumerate(entries):
        msb[i] = to_i64(tid.msb)
        lsb[i] = to_i64(tid.lsb)
        node[i] = tid.node
        kind[i] = int(tid.kind())
        status[i] = st
        row_lo, row_hi = _intervals_of(toks, rngs, max_intervals)
        lo[i] = row_lo
        hi[i] = row_hi
    return DepsTable(jnp.asarray(msb), jnp.asarray(lsb), jnp.asarray(node),
                     jnp.asarray(kind), jnp.asarray(status),
                     jnp.asarray(lo), jnp.asarray(hi))


def build_query(queries: Sequence[tuple],
                max_intervals: int) -> DepsQuery:
    """queries = [(started_before, witnesses, key_tokens, ranges)] or
    [(started_before, witnesses, key_tokens, ranges, self_txn_id)].

    When self_txn_id is omitted it defaults to the bound itself (correct for
    PreAccept, where bound == own TxnId); pass it explicitly for Accept-phase
    queries whose bound is the proposed executeAt.  Packs through the same
    matrix encoder as the fused path (one upload, one source of truth for
    the column/interval layout) and slices the columns on device."""
    ensure_x64()
    m = max_intervals
    q = jnp.asarray(pack_query_matrix(queries, m))
    return DepsQuery(q[:, 0], q[:, 1], q[:, 2].astype(jnp.int32),
                     q[:, 3].astype(jnp.int32),
                     q[:, 7:7 + m], q[:, 7 + m:7 + 2 * m],
                     q[:, 4], q[:, 5], q[:, 6].astype(jnp.int32))


def extract_deps(table: DepsTable, dep_mask) -> List[List[TxnId]]:
    """dep_mask bool[B, N] -> per-query sorted TxnId lists (host)."""
    mask = np.asarray(dep_mask)
    msb, lsb, node = (np.asarray(table.msb), np.asarray(table.lsb),
                      np.asarray(table.node))
    out: List[List[TxnId]] = []
    for b in range(mask.shape[0]):
        idx = np.nonzero(mask[b])[0]
        out.append(sorted(unpack_txn_id(msb[j], lsb[j], node[j]) for j in idx))
    return out


# -- device-resident attribution + elision (r15) ------------------------------
#
# r10 moved the exact overlap geometry on-device; what remained host-side was
# the ATTRIBUTION pass: per-token RedundantBefore floors, CommandsForKey
# transitive elision, and the per-(query, token, dep) dedupe — ~6ms/batch of
# numpy on the r13 profile, the last big host tax on every route.  The
# attributed kernel variants below fold all three INTO the device program:
# an entry that a floor or the elision rule would drop never enters the CSR
# (and never crosses the wire), and duplicate (slot, interval) emits reached
# through several query columns collapse in-kernel.  The attribution runs
# POST-COMPACTION — over the thousands of surviving codes, not the
# candidate matrix — so the stage costs O(s), and STATIC leg switches
# (``floors``/``elide``) drop dead legs from the traced program entirely
# (an empty elision index or a trivially-covered floor map compiles to the
# raw kernel plus a dedupe).
#
# Inputs, all device-resident / replicated:
#  - AttrCols: per-slot columns the dep MASK never needed but attribution
#    does — domain (key deps emit at their own footprint points), a FRESH
#    status (live->live moves included; elision reads the
#    TRANSITIVE/COMMITTED grades), the packed dep id (the floor compare;
#    redundant with DepsTable but the mesh bucketed shards have no local
#    slot table), and the decided executeAt.
#  - AttrIndex: the per-store floor + elision index.  Floors are the packed
#    RedundantBefore segment map (searchsorted per emitted token — exactly
#    deps_floor_batch's rule).  Elision is a CSR over the store's elidable
#    tokens: per token the SORTED committed-write executeAt list, flattened,
#    with each exec replaced by its composite rank ``seg * estride + rank``
#    so ONE int64 searchsorted answers "how many committed writes on token
#    t execute before bound b".  The per-query bound ranks (``rankb``) are
#    computed host-side against the same index and ride in as a [B] array —
#    no 128-bit comparisons on device.
#
# Attributed header layout (int32[5 + B]):
#    [0] total entries   [1] overflow-vs-s watermark  [2] overflow-vs-k
#    [3] rows elided as TRANSITIVE   [4] rows elided below a decided pivot
#    [5:] row_end[B]
# The overflow watermarks are the RAW (pre-attribution) totals — the
# learned s/k budgets size the raw compaction — and stay per-shard maxima
# under the mesh merge, so the collect-side re-run check is uniform:
# hdr[1] > s or hdr[2] > k.


class AttrCols(NamedTuple):
    """Per-slot attribution columns (device-resident, scatter-updated in
    lockstep with the DepsTable by the mirror).  The packed dep id rides
    here TOO (redundant with DepsTable.msb/lsb/node): the post-compaction
    attribution stage gathers ids per surviving entry, and the
    mesh-sharded BUCKETED kernel has no local slot table to gather from —
    one column set serves every route."""

    dom: jnp.ndarray      # int32[N]  Domain ordinal (Key == 0)
    status: jnp.ndarray   # int32[N]  fresh SLOT_* (elision reads grades)
    dmsb: jnp.ndarray     # int64[N]  packed TxnId (floor compares)
    dlsb: jnp.ndarray     # int64[N]
    dnode: jnp.ndarray    # int32[N]
    emsb: jnp.ndarray     # int64[N]  decided executeAt (valid iff eknown)
    elsb: jnp.ndarray     # int64[N]
    enode: jnp.ndarray    # int32[N]
    eknown: jnp.ndarray   # bool[N]


@jax.jit
def scatter_attr_cols(attr: "AttrCols", idx, dom, status, dmsb, dlsb,
                      dnode, emsb, elsb, enode, eknown) -> "AttrCols":
    """One fused dirty-row update for the attribution columns (the
    AttrCols sibling of scatter_table_rows); shared by the single-device
    mirror sync and the r21 per-slice store-shard sync."""
    return AttrCols(
        attr.dom.at[idx].set(dom),
        attr.status.at[idx].set(status),
        attr.dmsb.at[idx].set(dmsb),
        attr.dlsb.at[idx].set(dlsb),
        attr.dnode.at[idx].set(dnode),
        attr.emsb.at[idx].set(emsb),
        attr.elsb.at[idx].set(elsb),
        attr.enode.at[idx].set(enode),
        attr.eknown.at[idx].set(eknown))


class AttrIndex(NamedTuple):
    """Replicated per-store floor + elision index (host-built, cached on
    the RedundantBefore / CommandsForKey versions; pow2-padded so jit
    compiles a bounded number of shapes)."""

    fbnd: jnp.ndarray     # int64[F]   floor segment boundaries (pad +INF)
    fmsb: jnp.ndarray     # int64[F+1] per-segment deps_floor triples
    flsb: jnp.ndarray     # int64[F+1]
    fnode: jnp.ndarray    # int32[F+1]
    etok: jnp.ndarray     # int64[T]   elidable tokens, sorted (pad +INF)
    eptr: jnp.ndarray     # int32[T+1] CSR into the exec arrays (pad L)
    erank: jnp.ndarray    # int64[L]   seg*estride+rank composites, asc
    exm: jnp.ndarray      # int64[L]   the pivot executeAt triples
    exl: jnp.ndarray      # int64[L]
    exn: jnp.ndarray      # int32[L]
    estride: jnp.ndarray  # int64[]    U+1 — the composite stride erank used


def _attr_key_masks(tok, dmsb, dlsb, dnode, status, emsb, elsb, enode,
                    eknown, rankb_b, aidx: AttrIndex,
                    floors: bool = True, elide: bool = True):
    """The in-kernel attribution predicate for KEY-domain candidates, all
    elementwise over one candidate shape.  ``tok`` is the emitted token
    (the dep's own footprint point), ``rankb_b`` the per-candidate bound
    rank (broadcast from the query row).  Returns (keep_floor,
    elide_trans, elide_dec) — the caller scopes them to key-domain
    candidates.  ``floors``/``elide`` are STATIC leg switches the
    dispatcher sets per flush: when the exact per-token floors equal the
    already-applied batch prune, or the elision index is empty, the
    corresponding gathers and searches never enter the program."""
    ones = None
    if floors:
        # exact per-token RedundantBefore floor: dep >= deps_floor(token)
        fi = jnp.searchsorted(aidx.fbnd, tok, side="right")
        keep_floor = ~ts_lt(dmsb, dlsb, dnode,
                            aidx.fmsb[fi], aidx.flsb[fi], aidx.fnode[fi])
    else:
        ones = jnp.ones(jnp.broadcast_shapes(tok.shape, dmsb.shape), bool)
        keep_floor = ones
    # transitively-known entries never emit
    elide_trans = status == SLOT_TRANSITIVE
    if not elide:
        z = (~ones) if ones is not None else \
            jnp.zeros(jnp.broadcast_shapes(tok.shape, dmsb.shape), bool)
        return keep_floor, elide_trans, z
    # decided entries executing below the token's latest committed write
    # before the bound are reached through that write's stable deps
    t = aidx.etok.shape[0]
    seg = jnp.searchsorted(aidx.etok, tok)
    seg_c = jnp.minimum(seg, max(t - 1, 0))
    seg_ok = (aidx.etok[seg_c] == tok) if t else jnp.zeros(tok.shape, bool)
    base = aidx.eptr[seg_c]
    cnt = jnp.searchsorted(aidx.erank,
                           seg_c.astype(jnp.int64) * aidx.estride
                           + rankb_b) - base
    has_pivot = seg_ok & (cnt > 0)
    pidx = jnp.clip(base + cnt - 1, 0)
    below = ts_lt(emsb, elsb, enode,
                  aidx.exm[pidx], aidx.exl[pidx], aidx.exn[pidx])
    decided = (status >= SLOT_COMMITTED) & (status <= SLOT_APPLIED) & eknown
    elide_dec = decided & has_pivot & below
    return keep_floor, elide_trans, elide_dec


def _attr_post(tlo, attr: AttrCols, aidx: AttrIndex, rankb: jnp.ndarray,
               hdr_raw, ent, m_t: int, m: int,
               floors: bool = True, elide: bool = True, tok=None):
    """The POST-COMPACTION attribution stage shared by every attributed
    kernel: floors, elision and the key-domain query-column dedupe run
    over the COMPACTED entry buffer — thousands of surviving codes — not
    the candidate matrix (hundreds of thousands of cells).  The raw
    kernels already sorted/compacted, so rows are contiguous and
    same-(slot, col) key emits are adjacent; dropping entries is a mask +
    one global cumsum scatter, no re-sort.

    ``tlo`` is the interval-start matrix the emitted token gathers from
    (the slot table's lo; a mesh-bucketed caller passes ``tok``
    precomputed via a cross-shard psum instead).  Returns the attributed
    (header int32[5+B], entries) pair; the header's overflow watermarks
    are the RAW totals (the learned s/k budgets size the pre-attribution
    compaction)."""
    s = ent.shape[0]
    total = hdr_raw[0].astype(jnp.int64)
    maxc_raw = hdr_raw[1]
    row_end = hdr_raw[2:].astype(jnp.int64)
    b = row_end.shape[0]
    pos = jnp.arange(s, dtype=jnp.int64)
    live = pos < total
    code = ent.astype(jnp.int64)
    mq = m_t * m
    slot = jnp.clip(code // mq, 0)
    col = jnp.clip(code % mq // m, 0, m_t - 1)
    row_of = jnp.minimum(_entry_rows(row_end, s), b - 1)
    key_dep = attr.dom[slot] == 0
    status = attr.status[slot]
    if tok is None:
        tok = tlo[slot, col]
    # the key masks at entry level (1-D gathers only)
    keep_floor, el_trans, el_dec = _attr_key_masks(
        tok, attr.dmsb[slot], attr.dlsb[slot], attr.dnode[slot], status,
        attr.emsb[slot], attr.elsb[slot], attr.enode[slot],
        attr.eknown[slot], rankb[row_of], aidx, floors, elide)
    # key-domain query-column dedupe: codes are (slot, col, q)-ascending
    # within each row, so same-(slot, col) runs are adjacent
    pairkey = row_of * jnp.int64(1 << 40) + code // m
    firstp = jnp.concatenate(
        [jnp.ones(1, bool), pairkey[1:] != pairkey[:-1]])
    drop_key = ~keep_floor | el_trans | el_dec | ~firstp
    keep = live & (~key_dep | ~drop_key)
    n_trans = jnp.sum(live & key_dep & firstp & keep_floor & el_trans)
    n_dec = jnp.sum(live & key_dep & firstp & keep_floor
                    & ~el_trans & el_dec)
    out_pos = jnp.cumsum(keep) - 1
    out = jnp.full(s, -1, ent.dtype).at[
        jnp.where(keep, out_pos, s)].set(ent, mode="drop")
    drops = jnp.zeros(b, jnp.int64).at[
        jnp.where(live & ~keep, row_of, b)].add(1, mode="drop")
    new_end = row_end - jnp.cumsum(drops)
    header = jnp.concatenate(
        [jnp.stack([new_end[-1], total, maxc_raw.astype(jnp.int64),
                    n_trans, n_dec]).astype(jnp.int32),
         new_end.astype(jnp.int32)])
    return header, out


def flat_attr_local(table: DepsTable, attr: AttrCols, aidx: AttrIndex,
                    qmat: jnp.ndarray, rankb: jnp.ndarray,
                    m: int, s: int, k: int, prune=None, wide: bool = False,
                    floors: bool = True, elide: bool = True):
    """flat_csr_local with the attribution pass fused in AFTER the raw
    compaction: per-token floors, elision and the per-(slot, interval)
    key dedupe drop entries from the compacted CSR, so what ships is
    EXACTLY the entry set the host builders will keep.  Range-domain
    entries pass through untouched (the mask's batch-global prune floor
    is their whole floor story, matching the host oracle)."""
    hdr_raw, ent = flat_csr_local(table, qmat, m, s, k, prune, wide=wide)
    return _attr_post(table.lo, attr, aidx, rankb, hdr_raw, ent,
                      table.lo.shape[1], m, floors, elide)


@partial(jax.jit, static_argnames=("m", "s", "k", "wide", "floors",
                                   "elide"))
def calculate_deps_flat_attr(table: DepsTable, attr: AttrCols,
                             aidx: AttrIndex, qmat: jnp.ndarray,
                             rankb: jnp.ndarray,
                             prune_msb: jnp.ndarray, prune_lsb: jnp.ndarray,
                             prune_node: jnp.ndarray,
                             m: int, s: int, k: int, wide: bool = False,
                             floors: bool = True, elide: bool = True):
    """The dispatchable dense attributed kernel (always pruned: the
    attributed paths are the protocol paths, which enable the batch-global
    floor; a zero triple prunes nothing)."""
    return flat_attr_local(table, attr, aidx, qmat, rankb,
                           m, s, k, (prune_msb, prune_lsb, prune_node),
                           wide=wide, floors=floors, elide=elide)


def bucketed_attr(table, attr: AttrCols, aidx: AttrIndex, buckets: BucketTable,
                  qmat: jnp.ndarray, rankb: jnp.ndarray, m: int, span: int,
                  s: int, k: int, prune=None, row_offset=None,
                  keff: int = None, wide: bool = False, m_t: int = None,
                  floors: bool = True, elide: bool = True, tok=None):
    """bucketed_flat with the post-compaction attribution stage.  The
    emitted token gathers from ``table.lo`` by the entry's global
    (slot, col); the mesh-sharded wrapper passes ``tok`` resolved via a
    cross-shard psum instead (its local table holds only a slot slice)."""
    hdr_raw, ent = bucketed_flat(table, buckets, qmat, m, span, s, k,
                                 prune, row_offset=row_offset, keff=keff,
                                 wide=wide, m_t=m_t)
    if m_t is None:
        m_t = table.lo.shape[1]
    tlo = table.lo if table is not None else None
    return _attr_post(tlo, attr, aidx, rankb, hdr_raw, ent, m_t, m,
                      floors, elide, tok=tok)


bucketed_attr_jit = jax.jit(
    bucketed_attr,
    static_argnames=("m", "span", "s", "k", "keff", "wide", "m_t",
                     "floors", "elide"))


def _pad_attr_cols(cols, n: int):
    """Pad one store's attribution columns to ``n`` slots: appended
    slots are FREE (structurally excluded by the mask) so their grades are
    never read."""
    dom, status, dmsb, dlsb, dnode, emsb, elsb, enode, eknown = cols
    pad1 = lambda a, fill: jnp.pad(a, (0, n - a.shape[0]),       # noqa: E731
                                   constant_values=fill)
    return (pad1(dom, 1), pad1(status, SLOT_FREE), pad1(dmsb, 0),
            pad1(dlsb, 0), pad1(dnode, 0), pad1(emsb, 0),
            pad1(elsb, 0), pad1(enode, 0), pad1(eknown, False))


def _pad_attr_index(aidx: AttrIndex, f: int, t: int, l: int):
    """Pad one store's AttrIndex to the fused group's (F, T, L) shapes.
    Floor boundaries and elidable tokens pad with +INF (unreachable by any
    real token); exec composites pad with +INF (sort after every real
    key); eptr pads with the store's own live length so padded segments
    are empty."""
    inf = jnp.int64(np.iinfo(np.int64).max)

    def tail(a, n, fill):
        d = n - a.shape[0]
        return jnp.concatenate([a, jnp.full(d, fill, a.dtype)])

    live_l = aidx.eptr[-1]
    return AttrIndex(
        tail(aidx.fbnd, f, inf),
        tail(aidx.fmsb, f + 1, 0), tail(aidx.flsb, f + 1, 0),
        tail(aidx.fnode, f + 1, 0),
        tail(aidx.etok, t, inf),
        jnp.concatenate([aidx.eptr,
                         jnp.broadcast_to(live_l, (t + 1 - aidx.eptr.shape[0],))
                         .astype(aidx.eptr.dtype)]),
        tail(aidx.erank, l, inf),
        tail(aidx.exm, l, 0), tail(aidx.exl, l, 0), tail(aidx.exn, l, 0),
        aidx.estride)


_FUSED_ATTR_CACHE = {}


def fused_flat_attr(tables: Sequence[DepsTable], stacked_attr: AttrCols,
                    stacked_aidx: AttrIndex, qmats: np.ndarray,
                    rankbs: np.ndarray,
                    prunes: Tuple[np.ndarray, np.ndarray, np.ndarray],
                    m: int, s: int, k: int, wide: bool = False,
                    floors: bool = True, elide: bool = True):
    """One fused launch for S stores' ATTRIBUTED deps scans — the r08
    coalescing shape with the r15 attribution fused in: per-store tables
    are padded to the group maxima and stacked INSIDE the jitted program,
    then flat_attr_local is vmapped over the store axis.  Row i of the
    outputs is exactly the solo calculate_deps_flat_attr answer for store
    i (codes on the GROUP interval width).

    ``stacked_attr`` / ``stacked_aidx`` arrive PRE-STACKED on the leading
    store axis ([S, n_max] / [S, ...]; the dispatcher pads host-side and
    caches on the members' attr versions): passing 16 stores' 20 extra
    pytrees per launch measured ~5ms of pure argument flattening on the
    config-5 tiny-flush regime — the launch-tax the fused path exists to
    amortize."""
    caps = tuple((t.capacity, t.lo.shape[1]) for t in tables)
    b = qmats.shape[1]
    key = (caps, stacked_aidx.fbnd.shape, stacked_aidx.etok.shape,
           stacked_aidx.erank.shape, b, m, s, k, wide, floors, elide)
    fn = _FUSED_ATTR_CACHE.get(key)
    if fn is None:
        n_max = max(c for c, _ in caps)
        m_max = max(mi for _, mi in caps)

        def traced(flat_cols, stacked_a, stacked_i, qm, rb, pm, pl, pn):
            padded = [_pad_table_cols(cols, n_max, m_max)
                      for cols in flat_cols]
            stacked = DepsTable(*(jnp.stack(col) for col in zip(*padded)))
            return jax.vmap(
                lambda t, a, i, q, r, x, y, z: flat_attr_local(
                    t, a, i, q, r, m, s, k, (x, y, z), wide=wide,
                    floors=floors, elide=elide)
            )(stacked, stacked_a, stacked_i, qm, rb, pm, pl, pn)

        fn = _FUSED_ATTR_CACHE[key] = jax.jit(traced)
    return fn(tuple(tuple(t) for t in tables), stacked_attr, stacked_aidx,
              jnp.asarray(qmats), jnp.asarray(rankbs),
              jnp.asarray(prunes[0]), jnp.asarray(prunes[1]),
              jnp.asarray(prunes[2]))
