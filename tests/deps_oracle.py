"""Reference implementations of the deps flush, for tests only.

The store has ONE flush (``DeviceState.deps_query_batch_begin`` ->
``deps_query_batch_end_attributed``): every route hands the shared finalize
entries that are already floored, elided and deduped.  What that flush must
equal is kept here, outside the production class, as plain functions of a
``DeviceState``:

- :func:`exact_geometry` — the overlap geometry of a (query, slot) pair
  list as one numpy broadcast (the r10 host pass);
- :func:`attribute_batch` — the r15 host attribution pass: per-token
  RedundantBefore floors, CommandsForKey elision and key/range attribution
  read from the store itself;
- :func:`reference_builders` — the two composed over the host route's
  candidates (``dev.deps.host_pairs``, which stays in the product: it is a
  route, the fault failover and the paranoia shadow): the answer every
  route's flush is compared with, builder for builder;
- :func:`attributed_entries` — the entry set an attributed kernel must
  ship for a pair list: the geometry with the in-kernel key-domain
  first-query-column dedupe applied.

:func:`flush_builders` runs the PRODUCT flush, so a test reads
``flush_builders(...) == reference_builders(...)``.
"""

from typing import Dict

import numpy as np

from accord_tpu.local.device_index import (_finalize_key_batch,
                                           _finalize_range_batch,
                                           _pow2_at_least)
from accord_tpu.ops import deps_kernel as dk
from accord_tpu.ops.packing import to_i64
from accord_tpu.primitives.deps import DepsBuilder
from accord_tpu.primitives.timestamp import Domain, Timestamp, TxnId


def flush_builders(dev, safe, qs, immediate=True):
    """The product flush of ``qs``: one DepsBuilder per query."""
    builders = [DepsBuilder() for _ in qs]
    handle = dev.deps_query_batch_begin(qs, immediate=immediate)
    dev.deps_query_batch_end_attributed(safe, handle, builders)
    return builders


def dep_ids(builders):
    """Per query, the sorted TxnIds its built Deps name on any key or
    range — what a brute force over the registered txns can be held to."""
    out = []
    for b in builders:
        deps = b.build()
        out.append(sorted(set(deps.key_deps.txn_ids)
                          | set(deps.range_deps.txn_ids)))
    return out


def entry_pairs(tb, tj):
    """The (query, slot) pair list of entry arrays whose (b, j) runs are
    contiguous (true of every route's block: b-major, then the code sort /
    the host index order).  Returns (b_idx, j_idx, p_i), p_i mapping each
    entry to its pair row."""
    first = np.ones(len(tb), bool)
    if len(tb):
        first[1:] = (tb[1:] != tb[:-1]) | (tj[1:] != tj[:-1])
    return tb[first], tj[first], np.cumsum(first) - 1


def exact_geometry(b_idx, j_idx, ivs, qnp, q_m):
    """REFERENCE implementation of the exact overlap geometry over a
    (query, slot) pair list, yielding the (pair, dep-interval,
    query-interval) emit triples.  r10 pushed this into every device
    kernel (the CSR entries ARE the triples, as sorted composite
    codes); it is the executable spec of the emit-triple order
    (np.nonzero over [P, M, Q] = pair-major, dep-column, query-column —
    exactly the kernels' code sort)."""
    lo, hi, _dom = ivs
    lo_p, hi_p = lo[j_idx], hi[j_idx]                       # [P, M]
    used = lo_p <= hi_p
    qlo_p = qnp[b_idx, 7:7 + q_m]                           # [P, Q]
    qhi_p = qnp[b_idx, 7 + q_m:7 + 2 * q_m]
    overlap = (used[:, :, None]
               & (lo_p[:, :, None] <= qhi_p[:, None, :])
               & (qlo_p[:, None, :] <= hi_p[:, :, None]))   # [P, M, Q]
    p_i, m_i, q_i = np.nonzero(overlap)
    # drop pairs with no exact overlap (bounding-box false positives)
    present = np.zeros(len(j_idx), bool)
    present[p_i] = True
    if not present.all():
        new_pos = np.cumsum(present) - 1
        b_idx, j_idx = b_idx[present], j_idx[present]
        p_i = new_pos[p_i]
    return b_idx, j_idx, (p_i, m_i, q_i)


def attribute_batch(dev, safe, b_idx, j_idx, pmq, ids, ivs, qnp, queries,
                    builders) -> None:
    """REFERENCE attribution (the host pass the attributed kernels
    replaced in r15, moved out of DeviceState in PR 28): fold a batch's
    exact (query, slot) pairs + emit triples into the builders with the
    floors, elision and key/range attribution of the host path — the
    pairs answer "who", the mirror snapshot answers "where",
    RedundantBefore floors and the CFK elision rule decide "whether".
    It reads the per-token floors (``deps_floor_batch``) and the elision
    pivots (``CommandsForKey.can_elide``) straight from the store, not
    through the packed AttrIndex the flush uses.

    The geometry runs ONCE, vectorized over all (pair, dep-interval,
    query-interval) triples — no per-query Python overhead.  The
    unification that makes this possible: a key-domain dep's footprint
    is a point, so its emitted key is its own token whether the query
    interval was a key or a range; a range-domain dep emits the
    dep∩query interval clip, which for a point query degenerates to the
    width-1 range.  Python touches only the deduplicated surviving
    emits."""
    if len(j_idx) == 0:
        return
    lo, hi, dom = ivs
    rb = safe.redundant_before()
    _MISSING = object()
    cfks: Dict[int, object] = {}

    def elide_ctx(t: int, bound):
        """(cfk, pivot) when elision is possible on this key for this
        bound, else None — ONE lookup per (token, bound) instead of one
        per (dep, token) pair (the common key has nothing elidable)."""
        key = (t, bound)
        ctx = cfks.get(key, _MISSING)
        if ctx is not _MISSING:
            return ctx
        cfk = dev.store.commands_for_key.get(t)
        ctx = None
        if cfk is not None:
            pivot = cfk.can_elide(bound)
            if pivot is not None:
                ctx = (cfk, pivot)
        cfks[key] = ctx
        return ctx

    q_m = (qnp.shape[1] - 7) // 2
    # the exact (pair row, dep-interval col, query-interval col) emit
    # triples arrive precomputed from the collect pass (host probes or
    # np.nonzero over the kernel parts' overlap geometry)
    p_i, m_i, q_i = pmq
    key_dep = (dom[j_idx] == int(Domain.Key))[p_i]

    # key-domain deps: emitted at the dep's own footprint point,
    # deduped per (pair, token); floors + elision decide survival.
    # Emits reach the builders through the batch finalize (whole-batch
    # vectorized dedupe/CSR, set_prebuilt per builder) — per-emit
    # Python runs only for the rare keys with elidable state
    kp, km = p_i[key_dep], m_i[key_dep]
    (msb_a, lsb_a, node_a, obj_a, status_a, xm_a, xl_a, xn_a,
     xk_a) = ids
    if len(kp):
        jj, bb = j_idx[kp], b_idx[kp]
        tt = lo[jj, km]                   # key-domain footprint = point
        # vectorized RedundantBefore floor: dep >= floor(token),
        # lexicographic over the packed (msb, lsb, node) triples (the
        # same int64 ordering the kernel's ts_lt assumes)
        fmsb, flsb, fnode = rb.deps_floor_batch(tt)
        dmsb, dlsb, dnode = msb_a[jj], lsb_a[jj], node_a[jj]
        keep = ((dmsb > fmsb)
                | ((dmsb == fmsb)
                   & ((dlsb > flsb)
                      | ((dlsb == flsb) & (dnode >= fnode)))))
        jj_k, bb_k, tt_k = jj[keep], bb[keep], tt[keep]
        # object resolution: pure take from the snapshot object column
        deps_k = obj_a[jj_k]
        # VECTORIZED transitive elision (the per-key skip rule,
        # CommandsForKey.is_elided): transitively-known deps never
        # emit; decided deps executing below the key's latest
        # committed-write pivot (for this query's bound) are reached
        # through that write's stable deps.  The pivot is looked up
        # once per unique (token, query) on keys with anything
        # elidable; the per-emit judgement is pure array compares over
        # the mirror's status/executeAt snapshot — no per-emit Python
        uniq_t2, inv_t2 = np.unique(tt_k, return_inverse=True)
        tok_maybe = np.zeros(len(uniq_t2), bool)
        cfk_map = dev.store.commands_for_key
        for i, t in enumerate(uniq_t2.tolist()):
            cfk = cfk_map.get(t)
            if cfk is None:
                continue
            tok_maybe[i] = cfk.may_elide_any()
        status_k = status_a[jj_k]
        elide = status_k == dk.SLOT_TRANSITIVE
        flagged = tok_maybe[inv_t2]
        if flagged.any():
            f_idx = np.nonzero(flagged)[0]
            # (builder, token) pairs as ONE int64 composite key over
            # the token RANKS (np.unique(axis=0) on the raw 2-column
            # stack cost ~250ms/1k queries in the hot regime — the
            # void-dtype argsort dominated attribution)
            ntok2 = len(uniq_t2)
            key_bt = bb_k[f_idx] * np.int64(ntok2) + inv_t2[f_idx]
            ubt_key, inv_bt = np.unique(key_bt, return_inverse=True)
            pv = np.zeros((len(ubt_key), 3), np.int64)
            pv_ok = np.zeros(len(ubt_key), bool)
            ub_list = (ubt_key // ntok2).tolist()
            ut_list = uniq_t2[ubt_key % ntok2].tolist()
            for i, (b, t) in enumerate(zip(ub_list, ut_list)):
                ctx = elide_ctx(int(t), queries[b][1])
                if ctx is not None and ctx[1] is not Timestamp.NONE \
                        and ctx[1] is not None:
                    pv[i] = (to_i64(ctx[1].msb), to_i64(ctx[1].lsb),
                             ctx[1].node)
                    pv_ok[i] = True
            pm, pl, pn = (pv[inv_bt, 0], pv[inv_bt, 1], pv[inv_bt, 2])
            jf = jj_k[f_idx]
            sf = status_k[f_idx]
            xm, xl, xn = xm_a[jf], xl_a[jf], xn_a[jf]
            below = ((xm < pm) | ((xm == pm)
                                  & ((xl < pl)
                                     | ((xl == pl) & (xn < pn)))))
            decided = ((sf >= dk.SLOT_COMMITTED)
                       & (sf <= dk.SLOT_APPLIED) & xk_a[jf])
            elide[f_idx] |= pv_ok[inv_bt] & decided & below
        keep2 = ~elide
        if keep2.any():
            jj_f = jj_k[keep2]
            # dense dep ranks over the batch's unique slots, ordered by
            # the packed id (same signed lexicographic order the old
            # 5-column lexsort used) — the finalize sorts become single
            # int64 argsorts
            u_slots, slot_inv = np.unique(jj_f, return_inverse=True)
            ordr = np.lexsort((node_a[u_slots], lsb_a[u_slots],
                               msb_a[u_slots]))
            rank = np.empty(len(u_slots), np.int64)
            rank[ordr] = np.arange(len(u_slots))
            _finalize_key_batch(builders, bb_k[keep2], tt_k[keep2],
                                inv_t2[keep2], len(uniq_t2),
                                rank[slot_inv], len(u_slots),
                                deps_k[keep2])

    # range-domain deps: emit the dep∩query interval clip per pair —
    # batch-finalized (dedupe/sort/CSR in one vectorized pass; Range
    # objects materialize once per unique clip)
    rp, rm, rq = p_i[~key_dep], m_i[~key_dep], q_i[~key_dep]
    if len(rp):
        jj_r = j_idx[rp]
        bb_r = b_idx[rp]
        ilo = np.maximum(lo[jj_r, rm], qnp[bb_r, 7 + rq])
        ihi = np.minimum(hi[jj_r, rm], qnp[bb_r, 7 + q_m + rq]) + 1
        dmsb_r, dlsb_r, dnode_r = msb_a[jj_r], lsb_a[jj_r], node_a[jj_r]
        # batch-global RedundantBefore floor on range-domain deps (the
        # host analogue of the device prune, applied on EVERY attributed
        # path so pruned and unpruned kernels agree; the pruned history
        # is covered by the boundary fence dep, messages/preaccept.py:
        # add_boundary_deps)
        m_all = qnp[:, 7:7 + q_m]
        h_all = qnp[:, 7 + q_m:7 + 2 * q_m]
        u_all = m_all <= h_all
        if u_all.any():
            fl = rb.min_floor_over(int(m_all[u_all].min()),
                                   int(h_all[u_all].max()))
            if fl > TxnId.NONE:
                fm, fls, fn = (to_i64(fl.msb), to_i64(fl.lsb), fl.node)
                keep_r = ((dmsb_r > fm)
                          | ((dmsb_r == fm)
                             & ((dlsb_r > fls)
                                | ((dlsb_r == fls) & (dnode_r >= fn)))))
                rp, ilo, ihi, jj_r = (rp[keep_r], ilo[keep_r],
                                      ihi[keep_r], jj_r[keep_r])
                dmsb_r, dlsb_r, dnode_r = (dmsb_r[keep_r],
                                           dlsb_r[keep_r],
                                           dnode_r[keep_r])
        if len(rp):
            _finalize_range_batch(builders, b_idx[rp], ilo, ihi,
                                  dmsb_r, dlsb_r, dnode_r, obj_a[jj_r])


def attributed_entries(b_idx, j_idx, ivs, qnp, q_m):
    """What an attributed kernel ships for the pair list when no floor and
    no elision applies: :func:`exact_geometry`'s triples, a key-domain
    dep's (slot, interval) emitted once — at the FIRST query column that
    reaches it (ops.deps_kernel._attr_post's ``firstp``).  Returns entry
    arrays (tb, tj, tm, tq) in the kernels' order."""
    b_r, j_r, (p_r, m_r, q_r) = exact_geometry(b_idx, j_idx, ivs, qnp, q_m)
    _lo, _hi, dom = ivs
    key_dep = dom[j_r[p_r]] == int(Domain.Key)
    firstp = np.ones(len(p_r), bool)
    if len(p_r):
        firstp[1:] = (p_r[1:] != p_r[:-1]) | (m_r[1:] != m_r[:-1])
    keep = ~key_dep | firstp
    return b_r[p_r][keep], j_r[p_r][keep], m_r[keep], q_r[keep]


def reference_pairs(dev, qnp, q_m, floor_id):
    """The host route's candidates as the (query, slot) pair list +
    (pair row, dep column, query column) emit triples the reference passes
    consume."""
    cb, cj, cm, cq = dev.deps.host_pairs(qnp, q_m, floor_id)
    cap = np.int64(dev.deps.capacity)
    pair, p_i = np.unique(cb * cap + cj, return_inverse=True)
    return pair // cap, pair % cap, (p_i, cm, cq)


def reference_builders(dev, safe, qs, prune=True):
    """The reference answer for ``qs`` over the LIVE mirror: host
    candidates -> :func:`attribute_batch`.  ``prune=False`` scans the whole
    live table (no batch-global floor in the candidate index), so the
    exact floors of the attribution pass do all the flooring — both must
    build the same Deps."""
    builders = [DepsBuilder() for _ in qs]
    q_m = _pow2_at_least(max(len(t[3]) + len(t[4]) for t in qs))
    qnp = dk.pack_query_matrix(
        [(sb, wit, toks, rngs, tid) for (tid, sb, wit, toks, rngs) in qs],
        q_m)
    floor_id = dev._batch_floor(qnp, q_m)[0] if prune else None
    b_idx, j_idx, pmq = reference_pairs(dev, qnp, q_m, floor_id)
    d = dev.deps
    ids = (d.msb, d.lsb, d.node, d.obj, d.status, d.emsb, d.elsb, d.enode,
           d.eknown)
    ivs = (d.lo, d.hi, d.domain)
    attribute_batch(dev, safe, b_idx, j_idx, pmq, ids, ivs, qnp, list(qs),
                    builders)
    return builders
