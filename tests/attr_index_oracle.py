"""REFERENCE attribution index: the from-scratch build that
``DeviceState._attr_index`` / ``_AttrIndexHost.__init__`` were before PR 29
made the index maintained by token (moved here whole, as PR 28 moved the
reference attribution to ``tests/deps_oracle.py``).  The maintained index
must answer ``rank_bounds`` / ``floors_match`` / ``keep_floor`` /
``elide_decided`` exactly as this one does and assemble the same padded
device arrays, byte for byte, after any history
(``tests/test_attr_index_incremental.py``)."""

from __future__ import annotations

import numpy as np

from accord_tpu.local.device_index import (_I64_INF, _pow2_at_least,
                                           _ts_byte_keys)
from accord_tpu.primitives.timestamp import TxnId


class ReferenceAttrIndex:
    """One store's floor + elision index in the form PR 29 deleted from
    DeviceState: everything built eagerly, from scratch, over EVERY
    registry token — the global unique-executeAt ranks, the concatenated
    CSR and the pow2-padded arrays that upload as
    ops.deps_kernel.AttrIndex — and host readers that compare RANKS."""

    def __init__(self, floors, etok, eptr, exm, exl, exn):
        self.fbnd, self.fmsb, self.flsb, self.fnode = floors
        self.etok = etok
        self.eptr = eptr
        self.exm, self.exl, self.exn = exm, exl, exn
        # dense ranks over the UNIQUE exec triples: exec < bound compares
        # become integer rank compares on device
        keys = _ts_byte_keys(exm, exl, exn)
        self.uqkeys = np.unique(keys)
        self.u = len(self.uqkeys)
        rank = np.searchsorted(self.uqkeys, keys).astype(np.int64)
        seg = np.repeat(np.arange(len(etok), dtype=np.int64),
                        np.diff(eptr))
        self.erank = seg * np.int64(self.u + 1) + rank
        # pow2-padded device images (floors pad +INF / zero rows; elidable
        # tokens pad +INF; padded eptr segments are empty)
        fp = _pow2_at_least(max(len(self.fbnd), 1), 1)
        tp = _pow2_at_least(max(len(etok), 1), 1)
        lp = _pow2_at_least(max(len(self.erank), 1), 1)
        l_real = len(self.erank)

        def tail(a, n, fill, dtype):
            out = np.full(n, fill, dtype)
            out[: len(a)] = a
            return out

        self.pad = (
            tail(self.fbnd, fp, _I64_INF, np.int64),
            tail(self.fmsb, fp + 1, 0, np.int64),
            tail(self.flsb, fp + 1, 0, np.int64),
            tail(self.fnode, fp + 1, 0, np.int32),
            tail(etok, tp, _I64_INF, np.int64),
            tail(eptr, tp + 1, l_real, np.int32),
            tail(self.erank, lp, _I64_INF, np.int64),
            tail(exm, lp, 0, np.int64),
            tail(exl, lp, 0, np.int64),
            tail(exn, lp, 0, np.int32),
            np.int64(self.u + 1))

    def rank_bounds(self, qnp: np.ndarray) -> np.ndarray:
        """Per-query rank of the started-before bound among the index's
        unique committed-write executeAts — the ``rankb`` column the
        kernels (and the host route) compare in place of 128-bit
        timestamps."""
        if self.u == 0:
            return np.zeros(qnp.shape[0], np.int64)
        keys = _ts_byte_keys(qnp[:, 0], qnp[:, 1], qnp[:, 2])
        return np.searchsorted(self.uqkeys, keys).astype(np.int64)

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
        from accord_tpu.ops.packing import to_i64 as _ti
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

    def elide_decided(self, tok, emsb, elsb, enode, rankb_b) -> np.ndarray:
        """Per-entry decided-elision mask for candidates ALREADY known to
        be decided (Committed..Applied with executeAt): does a committed
        write on the token execute strictly between the dep and the
        bound?  The pivot search collapses to the UNIQUE (segment, bound
        rank) composites — the hot regime has a handful of hot tokens and
        bounds against tens of thousands of entries."""
        t = len(self.etok)
        seg = np.searchsorted(self.etok, tok)
        seg_c = np.minimum(seg, t - 1)
        seg_ok = self.etok[seg_c] == tok
        c = seg_c.astype(np.int64) * np.int64(self.u + 1) + rankb_b
        uc, inv = np.unique(c, return_inverse=True)
        base_u = self.eptr[np.minimum(uc // np.int64(self.u + 1),
                                      t - 1)].astype(np.int64)
        cnt_u = np.searchsorted(self.erank, uc) - base_u
        pidx_u = np.clip(base_u + cnt_u - 1, 0, max(len(self.exm) - 1, 0))
        pm = self.exm[pidx_u][inv]
        pl = self.exl[pidx_u][inv]
        pn = self.exn[pidx_u][inv]
        uem, upm = emsb.view(np.uint64), pm.view(np.uint64)
        uel, upl = elsb.view(np.uint64), pl.view(np.uint64)
        below = ((uem < upm) | ((uem == upm)
                               & ((uel < upl)
                                  | ((uel == upl) & (enode < pn)))))
        return seg_ok & (cnt_u[inv] > 0) & below


def build_reference(dev, registry) -> ReferenceAttrIndex:
    """The deleted ``DeviceState._attr_index`` body: the packed
    RedundantBefore floors plus the CFK committed-write pivot list of
    every token of ``registry`` (the tokens the store ever drove a decided
    key-domain write on — the deleted ``_elide_tokens``), read from the
    store as it stands now."""
    rb = getattr(dev.store, "redundant_before", None)
    cfk_map = getattr(dev.store, "commands_for_key", None) or {}
    if rb is not None:
        floors = rb.packed_floor_index()
    else:
        floors = (np.zeros(0, np.int64), np.zeros(1, np.int64),
                  np.zeros(1, np.int64), np.zeros(1, np.int32))
    packs = []
    keep_toks = []
    for t in sorted(registry):
        c = cfk_map.get(int(t))
        if c is None:
            continue
        p = c.packed_committed_execs()
        if len(p[0]):
            packs.append(p)
            keep_toks.append(t)
    if packs:
        etok = np.asarray(keep_toks, np.int64)
        lens = np.array([len(p[0]) for p in packs], np.int64)
        eptr = np.zeros(len(packs) + 1, np.int32)
        np.cumsum(lens, out=eptr[1:])
        exm = np.concatenate([p[0] for p in packs])
        exl = np.concatenate([p[1] for p in packs])
        exn = np.concatenate([p[2] for p in packs])
    else:
        etok = np.zeros(0, np.int64)
        eptr = np.zeros(1, np.int32)
        exm = np.zeros(0, np.int64)
        exl = np.zeros(0, np.int64)
        exn = np.zeros(0, np.int32)
    return ReferenceAttrIndex(floors, etok, eptr, exm, exl, exn)
