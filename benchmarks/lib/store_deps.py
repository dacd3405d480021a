"""The plain reference for a served store's deps answer: which txns a query
must name, by brute force over what the store must have witnessed, read
from the driver's own data and the store's command records, never from
its conflict indexes (CommandsForKey, the range-txn table) or its device
mirror:

- the history the driver drew and installed, each footprint clipped here
  to the store's ranges (``ranges_for_epoch``);
- every other txn the store holds a command record of (the window's and
  the warm-up's), its definition's footprint clipped the same way.

A query (txn_id, started_before, witnesses, tokens, ranges) names every
such txn below ``started_before``, of a kind ``witnesses`` admits, not
invalidated, whose footprint in the store meets the query's tokens or
ranges.  No floor, no elision: in the traffic this reference is used with
(YCSB E: one insert a key, scans that write nothing) neither removes a
dependency, and the comparison is of the txn ids a built Deps names, not of
its bytes (the routes' byte-equality is compared apart)."""

import numpy as np


class History:
    """The driver's history rows, (txn_id, execute_at, keys), as arrays of
    half-open footprints: a scan's range, an insert's [token, token + 1)."""

    def __init__(self, rows):
        from accord_tpu.primitives.keys import Ranges
        lo, hi = [], []
        self.ids = []
        for txn_id, _execute_at, keys in rows:
            for piece in _spans(keys, Ranges):
                lo.append(piece[0])
                hi.append(piece[1])
                self.ids.append(txn_id)
        self.lo = np.array(lo, np.int64)
        self.hi = np.array(hi, np.int64)
        self.id_set = frozenset(self.ids)


def _spans(keys, ranges_type):
    """A footprint as half-open (lo, hi) spans."""
    if isinstance(keys, ranges_type):
        return [(r.start, r.end) for r in keys]
    return [(k.token(), k.token() + 1) for k in keys]


def _clip(lo, hi, owned):
    """Each span cut to the owned (start, end) ranges: (lo, hi, index of
    the span) of every non-empty piece."""
    out_lo, out_hi, out_at = [], [], []
    for start, end in owned:
        a, b = np.maximum(lo, start), np.minimum(hi, end)
        keep = np.nonzero(a < b)[0]
        out_lo.append(a[keep])
        out_hi.append(b[keep])
        out_at.append(keep)
    return (np.concatenate(out_lo), np.concatenate(out_hi),
            np.concatenate(out_at))


class StoreIndex:
    """What one store must have witnessed, as arrays, read once."""

    def __init__(self, store, history):
        from accord_tpu.local.status import SaveStatus
        from accord_tpu.primitives.keys import Ranges
        owned = [(r.start, r.end) for r in store.ranges_for_epoch.all()]
        lo, hi, at = _clip(history.lo, history.hi, owned)
        ids = [history.ids[i] for i in at.tolist()]
        w_lo, w_hi = [], []
        for txn_id, cmd in store.commands.items():
            if txn_id in history.id_set or cmd.partial_txn is None \
                    or cmd.save_status is SaveStatus.Invalidated \
                    or not txn_id.kind().is_globally_visible():
                continue
            for piece in _spans(cmd.partial_txn.keys, Ranges):
                w_lo.append(piece[0])
                w_hi.append(piece[1])
                ids.append(txn_id)
        if w_lo:
            c_lo, c_hi, c_at = _clip(np.array(w_lo, np.int64),
                                     np.array(w_hi, np.int64), owned)
            lo = np.concatenate([lo, c_lo])
            hi = np.concatenate([hi, c_hi])
            ids = ids[:len(at)] + [ids[len(at) + i] for i in c_at.tolist()]
        self.lo, self.hi, self.ids = lo, hi, ids

    def deps(self, query):
        """The set of txn ids ``query`` must name."""
        _txn_id, before, witnesses, toks, rngs = query
        spans = [(t, t + 1) for t in toks] + [(r.start, r.end) for r in rngs]
        found = set()
        for lo, hi in spans:
            hit = np.nonzero((self.lo < hi) & (lo < self.hi))[0]
            found.update(self.ids[i] for i in hit.tolist())
        return {tid for tid in found
                if tid < before and witnesses.test(tid.kind())}


def named(built):
    """The txn ids one built Deps names, on any key or range."""
    return set(built.key_deps.txn_ids) | set(built.range_deps.txn_ids)
