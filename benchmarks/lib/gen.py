"""Seeded txn generators for the replica-store cells.

Shapes are bench.build_workload's (copied): a txn is a point txn or a range
txn with equal odds, has 1..max_iv intervals, is a write with probability
``write_share``, ranges are ``[s, s + w)`` with w in 1..max_width.  The draws
are vectorised; the python objects the store's API takes (TxnId, Keys,
Ranges) are made once, in set-up."""

from typing import NamedTuple

import numpy as np


class Txn(NamedTuple):
    tid: object          # TxnId
    toks: list           # point tokens ([] for a range txn)
    rngs: list           # Range objects ([] for a point txn)
    keys: object         # Keys | Ranges: what register() takes
    witnesses: object    # Kinds this txn takes deps on

    def query(self):
        """The PreAccept deps query of this txn itself."""
        return (self.tid, self.tid, self.witnesses, self.toks, self.rngs)


def make_txns(rng, hlcs, shape):
    """One Txn per entry of ``hlcs``, drawn from ``rng`` in ``shape``
    (keyspace, max_iv, write_share, point_share, max_width)."""
    from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind

    n = len(hlcs)
    keyspace, max_iv = int(shape["keyspace"]), int(shape["max_iv"])
    max_w = int(shape["max_width"])
    point = (rng.random(n) < shape["point_share"]).tolist()
    write = (rng.random(n) < shape["write_share"]).tolist()
    node = rng.integers(1, 6, n).tolist()
    n_iv = rng.integers(1, max_iv + 1, n).tolist()
    hlcs = [int(h) for h in hlcs]
    toks = rng.integers(0, keyspace, (n, max_iv)).tolist()
    starts = rng.integers(0, keyspace - max_w - 1, (n, max_iv)).tolist()
    widths = rng.integers(1, max_w + 1, (n, max_iv)).tolist()
    wit = {k: k.witnesses() for k in (TxnKind.Read, TxnKind.Write)}
    out = []
    for i in range(n):
        kind = TxnKind.Write if write[i] else TxnKind.Read
        m = n_iv[i]
        if point[i]:
            tid = TxnId.create(1, hlcs[i], kind, Domain.Key,
                               node[i])
            t = toks[i][:m]
            out.append(Txn(tid, t, [], Keys([IntKey(x) for x in t]),
                           wit[kind]))
        else:
            tid = TxnId.create(1, hlcs[i], kind, Domain.Range,
                               node[i])
            r = [Range(s, s + w)
                 for s, w in zip(starts[i][:m], widths[i][:m])]
            out.append(Txn(tid, [], r, Ranges.of(*r), wit[kind]))
    return out


def make_probe_queries(rng, n, shape, hlc_lo, hlc_hi):
    """bench.make_queries-shaped probes (copied): a write bound above every
    stored id, 1..max_iv intervals, each a point or a range with equal odds.
    Used by the correctness check only, never timed."""
    from accord_tpu.primitives.keys import Range
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind

    keyspace, max_iv = int(shape["keyspace"]), int(shape["max_iv"])
    max_w = int(shape["max_width"])
    qs = []
    for _ in range(n):
        bound = TxnId.create(1, int(rng.integers(hlc_lo, hlc_hi)),
                             TxnKind.Write, Domain.Key, 1)
        toks, rngs = [], []
        for _ in range(int(rng.integers(1, max_iv + 1))):
            if rng.random() < 0.5:
                toks.append(int(rng.integers(0, keyspace)))
            else:
                s = int(rng.integers(0, keyspace - max_w - 1))
                rngs.append(Range(s, s + int(rng.integers(1, max_w + 1))))
        qs.append((bound, bound, bound.kind().witnesses(), toks, rngs))
    return qs
