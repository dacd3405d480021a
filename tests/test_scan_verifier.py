"""Scans in the strict-serializability verifier: a scan is fed as a read of
EVERY known key in its range (one it did not return read as the empty
prefix, as sim/burn.py records its range reads), so phantoms are ordinary
anomalies; and the cross-key check costs an op as many edges as it has keys
(one hub per op), so scans of a hundred keys stay checkable."""

import bisect
import random

import pytest

from accord_tpu.sim.elle import CompositeVerifier, ListAppendCycleChecker
from accord_tpu.sim.verifier import (HistoryViolation,
                                     StrictSerializabilityVerifier)


def _verifier():
    return CompositeVerifier(StrictSerializabilityVerifier(),
                             ListAppendCycleChecker())


def _feed(verifier, ops, finals):
    for start, end, reads, appends in ops:
        verifier.on_result(verifier.begin(), start, end, reads, appends)
    for token, final in finals.items():
        verifier.set_final(token, final)


def _scan(known, lo, hi, rows):
    """A scan's rows expanded over the known keys in [lo, hi)."""
    reads = {k: () for k in known if lo <= k < hi}
    reads.update(rows)
    return reads


KNOWN = [1, 3, 5]
FINALS = {1: ("a",), 3: ("b",), 5: ("i",)}     # 5 was loaded: no writer


def test_a_scan_that_sees_every_insert_acknowledged_before_it_passes():
    v = _verifier()
    _feed(v, [(0, 10, {}, {1: ("a",)}),
              (20, 30, _scan(KNOWN, 0, 10, {1: ("a",), 5: ("i",)}), {}),
              (40, 50, {}, {3: ("b",)}),
              (60, 70, _scan(KNOWN, 0, 10, FINALS), {})], FINALS)
    v.verify()


def test_a_scan_that_misses_an_acknowledged_insert_fails():
    """The insert of 3 finished at 50; a scan that began at 60 and did not
    return it read key 3 as empty after the write was acknowledged."""
    v = _verifier()
    _feed(v, [(0, 10, {}, {1: ("a",)}),
              (40, 50, {}, {3: ("b",)}),
              (60, 70, _scan(KNOWN, 0, 10, {1: ("a",), 5: ("i",)}), {})],
          FINALS)
    with pytest.raises(HistoryViolation, match="real-time inversion"):
        v.verify()
    # unexpanded, the same reply names no key it missed, and passes
    v = _verifier()
    _feed(v, [(0, 10, {}, {1: ("a",)}),
              (40, 50, {}, {3: ("b",)}),
              (60, 70, {1: ("a",), 5: ("i",)}, {})], FINALS)
    v.verify()


def test_a_scan_that_sees_half_of_a_txns_inserts_is_a_cycle():
    v = _verifier()
    _feed(v, [(0, 10, {}, {1: ("a",), 3: ("b",)}),
              (5, 8, _scan(KNOWN, 0, 10, {3: ("b",), 5: ("i",)}), {})],
          FINALS)
    with pytest.raises(HistoryViolation, match="cycle"):
        v.verify()


def test_a_scan_that_loses_a_loaded_record_fails_once_it_was_seen():
    v = _verifier()
    _feed(v, [(0, 10, _scan(KNOWN, 4, 6, {5: ("i",)}), {}),
              (20, 30, _scan(KNOWN, 4, 6, {}), {})], {5: ("i",)})
    with pytest.raises(HistoryViolation, match="real-time violation"):
        v.verify()


def _serial_history(seed, n_keys, n_ops, width):
    """A serial run of scans and inserts over a sparse key space, one op
    after the other in real time, with loaded records."""
    rng = random.Random(seed)
    space = n_keys * 50
    loaded = sorted(rng.sample(range(space), n_keys))
    held = {k: (f"l{k}",) for k in loaded}
    known = list(loaded)
    ops, now = [], 0
    for n in range(n_ops):
        if rng.random() < 0.9:
            at = rng.randrange(len(known))
            lo = known[at]
            hi = known[min(at + width, len(known) - 1)] + 1
            keys = known[bisect.bisect_left(known, lo):
                         bisect.bisect_left(known, hi)]
            ops.append((now, now + 5, {k: held[k] for k in keys}, {}))
        else:
            k = rng.randrange(space)
            if k in held:
                continue
            held[k] = (f"v{n}",)
            bisect.insort(known, k)
            ops.append((now, now + 5, {}, {k: held[k]}))
        now += 10
    return ops, held


def test_wide_scans_stay_checkable_and_a_dropped_row_is_caught():
    """400 ops, nine in ten a scan of a hundred keys: 36,000 witnessed
    steps.  An edge per pair of an op's keys would be 3.6 million."""
    ops, finals = _serial_history(3, n_keys=2_000, n_ops=400, width=100)
    v = _verifier()
    _feed(v, ops, finals)
    v.verify()
    # the injected phantom: the last scan of a key that was inserted in
    # the run loses that row
    at, key = next((at, k) for at in range(len(ops) - 1, -1, -1)
                   for k, val in ops[at][2].items()
                   if val and val[0].startswith("v"))
    start, end, reads, appends = ops[at]
    ops[at] = (start, end, {**reads, key: ()}, appends)
    v = _verifier()
    _feed(v, ops, finals)
    with pytest.raises(HistoryViolation):
        v.verify()
