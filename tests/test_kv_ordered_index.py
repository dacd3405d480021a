"""KVDataStore's ordered token index against the full-walk oracle
(tests/kv_scan_oracle.py) under random appends, snapshots and journal
restores: a range read and a snapshot are bisect slices, in key order."""

import random

import pytest

from accord_tpu.primitives.keys import Range, Ranges
from accord_tpu.primitives.timestamp import Domain, Timestamp, TxnId, TxnKind
from accord_tpu.sim.kvstore import KVDataStore, KVRangeRead
from tests.kv_scan_oracle import read_range_full_walk, snapshot_full_walk

SPACE = 5_000


def _tid(hlc):
    return TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1)


def _mutate(rng, store, hlc):
    """One random mutation: an append, or a snapshot of another store."""
    if rng.random() < 0.8:
        tid = _tid(hlc)
        store.apply_append(rng.randrange(SPACE), (f"v{hlc}",), tid, tid)
        if rng.random() < 0.2:       # a re-apply is idempotent
            store.apply_append(rng.randrange(SPACE), (f"v{hlc}",), tid, tid)
        return
    donor = KVDataStore(2)
    for i in range(rng.randint(0, 30)):
        tid = _tid(hlc * 1000 + i)
        donor.apply_append(rng.randrange(SPACE), (f"s{hlc}.{i}",), tid, tid)
    lo = rng.randrange(SPACE)
    store.install_snapshot(donor.snapshot(
        Ranges.of(Range(lo, lo + rng.randint(1, SPACE)))))


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_range_reads_and_snapshots_match_the_full_walk(seed):
    rng = random.Random(seed)
    store = KVDataStore(1)
    for step in range(1, 400):
        _mutate(rng, store, step)
        if step % 7:
            continue
        assert store._sorted == sorted(store.log)
        lo = rng.randrange(-10, SPACE)
        hi = lo + rng.randint(1, SPACE // 3)
        at = Timestamp.from_values(1, rng.randrange(1, 2 * step * 1000), 1)
        got = store.read_range(lo, hi, at)
        assert got == read_range_full_walk(store, lo, hi, at)
        assert list(got) == sorted(got)          # in key order
        assert store.tokens_in(lo, hi) == sorted(
            t for t in store.log if lo <= t < hi)
        ranges = Ranges.of(Range(lo, hi), Range(hi + 50, hi + 90))
        assert store.snapshot(ranges) == snapshot_full_walk(store, ranges)
    assert store.scan_calls > 0 and store.scan_host_s > 0


def test_the_range_read_goes_through_the_index_and_counts():
    store = KVDataStore(1)
    for token in (40, 10, 30, 20):
        tid = _tid(token)
        store.apply_append(token, (token,), tid, tid)
    read = KVRangeRead(Ranges.of(Range(15, 35)))
    data = []
    read.read(Range(15, 35), None, Timestamp.from_values(1, 25, 1),
              store).begin(lambda d, _f: data.append(d))
    # 30 is held but was written at 30, above the read's executeAt
    assert data[0].values == {20: (20,), 30: ()}
    assert store.scan_calls == 1


def test_a_journal_restore_goes_through_install_snapshot(tmp_path):
    """DurableJournal.install_data used to write ``data_store.log`` itself,
    past the index."""
    from accord_tpu.journal.durable import DurableJournal
    journal = DurableJournal.__new__(DurableJournal)
    tid_a, tid_b = _tid(7), _tid(5)
    journal._restored_data = {9: [((1,), tid_a, tid_a), ((0,), tid_b, tid_b)],
                              3: [((2,), tid_a, tid_a)]}
    store = KVDataStore(1)
    journal.install_data(store)
    assert store._sorted == [3, 9]
    assert store.get(9) == (0, 1)                # sorted by executeAt
    assert store.tokens_in(0, 100) == [3, 9]
