"""Tests for bitsets, interval maps, async chains, random source
(ref test models: SimpleBitSetTest, ReducingRangeMapTest, async tests)."""

import pytest

from accord_tpu.primitives import Range, Ranges
from accord_tpu.utils import async_chain
from accord_tpu.utils.bitset import ImmutableBitSet, SimpleBitSet
from accord_tpu.utils.interval_map import ReducingRangeMap
from accord_tpu.utils.random_source import RandomSource


def test_bitset_basic():
    bs = SimpleBitSet(70)
    assert bs.set(3) and bs.set(65) and not bs.set(3)
    assert bs.get(3) and bs.get(65) and not bs.get(4)
    assert bs.count() == 2
    assert list(bs) == [3, 65]
    assert bs.first_set() == 3 and bs.last_set() == 65
    assert bs.next_set(4) == 65 and bs.prev_set(64) == 3
    assert bs.unset(3) and not bs.unset(3)
    assert bs.to_words()[2] == (1 << 1)  # bit 65 -> word 2 bit 1


def test_bitset_immutable():
    bs = SimpleBitSet.full(5).freeze()
    with pytest.raises(TypeError):
        bs.set(1)
    assert isinstance(bs.with_unset(0), ImmutableBitSet)
    assert list(bs.with_unset(0)) == [1, 2, 3, 4]


def test_range_map_of_and_get():
    m = ReducingRangeMap.of_ranges(Ranges.of(Range(10, 20)), 5)
    assert m.get(9) is None and m.get(10) == 5 and m.get(19) == 5 and m.get(20) is None


def test_range_map_merge_max():
    m = ReducingRangeMap.empty()
    m = m.add(Ranges.of(Range(0, 100)), 1, max)
    m = m.add(Ranges.of(Range(50, 150)), 2, max)
    assert m.get(10) == 1 and m.get(75) == 2 and m.get(120) == 2 and m.get(160) is None
    m = m.add(Ranges.of(Range(0, 200)), 0, max)
    assert m.get(10) == 1 and m.get(75) == 2 and m.get(180) == 0


def test_range_map_fold():
    m = ReducingRangeMap.of_ranges(Ranges.of(Range(0, 10), Range(20, 30)), 3)
    total = m.fold_over_ranges(Ranges.of(Range(5, 25)), lambda v, acc: acc + v, 0)
    assert total == 6
    segs = m.fold_with_bounds(lambda v, s, e, acc: acc + [(v, s, e)], [])
    assert segs == [(3, 0, 10), (3, 20, 30)]


def test_async_chain_map_flatmap():
    out = []
    async_chain.success(2).map(lambda x: x + 1).flat_map(
        lambda x: async_chain.success(x * 10)).begin(
        lambda r, f: out.append((r, f)))
    assert out == [(30, None)]


def test_async_chain_failure_propagates():
    out = []
    boom = ValueError("boom")
    async_chain.failure(boom).map(lambda x: x + 1).begin(lambda r, f: out.append((r, f)))
    assert out == [(None, boom)]
    out2 = []
    async_chain.failure(boom).recover(lambda e: 42).begin(lambda r, f: out2.append((r, f)))
    assert out2 == [(42, None)]


def test_async_result_settles_once():
    r = async_chain.AsyncResult()
    seen = []
    r.begin(lambda v, f: seen.append(v))
    r.set_success(1)
    r.set_success(2)
    assert seen == [1] and r.result() == 1


def test_async_all_and_reduce():
    a, b = async_chain.AsyncResult(), async_chain.AsyncResult()
    out = []
    async_chain.reduce([a, b], lambda x, y: x + y).begin(lambda r, f: out.append(r))
    assert out == []
    b.set_success(10)
    a.set_success(1)
    assert out == [11]


def test_random_source_determinism():
    a, b = RandomSource(7), RandomSource(7)
    assert [a.next_int(100) for _ in range(20)] == [b.next_int(100) for _ in range(20)]
    fa, fb = a.fork(), b.fork()
    assert fa.next_long() == fb.next_long()


def test_random_zipf_skews():
    rs = RandomSource(3)
    draws = [rs.next_zipf(100, 0.99) for _ in range(2000)]
    assert all(0 <= d < 100 for d in draws)
    low = sum(1 for d in draws if d < 10)
    assert low > len(draws) * 0.4  # heavily skewed to small indices


def test_searchable_range_list_matches_bruteforce():
    """CINTIA index vs brute force on random interval sets
    (ref: utils/SearchableRangeListTest)."""
    import random
    from tests.range_index_oracle import SearchableRangeList
    rng = random.Random(7)
    for trial in range(30):
        n = rng.randint(0, 60)
        entries = []
        for i in range(n):
            s = rng.randint(0, 500)
            e = s + rng.randint(1, 80)
            entries.append((s, e, f"p{i}"))
        idx = SearchableRangeList(entries)
        for _ in range(40):
            t = rng.randint(-10, 600)
            got = sorted(p for _s, _e, p in idx.stabbing(t))
            want = sorted(p for s, e, p in entries if s <= t < e)
            assert got == want, (trial, t, got, want)
            lo = rng.randint(-10, 600)
            hi = lo + rng.randint(1, 120)
            got = sorted(p for _s, _e, p in idx.overlapping(lo, hi))
            want = sorted(p for s, e, p in entries if s < hi and e > lo)
            assert got == want, (trial, lo, hi, got, want)


def test_range_map_splice_add_matches_merge_add():
    """r16: ``ReducingRangeMap.add`` splices single ranges in O(log N +
    touched) instead of the full merge rebuild (one add per commit on the
    serving hot path).  The splice must produce the IDENTICAL canonical
    compacted form the merge path produces — boundaries AND values — for
    every reduce function, including reducers that equalize neighbouring
    gaps (max above both) and non-commutative ones."""
    import random

    def merge_add(m, ranges, value, fn):
        out = m
        for r in ranges:
            out = out.merge(ReducingRangeMap.of_ranges([r], value), fn)
        return out

    fns = [lambda a, b: a if a >= b else b,   # max: the watermark shape
           lambda a, b: a + b,                # accumulating
           lambda a, b: min(a, b),
           lambda a, b: b]                    # last-writer (non-commut.)
    rng = random.Random(11)
    for trial in range(400):
        fn = rng.choice(fns)
        m_new = ReducingRangeMap.empty()
        m_old = ReducingRangeMap.empty()
        for _step in range(rng.randint(1, 12)):
            n = rng.randint(1, 3)
            pts = sorted(rng.sample(range(0, 64), 2 * n))
            ranges = [Range(pts[2 * i], pts[2 * i + 1]) for i in range(n)
                      if pts[2 * i] < pts[2 * i + 1]]
            if not ranges:
                continue
            val = rng.randint(0, 5)
            m_new = m_new.add(ranges, val, fn)
            m_old = merge_add(m_old, ranges, val, fn)
            assert m_new.boundaries == m_old.boundaries, (trial, m_new, m_old)
            assert m_new.values == m_old.values, (trial, m_new, m_old)
        # the results keep answering point queries identically
        for t in range(-2, 66):
            assert m_new.get(t) == m_old.get(t)


def _max_conflicts_history(shape, rng):
    """(keys_or_ranges, ts) updates as the stores issue them: a txn's
    Keys at PreAccept / Accept / Commit (the same or a later ts), a range
    txn's Ranges, GetMaxConflict's RoutingKeys, and install_history's one
    update over the store's whole owned Ranges."""
    from accord_tpu.primitives.keys import IntKey, Keys, RoutingKeys
    from accord_tpu.primitives.timestamp import (Domain, Timestamp, TxnId,
                                                 TxnKind)

    def ts():
        hlc, node = rng.randint(1, 40), rng.randint(1, 3)
        if rng.random() < 0.3:       # a TxnId equal to a Timestamp
            return TxnId.create(1, hlc, TxnKind.Write, Domain.Key, node)
        return Timestamp.from_values(1, hlc, node)

    def ranges():
        pts = sorted(rng.sample(range(0, 260), 2 * rng.randint(1, 3)))
        return Ranges([Range(pts[2 * i], pts[2 * i + 1])
                       for i in range(len(pts) // 2)])

    owned = Ranges.of(Range(20, 90), Range(150, 240))
    out = []
    for _ in range(rng.randint(20, 80)):
        kind = shape if shape != "mixed" else rng.choice(
            ["points", "ranges", "owned"])
        if kind == "points":
            toks = [rng.randint(0, 255) for _ in range(rng.randint(1, 8))]
            keys = (Keys([IntKey(t) for t in toks]) if rng.random() < 0.8
                    else RoutingKeys(toks))
            t = ts()
            out.append((keys, t))
            if rng.random() < 0.5:   # its Commit, at the decided executeAt
                out.append((keys, max(t, ts())))
        elif kind == "ranges":
            out.append((ranges(), ts()))
        else:
            out.append((owned, ts()) if rng.random() < 0.2
                       else (Keys([IntKey(rng.randint(0, 255))]), ts()))
    return out


def _assert_max_conflicts_matches(updates, rng):
    """Feed ``updates`` through ``MaxConflicts`` and, as its map did before
    the splice moved in place, through ``ReducingRangeMap.add`` with the
    max reducer: the same boundaries and values after every update, the
    same lists throughout, and the same ``get_max`` for Keys and Ranges."""
    from accord_tpu.local.redundant import MaxConflicts
    from accord_tpu.primitives.keys import IntKey, Keys
    from accord_tpu.primitives.timestamp import Timestamp, max_timestamp
    fmax = lambda a, b: a if a >= b else b   # noqa: E731

    def as_ranges(q):
        if isinstance(q, Ranges):
            return q
        return Ranges([Range(t, t + 1) for t in q.tokens()])

    mc, ref = MaxConflicts(), ReducingRangeMap.empty()
    b, v = mc._b, mc._v
    for q, ts in updates:
        mc.update(q, ts)
        ref = ref.add(as_ranges(q), ts, fmax)
        assert mc._b == list(ref.boundaries), (q, ts, mc._b, ref)
        assert mc._v == list(ref.values), (q, ts, mc._v, ref)
    assert mc._b is b and mc._v is v
    assert mc.n_adds == sum(len(q) for q, _ts in updates)
    hi = max(ref.boundaries, default=0) + 3
    for _ in range(60):
        if rng.random() < 0.5:
            q = Keys([IntKey(rng.randint(-2, hi))
                      for _ in range(rng.randint(1, 6))])
        else:
            pts = sorted(rng.sample(range(-2, hi + 1), 2))
            q = Ranges.of(Range(pts[0], pts[1]))
        want = ref.fold_over_ranges(
            as_ranges(q), lambda x, acc: max_timestamp(acc, x), None)
        assert mc.get_max(q) == (want if want is not None
                                 else Timestamp.NONE), q
    return mc


def _journal_order_histories(monkeypatch):
    """A three-node sim cluster's contended multi-key traffic, then node 2
    restarted from its journal: every store's updates as the protocol
    issued them live, and the restored stores' as the journal replay
    issued them, each with the store whose MaxConflicts took them."""
    from accord_tpu.local.redundant import MaxConflicts
    from accord_tpu.sim.cluster import Cluster
    from accord_tpu.sim.kvstore import KVDataStore, kv_txn
    from accord_tpu.sim.topology_factory import build_topology
    import random
    logs = {}
    live_update = MaxConflicts.update

    def recording(self, keys_or_ranges, ts):
        logs.setdefault(id(self), []).append((keys_or_ranges, ts))
        live_update(self, keys_or_ranges, ts)

    monkeypatch.setattr(MaxConflicts, "update", recording)
    cluster = Cluster(topology=build_topology(1, (1, 2, 3), 3, 4), seed=7,
                      data_store_factory=KVDataStore)
    rng = random.Random(7)
    done = []
    for i in range(24):
        keys = rng.sample([10, 20, 30, 250_000, 500_010, 740_000], 3)
        cluster.nodes[1 + i % 3].coordinate(
            kv_txn(keys, {keys[0]: (f"v{i}",)})).begin(
            lambda r, f: done.append(f))
        if i % 4 == 3:               # four at a time: they contend
            cluster.run_until_quiescent()
    cluster.run_until_quiescent()
    assert done == [None] * 24 and cluster.failures == []
    stores = [s for n in cluster.nodes.values()
              for s in n.command_stores.unsafe_all_stores()]
    live = [(s.max_conflicts, logs[id(s.max_conflicts)]) for s in stores]
    restored = cluster.restart_node(2)
    replayed = [(s.max_conflicts, logs[id(s.max_conflicts)])
                for s in restored.command_stores.unsafe_all_stores()]
    return live + replayed


@pytest.mark.parametrize("shape", ["points", "ranges", "owned", "mixed",
                                   "journal-order"])
def test_max_conflicts_splice_in_place_matches_the_immutable_map(
        shape, monkeypatch):
    """``MaxConflicts`` splices each witnessed range into its own two lists
    in place; ``ReducingRangeMap.add`` (which stays immutable, and which
    it used before) is the reference: identical canonical form, boundary
    for boundary, after every update of random histories of point keys,
    ranges and whole-owned-range updates, and identical ``get_max``.  The
    ``journal-order`` case replays each store's own updates, live and as
    the journal's restore issued them: a fresh structure fed them in that
    order ends with the store's lists, and answers as the reference does."""
    import random
    rng = random.Random(43)
    if shape != "journal-order":
        for _trial in range(60):
            _assert_max_conflicts_matches(
                _max_conflicts_history(shape, rng), rng)
        return
    histories = _journal_order_histories(monkeypatch)
    assert all(updates for _mc, updates in histories)
    monkeypatch.undo()
    for store_mc, updates in histories:
        mc = _assert_max_conflicts_matches(updates, rng)
        assert (mc._b, mc._v) == (store_mc._b, store_mc._v)


def test_range_map_splice_add_edges():
    """Splice edge shapes: exact-boundary hits, containment, adjacency,
    empty map, full overwrite."""
    fmax = lambda a, b: a if a >= b else b   # noqa: E731
    m = ReducingRangeMap.empty().add([Range(10, 20)], 5, fmax)
    assert (m.boundaries, m.values) == ((10, 20), (None, 5, None))
    # same range, smaller value: unchanged (max), still compacted
    m2 = m.add([Range(10, 20)], 3, fmax)
    assert (m2.boundaries, m2.values) == ((10, 20), (None, 5, None))
    # interior sub-range with larger value splits
    m3 = m.add([Range(12, 15)], 9, fmax)
    assert (m3.boundaries, m3.values) == ((10, 12, 15, 20),
                                          (None, 5, 9, 5, None))
    # covering range with a larger value swallows the splits back
    m4 = m3.add([Range(0, 30)], 9, fmax)
    assert (m4.boundaries, m4.values) == ((0, 30), (None, 9, None))
    # adjacency: [20, 30) with the same value extends without a seam
    m5 = m.add([Range(20, 30)], 5, fmax)
    assert (m5.boundaries, m5.values) == ((10, 30), (None, 5, None))
    # exact left-edge overwrite
    m6 = m.add([Range(10, 12)], 7, fmax)
    assert (m6.boundaries, m6.values) == ((10, 12, 20), (None, 7, 5, None))
