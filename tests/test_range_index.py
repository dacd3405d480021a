"""CommandStore's range index, kept incrementally (utils/interval_index.py
RangeIndex), against the rebuild-from-scratch oracle
(tests/range_index_oracle.py) under interleaved put / re-put / drop; and the
range branch of map_reduce_active against a walk of every CommandsForKey."""

import random

import pytest

from accord_tpu.local.command_store import (CommandStore, PreLoadContext,
                                            SafeCommandStore)
from accord_tpu.local.commands_for_key import InternalStatus
from accord_tpu.primitives.keys import Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
from accord_tpu.utils.interval_index import RangeIndex
from tests.range_index_oracle import SearchableRangeList

SPACE = 1 << 20


class _Node:
    node_id = 1
    device_mode = False
    journal = None

    def progress_log_factory(self, _store):
        return None


def _store():
    store = CommandStore(0, _Node())
    store.ranges_for_epoch.snapshot(1, Ranges.of(Range(0, SPACE)))
    return store


def _tid(hlc, kind=TxnKind.Read, domain=Domain.Range):
    return TxnId.create(1, hlc, kind, domain, 1)


def _ranges(rng):
    out = []
    for _ in range(rng.randint(1, 3)):
        # scans (narrow), and now and then a fence over much of the space
        width = rng.randint(1, 200) if rng.random() < 0.9 \
            else rng.randint(SPACE // 8, SPACE // 2)
        lo = rng.randrange(0, SPACE - width)
        out.append(Range(lo, lo + width))
    return Ranges(out)


def _entries(index, lo, hi):
    return sorted(index.overlapping(lo, hi))


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_incremental_range_index_matches_the_rebuild(seed):
    rng = random.Random(seed)
    store = _store()
    live = []
    for step in range(1, 600):
        r = rng.random()
        if r < 0.6 or not live:
            tid = _tid(step)
            store.put_range_command(tid, _ranges(rng))
            live.append(tid)
        elif r < 0.75:               # re-registration: same, or widened
            tid = rng.choice(live)
            had = store.range_commands[tid]
            store.put_range_command(
                tid, had if rng.random() < 0.5 else had.with_(_ranges(rng)))
        else:
            store.drop_range_command(live.pop(rng.randrange(len(live))))
            store.drop_range_command(_tid(10_000_000))     # unknown: no-op
        if step % 5:
            continue
        oracle = SearchableRangeList(
            (r.start, r.end, tid)
            for tid, rs in store.range_commands.items() for r in rs)
        index = store.range_index()
        assert len(index) == len(oracle)
        for _ in range(8):
            token = rng.randrange(-5, SPACE + 5)
            assert sorted(index.stabbing(token)) \
                == sorted(oracle.stabbing(token))
            lo = rng.randrange(0, SPACE)
            hi = lo + rng.randint(1, SPACE // 4)
            assert _entries(index, lo, hi) == sorted(
                oracle.overlapping(lo, hi))


@pytest.mark.parametrize("reads_at", [0, 40])
def test_the_range_index_is_kept_from_its_first_reader_on(reads_at):
    """A store whose deps flush answers from the device mirror never reads
    its own range index and keeps none; the first reader gets it built
    from every range command, and from then on it is kept in step."""
    rng = random.Random(11 + reads_at)
    store = _store()
    for step in range(1, 80):
        if step == reads_at + 1:
            store.range_index()
        assert (store._range_index is None) == (step <= reads_at)
        tid = _tid(step)
        store.put_range_command(tid, _ranges(rng))
        if step % 3 == 0:
            store.drop_range_command(_tid(rng.randrange(1, step)))
    built = store._range_index
    want = sorted((r.start, r.end, tid)
                  for tid, rs in store.range_commands.items() for r in rs)
    assert _entries(store.range_index(), 0, SPACE) == want
    assert store.range_index() is built          # never rebuilt


def test_range_index_refuses_to_remove_what_it_does_not_hold():
    index = RangeIndex([(0, 10, "a"), (0, 10, "b"), (5, 2000, "c")])
    index.remove(0, 10, "a")
    assert sorted(index.stabbing(7)) == [(0, 10, "b"), (5, 2000, "c")]
    with pytest.raises((KeyError, IndexError)):
        index.remove(0, 10, "a")
    assert len(index) == 2


@pytest.mark.parametrize("seed", [8, 9])
def test_the_range_scan_reads_a_slice_of_the_keys_not_every_key(seed):
    """map_reduce_active over Ranges: the same deps as a walk of every
    CommandsForKey, and the keys outside the scanned ranges are never
    visited."""
    rng = random.Random(seed)
    store = _store()
    safe = SafeCommandStore(store, PreLoadContext.empty())
    writes = {}
    for hlc in range(1, 300):
        token = rng.randrange(SPACE)
        tid = _tid(hlc, TxnKind.Write, Domain.Key)
        safe.cfk(token).update(tid, InternalStatus.PREACCEPTED, None)
        writes.setdefault(token, []).append(tid)
    assert store._cfk_tokens == sorted(store.commands_for_key)
    visited = []
    real = store.commands_for_key

    class Spy(dict):
        def __getitem__(self, token):
            visited.append(token)
            return real[token]

    store.commands_for_key = Spy(real)
    bound = _tid(10_000)
    for _ in range(20):
        lo = rng.randrange(SPACE)
        scan = Ranges.of(Range(lo, lo + SPACE // 16))
        del visited[:]
        got = safe.map_reduce_active(
            scan, bound, bound.kind().witnesses(),
            lambda key, tid, acc: acc + [(key, tid)], [])
        want = [(t, tid) for t in sorted(writes) if scan.contains_token(t)
                for tid in writes[t]]
        assert sorted(got) == sorted(want)
        assert set(visited) == {t for t, _tid in want}
