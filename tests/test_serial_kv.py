"""sim/serial_kv.py, the plain reference of the list-append store: what it
accepts, what it refuses and why (scans, phantoms and stale scans among
them), its second copy in benchmarks/lib/, and
the simulated cluster at the lin-kv-5n-zipf shape replayed through it."""

import json
import os
import random

import pytest

from accord_tpu.sim import serial_kv
from accord_tpu.sim.serial_kv import NotSerial, replay

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (answered, unanswered, finals, the kind it fails with or None); a txn is
# (start, end, reads, appends)
_NO = {}
HISTORIES = {
    "serial": (
        [(0, 10, {}, {"x": (1,)}),
         (20, 30, {"x": (1,)}, {"y": (2,)}),
         (25, 50, {"x": (1,), "y": (2,)}, {"x": (3,)}),
         (60, 70, {"x": (1, 3), "y": (2,), "z": ()}, {})],
        [], {"x": (1, 3), "y": (2,)}, None),
    "concurrent-txns-any-order": (
        [(0, 10, {"y": ()}, {"x": (1,)}),
         (0, 10, {"x": (1,)}, {"y": (2,)})],
        [], {"x": (1,), "y": (2,)}, None),
    "unanswered-append-that-landed-and-one-that-did-not": (
        [(0, 10, {"x": (5,)}, {})],
        [(0, {"x": (5,)}), (0, {"x": (6,)})], {"x": (5,)}, None),
    "write-skew-cycle": (
        [(0, 10, {"y": ()}, {"x": (1,)}),
         (0, 10, {"x": ()}, {"y": (2,)})],
        [], {"x": (1,), "y": (2,)}, "cycle"),
    "three-txn-cycle": (
        [(0, 10, {"z": ()}, {"x": (1,)}),
         (0, 10, {"x": ()}, {"y": (2,)}),
         (0, 10, {"y": ()}, {"z": (3,)})],
        [], {"x": (1,), "y": (2,), "z": (3,)}, "cycle"),
    "stale-read": (
        [(0, 10, {}, {"x": (1,)}),
         (20, 30, {"x": ()}, {})],
        [], {"x": (1,)}, "stale-read"),
    "non-prefix-read": (
        [(0, 10, {}, {"x": (1,)}),
         (0, 10, {}, {"x": (2,)}),
         (0, 30, {"x": (2,)}, {})],
        [], {"x": (1, 2)}, "non-prefix"),
    "acknowledged-append-missing": (
        [(0, 10, {}, {"x": (1,)}),
         (0, 10, {}, {"x": (2,)})],
        [], {"x": (1,)}, "missing-ack"),
    "real-time-inversion": (
        [(0, 10, {}, {"x": (1,)}),
         (20, 30, {}, {"x": (2,)})],
        [], {"x": (2, 1)}, "real-time"),
    "unanswered-txn-landed-in-part": (
        [], [(0, {"x": (5,), "y": (6,)})], {"x": (5,)}, "atomicity"),
    "unanswered-txn-before-it-was-submitted": (
        [(0, 10, {"x": (5,)}, {})],
        [(20, {"x": (5,)})], {"x": (5,)}, "real-time"),
    "value-nobody-appended": (
        [], [], {"x": (5,)}, "phantom"),
    "value-twice": (
        [(0, 10, {}, {"x": (1,)})], [], {"x": (1, 1)}, "duplicate"),
}


# histories with scans: a txn that scanned has a fifth member, one
# ((lo, hi), rows) per scan; the sixth member of a history is the initial
# state.  Key 5 is loaded; keys 1 and 3 are inserted by txns.
_INSERT_1 = (0, 10, _NO, {1: ("a",)})
_INSERT_3 = (40, 50, _NO, {3: ("b",)})
_ALL = {1: ("a",), 3: ("b",), 5: ("i",)}
_LOADED = {5: ("i",)}
HISTORIES.update({
    "scan-serial": (
        [_INSERT_1,
         (20, 30, _NO, _NO, [((0, 10), [(1, ("a",)), (5, ("i",))])]),
         _INSERT_3,
         (60, 70, {5: ("i",)}, _NO,
          [((0, 4), [(1, ("a",)), (3, ("b",))]), ((6, 9), [])])],
        [], _ALL, None, _LOADED),
    "scan-concurrent-with-the-insert-it-missed": (
        [_INSERT_1, _INSERT_3,
         (45, 70, _NO, _NO, [((0, 10), [(1, ("a",)), (5, ("i",))])])],
        [], _ALL, None, _LOADED),
    # the insert of 3 was acknowledged at 50; a scan submitted at 60 has
    # to return it
    "scan-phantom": (
        [_INSERT_1, _INSERT_3,
         (60, 70, _NO, _NO, [((0, 10), [(1, ("a",)), (5, ("i",))])])],
        [], _ALL, "stale-read", _LOADED),
    # it sees the insert of 3 but not the one of 1, of ONE txn
    "scan-sees-half-a-txn": (
        [(0, 10, _NO, {1: ("a",), 3: ("b",)}),
         (5, 8, _NO, _NO, [((0, 10), [(3, ("b",)), (5, ("i",))])])],
        [], _ALL, "cycle", _LOADED),
    # key 5 was appended to, acknowledged at 10; the scan returns the
    # loaded list alone
    "scan-stale": (
        [(0, 10, _NO, {5: ("j",)}),
         (20, 30, _NO, _NO, [((0, 10), [(5, ("i",))])])],
        [], {5: ("i", "j")}, "stale-read", _LOADED),
    "scan-loses-a-loaded-record": (
        [(20, 30, _NO, _NO, [((0, 10), [])])],
        [], _LOADED, "stale-read", _LOADED),
    "scan-returns-a-record-nobody-wrote": (
        [(20, 30, _NO, _NO, [((0, 10), [(5, ("i",)), (7, ("z",))])])],
        [], _LOADED, "non-prefix", _LOADED),
    "scan-rows-out-of-order": (
        [_INSERT_1,
         (20, 30, _NO, _NO, [((0, 10), [(5, ("i",)), (1, ("a",))])])],
        [], {1: ("a",), 5: ("i",)}, "scan-shape", _LOADED),
    "scan-row-outside-its-range": (
        [(20, 30, _NO, _NO, [((0, 5), [(5, ("i",))])])],
        [], _LOADED, "scan-shape", _LOADED),
    "scan-returns-an-empty-row": (
        [_INSERT_1,
         (0, 5, _NO, _NO, [((0, 10), [(1, ()), (5, ("i",))])])],
        [], {1: ("a",), 5: ("i",)}, "scan-mismatch", _LOADED),
    "scan-disagrees-with-the-txns-own-read": (
        [(20, 30, {5: ()}, _NO, [((0, 10), [(5, ("i",))])])],
        [], _LOADED, "read-mismatch", _LOADED),
})


@pytest.mark.parametrize("name", sorted(HISTORIES))
def test_replay_accepts_serial_histories_and_names_what_it_refuses(name):
    answered, unanswered, finals, kind, *initial = HISTORIES[name]
    if kind is None:
        order = replay(answered, unanswered, finals, *initial)
        assert sorted(order) == sorted(set(order))
        assert set(range(len(answered))) <= set(order)
        return
    with pytest.raises(NotSerial) as refused:
        replay(answered, unanswered, finals, *initial)
    assert refused.value.kind == kind, refused.value
    assert str(refused.value).startswith(kind + ": ")
    if kind in ("cycle", "stale-read", "real-time"):
        assert "no serial order exists: " in str(refused.value)
        assert "->" in str(refused.value)       # the cycle is named


def test_replay_gives_the_order_it_executed():
    answered, unanswered, finals, _ = HISTORIES["serial"]
    assert replay(answered, unanswered, finals) == [0, 1, 2, 3]
    answered, unanswered, finals, _ = HISTORIES[
        "unanswered-append-that-landed-and-one-that-did-not"]
    assert replay(answered, unanswered, finals) == [1, 0]
    answered, unanswered, finals, _, initial = HISTORIES[
        "scan-concurrent-with-the-insert-it-missed"]
    # the scan before the insert it did not see; the initial state, which
    # replays first, is not in the order
    assert replay(answered, unanswered, finals, initial) == [0, 2, 1]


def test_the_benchmarks_copy_is_the_same_text():
    """benchmarks/lib/serial_scan_kv.py is the copy (PR 30: scans);
    benchmarks/lib/serial_kv.py beside it is the text from before the
    scans, which the benchmark's older driver keeps and no PR may edit."""
    with open(serial_kv.__file__) as ours, \
            open(os.path.join(ROOT, "benchmarks", "lib",
                              "serial_scan_kv.py")) as theirs:
        assert ours.read() == theirs.read()
    with open(serial_kv.__file__) as f:       # and it stands alone
        imports = [ln.split()[1] for ln in f if ln.startswith(("import ",
                                                                "from "))]
    assert imports == ["bisect"], imports


def zipf_table():
    """The benchmark's rank -> key table (drivers/served_txn.py builds the
    same from the same two numbers)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lin-kv-5n-zipf.json")) as f:
        config = json.load(f)
    n = config["sizes"]["keys"]
    keys = [k * ((1 << 32) // n) for k in range(n)]
    random.Random(config["population_seed"]).shuffle(keys)
    return keys


@pytest.mark.parametrize("device_mode", [False, True])
def test_sim_at_the_5n_zipf_shape_replays_serially(device_mode):
    """BASELINE configs[1] in the simulated cluster: 5 nodes, 16 shards, 4
    distinct Zipf-0.9 keys of 10,000 per txn through the benchmark's rank ->
    key table.  The history passes the verifier (run_workload) AND replays
    through the plain reference; the draws reach every shard evenly enough;
    PreAccept decided both ways."""
    from accord_tpu.maelstrom.runner import MaelstromRunner
    table = zipf_table()
    runner = MaelstromRunner(5, seed=11, shards=16, device_mode=device_mode)
    res = runner.run_workload(n_ops=160, n_keys=len(table), keys_per_txn=4,
                              zipf_skew=0.9, key_table=table)
    assert res.ops_unresolved == 0 and res.ops_ok >= 150, res
    assert len(res.answered) == res.ops_ok
    order = replay(res.answered, res.unanswered, res.finals)
    assert set(range(res.ops_ok)) <= set(order)
    draws = [token for _s, _e, reads, appends in res.answered
             for token in list(reads) + list(appends)]
    assert len(draws) == 4 * res.ops_ok            # distinct keys, 4 a txn
    per_shard = [0] * 16
    for token in draws:
        per_shard[token * 16 >> 32] += 1
    assert min(per_shard) > 0, per_shard
    assert max(per_shard) <= 2 * len(draws) / 16, per_shard
    metrics = runner.obs.metrics
    if runner.obs.spans is not None:               # ACCORD_TPU_OBS=off
        fast = metrics.peek_counter("txn_path", path="fast")
        slow = metrics.peek_counter("txn_path", path="slow")
        assert fast > 0 and slow > 0, (fast, slow)
        assert fast + slow >= res.ops_ok


def test_the_rank_to_key_table_spreads_the_zipf_mass_over_the_shards():
    """No shard takes more than twice an even share of the draws, by the
    distribution itself (population_seed is chosen for that)."""
    table = zipf_table()
    mass = [(rank + 1) ** -0.9 for rank in range(len(table))]
    per_shard = [0.0] * 16
    for rank, key in enumerate(table):
        per_shard[key * 16 >> 32] += mass[rank]
    total = sum(mass)
    assert max(per_shard) / total <= 2 / 16, per_shard
    assert min(per_shard) / total >= 0.5 / 16, per_shard
