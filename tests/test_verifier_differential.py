"""The verifier's cross-key check (Kahn's sort over one hub node an op)
against the fixpoint it replaced (tests/verifier_oracle.py) on seeded random
multi-key list-append histories: serial executions observed through
overlapping real-time windows, left as they are or bent by a planted
anomaly (a stale read of one key beside fresh ones, two ops' reads swapped,
an op moved in time, a write-skew pair).  The checks before the cross-key
one are shared code, so a history they refuse says nothing here: it is
counted and left out; of the others both verifiers give the same verdict,
and cycles, real-time inversions and sound histories are all among them."""

import random

import pytest

from accord_tpu.sim.verifier import (HistoryViolation,
                                     StrictSerializabilityVerifier)
from tests.verifier_oracle import FixpointVerifier

KEYS = 6


def _history(rng):
    """``(ops, finals)`` of a serial execution: op i takes effect at
    10 * i and is observed somewhere in a window around it, so windows
    overlap; ``ops`` holds ``[start, end, reads, appends]``."""
    state = {k: () for k in range(KEYS)}
    ops = []
    for i in range(rng.randint(6, 22)):
        touched = rng.sample(range(KEYS), rng.randint(1, 4))
        reads, appends = {}, {}
        for k in touched:
            if rng.random() < 0.55:
                reads[k] = state[k]
        for k in touched:
            if k not in reads or rng.random() < 0.3:
                appends[k] = (f"v{i}.{k}",)
        for k, v in appends.items():
            state[k] = state[k] + v
        at = 10 * i
        ops.append([at - rng.randint(0, 25), at + rng.randint(1, 25),
                    reads, appends])
    return ops, dict(state)


def _bend(rng, ops, finals, how):
    readers = [op for op in ops if op[2]]
    if how == "stale-read" and readers:
        # one key of a multi-key read falls back to an older state
        op = rng.choice(readers)
        k = rng.choice(sorted(op[2]))
        if op[2][k]:
            op[2][k] = op[2][k][:rng.randrange(len(op[2][k]))]
    elif how == "swapped-reads" and len(readers) > 1:
        a, b = rng.sample(readers, 2)
        shared = sorted(set(a[2]) & set(b[2]))
        if shared:
            k = rng.choice(shared)
            a[2][k], b[2][k] = b[2][k], a[2][k]
    elif how == "moved":
        op = rng.choice(ops)
        shift = rng.choice([-1, 1]) * rng.randint(40, 200)
        op[0] += shift
        op[1] += shift
    elif how == "write-skew":
        # two ops, each reading the key the other appends to as it was
        # BEFORE the other's append: a cycle no single key shows
        a, b = rng.sample(range(KEYS), 2)
        at = 10 * len(ops) + 50
        ops.append([at, at + 30, {a: finals[a]}, {b: ("skew.b",)}])
        ops.append([at + 5, at + 35, {b: finals[b]}, {a: ("skew.a",)}])
        finals[a] = finals[a] + ("skew.a",)
        finals[b] = finals[b] + ("skew.b",)


def _verdict(cls, ops, finals):
    """``shared`` when a check before the cross-key one refused the
    history, else what the cross-key check said of it."""
    v = cls()
    for start, end, reads, appends in ops:
        v.on_result(v.begin(), start, end, reads, appends)
    for token, final in finals.items():
        v.set_final(token, final)
    v._effective_finals = v._compute_effective_finals()
    try:
        v._check_prefixes()
        v._check_realtime()
        v._check_own_writes()
    except HistoryViolation:
        return "shared"
    try:
        v._check_cross_key()
    except HistoryViolation as e:
        return "cycle" if "cross-key cycle" in str(e) else \
            "inversion" if "real-time inversion" in str(e) else str(e)
    return "ok"


BENDS = ["none", "stale-read", "swapped-reads", "moved", "write-skew"]


@pytest.mark.parametrize("how", BENDS)
def test_kahn_and_the_fixpoint_give_the_same_verdict(how):
    seen = {}
    for seed in range(160):
        rng = random.Random(f"{how}/{seed}")
        ops, finals = _history(rng)
        _bend(rng, ops, finals, how)
        new = _verdict(StrictSerializabilityVerifier, ops, finals)
        old = _verdict(FixpointVerifier, ops, finals)
        assert new == old, (how, seed, new, old)
        seen[new] = seen.get(new, 0) + 1
    # the family reaches the cross-key check, and finds what was planted
    if how == "none":
        assert seen == {"ok": 160}
    elif how == "write-skew":
        assert seen == {"cycle": 160}
    elif how == "moved":
        assert seen.get("inversion", 0) >= 5 and seen.get("ok", 0) >= 5
    elif how == "stale-read":
        assert seen.get("ok", 0) >= 5 and seen.get("cycle", 0) >= 5 \
            and seen.get("inversion", 0) >= 1
    else:               # swapped reads mostly trip the per-key checks
        assert seen.get("ok", 0) >= 5


def test_a_scan_sized_op_is_what_the_fixpoint_could_not_afford():
    """Why the algorithm changed: one op over n keys is n hub edges against
    n * n pair edges; both still agree on it."""
    n = 60
    finals = {k: (f"w{k}",) for k in range(n)}
    ops = [[0, 10, {}, {k: (f"w{k}",)}] for k in range(n)]
    ops.append([20, 30, dict(finals), {}])           # the scan
    stale = dict(finals)
    stale[7] = ()                                    # a phantom, afterwards
    ops.append([40, 50, stale, {}])
    assert _verdict(StrictSerializabilityVerifier, ops[:-1], finals) \
        == _verdict(FixpointVerifier, ops[:-1], finals) == "ok"
    assert _verdict(StrictSerializabilityVerifier, ops, finals) \
        == _verdict(FixpointVerifier, ops, finals) != "ok"
