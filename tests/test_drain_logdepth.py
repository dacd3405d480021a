"""r19 log-depth drain: every route vs a brute-force host oracle.

The fixpoint kernels are the standing oracle for ``applied``/``newly``
(exactly as ``tests/deps_oracle.attribute_batch`` is for attribution),
and a brute-force host Kahn/fixpoint drain is the oracle for THEM — so
this sweep pins the
whole route fan (dense/ELL x fixpoint/log-depth x fused/solo, plus the
watermark prefix form and the routed ``drain_auto`` entrypoints) to one
numpy reference over random DAGs that exercise every gate the drain
encodes: undecided deps (block forever), invalidated/free deps (never
gate), Committed-but-not-Stable deps (decided, gate by executeAt, never
apply), ``awaits_all`` rows (gate regardless of executeAt order — the only
way blocking cycles exist), and executeAt TIES (strict ``ts_lt`` means a
tie never gates).  A divergence shrinks to a minimal counterexample and
prints the replay seed (tests/proptest.py kit).
"""

import os

import numpy as np
import jax.numpy as jnp
import pytest

from accord_tpu.ops import drain_kernel as drk
from accord_tpu.ops.deps_kernel import (SLOT_ACCEPTED, SLOT_APPLIED,
                                        SLOT_COMMITTED, SLOT_FREE,
                                        SLOT_INVALIDATED, SLOT_PREACCEPTED,
                                        SLOT_STABLE, SLOT_TRANSITIVE)
from tests.proptest import case_budget, run_property

_STATUSES = [SLOT_FREE, SLOT_TRANSITIVE, SLOT_PREACCEPTED, SLOT_ACCEPTED,
             SLOT_COMMITTED, SLOT_STABLE, SLOT_STABLE, SLOT_STABLE,
             SLOT_APPLIED, SLOT_APPLIED, SLOT_INVALIDATED]


def make_case(rng):
    n = rng.next_int_range(2, 20)
    edges = set()
    for i in range(1, n):
        for _ in range(rng.next_int(4)):
            edges.add((i, rng.next_int(i)))        # DAG backbone: dep j < i
    for _ in range(rng.next_int(3)):
        a, b = rng.next_int(n), rng.next_int(n)    # arbitrary edge: cycle
        if a != b:                                 # material (gates only
            edges.add((a, b))                      # via awaits_all rows)
    return {
        "n": n,
        "edges": sorted(edges),
        "status": [rng.pick(_STATUSES) for _ in range(n)],
        # small msb range forces executeAt TIES; node breaks some of them
        "msb": [rng.next_int(6) for _ in range(n)],
        "node": [rng.next_int_range(1, 3) for _ in range(n)],
        "awaits": [rng.decide(0.15) for _ in range(n)],
    }


def shrink_candidates(case):
    n = case["n"]
    if n > 2:
        yield {
            "n": n - 1,
            "edges": [(i, j) for i, j in case["edges"]
                      if i < n - 1 and j < n - 1],
            "status": case["status"][:n - 1],
            "msb": case["msb"][:n - 1],
            "node": case["node"][:n - 1],
            "awaits": case["awaits"][:n - 1],
        }
    for k in range(len(case["edges"])):
        yield dict(case, edges=case["edges"][:k] + case["edges"][k + 1:])
    for i, a in enumerate(case["awaits"]):
        if a:
            yield dict(case, awaits=case["awaits"][:i] + [False]
                       + case["awaits"][i + 1:])


def build_states(case):
    n = case["n"]
    adj = np.zeros((n, n), bool)
    for i, j in case["edges"]:
        adj[i, j] = True
    dense = drk.DrainState(
        jnp.asarray(adj), jnp.asarray(case["status"], jnp.int32),
        jnp.asarray(case["msb"], jnp.int64), jnp.zeros(n, jnp.int64),
        jnp.asarray(case["node"], jnp.int32), jnp.asarray(case["awaits"]))
    return dense, drk.dense_to_ell(dense)


def host_oracle(case):
    """Brute-force fixpoint on the host, mirroring the gate exactly:
    (applied, newly, level) with level[i] = the sweep that applies slot i
    (0 = already applied, -1 = never)."""
    n = case["n"]
    status = np.asarray(case["status"])
    stable = status == SLOT_STABLE
    applied0 = status == SLOT_APPLIED
    undecided = (status >= 0) & (status < SLOT_COMMITTED)
    dead = (status == SLOT_INVALIDATED) | (status == SLOT_FREE)
    # non-negative timestamps: the packed unsigned-msb flip is monotone
    # here, so plain lexicographic (msb, lsb, node) IS ts_lt
    key = [(case["msb"][i], 0, case["node"][i]) for i in range(n)]
    blocking = np.zeros((n, n), bool)
    for i, j in case["edges"]:
        gates = undecided[j] or key[j] < key[i] or case["awaits"][i]
        blocking[i, j] = gates and not dead[j]
    applied = applied0.copy()
    level = np.where(applied0, 0, -1)
    for sweep in range(1, n + 2):
        ready = stable & ~applied & ~(blocking & ~applied[None, :]).any(1)
        if not ready.any():
            break
        applied |= ready
        level[ready] = sweep
    return applied, applied & ~applied0, level


def check(case):
    dense, ell = build_states(case)
    want_applied, want_newly, want_level = host_oracle(case)

    def eq(tag, got_applied, got_newly):
        assert np.array_equal(np.asarray(got_applied), want_applied) \
            and np.array_equal(np.asarray(got_newly), want_newly), \
            f"{tag}: applied/newly diverged from host oracle"

    a, nw, _s = drk.drain_levels(dense)
    eq("dense-fixpoint", a, nw)
    a, nw, _r = drk.drain_logdepth(dense)
    eq("dense-logdepth", a, nw)
    a, nw, _q = drk.drain_dense_logsq(dense)
    eq("dense-logsq", a, nw)
    a, nw, _s = drk.drain_ell_levels(ell)
    eq("ell-fixpoint", a, nw)
    a, nw, _r = drk.drain_ell_logdepth(ell)
    eq("ell-logdepth", a, nw)
    a, nw, _s, _route = drk.drain_auto(dense)
    eq("dense-auto", a, nw)
    a, nw, _s, _route = drk.drain_ell_auto(ell)
    eq("ell-auto", a, nw)
    # level assignment: the finite levels ARE the oracle's sweep indices
    lv, _rounds = drk.level_assign_ell(ell)
    lv = np.asarray(lv)
    got = np.where(lv < drk.LEVEL_INF, lv, -1)
    want = np.where((want_level > 0) | (np.asarray(case["status"])
                                        == SLOT_APPLIED), want_level, -1)
    assert np.array_equal(got, want), \
        f"level_assign_ell levels {got} != oracle sweeps {want}"
    lvd, _rounds = drk.level_assign_dense(dense)
    assert np.array_equal(np.asarray(lvd), lv), \
        "dense/ell level assignment disagree"
    # watermark drain == the exact w-sweep fixpoint prefix
    status = np.asarray(case["status"])
    for w in (0, 1, 2, case["n"]):
        aw, nww = drk.drain_ell_watermark(ell, jnp.int32(w))
        prefix = (status == SLOT_APPLIED) | \
            ((want_level >= 0) & (want_level <= w))
        assert np.array_equal(np.asarray(aw), prefix), \
            f"ell watermark {w} != {w}-sweep prefix"
        ad, _ = drk.drain_dense_watermark(dense, jnp.int32(w))
        assert np.array_equal(np.asarray(ad), prefix), \
            f"dense watermark {w} != {w}-sweep prefix"
    # fused frontier == solo frontier, per member (pad-and-stack must not
    # change any store's candidates)
    solo_d = np.asarray(drk.ready_frontier(dense))
    solo_e = np.asarray(drk.ready_frontier_ell(ell))
    fused_d = np.asarray(drk.fused_ready_frontier([dense, dense]))
    fused_e = np.asarray(drk.fused_ready_frontier_ell([ell, ell]))
    for row in range(2):
        assert np.array_equal(fused_d[row][:case["n"]], solo_d), \
            "fused dense frontier != solo"
        assert np.array_equal(fused_e[row][:case["n"]], solo_e), \
            "fused ell frontier != solo"


def test_drain_routes_vs_host_oracle():
    n = run_property(
        case_budget(60), base_seed=19,
        make_case=make_case, check=check,
        shrink_candidates=shrink_candidates,
        replay_hint="python -m pytest tests/test_drain_logdepth.py -q")
    assert n >= 1


@pytest.mark.slow
def test_drain_routes_vs_host_oracle_soak():
    run_property(
        case_budget(1000), base_seed=1019,
        make_case=make_case, check=check,
        shrink_candidates=shrink_candidates,
        replay_hint="python -m pytest tests/test_drain_logdepth.py -q")


def test_escape_hatch_pins_fixpoint(monkeypatch):
    """ACCORD_TPU_DRAIN=fixpoint routes every drain_auto call to the
    fixpoint oracle (same contract as ACCORD_TPU_FUSION=off)."""
    monkeypatch.setenv("ACCORD_TPU_DRAIN", "fixpoint")
    assert not drk.drain_logdepth_enabled()
    case = {"n": 4, "edges": [(1, 0), (2, 1), (3, 2)],
            "status": [SLOT_APPLIED, SLOT_STABLE, SLOT_STABLE, SLOT_STABLE],
            "msb": [0, 1, 2, 3], "node": [1, 1, 1, 1],
            "awaits": [False] * 4}
    dense, ell = build_states(case)
    a, nw, sweeps, route = drk.drain_auto(dense)
    assert route == "dense-fixpoint"
    a2, nw2, sweeps2, route2 = drk.drain_ell_auto(ell)
    assert route2 == "ell-fixpoint"
    want_applied, want_newly, _ = host_oracle(case)
    assert np.array_equal(np.asarray(a), want_applied)
    assert np.array_equal(np.asarray(a2), want_applied)
    monkeypatch.delenv("ACCORD_TPU_DRAIN")
    assert drk.drain_logdepth_enabled()


def test_route_stats_price_the_regimes(monkeypatch):
    """A deep chain prices to the doubling pass; routing learns from the
    recorded (depth, rounds) of this exact shape — no depth threshold
    exists anywhere to go stale."""
    # pricing only runs with the hatch open: pin it open so the test
    # still tests under the ACCORD_TPU_DRAIN=fixpoint canary run
    monkeypatch.delenv("ACCORD_TPU_DRAIN", raising=False)
    drk.reset_drain_routing()
    drk.set_drain_calibration(c_sweep_ell=1e-9, c_round_ell=2e-9,
                              c_sweep_dense=1e-10, c_sq_dense=1e-10,
                              c_conv=1e-9)
    try:
        chain = drk._probe_chain_ell(128)
        a, nw, r1, route1 = drk.drain_ell_auto(chain)
        assert route1 == "ell-logdepth"      # unseen shape: optimistic
        a, nw, r2, route2 = drk.drain_ell_auto(chain)
        # depth 127, rounds ~2 log2: doubling stays priced in
        assert route2 == "ell-logdepth" and r2 < 30
        counters = drk.drain_counters()
        assert counters["drain_logdepth"] == 2
    finally:
        drk.reset_drain_routing()
        drk._DRAIN_CALIB = None


def test_fused_front_cache_is_bounded():
    """The fused-frontier jit cache evicts LRU past its cap (satellite:
    shape-churning workloads must not grow it without bound)."""
    drk.reset_drain_routing()
    saved = dict(drk._FUSED_FRONT_CACHE)
    drk._FUSED_FRONT_CACHE.clear()
    try:
        for n in range(2, 2 + drk._FUSED_FRONT_CACHE_CAP + 4):
            sts = [drk._probe_chain_dense(n), drk._probe_chain_dense(n + 1)]
            drk.fused_ready_frontier(sts)
        assert len(drk._FUSED_FRONT_CACHE) == drk._FUSED_FRONT_CACHE_CAP
        assert drk.drain_counters()["fused_front_evictions"] == 4
    finally:
        drk._FUSED_FRONT_CACHE.clear()
        drk._FUSED_FRONT_CACHE.update(saved)
        drk.reset_drain_routing()
