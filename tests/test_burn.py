"""Burn-test gate: the deterministic chaos simulation must complete — every
op resolved, strict serializability verified — across many seeds.

Ref behavior to match: accord-core/src/test/java/accord/burn/BurnTest.java
:546-591 (watchdogged seeds, seed replayable from the failure message).
The livelock class this guards against: recovery/progress-log storms that
never quiesce (round-1 seed 2 regression).
"""

import pytest

from accord_tpu.sim.burn import run_burn

SEEDS = list(range(20))


@pytest.mark.parametrize("seed", SEEDS)
def test_burn_seed(seed):
    result = run_burn(seed, n_ops=200)
    assert result.ops_unresolved == 0, (
        f"seed {seed}: {result.ops_unresolved} ops never resolved "
        f"(repro: python -m accord_tpu.sim.burn -s {seed} -o 200)")
    # chaos may legitimately fail ops (timeouts/invalidation/crashed
    # coordinators), but the vast majority must commit
    assert result.ops_ok >= 2 * result.ops_failed, f"seed {seed}: {result}"
    # the persistence chaos must actually have been exercised
    assert result.restarts >= 1 and result.evictions >= 1, f"seed {seed}: {result}"


def test_burn_deterministic():
    """Same seed -> identical outcome (the race detector,
    ref: burn/ReconcilingLogger same-seed diffing) — including through
    clock drift, crash-restarts and journal eviction/reload.  The r09 obs
    exports join the matrix: the metrics snapshot and the canonical span
    export must be BYTE-IDENTICAL across the double run (sim-time
    stamping only — a wall-clock leak into either is a determinism bug),
    and spans must survive the run's crash-restarts (a dead coordinator's
    open spans export as unfinished, never corrupt)."""
    a = run_burn(11, n_ops=40)
    b = run_burn(11, n_ops=40)
    assert (a.ops_ok, a.ops_failed, a.epochs, a.restarts, a.evictions) == \
        (b.ops_ok, b.ops_failed, b.epochs, b.restarts, b.evictions)
    assert a.stats == b.stats
    assert a.metrics_snapshot == b.metrics_snapshot
    assert a.span_export == b.span_export
    if a.span_export is not None:       # ACCORD_TPU_OBS=off canary run
        import json
        doc = json.loads(a.span_export)
        assert doc["spans"], "burn coordinated txns but exported no spans"
        assert a.restarts >= 1          # the crash-restart leg was exercised
        phases = {c["name"] for r in doc["spans"]
                  for c in r.get("children", ())}
        assert {"preaccept", "stable", "apply"} <= phases, phases
        assert a.fast_path_rate is not None and 0 <= a.fast_path_rate <= 1


def test_burn_seed7_30ops_epoch_turnover():
    """Regression: a txn with an old TxnId slow-pathing past a bootstrap
    fence used to lose its write on the joining replica (snapshot didn't
    contain it, joiner skipped it as pre-bootstrap).  Fixed by rejectBefore
    (ExclusiveSyncPoint fences lower TxnIds) + executeAt-gated apply."""
    result = run_burn(7, n_ops=30)
    assert result.ops_unresolved == 0


@pytest.mark.parametrize("seed", [3, 8, 15])
def test_burn_endurance(seed):
    """Endurance gate: 500 ops across a 60s workload window with chaos,
    churn and restarts all on.  This is exactly the horizon where the
    round-3 wedge lived (re-bootstrap fences stuck at ReadyToExecute behind
    a CheckStatus refetch storm — seed 3 ground ~4 minutes wall); the
    progress log standing down once local knowledge is maximal keeps the
    fetch traffic bounded and the run converging promptly."""
    result = run_burn(seed, n_ops=500, workload_micros=60_000_000)
    assert result.ops_unresolved == 0, (
        f"seed {seed}: {result.ops_unresolved} ops never resolved")
    assert result.ops_ok >= 4 * result.ops_failed, f"seed {seed}: {result}"
    # the refetch storm must stay dead: the healthy ceiling is a few
    # CheckStatus per blocked txn, orders of magnitude below the 122k
    # the wedge produced at this op count
    assert result.stats.get("CheckStatus", 0) < 40_000, (
        f"seed {seed}: CheckStatus storm is back: "
        f"{result.stats.get('CheckStatus')}")


@pytest.mark.parametrize("rf", [2, 3, 4, 5, 6, 7, 8, 9])
def test_burn_rf_sweep(rf):
    """Quorum geometry sweep rf 2..9 with node count up to 3*rf and churn
    (incl. FASTPATH electorate mutation) on
    (ref: BurnTest.java:600-609 + TopologyRandomizer FASTPATH)."""
    n = 3 * rf if rf <= 6 else 2 * rf + rf // 2
    result = run_burn(700 + rf, n_ops=60,
                      node_ids=tuple(range(1, n + 1)), rf=rf,
                      shards=min(6, max(4, rf)))
    assert result.ops_unresolved == 0, f"rf={rf}: {result}"
    assert result.ops_ok >= 2 * result.ops_failed, f"rf={rf}: {result}"


@pytest.mark.parametrize("seed", [201, 202])
def test_burn_big_cluster(seed):
    """Quorum geometry beyond rf=3 (ref: BurnTest rf 2..9): 7 nodes, rf 5,
    with churn preserving the replication degree."""
    result = run_burn(seed, n_ops=120, node_ids=(1, 2, 3, 4, 5, 6, 7),
                      rf=5, shards=6)
    assert result.ops_unresolved == 0, (
        f"seed {seed}: {result} (repro: rf=5 nodes=7)")
    assert result.ops_ok >= 2 * result.ops_failed, f"seed {seed}: {result}"


@pytest.mark.parametrize("seed", list(range(900, 920)))
def test_burn_boundary_churn_sweep(seed):
    """Arbitrary shard-boundary churn (ref: TopologyRandomizer.java:427
    SPLIT/MERGE/MOVE): every epoch change splits one range, merges two, or
    moves one boundary — stores keep PART of their ranges across epochs
    (the partial-bootstrap path a uniform re-split never drives).  20 seeds
    must converge with strict serializability intact."""
    result = run_burn(seed, n_ops=30, workload_micros=12_000_000,
                      restarts=False, boundary_churn_only=True)
    assert result.ops_unresolved == 0, f"seed {seed}: {result}"
    assert result.epochs >= 2, f"seed {seed}: no churn happened"
    assert result.ops_ok >= 2 * result.ops_failed, f"seed {seed}: {result}"


@pytest.mark.faults
@pytest.mark.parametrize("kind", ["transfer", "all"])
def test_burn_device_faults_equivalent_and_deterministic(
        kind, drain_ticks_on_device):
    """Device-fault nemesis (--device-faults): with accelerator faults
    continuously injected at 5% per boundary crossing, the burn must (a)
    complete with zero unresolved ops and zero node-level failures, (b)
    produce a protocol stream BYTE-IDENTICAL to the fault-free run at the
    same seed — same client outcomes, same message counts, same total
    deps_found (the degradation ladder is invisible), and (c) be
    deterministic under a same-seed double run including every
    fault/quarantine counter (the fault stream is seeded too)."""
    base = run_burn(5, n_ops=60)
    a = run_burn(5, n_ops=60, device_faults=kind)
    b = run_burn(5, n_ops=60, device_faults=kind)
    assert a.ops_unresolved == 0
    assert a.stats == b.stats, "same-seed fault run must replay exactly"
    assert a.span_export == b.span_export, \
        "same-seed fault run must export identical span trees"
    if a.span_export is not None:
        # the degradation ladder is protocol-invisible, so the faulted
        # run's span trees must equal the fault-free run's EXCEPT for the
        # deps_route events (quarantined stores legitimately fall back to
        # the host route) — phase timings included, byte for byte
        import json

        def strip_routes(export):
            doc = json.loads(export)
            for root in doc["spans"]:
                evs = [e for e in root.get("events", ())
                       if e["name"] != "deps_route"]
                root.pop("events", None)
                if evs:
                    root["events"] = evs
            return json.dumps(doc, sort_keys=True)

        assert strip_routes(a.span_export) == strip_routes(base.span_export)
    assert a.stats["deps_found"] == base.stats["deps_found"]
    assert (a.ops_ok, a.ops_failed, a.epochs, a.restarts, a.evictions) == \
        (base.ops_ok, base.ops_failed, base.epochs, base.restarts,
         base.evictions)
    # the ladder's own counters (and routing) may differ; everything the
    # protocol emitted must not
    ladder = ("DepsRoute.", "DeviceFault.", "DeviceDispatch.")
    skip = {"device_fallback_queries", "device_dispatches",
            "device_fused_launches", "device_fused_tick_launches"}
    strip = lambda st: {k: v for k, v in st.items()          # noqa: E731
                        if not k.startswith(ladder) and k not in skip}
    assert strip(a.stats) == strip(base.stats)
    # and the nemesis must have actually bitten
    assert any(k.startswith("DeviceFault.fault.") for k in a.stats), a.stats
    assert a.stats.get("device_fallback_queries", 0) > 0
    # the fault-free run must exercise r08 launch coalescing, so the
    # equivalence above also proves faults compose with FUSED launches
    # (except under the ACCORD_TPU_FUSION=off canary, where solo pinning
    # is exactly the property being checked)
    from accord_tpu.local.dispatch import fusion_enabled
    if fusion_enabled():
        assert base.stats.get("device_fused_launches", 0) > 0 or \
            base.stats.get("device_fused_tick_launches", 0) > 0, base.stats


@pytest.mark.parametrize("seed", [0, 5, 11])
def test_burn_recovery_nemesis_converges(seed):
    """r14 recovery-under-chaos nemesis: with chaos aimed AT live
    recoveries — coordinator kill mid-recovery, partition/heal around the
    recovery quorum, concurrent-recoverer ballot races — every burn must
    still converge with zero unresolved ops and zero node-level failures,
    and the nemesis must actually have bitten."""
    result = run_burn(seed, n_ops=80, recovery_nemesis=True)
    assert result.ops_unresolved == 0, (
        f"seed {seed}: {result.ops_unresolved} ops never resolved "
        f"(repro: python -m accord_tpu.sim.burn -s {seed} -o 80 "
        f"--recovery-nemesis)")
    # targeted coordinator kills legitimately fail more client sessions
    # than ambient chaos, but the vast majority must still commit
    assert result.ops_ok >= 2 * result.ops_failed, f"seed {seed}: {result}"
    assert sum(result.nemesis.values()) >= 3, (
        f"seed {seed}: nemesis barely fired: {result.nemesis}")
    assert result.recoveries.get("attempt", 0) > 0, result.recoveries


def test_burn_recovery_nemesis_deterministic():
    """Same-seed nemesis runs must replay byte-for-byte — protocol stats,
    recovery/nemesis counters, metrics snapshot, and the canonical span
    AND flight exports (the acceptance bar: chaos aimed at recovery stays
    inside the determinism matrix)."""
    a = run_burn(5, n_ops=60, recovery_nemesis=True)
    b = run_burn(5, n_ops=60, recovery_nemesis=True)
    assert a.stats == b.stats
    assert a.metrics_snapshot == b.metrics_snapshot
    assert a.span_export == b.span_export
    assert a.flight_export == b.flight_export
    assert a.recoveries == b.recoveries and a.nemesis == b.nemesis
    assert (a.ops_ok, a.ops_failed, a.epochs, a.restarts, a.evictions) == \
        (b.ops_ok, b.ops_failed, b.epochs, b.restarts, b.evictions)
    # every leg class must have fired at this seed (pinned so the sweep
    # can't silently degenerate to one leg)
    assert set(a.nemesis) == {"kill", "partition", "race"}, a.nemesis


@pytest.mark.faults
def test_burn_recovery_nemesis_composes_with_device_faults(
        drain_ticks_on_device):
    """The r07 device-fault nemesis and the r14 recovery nemesis compose:
    with both armed, the burn converges, replays deterministically, and
    the degradation ladder stays protocol-invisible — the composed run's
    protocol stats equal the recovery-nemesis-only run's (ladder counters
    and routing stripped, recovery lifecycle counters INCLUDED)."""
    base = run_burn(5, n_ops=60, recovery_nemesis=True)
    a = run_burn(5, n_ops=60, recovery_nemesis=True,
                 device_faults="transfer")
    b = run_burn(5, n_ops=60, recovery_nemesis=True,
                 device_faults="transfer")
    assert a.ops_unresolved == 0
    assert a.stats == b.stats, "same-seed composed run must replay exactly"
    ladder = ("DepsRoute.", "DeviceFault.", "DeviceDispatch.")
    skip = {"device_fallback_queries", "device_dispatches",
            "device_fused_launches", "device_fused_tick_launches"}
    strip = lambda st: {k: v for k, v in st.items()          # noqa: E731
                        if not k.startswith(ladder) and k not in skip}
    assert strip(a.stats) == strip(base.stats)
    assert a.recoveries == base.recoveries
    assert a.nemesis == base.nemesis
    assert any(k.startswith("DeviceFault.fault.") for k in a.stats), a.stats


@pytest.mark.parametrize("seed", [21, 22])
def test_post_chaos_quiescence_gate(seed):
    """After chaos/churn stop and the drain completes, a silent window must
    show recovery traffic decayed to idle: no CheckStatus/BeginRecovery
    grind persists (ref: BurnTest.java:480-499's message-count assertions).
    This turns 'the timeouts were chaos losses' from a claim into a
    measured property — a slow liveness leak would keep the recovery
    machinery churning here."""
    result = run_burn(seed, n_ops=150, workload_micros=25_000_000)
    assert result.ops_unresolved == 0, f"seed {seed}: {result}"
    # idle ceiling: a handful of in-flight stragglers finishing their last
    # round; sustained grind would show hundreds+
    assert result.quiet_recovery_msgs < 60, (
        f"seed {seed}: recovery traffic has not quiesced: "
        f"{result.quiet_recovery_msgs} recovery messages in the silent window")


def test_burn_reconfig_churn_composes_and_is_deterministic():
    """r17 serving-shaped epoch churn: the SAME add/remove/move planners
    the TCP reconfigure verb proposes, driven through the sim, composed
    with the recovery nemesis — byte-deterministic across a double run
    (stats + span + flight exports), every op resolved, churn fired.
    The churn stream is a fork appended after every existing one, so
    churn-off runs stay byte-identical to prior rounds by construction."""
    from accord_tpu.sim.burn import run_burn
    a = run_burn(5, n_ops=40, reconfig_churn=True, recovery_nemesis=True)
    b = run_burn(5, n_ops=40, reconfig_churn=True, recovery_nemesis=True)
    assert a.ops_unresolved == 0
    assert sum(a.reconfig_churn.values()) > 0, "churn never fired"
    assert a.epochs > 1
    diff = {k for k in set(a.stats) | set(b.stats)
            if a.stats.get(k) != b.stats.get(k)}
    assert not diff, f"nondeterministic under reconfig churn: {sorted(diff)[:6]}"
    assert a.span_export == b.span_export
    assert a.flight_export == b.flight_export
    # the churn legs ride stats for exactly this comparison
    assert any(k.startswith("ReconfigChurn.") for k in a.stats)
