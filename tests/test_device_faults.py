"""Device-fault tolerance matrix: the degradation ladder must be invisible
to the protocol.

Every injected fault class x every route (host / bucketed-adaptive device /
dense; the mesh kernels ride the same dispatch under the 8-device test mesh)
must yield BYTE-IDENTICAL attributed deps vs. a fault-free run — the
quarantine -> host-fallback ladder in local.device_index absorbs the fault.
Plus the state machine itself: quarantine -> exponential backoff -> probe ->
restore transitions, shadow-verify catching silent result corruption, and
the HBM budget path compacting below the RedundantBefore floor then
degrading pinned-to-host instead of dying."""

import os

import numpy as np
import pytest

from accord_tpu.primitives.deps import DepsBuilder
from accord_tpu.utils import faults
from accord_tpu.utils.random_source import RandomSource

from tests.conftest import make_device_state, make_dispatch_node
from tests.test_routing import (_attributed, _build, _enqueue_flush,
                                _reference, _unpack_builders)

pytestmark = pytest.mark.faults

ROUTES = ("host", "device", "dense")
RAISING = ("kernel_launch", "transfer")


def _rng():
    return RandomSource(0xDEC0)


def _dev_q(dev):
    """Total queries served by ANY device route (the auto test mesh routes
    'dense' through the sharded kernels)."""
    return (dev.n_dense_queries + dev.n_bucketed_queries
            + dev.n_mesh_queries)


# ---------------------------------------------------------------------------
# fault x route equivalence matrix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("kind", RAISING)
@pytest.mark.parametrize("seed", [31, 53])
def test_fault_route_matrix_raising(route, kind, seed):
    """Launch/transfer faults at p=1.0 on every route: the WHOLE flush
    fails over to the host route (same bytes — the host filter applies the
    identical floor/elision drops, and both equal the reference), then the
    store quarantines."""
    store, dev, safe, entries, floor, qs = _build(seed=seed)
    dev.route_override = route
    expect = _attributed(dev, safe, qs)
    assert expect == _reference(dev, safe, qs)
    with faults.device_fault(kind, 1.0, _rng()):
        got = _attributed(dev, safe, qs)
    assert got == expect
    if route == "host":
        # the host route never crosses the device boundary: no faults
        assert dev.n_device_faults == 0
    else:
        assert dev.n_device_faults >= 1
        assert dev.n_quarantines >= 1
        assert dev._dev_quar_flushes > 0 or dev._dev_backoff > 0
        assert dev.n_fallback_queries >= len(qs)


@pytest.mark.parametrize("route", ROUTES)
def test_fault_route_matrix_stale_result(route):
    """Silent result corruption at p=1.0: paranoia shadow-verify catches the
    mismatch, quarantines the route, and serves the host answer — results
    stay byte-identical."""
    store, dev, safe, entries, floor, qs = _build(seed=32)
    dev.route_override = route
    dev.paranoia = True
    expect = _attributed(dev, safe, qs)
    checks_before = dev.n_shadow_checks
    with faults.device_fault("stale_result", 1.0, _rng()):
        got = _attributed(dev, safe, qs)
    assert got == expect
    if route == "host":
        assert dev.n_shadow_mismatches == 0
    else:
        assert dev.n_shadow_checks > checks_before
        assert dev.n_shadow_mismatches >= 1
        assert dev.n_quarantines >= 1


def test_paranoia_clean_run_restores_nothing():
    """Shadow-verify on a healthy device: every check passes, no
    quarantine, and the device routes keep serving."""
    store, dev, safe, entries, floor, qs = _build(seed=33)
    dev.route_override = "dense"
    dev.paranoia = True
    _attributed(dev, safe, qs)
    assert dev.n_shadow_checks >= 1
    assert dev.n_shadow_mismatches == 0
    assert dev.n_quarantines == 0


# ---------------------------------------------------------------------------
# fused launches (r08) x the fault ladder: a device fault inside a fused
# launch fails the WHOLE batch over to the host route deterministically,
# then quarantines per-store exactly as solo faults do
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", RAISING)
def test_fused_launch_fault_fails_whole_batch_to_host(kind):
    """Launch/upload faults at p=1.0 fire INSIDE the fused dispatch: every
    member store's flush fails over to host with byte-identical results,
    and every member quarantines."""
    node, stores = make_dispatch_node((31, 47), fusion=True)
    expected = [_attributed(dev, safe, qs)
                for dev, safe, qs in stores]
    results = []
    with faults.device_fault(kind, 1.0, _rng()):
        for dev, _safe, qs in stores:
            results.append(_enqueue_flush(dev, qs))
        node.scheduler.run()
    if kind == "kernel_launch":
        assert node.dispatcher.n_fused_launches == 0  # never left the host
    # (a transfer fault fires at the upload when the table is cold, or at
    # the shared download when it is cached — either way the whole batch
    # fails over below)
    for i, (dev, _safe, _qs) in enumerate(stores):
        builders, failures = results[i]
        assert not failures
        assert _unpack_builders(builders) == expected[i], f"store {i}"
        assert dev.n_device_faults >= 1
        assert dev.n_quarantines >= 1
        assert dev.n_fallback_queries > 0


def test_fused_download_fault_fails_whole_batch_to_host():
    """The fused launch succeeds but the shared result download faults at
    harvest: the first member poisons the batch, EVERY member quarantines
    and serves its flush from the begin-time snapshot host scan — same
    bytes."""
    node, stores = make_dispatch_node((31, 47), fusion=True)
    expected = [_attributed(dev, safe, qs)
                for dev, safe, qs in stores]
    results = [_enqueue_flush(dev, qs) for dev, _safe, qs in stores]
    # step ONE scheduler event: the dispatcher — the fused launch is
    # enqueued healthy; then arm the fault so it fires at download
    node.scheduler.q.pop(0)()
    assert node.dispatcher.n_fused_launches == 1
    with faults.device_fault("transfer", 1.0, _rng()):
        node.scheduler.run()
    for i, (dev, _safe, _qs) in enumerate(stores):
        builders, failures = results[i]
        assert not failures
        assert _unpack_builders(builders) == expected[i], f"store {i}"
        assert dev.n_quarantines >= 1
        assert dev.n_fallback_queries > 0


def test_fused_stale_result_detected_by_shadow():
    """Silent corruption mid-fused batch: paranoia shadow-verify (against
    the begin-time SNAPSHOT host scan) catches every member's mismatch,
    quarantines, and serves the host answer — results stay
    byte-identical."""
    node, stores = make_dispatch_node((31, 47), fusion=True)
    for dev, _safe, _qs in stores:
        dev.paranoia = True
    expected = [_attributed(dev, safe, qs)
                for dev, safe, qs in stores]
    results = [_enqueue_flush(dev, qs) for dev, _safe, qs in stores]
    with faults.device_fault("stale_result", 1.0, _rng()):
        node.scheduler.run()
    assert node.dispatcher.n_fused_launches == 1
    for i, (dev, _safe, _qs) in enumerate(stores):
        builders, failures = results[i]
        assert not failures
        assert _unpack_builders(builders) == expected[i], f"store {i}"
        assert dev.n_shadow_mismatches >= 1
        assert dev.n_quarantines >= 1


def test_fused_quarantine_recovers_to_fused():
    """After a fused-batch fault, the members re-probe independently and —
    once healthy — fuse again: the ladder composes with coalescing."""
    node, stores = make_dispatch_node((31, 47), fusion=True)
    expected = [_attributed(dev, safe, qs)
                for dev, safe, qs in stores]

    def round_trip():
        results = [_enqueue_flush(dev, qs) for dev, _safe, qs in stores]
        node.scheduler.run()
        for i in range(len(stores)):
            builders, failures = results[i]
            assert not failures
            assert _unpack_builders(builders) == expected[i]

    with faults.device_fault("kernel_launch", 1.0, _rng()):
        round_trip()                       # faulted fused dispatch
    quarantined = max(dev._dev_quar_flushes for dev, _s, _q in stores)
    assert quarantined > 0
    for _ in range(quarantined):           # burn down the quarantine
        round_trip()
    launches_before = node.dispatcher.n_fused_launches
    round_trip()                           # probe flushes: healthy again
    round_trip()                           # ...and fusing again
    assert node.dispatcher.n_fused_launches > launches_before
    for dev, _s, _q in stores:
        assert dev._dev_quar_flushes == 0 and dev._dev_backoff == 0


# ---------------------------------------------------------------------------
# quarantine state machine: enter -> backoff -> probe -> restore
# ---------------------------------------------------------------------------
def test_quarantine_backoff_probe_restore():
    store, dev, safe, entries, floor, qs = _build(seed=34)
    dev.route_override = "dense"
    expect = _attributed(dev, safe, qs)
    with faults.device_fault("transfer", 1.0, _rng()):
        got = _attributed(dev, safe, qs)   # faulted flush
    assert got == expect
    assert dev.n_quarantines == 1 and dev._dev_backoff == 1
    quarantined = dev._dev_quar_flushes
    assert quarantined > 0
    # while quarantined every flush is pinned to host (no device queries)
    dev_mid = _dev_q(dev)
    fallback_before = dev.n_fallback_queries
    for _ in range(quarantined):
        assert _attributed(dev, safe, qs) == expect
    assert _dev_q(dev) == dev_mid
    assert dev.n_fallback_queries > fallback_before
    assert dev._dev_quar_flushes == 0
    # quarantine expired: the next flush is the PROBE — fault gone, so it
    # succeeds on the device route and restores health
    assert _attributed(dev, safe, qs) == expect
    assert dev.n_reprobes == 1
    assert dev.n_restores == 1
    assert dev._dev_backoff == 0 and dev._dev_quar_flushes == 0
    assert _dev_q(dev) > dev_mid
    # and the restored route keeps serving device-side
    dev_after = _dev_q(dev)
    assert _attributed(dev, safe, qs) == expect
    assert _dev_q(dev) > dev_after


def test_probe_failure_requarantines_deeper():
    store, dev, safe, entries, floor, qs = _build(seed=35)
    dev.route_override = "dense"
    expect = _attributed(dev, safe, qs)
    with faults.device_fault("kernel_launch", 1.0, _rng()):
        assert _attributed(dev, safe, qs) == expect
        first = dev._dev_quar_flushes
        # burn down the quarantine with the fault STILL armed: the probe
        # flush fails and re-quarantines with a deeper backoff
        for _ in range(first + 1):
            assert _attributed(dev, safe, qs) == expect
    assert dev._dev_backoff == 2
    assert dev.n_quarantines == 2
    assert dev._dev_quar_flushes > first  # exponential: 8+jitter > 4+jitter


# ---------------------------------------------------------------------------
# HBM capacity backpressure: budget -> compaction -> degrade-to-host
# ---------------------------------------------------------------------------
def _register_n(dev, n, hlc_base, keyspace=4096):
    from accord_tpu.local.commands_for_key import InternalStatus
    from accord_tpu.primitives.keys import IntKey, Keys
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    ids = []
    for i in range(n):
        tid = TxnId.create(1, hlc_base + i, TxnKind.Write, Domain.Key,
                           1 + (i % 5))
        dev.register(tid, int(InternalStatus.PREACCEPTED),
                     Keys([IntKey((i * 37) % keyspace)]))
        ids.append(tid)
    return ids


def test_oom_budget_compacts_below_floor():
    """At the budget, _grow_capacity frees the below-floor tail instead of
    doubling: capacity stays flat, the store keeps accepting txns."""
    from accord_tpu.primitives.keys import Range, Ranges
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    store, dev, safe = make_device_state(mesh=None)
    dev.device_budget_slots = 128
    _register_n(dev, 100, hlc_base=1)
    # everything registered so far is redundant (covered by the watermark)
    floor = TxnId.create(1, 100_000, TxnKind.ExclusiveSyncPoint,
                         Domain.Range, 1)
    store.redundant_before.add_redundant(
        Ranges.of(Range(-(1 << 60), 1 << 60)), floor)
    assert dev.deps.capacity == 128
    _register_n(dev, 100, hlc_base=200_000)   # forces grow past the budget
    assert dev.n_compactions >= 1
    assert dev.n_compacted_slots >= 100
    assert dev.deps.capacity == 128           # compacted, not doubled
    assert not dev.host_pinned


def test_oom_degrades_to_host_when_compaction_cannot_help():
    """No floor to compact under: the budget breach degrades the store to
    pinned-host (degraded-but-live) — and results stay correct."""
    store, dev, safe = make_device_state(mesh=None)
    dev.route_override = "dense"
    dev.device_budget_slots = 128
    _register_n(dev, 200, hlc_base=1)         # no RedundantBefore floor set
    assert dev.n_compactions >= 1
    assert dev.host_pinned
    assert dev.n_oom_degraded == 1
    assert dev.deps.capacity >= 256           # host arrays still grew: live
    # flushes now pin to host regardless of the route override, and agree
    # with an unbudgeted reference store over the same registrations
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    bound = TxnId.create(1, 10_000_000, TxnKind.Write, Domain.Key, 1)
    qs = [(bound, bound, bound.kind().witnesses(), [(i * 37) % 4096], [])
          for i in range(8)]
    got = _attributed(dev, safe, qs)
    store2, dev2, safe2 = make_device_state(mesh=None)
    dev2.route_override = "dense"
    _register_n(dev2, 200, hlc_base=1)
    expect = _attributed(dev2, safe2, qs)
    assert got == expect
    host_before = dev.n_host_queries
    _attributed(dev, safe, qs)
    assert dev.n_host_queries > host_before


def test_injected_hbm_oom_triggers_backpressure():
    """The hbm_oom fault class forces the budget path without a budget."""
    store, dev, safe = make_device_state(mesh=None)
    with faults.device_fault("hbm_oom", 1.0, _rng()):
        _register_n(dev, 200, hlc_base=1)
    assert dev.n_compactions >= 1
    assert dev.host_pinned and dev.n_oom_degraded == 1


# ---------------------------------------------------------------------------
# faults.enabled context manager (flag flips without try/finally)
# ---------------------------------------------------------------------------
def test_enabled_context_manager_flips_and_restores():
    assert faults.TRANSACTION_INSTABILITY is False
    with faults.enabled("TRANSACTION_INSTABILITY"):
        assert faults.TRANSACTION_INSTABILITY is True
        with faults.enabled("PARANOIA"):
            assert faults.PARANOIA is True
        assert faults.PARANOIA is False
    assert faults.TRANSACTION_INSTABILITY is False


def test_enabled_rejects_unknown_flags():
    with pytest.raises(AttributeError):
        with faults.enabled("NO_SUCH_FLAG"):
            pass
    with pytest.raises(ValueError):
        with faults.enabled("DEVICE_FAULT_KINDS"):
            pass


def test_inject_rejects_unknown_kind():
    with pytest.raises(ValueError):
        faults.inject_device_fault("bit_flip", 0.5, _rng())


def test_device_fault_context_restores_prior_arming():
    faults.inject_device_fault("transfer", 0.25, _rng())
    try:
        with faults.device_fault("transfer", 1.0, _rng()):
            assert faults.active_device_faults()["transfer"] == 1.0
        assert faults.active_device_faults()["transfer"] == 0.25
    finally:
        faults.clear_device_faults()
    assert faults.active_device_faults() == {}


# ---------------------------------------------------------------------------
# r15: faults during the collect of the in-kernel floored/elided entries
# (seed 53; the route x kind matrix is test_fault_route_matrix_raising's)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route", ("device", "dense"))
def test_attr_stale_result_detected_by_shadow(route):
    """Injected stale results inside an attributed collect: paranoia
    shadow-verifies the pre-attributed entry set against the host filter
    and serves the host answer — bytes never change."""
    store, dev, safe, entries, floor, qs = _build(seed=53)
    dev.route_override = route
    expect = _attributed(dev, safe, qs)
    dev.paranoia = True
    with faults.device_fault("stale_result", 1.0, _rng()):
        got = _attributed(dev, safe, qs)
    assert got == expect
    assert dev.n_shadow_mismatches >= 1
    assert dev.n_quarantines >= 1


def test_attr_quarantine_recovers_and_serves_device_again():
    """After an attributed-collect fault the quarantine expires, the next
    device flush is the probe, and a healthy device serves attributed
    blocks again — all byte-identical throughout."""
    store, dev, safe, entries, floor, qs = _build(seed=53)
    dev.route_override = "dense"
    expect = _attributed(dev, safe, qs)
    with faults.device_fault("transfer", 1.0, _rng()):
        assert _attributed(dev, safe, qs) == expect
    assert dev._dev_quar_flushes > 0
    while dev._dev_quar_flushes > 0:
        assert _attributed(dev, safe, qs) == expect
    assert _attributed(dev, safe, qs) == expect     # the probe
    assert dev._dev_backoff == 0 and dev.n_restores >= 1
    before = dev.n_fallback_queries
    assert _attributed(dev, safe, qs) == expect     # healthy again
    assert dev.n_fallback_queries == before


# ---------------------------------------------------------------------------
# r19 log-depth drain x the fault ladder: a fault inside the routed
# log-depth launch fails the WHOLE flush over to the fixpoint route,
# byte-identically — the fixpoint is both the oracle and the failover
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", RAISING)
def test_logdepth_drain_fault_fails_over_to_fixpoint(kind, monkeypatch):
    from accord_tpu.ops import drain_kernel as drk

    # the machinery under test IS the log-depth route: force the escape
    # hatch open even under the ACCORD_TPU_DRAIN=fixpoint canary run
    monkeypatch.delenv("ACCORD_TPU_DRAIN", raising=False)
    drk.reset_drain_routing()
    try:
        ell = drk._probe_chain_ell(96)
        dense = drk._probe_chain_dense(96)
        exp_a, exp_n, _ = drk.drain_ell_levels(ell)
        with faults.device_fault(kind, 1.0, _rng()):
            a, nw, sweeps, route = drk.drain_ell_auto(ell)
            assert route == "ell-fixpoint-failover"
            np.testing.assert_array_equal(np.asarray(a), np.asarray(exp_a))
            np.testing.assert_array_equal(np.asarray(nw), np.asarray(exp_n))
            a2, _nw2, _s2, route2 = drk.drain_auto(dense)
            assert route2 == "dense-fixpoint-failover"
            np.testing.assert_array_equal(np.asarray(a2), np.asarray(exp_a))
        got = drk.drain_counters()
        assert got["drain_logdepth_failovers"] == 2
        assert got["drain_fixpoint"] == 2 and got["drain_logdepth"] == 0
        # fault cleared: the next routed call runs the log-depth pass again
        a3, _nw3, rounds, route3 = drk.drain_ell_auto(ell)
        assert route3 == "ell-logdepth" and rounds < 30
        np.testing.assert_array_equal(np.asarray(a3), np.asarray(exp_a))
    finally:
        drk.reset_drain_routing()


def test_wavefront_tick_fault_falls_back_to_frontier_sweep(monkeypatch):
    """A widened-wavefront tick (W > 1) that faults at the device boundary
    resets W to 1 and serves the tick through the ordinary frontier ladder
    (the host fallback) — same candidates, no lost wakeup."""
    from accord_tpu.ops import deps_kernel as dk
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind

    # wavefront widening requires the log-depth hatch open; pin it so
    # the test still tests under the ACCORD_TPU_DRAIN=fixpoint canary
    monkeypatch.delenv("ACCORD_TPU_DRAIN", raising=False)

    store, dev, safe = make_device_state(mesh=None)

    class _NoCommandsSafe:
        """Every kernel-proposed candidate re-validates against the host
        command records; absent records degrade to a no-op."""
        store = safe.store

        @staticmethod
        def if_present(_txn_id):
            return None

    ids = [TxnId.create(1, 100 + i, TxnKind.Write, Domain.Key, 1)
           for i in range(6)]
    slots = [dev.drain.alloc(t) for t in ids]
    for a, b in zip(slots[1:], slots):
        dev.drain.add_edge(a, b)
    for t, s in zip(ids, slots):
        dev.drain.set_status(s, dk.SLOT_STABLE, t)
        dev.drain.active[s] = True
    dev._drain_wavefront = 4
    with faults.device_fault("kernel_launch", 1.0, _rng()):
        dev._tick(_NoCommandsSafe())
    assert dev._drain_wavefront == 1        # reset on the faulted tick
    assert dev.n_host_ticks >= 1            # ladder served the candidates
    assert dev.n_device_faults >= 1
    # healthy W>1 tick on a quarantine-free mirror runs the level kernel
    dev2_store, dev2, safe2 = make_device_state(mesh=None)
    for t, s in zip(ids, [dev2.drain.alloc(t) for t in ids]):
        dev2.drain.set_status(s, dk.SLOT_STABLE, t)
        dev2.drain.active[s] = True
    dev2._drain_wavefront = 4
    dev2._tick(_NoCommandsSafe())
    assert dev2.n_wavefront_ticks == 1
    assert dev2.n_host_ticks == 0


# ---------------------------------------------------------------------------
# r21 store-sharded tables x the fault ladder: a fault during a SLICED
# collect quarantines one slice (the hybrid route answers its slots from
# the host twin) while healthy slices stay on device — one sick chip
# degrades a slice, not the node
# ---------------------------------------------------------------------------
_shard_canary = pytest.mark.skipif(
    os.environ.get("ACCORD_TPU_STORE_SHARD", "").lower()
    in ("off", "0", "false", "no"),
    reason="ACCORD_TPU_STORE_SHARD=off canary run: spill rung dormant")


def _sharded_build(seed=31):
    """A _build store pushed past its budget so the spill rung activates
    sliced residency (the r21 rung between compact and host-pinned)."""
    store, dev, safe, entries, floor, qs = _build(seed)
    dev.route_override = "dense"
    dev.device_budget_slots = 64
    _register_n(dev, 300, hlc_base=900_000)   # above the floor: live
    assert dev.store_shards is not None and dev.store_shards.active
    assert not dev.host_pinned
    return store, dev, safe, qs


@pytest.mark.parametrize("kind", RAISING)
@_shard_canary
def test_slice_fault_quarantines_one_slice_only(kind):
    """Launch/transfer faults at p=1.0 during a sliced flush: the flush
    fails over to host byte-identically, and exactly ONE slice quarantines
    — the whole-device ladder stays untouched."""
    store, dev, safe, qs = _sharded_build(seed=31)
    expect = _attributed(dev, safe, qs)
    quar_before = dev.n_quarantines
    with faults.device_fault(kind, 1.0, _rng()):
        got = _attributed(dev, safe, qs)
    assert got == expect
    assert dev.n_slice_quarantines == 1
    assert dev.n_quarantines == quar_before      # no whole-device quarantine
    sh = dev.store_shards
    assert sum(1 for q in sh.quar if q > 0) == 1


@_shard_canary
def test_slice_quarantine_hybrid_then_probe_restore():
    """The full per-slice cycle: fault -> slice quarantine -> hybrid
    flushes (masked device dispatch + host twin for the sick slice) ->
    backoff expiry -> reprobe -> restore.  Byte-identical at every step."""
    store, dev, safe, qs = _sharded_build(seed=47)
    expect = _attributed(dev, safe, qs)
    with faults.device_fault("transfer", 1.0, _rng()):
        assert _attributed(dev, safe, qs) == expect
    sh = dev.store_shards
    assert sh.any_quarantined()
    sharded_before = dev.n_store_sharded_flushes
    # hybrid flushes while quarantined: device route still counted, the
    # sick slice answered from the host twin
    while sh.any_quarantined():
        assert _attributed(dev, safe, qs) == expect
    assert dev.n_store_sharded_flushes > sharded_before
    # the tick that hit zero marked the slice suspect; the next healthy
    # flush is the probe and restores it
    assert _attributed(dev, safe, qs) == expect
    assert dev.n_slice_restores >= 1
    assert not any(sh.suspect)
    assert _attributed(dev, safe, qs) == expect


@_shard_canary
def test_slice_stale_result_detected_by_shadow():
    """Silent corruption during a sliced collect: paranoia shadow-verify
    catches it and quarantines the SLICE, not the device."""
    store, dev, safe, qs = _sharded_build(seed=53)
    expect = _attributed(dev, safe, qs)
    dev.paranoia = True
    quar_before = dev.n_quarantines
    with faults.device_fault("stale_result", 1.0, _rng()):
        got = _attributed(dev, safe, qs)
    assert got == expect
    assert dev.n_shadow_mismatches >= 1
    assert dev.n_slice_quarantines >= 1
    assert dev.n_quarantines == quar_before


@_shard_canary
def test_flush_under_slice_quarantine_is_hybrid_not_whole_host():
    """While a slice is quarantined a flush is HYBRID: the healthy slices
    answer through the masked sharded dense kernel, the sick slice's slots
    through a host twin part — byte-identical, counted as a sharded flush,
    never as a fallback."""
    store, dev, safe, qs = _sharded_build(seed=31)
    expect = _attributed(dev, safe, qs)
    assert expect == _reference(dev, safe, qs)
    with faults.device_fault("transfer", 1.0, _rng()):
        _attributed(dev, safe, qs)
    sh = dev.store_shards
    assert sh.any_quarantined()
    sharded_before = dev.n_store_sharded_flushes
    fallback_before = dev.n_fallback_queries
    builders = [DepsBuilder() for _ in qs]
    handle = dev.deps_query_batch_begin(qs, immediate=True)
    assert [p["kind"] for p in handle[0]] == ["attr_sharded", "host_slice"]
    dev.deps_query_batch_end_attributed(safe, handle, builders)
    assert _unpack_builders(builders) == expect
    assert dev.n_store_sharded_flushes == sharded_before + 1
    assert dev.n_fallback_queries == fallback_before
