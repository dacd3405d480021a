"""DeviceDispatcher (r08 launch coalescing) scheduler-level properties.

The byte-identity of fused vs solo launches lives in test_routing; the
fault composition in test_device_faults.  Here: the scheduling contracts —
tick coalescing never double-enqueues, the drain state's delta-upload cache
re-ticks without re-uploading, fused frontier sweeps match the solo kernel,
the ACCORD_TPU_FUSION knob is honored, and a live sim actually coalesces."""

import numpy as np
import pytest

from tests.conftest import make_device_state, make_dispatch_node


# ---------------------------------------------------------------------------
# schedule_tick coalescing audit (r08 satellite): a status change arriving
# while a tick is already scheduled for the same window must not enqueue a
# second tick — across the dispatcher path too
# ---------------------------------------------------------------------------
def test_schedule_tick_coalesces_across_dispatcher():
    node, stores = make_dispatch_node((11,))
    dev, _safe, _qs = stores[0]
    dev.schedule_tick()
    assert dev._tick_scheduled
    dev.schedule_tick()          # second request in the same window
    dev.schedule_tick()
    assert len(node.dispatcher._tick_pending) == 1
    assert len(node.scheduler.q) == 1        # ONE dispatcher tick event
    node.scheduler.run()
    assert not dev._tick_scheduled           # tick ran, flag cleared
    dev.schedule_tick()                      # and re-arming works
    assert len(node.dispatcher._tick_pending) == 1
    node.scheduler.run()


def test_two_stores_share_one_tick_event():
    node, stores = make_dispatch_node((11, 23))
    for dev, _safe, _qs in stores:
        dev.schedule_tick()
    assert len(node.scheduler.q) == 1        # one event for both stores
    assert len(node.dispatcher._tick_pending) == 2
    node.scheduler.run()
    for dev, _safe, _qs in stores:
        assert not dev._tick_scheduled


# ---------------------------------------------------------------------------
# drain delta uploads: the device state is cached between ticks; scalar
# churn scatter-updates dirty rows; membership/edge changes rebuild
# ---------------------------------------------------------------------------
def _armed_drain(n=6):
    from accord_tpu.local.device_index import _DrainMirror
    from accord_tpu.ops import deps_kernel as dk
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    dr = _DrainMirror()
    ids = [TxnId.create(1, 100 + i, TxnKind.Write, Domain.Key, 1)
           for i in range(n)]
    slots = [dr.alloc(t) for t in ids]
    for i in range(1, n):
        dr.add_edge(slots[i], slots[i - 1])
    for i, (t, s) in enumerate(zip(ids, slots)):
        dr.set_status(s, dk.SLOT_STABLE, t)
        dr.active[s] = True
    return dr, ids, slots


def test_drain_state_cached_between_ticks():
    dr, ids, slots = _armed_drain()
    s1, live1 = dr.state()
    s2, live2 = dr.state()
    assert s1 is s2              # unchanged mirror: ZERO upload
    assert live1 is live2


def test_drain_state_scalar_delta_keeps_adjacency():
    from accord_tpu.ops import deps_kernel as dk
    dr, ids, slots = _armed_drain()
    s1, live = dr.state()
    dr.set_status(slots[0], dk.SLOT_APPLIED, ids[0])
    s2, live2 = dr.state()
    assert s2 is not s1
    assert s2.adj is s1.adj      # delta path: adjacency NOT re-uploaded
    assert live2 is live
    # and the scattered row is correct
    li = int(np.nonzero(live == slots[0])[0][0])
    assert int(np.asarray(s2.status)[li]) == dk.SLOT_APPLIED
    # results match a from-scratch rebuild
    from accord_tpu.ops import drain_kernel as drk
    fresh = _DrainRebuild(dr)
    np.testing.assert_array_equal(np.asarray(drk.ready_frontier(s2)),
                                  np.asarray(drk.ready_frontier(fresh)))


def _DrainRebuild(dr):
    """Force a cache-bypassing rebuild of the same mirror."""
    saved = dr._state_cache
    dr._state_cache = None
    state, _live = dr.state()
    dr._state_cache = saved
    return state


def test_drain_state_membership_change_rebuilds():
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    dr, ids, slots = _armed_drain()
    s1, _ = dr.state()
    dr.alloc(TxnId.create(1, 999, TxnKind.Write, Domain.Key, 1))
    s2, live2 = dr.state()
    assert s2.adj is not s1.adj  # full rebuild: the live set changed
    assert len(live2) == len(slots) + 1


# ---------------------------------------------------------------------------
# fused frontier sweep == solo kernel, over real mirror-built states
# ---------------------------------------------------------------------------
def test_fused_frontier_matches_solo_over_mirrors():
    from accord_tpu.ops import drain_kernel as drk
    a, _ids, _slots = _armed_drain(4)
    b, bids, bslots = _armed_drain(9)
    from accord_tpu.ops import deps_kernel as dk
    b.set_status(bslots[0], dk.SLOT_APPLIED, bids[0])
    sa, la = a.state()
    sb, lb = b.state()
    fused = np.asarray(drk.fused_ready_frontier([sa, sb]))
    np.testing.assert_array_equal(
        fused[0][: sa.status.shape[0]], np.asarray(drk.ready_frontier(sa)))
    np.testing.assert_array_equal(
        fused[1][: sb.status.shape[0]], np.asarray(drk.ready_frontier(sb)))


# ---------------------------------------------------------------------------
# the ACCORD_TPU_FUSION knob
# ---------------------------------------------------------------------------
def test_fusion_env_knob(monkeypatch):
    from accord_tpu.local import dispatch
    monkeypatch.delenv("ACCORD_TPU_FUSION", raising=False)
    assert dispatch.fusion_enabled()
    for off in ("off", "0", "false", "no", "OFF"):
        monkeypatch.setenv("ACCORD_TPU_FUSION", off)
        assert not dispatch.fusion_enabled()
    monkeypatch.setenv("ACCORD_TPU_FUSION", "on")
    assert dispatch.fusion_enabled()


# ---------------------------------------------------------------------------
# live sim: the burn exercises fused launches and stays green
# ---------------------------------------------------------------------------
@pytest.mark.skipif(
    __import__("accord_tpu.local.dispatch",
               fromlist=["fusion_enabled"]).fusion_enabled() is False,
    reason="ACCORD_TPU_FUSION=off canary run: live-path fusion pinned solo")
def test_sim_burn_coalesces_launches(drain_ticks_on_device):
    from accord_tpu.sim.burn import run_burn
    r = run_burn(5, n_ops=30)
    assert r.ops_unresolved == 0
    fused = r.stats.get("device_fused_launches", 0) \
        + r.stats.get("device_fused_tick_launches", 0)
    assert fused > 0, r.stats
