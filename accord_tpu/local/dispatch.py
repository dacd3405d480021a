"""Per-node device dispatch scheduler: cross-store launch coalescing (r08).

r06 made every deps *scan* cheap (regime-adaptive routing); r07 made the
accelerator a survivable failure domain.  What remained (device_index's own
docstring flagged it) is the LAUNCH tax: every CommandStore paid its own
device dispatch per flush and per drain tick, so on a node with many stores
the per-launch overhead (dispatch + PCIe/ICI round trip) dominates the
per-element work the kernels already amortize.  This module is the analogue
of the reference's per-store task-queue amortization
(InMemoryCommandStore's executor batching, SURVEY §7) lifted to the DEVICE
boundary:

- **Flush coalescing**: deps flushes from all CommandStores of one node
  that become runnable in the same sim event-loop step register with ONE
  dispatcher event.  Stores whose adaptive route is a device kernel are
  priced fused-vs-solo (the same micro-probe calibration the r06 router
  uses: fusing S launches saves (S-1) round trips and pays for the padding
  waste of stacking unequal tables); when fusion wins, ONE store-tagged
  ATTRIBUTED kernel launch (ops.deps_kernel.fused_flat_attr, or
  parallel.sharded.sharded_fused_attr under a mesh — floors/elision fold
  in-kernel, r15) answers every member.
- **Async harvest**: the fused launch is enqueued WITHOUT blocking — jax's
  async dispatch overlaps the device work with host protocol processing —
  and each member harvests its block in its own store task, enqueued at
  dispatch in store-id order: results land at the next event-loop boundary
  BEFORE any dependent task of that store runs, so determinism is the
  scheduler order, never device completion order.
- **Tick coalescing**: drain ticks registered within one tick window share
  one dispatcher event, and the single-device frontier sweeps of the
  members fuse into one vmapped launch
  (ops.drain_kernel.fused_ready_frontier[_ell]) when the same pricing says
  it pays.  Members are the stores whose sweep goes to the device at all:
  a live set too small to pay for a round trip is swept on the host
  (DeviceState._host_tick_pays) and joins no fused launch.

Correctness contract: every fused launch is BIT-IDENTICAL to the solo
launches it replaces (tests/test_routing.py property tests), and the r07
fault ladder composes — a device fault inside a fused launch fails the
WHOLE batch over to the host route deterministically, then quarantines
per-store exactly as solo faults do (tests/test_device_faults.py).

Knobs: ``ACCORD_TPU_FUSION=off`` pins solo launches (the conftest canary
asserts tier-1 passes with it set — fusion must never become load-bearing
for correctness); everything else is priced, not thresholded.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Dict, List, Optional

import numpy as np

from ..obs import devprof
from ..ops import deps_kernel as dk
from ..ops import drain_kernel as drk
from ..utils import faults
from .device_index import _pow2_at_least


def fusion_enabled() -> bool:
    """The ACCORD_TPU_FUSION escape hatch: default ON; "off"/"0"/"false"/
    "no" pins every launch solo (correctness must never depend on fusion)."""
    return os.environ.get("ACCORD_TPU_FUSION", "").lower() not in (
        "off", "0", "false", "no")


def _harvest_span(name, dev0, members):
    """The span of one fused-result download: the harvest-barrier slice,
    pid-matched to the dispatch slice's node row.  Shared by the flush and
    tick harvest paths."""
    return devprof.span(
        name, pid=getattr(getattr(dev0.store, "node", None),
                          "node_id", 0) or 0,
        args={"members": members})


class FusedFlushLaunch:
    """One in-flight fused ATTRIBUTED deps launch: the shared device
    buffers plus the member hints.  The download happens at the FIRST
    member's harvest (faults.check rides it — one transfer crossing per
    fused launch) and is TWO-STAGE like the solo path: the stacked scalar
    headers first, then one slice carrying only the live prefix of every
    member's (merged, under a mesh) entry block; any device-boundary
    failure poisons the whole batch: every member quarantines and serves
    its flush from the snapshot host scan."""

    def __init__(self, dev_out, hints, s: int, k: int, d_ent: int,
                 b_pad: int, wide: bool):
        self.hdr_dev, self.ent_dev = dev_out
        self.hints = hints
        self.s = s
        self.k = k
        self.d_ent = d_ent        # entries per store = d_ent * s
        self.b_pad = b_pad
        self.wide = wide
        self._out = None
        self.failed: Optional[BaseException] = None

    def materialize(self):
        if self.failed is not None:
            raise self.failed
        if self._out is None:
            from .device_index import _prefix_len
            n_s = len(self.hints)
            itemsize = 8 if self.wide else 4
            dev0 = self.hints[0]["dev"]
            faults.check("transfer", "fused header download")
            with _harvest_span("fused_flush_harvest_header", dev0, n_s):
                hdr = np.asarray(self.hdr_dev)
            hdr = hdr.reshape(n_s, 5 + self.b_pad)
            s_eff = self.d_ent * self.s
            maxtot = min(int(hdr[:, 0].max()), s_eff)
            length = _prefix_len(maxtot, s_eff)
            faults.check("transfer", "fused entry download")
            ent3 = self.ent_dev.reshape(n_s, s_eff)[:, :length]
            with _harvest_span("fused_flush_harvest_entries", dev0, n_s):
                ent = np.asarray(ent3)
            # byte accounting lands on the first harvester (deterministic:
            # harvest order is store-id order)
            dev0.download_bytes += hdr.nbytes + ent.nbytes
            dev0.download_bytes_padded += \
                hdr.nbytes + n_s * s_eff * itemsize
            dev0.attr_download_bytes += hdr.nbytes + ent.nbytes
            self._out = (hdr, ent)
        return self._out

    def poison(self, exc: BaseException) -> None:
        if self.failed is None:
            self.failed = exc
            for h in self.hints:
                h["dev"]._device_fault(exc, f"fused collect: {exc}",
                                       sliced=True)
                h["probing"] = False


class FusedTick:
    """One in-flight fused drain-frontier launch (see FusedFlushLaunch for
    the failure contract)."""

    def __init__(self, dev_out, group):
        self.dev = dev_out
        self.rows = {id(dev): (i, live, dev.drain.version)
                     for i, (dev, _st, live) in enumerate(group)}
        self.members = [dev for dev, _st, _lv in group]
        self._out = None
        self.failed: Optional[BaseException] = None

    def serves(self, dev) -> bool:
        return id(dev) in self.rows

    def version_for(self, dev) -> int:
        return self.rows[id(dev)][2]

    def result_for(self, dev) -> np.ndarray:
        if self.failed is not None:
            raise self.failed
        if self._out is None:
            faults.check("transfer", "fused drain download")
            with _harvest_span("fused_tick_harvest", self.members[0],
                               len(self.members)):
                self._out = np.asarray(self.dev)
        i, live, _v = self.rows[id(dev)]
        ready = self._out[i][: len(live)]
        return live[ready & dev.drain.active[live]]

    def poison(self, exc: BaseException) -> None:
        if self.failed is None:
            self.failed = exc
            for dev in self.members:
                dev._device_fault(exc, f"fused drain collect: {exc}")


class DeviceDispatcher:
    """The per-node scheduler coalescing device launches across the node's
    CommandStores (module docstring)."""

    def __init__(self, node):
        self.node = node
        self.fusion = fusion_enabled()
        self._flush_pending: List = []
        self._flush_scheduled = False
        self._tick_pending: List = []
        self._tick_scheduled = False
        # launch accounting (the bench "# index" line and the sim stats
        # read these): fused launches serve many member flushes/ticks each
        self.n_fused_launches = 0
        self.n_fused_members = 0
        self.n_solo_flushes = 0
        self.n_fused_tick_launches = 0
        self.n_fused_tick_members = 0
        self.n_solo_ticks = 0
        # cross-request flush occupancy (r16): one dispatcher event
        # serves every store flush registered in the same scheduler tick,
        # and each store's batch carries every query queued by that
        # tick's ops — the serving path's batch envelopes land their
        # sub-ops in one tick precisely so these ratios grow.  events ->
        # member flushes -> queries is the device-side occupancy ladder
        # (the wire-side analogue is the server's batch_occupancy_p50).
        self.n_flush_events = 0
        self.n_flush_members = 0
        self.n_flush_queries = 0
        # observer(kind, n_members, nq) — the sim cluster wires stats/trace
        self.on_fused = None

    def _handled(self, exc: BaseException) -> None:
        agent = getattr(self.node, "agent", None)
        if agent is not None and hasattr(agent, "on_handled_exception"):
            agent.on_handled_exception(exc)

    # -- flush side ---------------------------------------------------------
    def register_flush(self, dev) -> None:
        self._flush_pending.append(dev)
        if not self._flush_scheduled:
            self._flush_scheduled = True
            # one scheduler hop (zero sim-time) so every same-instant
            # message's store task enqueues its queries BEFORE dispatch
            self.node.scheduler.now(self._run_flushes)

    def _run_flushes(self) -> None:
        self._flush_scheduled = False
        devs = self._flush_pending
        self._flush_pending = []
        if not getattr(self.node, "alive", True):
            return    # dead incarnation (restart): ghost work must not run
        # the planning of a tick's flushes (a fused launch with it); the
        # flushes themselves are store tasks, each a ``srv.deps_flush``
        with devprof.span("srv.deps_plan",
                          getattr(self.node, "loop_times", None)):
            self._plan_flushes(devs)

    def _plan_flushes(self, devs) -> None:
        from .command_store import PreLoadContext
        devs.sort(key=lambda d: d.store.store_id)
        plans = []
        for dev in devs:
            batch = dev._q_pending
            dev._q_pending = []
            if batch:
                plans.append((dev, batch))
        if plans:
            self.n_flush_events += 1
            self.n_flush_members += len(plans)
            self.n_flush_queries += sum(len(b) for _d, b in plans)
        hints: Dict[int, dict] = {}
        launch = None
        if self.fusion and len(plans) >= 2:
            try:
                for dev, batch in plans:
                    h = dev.fused_eligible([q for q, _b, _d in batch])
                    if h is not None:
                        h["batch"] = batch
                        hints[id(dev)] = h
                if len(hints) >= 2 and \
                        self._fused_flush_pays(list(hints.values())):
                    # pack + stack + async enqueue of ONE store-tagged
                    # launch in place of len(hints) solo launches: the
                    # coalescing win as a timeline slice (the harvest
                    # lands in fused_flush_harvest_*)
                    members = list(hints.values())
                    with devprof.span(
                            "fused_flush_dispatch",
                            pid=getattr(self.node, "node_id", 0),
                            args={"members": len(members),
                                  "nq": sum(h["nq"] for h in members)}):
                        launch = self._launch_fused_flush(members)
                else:
                    hints = {}
            except BaseException as e:  # noqa: BLE001
                # NOT a device fault (those are absorbed inside
                # _launch_fused_flush as the whole-batch host failover) —
                # an unexpected host-side error must never strand the
                # claimed batches with their done callbacks unfired: fall
                # back to solo flushes, which carry their own failure
                # delivery
                self._handled(e)
                hints = {}
                launch = None
        # harvest order IS the deterministic scheduler order: one store
        # task per member, enqueued here in ascending store id
        for dev, batch in plans:
            h = hints.get(id(dev))
            if h is not None:
                dev.store.execute(
                    PreLoadContext.empty(),
                    partial(dev.fused_harvest, hint=h, launch=launch))
            else:
                self.n_solo_flushes += 1
                dev.store.execute(PreLoadContext.empty(),
                                  partial(dev._flush_batch, batch=batch))

    def _stacked_attr(self, hints):
        """Pre-stacked [S, ...] AttrCols + AttrIndex for the fused
        attributed launch, cached on the members' attr versions and index
        identities: 16 stores' twenty extra per-store pytrees per launch
        measured ~5ms of pure jax argument flattening on the tiny-flush
        regime — stacking host-side hands the jit TWO pytrees and keeps
        the device copies resident between launches."""
        import jax
        key = (tuple(id(h["dev"]) for h in hints),
               tuple(h["dev"].deps.attr_version for h in hints),
               tuple(h["aidx"].seq for h in hints))
        cached = getattr(self, "_stacked_attr_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1], cached[2]
        n_max = max(h["dev"].deps.capacity for h in hints)
        cols = []
        for h in hints:
            hc = h["dev"].deps._attr_host_cols()
            cols.append([np.concatenate(
                [a, np.full(n_max - len(a),
                            dk.SLOT_FREE if i == 1 else (1 if i == 0 else 0),
                            a.dtype)]) if len(a) < n_max else a
                for i, a in enumerate(hc)])
        sa = dk.upload(dk.attr_words(*(np.stack(c) for c in zip(*cols))))
        pads = [h["aidx"].pad for h in hints]
        f_max = max(len(p[0]) for p in pads)
        t_max = max(len(p[4]) for p in pads)
        l_max = max(len(p[6]) for p in pads)
        import numpy as _np

        def tail(a, n, fill):
            if len(a) >= n:
                return a
            out = _np.full(n, fill, a.dtype)
            out[: len(a)] = a
            return out

        inf = _np.int64(_np.iinfo(_np.int64).max)
        rows = []
        for p in pads:
            live_l = p[5][-1]
            rows.append((tail(p[0], f_max, inf),
                         tail(p[1], f_max + 1, 0), tail(p[2], f_max + 1, 0),
                         tail(p[3], f_max + 1, 0),
                         tail(p[4], t_max, inf), tail(p[5], t_max + 1, live_l),
                         tail(p[6], l_max, inf), tail(p[7], l_max, 0),
                         tail(p[8], l_max, 0), tail(p[9], l_max, 0),
                         p[10]))
        si = dk.upload(jax.tree.map(lambda *leaves: np.stack(leaves),
                                    *(dk.index_words(*r) for r in rows)))
        self._stacked_attr_cache = (key, sa, si)
        return sa, si

    def _fused_flush_pays(self, hints) -> bool:
        """Price ONE fused launch against the members' solo launches with
        the r06 micro-probe calibration: fusing saves (S-1) round trips
        and pays the padding waste of stacking unequal tables / batches."""
        dev0 = hints[0]["dev"]
        calib = dev0._calibration()
        rtt, c_dev = calib["rtt"], calib["c_dev"]
        d = 1
        if dev0.mesh is not None:
            d = max(len(dev0.mesh.devices.flat), 1)
            rtt = calib.get("rtt_mesh", rtt)
        solo = sum(2.0 * rtt + c_dev * h["solo_elems"] for h in hints)
        b_pad = _pow2_at_least(max(h["b_pad"] for h in hints), 1)
        q_m = max(h["q_m"] for h in hints)
        n_max = max(h["cap"] for h in hints)
        m_max = max(h["m_iv"] for h in hints)
        fused_elems = len(hints) * b_pad * (n_max // d) * q_m * m_max
        # the deferred harvest needs begin-time mirror snapshots the solo
        # immediate path never takes — charge the stale members' copies at
        # the measured memcpy rate (version-cached, so an unmutated mirror
        # re-fuses for free)
        c_copy = calib.get("c_copy", calib["c_host"] / 20.0)
        snap_cost = c_copy * sum(h["snap_elems"] for h in hints)
        return 2.0 * rtt + c_dev * fused_elems + snap_cost < solo

    def _launch_fused_flush(self, hints) -> Optional[FusedFlushLaunch]:
        devs = [h["dev"] for h in hints]
        mesh = devs[0].mesh            # one node -> one mesh for all stores
        d = 1 if mesh is None else max(len(mesh.devices.flat), 1)
        q_m = max(h["q_m"] for h in hints)
        b_pad = _pow2_at_least(max(h["b_pad"] for h in hints), 1)
        budgets = [dev._serving_budgets(True) for dev in devs]
        s = max(min(bs, b_pad * (h["cap"] // d) * h["m_iv"] * q_m)
                for (bs, _bk), h in zip(budgets, hints))
        k = max(min(bk, (h["cap"] // d) * h["m_iv"] * q_m)
                for (_bs, bk), h in zip(budgets, hints))
        qmats = np.empty((len(hints), b_pad, 7 + 2 * q_m), np.int64)
        pm = np.zeros(len(hints), np.int64)
        pl = np.zeros(len(hints), np.int64)
        pn = np.zeros(len(hints), np.int32)
        m_max = max(h["m_iv"] for h in hints)
        # the fused trace pads every table to the group's interval width,
        # so codes scale on m_max; the entry dtype must hold the WIDEST
        # member's codes — under a mesh the merged entries carry GLOBAL
        # slot ids on the padded shard stride, so the crossover is the
        # whole padded slot space
        rankbs = np.zeros((len(hints), b_pad), np.int64)
        pad_shard_n = max(h["cap"] // d for h in hints)
        if mesh is not None:
            wide = dk.wide_codes(d * pad_shard_n, m_max, q_m)
        else:
            wide = any(dk.wide_codes(h["cap"], m_max, q_m) for h in hints)
        for i, h in enumerate(hints):
            qnp, qmi, nq = h["qnp"], h["q_m"], h["nq"]
            rows_p = np.minimum(np.arange(b_pad), nq - 1)
            qmats[i, :, :7] = qnp[rows_p, :7]
            qmats[i, :, 7:7 + q_m] = dk.PAD_LO
            qmats[i, :, 7 + q_m:] = dk.PAD_HI
            qmats[i, :, 7:7 + qmi] = qnp[rows_p, 7:7 + qmi]
            qmats[i, :, 7 + q_m:7 + q_m + qmi] = qnp[rows_p, 7 + qmi:]
            rankbs[i] = h["rankb_np"][rows_p]
            if h["prune"] is not None:
                pm[i], pl[i], pn[i] = h["prune"]
            h["gmap"] = np.where(np.arange(b_pad) < nq,
                                 np.arange(b_pad), -1)
            h["row"] = i
            h["d"] = d
            h["d_mesh"] = d
            h["shard_n"] = h["cap"] // d
            h["pad_shard_n"] = pad_shard_n if mesh is not None else None
            h["b_pad_c"] = b_pad
            h["q_m_c"] = q_m
            h["m_max"] = m_max
            h["mq"] = m_max * q_m
            h["wide"] = wide
            h["qmat_np"] = qmats[i]
            h["rankb_pad"] = rankbs[i]
        # commit first (probe bookkeeping, mirror snapshots, route
        # observation): a launch fault below must still find the begin-time
        # snapshot to serve the host failover from
        for h in hints:
            h["dev"].fused_commit(h)
        try:
            dk.launch_check("fused")
            tables = [h["dev"].fused_table() for h in hints]
            for h, t in zip(hints, tables):
                h["table"] = t
            import jax.numpy as jnp
            # static leg switches, OR'd over the group: a member with a
            # trivial floor map / empty elision index just computes
            # nothing in the shared legs
            fl_ = any(not h.get("floor_skip", False) for h in hints)
            el_ = any(h["aidx"].n_execs > 0 for h in hints)
            if mesh is not None:
                from ..parallel.sharded import sharded_fused_attr
                attrs = [h["dev"].deps.device_attr_cols_sharded(mesh)
                         for h in hints]
                aidxs = [h["aidx"].device_replicated(mesh) for h in hints]
                out = sharded_fused_attr(mesh, len(hints), q_m, s, k,
                                         wide, fl_, el_)(
                    *tables, *attrs, *aidxs,
                    jnp.asarray(dk.qmat_words(qmats)),
                    jnp.asarray(dk.rank_words(rankbs)),
                    *dk.upload(dk.prune_words(pm, pl, pn)))
            else:
                sa, si = self._stacked_attr(hints)
                out = dk.fused_flat_attr(tables, sa, si, qmats,
                                         rankbs, (pm, pl, pn),
                                         q_m, s, k, wide, fl_, el_)
        except faults.DEVICE_EXCEPTIONS as e:
            # a device fault inside the fused launch fails the WHOLE batch
            # over to the host route, then quarantines per-store as solo
            # faults do
            for h in hints:
                h["dev"].fused_fail_to_host(h, e)
            return None
        self.n_fused_launches += 1
        self.n_fused_members += len(hints)
        if self.on_fused is not None:
            self.on_fused("flush", len(hints),
                          sum(h["nq"] for h in hints))
        return FusedFlushLaunch(out, hints, s, k,
                                d if mesh is not None else 1, b_pad, wide)

    def warm_fused(self, devs, batches) -> None:
        """Load, before traffic, the fused launch's programs for this
        node's device stores flushing together, one a batch size in
        ``batches``: each store's ``warm_queries`` through the planning,
        launch and harvest a serving flush takes, on the device route,
        the answers dropped; and the live-prefix slices of the shared
        download.  Run after the stores' own warm_flush (their budgets are
        the launch's)."""
        from ..primitives.deps import DepsBuilder
        devs = [d for d in devs if d.mesh is None and not d.host_pinned]
        if not self.fusion or len(devs) < 2:
            return
        saved = [d.route_override for d in devs]
        for d in devs:
            d.route_override = "device"
        queries = [d.warm_queries(max(batches)) for d in devs]
        try:
            for b in batches:
                hints = []
                for d, qs in zip(devs, queries):
                    qs = qs[:b]
                    h = d.fused_eligible(qs) if qs else None
                    if h is not None:
                        h["batch"] = [(q, DepsBuilder(), lambda _f, _s: None)
                                      for q in qs]
                        hints.append(h)
                if len(hints) < 2:
                    return
                launch = self._launch_fused_flush(hints)
                for h in hints:
                    h["dev"].fused_harvest(None, h, launch)
        finally:
            for d, route in zip(devs, saved):
                d.route_override = route
        import jax.numpy as jnp
        s = max(d._serving_budgets(True)[0] for d in devs)
        wide = any(dk.wide_codes(d.deps.capacity, d.deps.max_intervals,
                                 _pow2_at_least(1)) for d in devs)
        ent = jnp.zeros((len(devs), s), jnp.int64 if wide else jnp.int32)
        gran = max(128, s >> 4)
        for length in range(gran, s + 1, gran):
            ent.reshape(len(devs), s)[:, :length].block_until_ready()

    # -- tick side ----------------------------------------------------------
    def register_tick(self, dev) -> None:
        self._tick_pending.append(dev)
        if not self._tick_scheduled:
            self._tick_scheduled = True
            self.node.scheduler.once(dev.TICK_DELAY_MICROS, self._run_ticks)

    def _run_ticks(self) -> None:
        from .command_store import PreLoadContext
        self._tick_scheduled = False
        devs = self._tick_pending
        self._tick_pending = []
        if not getattr(self.node, "alive", True):
            return    # dead incarnation (restart): ghost work must not run
        devs.sort(key=lambda d: d.store.store_id)
        fused_by: Dict[int, FusedTick] = {}
        if self.fusion and len(devs) >= 2:
            try:
                fused_by = self._prepare_fused_ticks(devs)
            except BaseException as e:  # noqa: BLE001
                # an unexpected host-side error preparing the fused sweep
                # must never leave the members' _tick_scheduled flags
                # stuck True (a node-wide lost wakeup): every member still
                # gets its solo tick task below
                self._handled(e)
                fused_by = {}
        for dev in devs:
            f = fused_by.get(id(dev))
            if f is None:
                self.n_solo_ticks += 1
            dev.store.execute(PreLoadContext.empty(),
                              partial(dev._tick, fused=f))

    def _prepare_fused_ticks(self, devs) -> Dict[int, FusedTick]:
        # a widened-wavefront store (r19, _drain_wavefront > 1) is mid-
        # cascade and runs the level kernel solo — the fused frontier sweep
        # would shrink its candidate set back to one antichain.  A store
        # whose sweep is priced cheaper on the host stays out too: a fused
        # tick pays the same round trip its solo tick would (and its audit
        # tick, DeviceState._audit_tick, runs solo: one program, not one
        # for every tuple of sizes)
        cands = [d for d in devs
                 if not (d.host_pinned or d._dev_quar_flushes > 0)
                 and getattr(d, "_drain_wavefront", 1) <= 1
                 and d.drain.active.any()
                 and not d._host_tick_pays()]
        if len(cands) < 2:
            return {}
        try:
            dk.launch_check("fused drain")
            built = [(d,) + d.drain.state() for d in cands]
        except faults.DEVICE_EXCEPTIONS as e:
            # whole-batch failover: every candidate quarantines; their
            # tick tasks sweep on host via the quarantine guard
            for d in cands:
                d._device_fault(e, f"fused drain tick: {e}")
            return {}
        dense, ell = [], []
        for dev, state, live in built:
            if isinstance(state, drk.EllDrainState):
                ell.append((dev, state, live))
            else:
                n = state.status.shape[0]
                if dev.mesh is not None \
                        and n % len(dev.mesh.devices.flat) == 0 \
                        and dev._mesh_tick_pays(n):
                    continue       # the solo mesh sweep is the modeled winner
                dense.append((dev, state, live))
        out: Dict[int, FusedTick] = {}
        calib = devs[0]._calibration()
        for group, kernel, kind in (
                (dense, drk.fused_ready_frontier, "dense"),
                (ell, drk.fused_ready_frontier_ell, "ell")):
            if len(group) < 2 or not self._fused_tick_pays(group, calib,
                                                           kind):
                continue
            try:
                with devprof.span(
                        "fused_tick_dispatch",
                        pid=getattr(self.node, "node_id", 0),
                        args={"members": len(group), "kind": kind}):
                    out_dev = kernel([st for _d, st, _lv in group])
            except faults.DEVICE_EXCEPTIONS as e:
                for dev, _st, _lv in group:
                    dev._device_fault(e, f"fused drain launch: {e}")
                continue
            ft = FusedTick(out_dev, group)
            self.n_fused_tick_launches += 1
            self.n_fused_tick_members += len(group)
            if self.on_fused is not None:
                self.on_fused("tick", len(group), 0)
            for dev, _st, _lv in group:
                out[id(dev)] = ft
        return out

    def _fused_tick_pays(self, group, calib, kind: str) -> bool:
        rtt, c_dev = calib["rtt"], calib["c_dev"]
        if kind == "dense":
            sizes = [st.status.shape[0] for _d, st, _lv in group]
            n_max = max(sizes)
            waste = c_dev * (len(sizes) * n_max * n_max
                             - sum(n * n for n in sizes))
        else:
            shapes = [st.adj_idx.shape for _d, st, _lv in group]
            n_max = max(sh[0] for sh in shapes)
            d_max = max(sh[1] for sh in shapes)
            waste = c_dev * (len(shapes) * n_max * d_max
                             - sum(n * dd for n, dd in shapes))
        return 2.0 * rtt * (len(group) - 1) > waste
