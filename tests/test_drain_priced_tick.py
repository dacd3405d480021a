"""The priced drain tick: a store's frontier sweep goes to the device only
when the round trip pays (DeviceState._host_tick_pays).

Here: the predicate under an injected calibration, the audit tick that
keeps a store priced to the host in touch with the device, the equivalence
of the priced host tick and the pinned device tick on random drain mirrors,
and the dispatcher's fused ticks, which take no member that is priced to the
host."""

import numpy as np
import pytest

from accord_tpu.local import commands
from accord_tpu.local.device_index import DeviceState
from accord_tpu.ops import deps_kernel as dk
from accord_tpu.primitives.timestamp import (Domain, Timestamp, TxnId,
                                             TxnKind)
from tests.conftest import make_device_state, make_dispatch_node


@pytest.fixture
def calibration():
    """A calibration under which a round trip costs what sweeping 2,000
    rows or edges costs; the process's own is put back afterwards."""
    saved = DeviceState._CALIB
    DeviceState.set_route_calibration(rtt=1e-3, c_host=1e-8, c_dev=1e-10,
                                      c_sweep=1e-6)
    try:
        yield DeviceState._CALIB
    finally:
        DeviceState._CALIB = saved


class _NoCommandsSafe:
    """Absent command records: a candidate orders by its TxnId."""

    def __init__(self, store):
        self.store = store

    @staticmethod
    def if_present(_txn_id):
        return None


def _txn_id(i, kind=TxnKind.Write):
    return TxnId.create(1, 100 + i, kind, Domain.Key, 1 + i % 3)


def _arm_rows(dev, n_rows, degree):
    """``n_rows`` driven Stable rows, each waiting on ``degree`` committed
    deps that execute later (nothing gates)."""
    dr = dev.drain
    rows = [dr.alloc(_txn_id(i)) for i in range(n_rows)]
    deps = [dr.alloc(_txn_id(n_rows + i)) for i in range(max(degree, 1))]
    for j, s in enumerate(deps):
        dr.set_status(s, dk.SLOT_COMMITTED,
                      Timestamp.from_values(1, 10_000_000 + j, 1))
    for i, s in enumerate(rows):
        dr.set_status(s, dk.SLOT_STABLE, _txn_id(i))
        dr.active[s] = True
        for d in deps[:degree]:
            dr.add_edge(s, d)
    return rows


# ---------------------------------------------------------------------------
# (1) the predicate
# ---------------------------------------------------------------------------
def test_small_live_set_prices_to_host_and_large_dense_to_device(calibration):
    _store, small, _safe = make_device_state(mesh=None)
    _arm_rows(small, 12, 2)
    assert small._host_tick_pays()          # 36 us against 2 ms
    _store, large, _safe = make_device_state(mesh=None)
    _arm_rows(large, 1500, 2)               # 4.5 ms against 2.4 ms
    assert len(large.drain.id_of) <= large.drain.DENSE_MAX
    assert not large._host_tick_pays()


def test_price_is_monotonic_in_the_edge_count(calibration):
    _store, dev, _safe = make_device_state(mesh=None)
    rows = _arm_rows(dev, 400, 0)
    extra = [dev.drain.alloc(_txn_id(10_000 + i)) for i in range(40)]
    # the live set, so the device's price, is fixed from here on
    decisions = [dev._host_tick_pays()]
    for d in extra:
        for s in rows:
            dev.drain.add_edge(s, d)
        decisions.append(dev._host_tick_pays())
    assert decisions[0] and not decisions[-1]
    flips = [a != b for a, b in zip(decisions, decisions[1:])]
    assert sum(flips) == 1                  # host ... host, device ... device
    assert dev.drain.n_edges == len(rows) * len(extra)
    for s in rows:
        dev.drain.clear_deps(s)
    assert dev.drain.n_edges == 0 and dev._host_tick_pays()


def test_n_edges_follows_every_edge_mutation():
    _store, dev, _safe = make_device_state(mesh=None)
    dr = dev.drain
    a, b, c = (dr.alloc(_txn_id(i)) for i in range(3))
    dr.add_edge(a, b)
    dr.add_edge(a, b)                       # a set: counted once
    dr.add_edge(a, c)
    dr.add_edge(b, c)
    assert dr.n_edges == 3
    dr.free(c)                              # takes both edges into c along
    assert dr.n_edges == 1
    dr.clear_deps(a)
    assert dr.n_edges == 0 == sum(map(len, dr.deps_of))


@pytest.mark.parametrize("pin,host", [("host", True), ("device", False),
                                      ("dense", False)])
def test_route_override_pins_the_tick(calibration, pin, host):
    _store, dev, safe = make_device_state(mesh=None)
    _arm_rows(dev, 1500 if host else 6, 2)  # the size the model prices away
    dev.route_override = pin
    assert dev._host_tick_pays() is host
    if not host:
        dev._tick(_NoCommandsSafe(safe.store))
        assert dev.n_priced_host_ticks == 0
        assert dev.kernel_times["drain_tick_wait"][0] == 1
        assert "drain_tick_host" not in dev.kernel_times


def test_priced_tick_is_no_fault_and_quarantined_tick_still_is(calibration):
    _store, dev, safe = make_device_state(mesh=None)
    _arm_rows(dev, 6, 1)
    seen = []
    dev.store.node = type("N", (), {"drain_observer": staticmethod(
        lambda _store, mode, n: seen.append((mode, n)))})()
    dev._tick(_NoCommandsSafe(safe.store))
    assert dev.n_priced_host_ticks == 1 and dev.n_host_ticks == 0
    assert dev.kernel_times["drain_tick_host"][0] == 1
    assert "drain_tick_wait" not in dev.kernel_times
    assert dev.n_device_faults == 0 and dev.n_quarantines == 0
    assert dev.drain._state_cache is None   # nothing was uploaded
    dev._dev_quar_flushes = 3               # the ladder's guard
    dev._tick(_NoCommandsSafe(safe.store))
    assert dev.n_host_ticks == 1 and dev.n_priced_host_ticks == 1
    assert dev.kernel_times["drain_tick_host"][0] == 1
    assert seen == [("host-priced", 6), ("host", 6)]


def test_widened_wavefront_is_not_repriced(calibration, monkeypatch):
    monkeypatch.delenv("ACCORD_TPU_DRAIN", raising=False)
    _store, dev, safe = make_device_state(mesh=None)
    _arm_rows(dev, 6, 1)
    assert dev._host_tick_pays()
    dev._drain_wavefront = 4
    dev._tick(_NoCommandsSafe(safe.store))
    assert dev.n_wavefront_ticks == 1 and dev.n_priced_host_ticks == 0


class _Clock:
    """A node that has a clock and nothing else."""

    def __init__(self):
        self.micros = 0

    def now_micros(self):
        return self.micros


def _tick_modes(dev, safe, clock, steps):
    """One tick at each of ``steps`` (micros of the node's clock): which
    of them went to the device."""
    on_device = []
    for at in steps:
        clock.micros = at
        waits = dev.kernel_times.get("drain_tick_wait", (0, 0.0))[0]
        dev._tick(_NoCommandsSafe(safe.store))
        on_device.append(
            dev.kernel_times.get("drain_tick_wait", (0, 0.0))[0] > waits)
    return on_device


def test_audit_tick_crosses_the_device_once_a_period(calibration):
    """Priced to the host at every tick, yet the device route is not left
    alone for longer than TICK_AUDIT_MICROS of the node's clock: the
    store's first tick and the first tick a period after each device tick
    are whole device ticks, counted, and no fault."""
    _store, dev, safe = make_device_state(mesh=None)
    _arm_rows(dev, 6, 1)
    dev.store.node = clock = _Clock()
    t = dev.TICK_AUDIT_MICROS
    steps = [5, t // 2, t + 4, t + 5, t + 6, 2 * t + 4, 2 * t + 5, 2 * t + 6]
    assert _tick_modes(dev, safe, clock, steps) == [
        True, False, False, True, False, False, True, False]
    assert dev.n_audit_ticks == 3 and dev.n_priced_host_ticks == 5
    assert dev.kernel_times["drain_tick_host"][0] == 5
    assert dev.n_host_ticks == 0 and dev.n_device_faults == 0
    assert dev._host_tick_pays()            # the price never moved


class _ClockWithDispatcher(_Clock):
    """A node with a clock whose dispatcher keeps what registers."""

    def __init__(self):
        super().__init__()
        self.dispatcher = self
        self.ticks, self.flushes = [], []

    def register_tick(self, dev):
        self.ticks.append(dev)

    def register_flush(self, dev):
        self.flushes.append(dev)


@pytest.mark.parametrize("case", ["clock", "quarantined", "no-clock"])
def test_a_flush_schedules_no_tick_on_a_store_that_arms_nothing(
        calibration, case):
    """Range scans over rare inserts never wait on each other: nothing is
    armed, no status move schedules a tick.  A served flush schedules none
    either, however old the store's last device tick is: the audit rides
    ticks that carry work, and a store without any is asked for it by its
    serving node's timer alone (audit_route, below)."""
    _store, dev, safe = make_device_state(mesh=None)
    node = _ClockWithDispatcher()
    if case != "no-clock":
        dev.store.node = node
    else:
        dev.store.node = type("N", (), {"dispatcher": node})()
    if case == "quarantined":
        dev._dev_quar_flushes = 1 << 30
    t = dev.TICK_AUDIT_MICROS
    for at in (5, t // 2, t + 5, 3 * t):
        node.micros = at
        dev.enqueue_query(("q",), None, None)
    assert node.ticks == [] and dev.n_ticks == dev.n_audit_ticks == 0
    assert "drain_tick_wait" not in dev.kernel_times
    assert len(node.flushes) == 1            # the queue registered once
    # and a tick that finds nothing active launches nothing
    dev._tick(_NoCommandsSafe(safe.store))
    assert "drain_tick_wait" not in dev.kernel_times
    assert dev.n_audit_ticks == 0


def test_audit_route_audits_a_store_that_drives_nothing(calibration):
    """The serving node's timer: every tick it asks for crosses the device
    boundary, a whole tick that finds no candidate where the store has no
    row to drive; the ticks traffic schedules audit a period after the
    last device tick, the timer's included, and launch nothing on an idle
    store."""
    _store, dev, safe = make_device_state(mesh=None)
    dev.store.node = node = _ClockWithDispatcher()
    seen = []
    node.drain_observer = lambda _store, mode, n: seen.append((mode, n))
    t = dev.TICK_AUDIT_MICROS

    def on_device(at, ask):
        node.micros = at
        if ask:
            dev.audit_route()
            assert node.ticks.pop() is dev and not node.ticks
            dev._tick_scheduled = False     # as the dispatcher's run does
        waits = dev.kernel_times.get("drain_tick_wait", (0, 0.0))[0]
        dev._tick(_NoCommandsSafe(safe.store))
        return dev.kernel_times.get("drain_tick_wait", (0, 0.0))[0] > waits

    # the timer's period is the audit's: a tick 2 ms after each firing
    assert [on_device(at, True) for at in (5, t + 3, 2 * t + 1)] == [True] * 3
    assert dev.n_audit_ticks == 3 and seen == [("device", 0)] * 3
    assert not on_device(4 * t, False)      # idle and nobody asked
    assert dev.n_audit_ticks == 3 and dev.n_ticks == 4 and not dev._audit_asked
    assert dev.n_priced_host_ticks == dev.n_host_ticks == 0
    assert dev.n_device_faults == 0
    # rows to drive: an asked tick is the work tick, on the device; the
    # ticks of traffic ride a period behind it
    _arm_rows(dev, 6, 1)
    assert [on_device(5 * t, True), on_device(5 * t + 9, False),
            on_device(6 * t - 1, False), on_device(6 * t, False)] \
        == [True, False, False, True]
    assert dev.n_audit_ticks == 5 and dev.n_priced_host_ticks == 2
    assert seen[3:] == [("device", 6), ("host-priced", 6),
                        ("host-priced", 6), ("device", 6)]


@pytest.mark.parametrize("case", ["no-clock", "pinned-host", "quarantined"])
def test_audit_route_launches_nothing_without_a_clock_under_a_pin_or_the_ladder(
        calibration, case):
    _store, dev, safe = make_device_state(mesh=None)
    node = _ClockWithDispatcher()
    if case != "no-clock":
        dev.store.node = node
    else:
        dev.store.node = type("N", (), {"dispatcher": node})()
    if case == "pinned-host":
        dev.route_override = "host"
    if case == "quarantined":
        dev._dev_quar_flushes = 1 << 30
    for at in (1, dev.TICK_AUDIT_MICROS + 1):
        node.micros = at
        dev.audit_route()
        dev._tick_scheduled = False
        dev._tick(_NoCommandsSafe(safe.store))
    assert len(node.ticks) == 2 and dev.n_ticks == 2
    assert dev.n_audit_ticks == dev.n_host_ticks == 0
    assert "drain_tick_wait" not in dev.kernel_times


@pytest.mark.parametrize("case", ["no-clock", "pinned-host", "quarantined",
                                  "priced-to-device"])
def test_no_audit_without_a_clock_under_a_pin_or_the_ladder(calibration,
                                                            case):
    _store, dev, safe = make_device_state(mesh=None)
    _arm_rows(dev, 1500 if case == "priced-to-device" else 6, 2)
    clock = _Clock()
    if case != "no-clock":
        dev.store.node = clock
    if case == "pinned-host":
        dev.route_override = "host"
    if case == "quarantined":
        dev._dev_quar_flushes = 1 << 30
    t = dev.TICK_AUDIT_MICROS
    on_device = _tick_modes(dev, safe, clock, [1, t + 1, 2 * t + 2])
    assert on_device == [case == "priced-to-device"] * 3
    assert dev.n_audit_ticks == 0
    if case == "priced-to-device":          # its own ticks keep the clock
        assert dev._tick_dev_micros == 2 * t + 2


def test_fault_on_an_audit_tick_meets_the_ladder(calibration):
    """What the audit is for: a device route that broke while every tick
    was priced to the host is found by the ladder at the next audit, and
    that tick is served by the host fallback with the same candidates."""
    from accord_tpu.utils import faults
    from accord_tpu.utils.random_source import RandomSource
    _store, dev, safe = make_device_state(mesh=None)
    rows = _arm_rows(dev, 6, 1)
    dev.store.node = clock = _Clock()
    seen = []
    clock.drain_observer = lambda _store, mode, n: seen.append((mode, n))
    t = dev.TICK_AUDIT_MICROS
    assert _tick_modes(dev, safe, clock, [1, 2]) == [True, False]
    clock.micros = t + 1
    with faults.device_fault("kernel_launch", 1.0, RandomSource(7)):
        dev._tick(_NoCommandsSafe(safe.store))
    assert dev.n_audit_ticks == 2 and dev.n_device_faults == 1
    assert dev.n_host_ticks == 1 and dev.n_quarantines == 1
    assert seen == [("device", len(rows)), ("host-priced", len(rows)),
                    ("host", len(rows))]


def test_measured_calibration_prices_the_python_sweep():
    calib = DeviceState._measure_route_calibration()
    # a Python loop: far dearer per element than the numpy pass c_host prices
    assert calib["c_sweep"] > 10 * calib["c_host"]
    assert 1e-8 < calib["c_sweep"] < 1e-3


# ---------------------------------------------------------------------------
# (2) the two routes hand over the same candidates in the same order
# ---------------------------------------------------------------------------
def _random_mirror(dev, n, rng):
    """Chains, fans, awaits-all rows, undecided and terminal deps."""
    dr = dev.drain
    kinds = [TxnKind.Write, TxnKind.Read, TxnKind.ExclusiveSyncPoint]
    ids = [_txn_id(i, kinds[int(rng.integers(10)) // 4]) for i in range(n)]
    slots = [dr.alloc(t) for t in ids]
    statuses = [dk.SLOT_PREACCEPTED, dk.SLOT_ACCEPTED, dk.SLOT_COMMITTED,
                dk.SLOT_STABLE, dk.SLOT_STABLE, dk.SLOT_STABLE,
                dk.SLOT_APPLIED, dk.SLOT_INVALIDATED]
    for t, s in zip(ids, slots):
        st = statuses[int(rng.integers(len(statuses)))]
        # executeAt moves off the TxnId for some, with ties in hlc that the
        # node breaks
        exec_at = t if rng.random() < 0.6 else Timestamp.from_values(
            1, 100 + int(rng.integers(2 * n)), 1 + int(rng.integers(3)))
        dr.set_status(s, st, exec_at if st >= dk.SLOT_ACCEPTED else None)
        dr.active[s] = st == dk.SLOT_STABLE and rng.random() < 0.9
    dr.set_status(slots[0], dk.SLOT_STABLE, ids[0])     # one row is driven
    dr.active[slots[0]] = True
    for i in range(1, n):
        if rng.random() < 0.5:              # chains
            dr.add_edge(slots[i], slots[i - 1])
    hub = slots[int(rng.integers(n))]
    for s in slots:                         # a fan into one hub
        if s != hub and rng.random() < 0.3:
            dr.add_edge(s, hub)
    for _ in range(n):                      # and the rest at random
        a, b = (slots[int(x)] for x in rng.integers(n, size=2))
        if a != b:
            dr.add_edge(a, b)
    return ids


def _tick_candidates(dev, safe, monkeypatch):
    got = []
    monkeypatch.setattr(commands, "refresh_waiting_and_maybe_execute",
                        lambda _safe, txn_id: got.append(txn_id))
    dev._tick(_NoCommandsSafe(safe.store))
    return got


@pytest.mark.parametrize("n", [5, 48, 200])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_priced_host_tick_equals_pinned_device_tick(n, seed, calibration,
                                                    monkeypatch):
    out = {}
    for route in (None, "device"):
        _store, dev, safe = make_device_state(mesh=None)
        _random_mirror(dev, n, np.random.default_rng(1000 * n + seed))
        dev.route_override = route
        out[route] = _tick_candidates(dev, safe, monkeypatch)
        if route is None:
            assert dev.n_priced_host_ticks == 1 and dev.n_host_ticks == 0
        else:
            assert dev.n_priced_host_ticks == 0 and dev.n_host_ticks == 0
            assert dev.kernel_times["drain_tick_wait"][0] == 1
    assert out[None] == out["device"]
    assert n < 48 or out[None]              # the mirrors are not all blocked


# ---------------------------------------------------------------------------
# (2b) a tick priced to the host starts the log-depth cascade all the same
# ---------------------------------------------------------------------------
def _apply_synchronously(dev, monkeypatch):
    """The synchronous-cascade regime: a candidate whose deps have all
    applied reaches Applied before its tick returns; any other candidate is
    the no-op the real re-validation makes of it."""
    dr = dev.drain

    def execute(_safe, txn_id):
        s = dr.slot_of[txn_id]
        if all(int(dr.status[d]) == dk.SLOT_APPLIED for d in dr.deps_of[s]):
            dr.set_status(s, dk.SLOT_APPLIED, None)
            dr.active[s] = False

    monkeypatch.setattr(commands, "refresh_waiting_and_maybe_execute",
                        execute)


def _arm_chain(dev, links):
    dr = dev.drain
    ids = [_txn_id(i) for i in range(links)]
    slots = [dr.alloc(t) for t in ids]
    for t, s in zip(ids, slots):
        dr.set_status(s, dk.SLOT_STABLE, t)
        dr.active[s] = True
    for a, b in zip(slots[1:], slots):
        dr.add_edge(a, b)
    return slots


@pytest.mark.parametrize("links", [40, 500])
def test_chain_on_a_host_priced_store_drains_in_log_depth_ticks(
        links, calibration, monkeypatch):
    """Unpinned, and small enough that its one-antichain sweep prices to the
    host: the first tick is the host's, it applied all it found, so the
    wavefront widens and the level kernel takes the chain from there —
    O(log depth) ticks, not a tick (and an O(live set) sweep) a link."""
    monkeypatch.delenv("ACCORD_TPU_DRAIN", raising=False)
    _store, dev, safe = make_device_state(mesh=None)
    slots = _arm_chain(dev, links)
    assert dev.route_override is None and dev._host_tick_pays()
    _apply_synchronously(dev, monkeypatch)
    ticks = 0
    while dev.drain.active.any():
        dev._tick(_NoCommandsSafe(safe.store))
        ticks += 1
        assert ticks <= links
    assert all(int(dev.drain.status[s]) == dk.SLOT_APPLIED for s in slots)
    assert dev.n_priced_host_ticks == 1 and dev.n_wavefront_ticks > 0
    assert ticks <= 2 + links.bit_length()
    assert dev.n_host_ticks == 0 and dev.n_device_faults == 0


def test_host_priced_tick_that_leaves_a_candidate_unapplied_does_not_widen(
        calibration, monkeypatch):
    """Protocol-flow ticks (execution is asynchronous: a candidate is still
    Stable when its tick returns) stay on the priced one-antichain sweep."""
    monkeypatch.delenv("ACCORD_TPU_DRAIN", raising=False)
    _store, dev, safe = make_device_state(mesh=None)
    _arm_chain(dev, 6)
    for _ in range(3):
        assert _tick_candidates(dev, safe, monkeypatch) == [_txn_id(0)]
        assert dev._drain_wavefront == 1
    assert dev.n_priced_host_ticks == 3 and dev.n_wavefront_ticks == 0
    assert "drain_tick_wait" not in dev.kernel_times


# ---------------------------------------------------------------------------
# (3) fused ticks take no member that is priced to the host
# ---------------------------------------------------------------------------
def test_stores_priced_to_the_host_join_no_fused_tick(calibration):
    node, stores = make_dispatch_node((11, 23, 37, 41), fusion=True,
                                      route=None)
    devs = [dev for dev, _safe, _qs in stores]
    for dev, rows in zip(devs, (1500, 4, 1500, 4)):
        dev.mesh = None
        _arm_rows(dev, rows, 2)
        dev.store.execute = lambda _ctx, fn, shim=dev.store: \
            node.scheduler.now(lambda: fn(_NoCommandsSafe(shim)))
    assert [d._host_tick_pays() for d in devs] == [False, True, False, True]
    fused_by = node.dispatcher._prepare_fused_ticks(devs)
    assert set(fused_by) == {id(devs[0]), id(devs[2])}
    (tick,) = set(fused_by.values())
    assert tick.members == [devs[0], devs[2]]
    assert not tick.serves(devs[1]) and not tick.serves(devs[3])
    # a node whose stores are all priced to the host launches nothing
    assert node.dispatcher._prepare_fused_ticks([devs[1], devs[3]]) == {}
    launches = node.dispatcher.n_fused_tick_launches
    for dev in devs:
        dev.schedule_tick()
    node.scheduler.run()
    assert node.dispatcher.n_fused_tick_launches == launches + 1
    assert [d.n_fused_ticks for d in devs] == [1, 0, 1, 0]
    assert [d.n_priced_host_ticks for d in devs] == [0, 1, 0, 1]
    assert all(d.n_host_ticks == 0 for d in devs)
