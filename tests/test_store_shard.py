"""Store-sharded device tables (r21): sliced residency must be invisible.

One store's slot table lives partitioned across the mesh — each device owns
a contiguous slot slice, registrations scatter to the owning slice, and a
deps query fans to every slice with the pair merge done on device.  The
subsystem is a SCALING layer riding the budget ladder (breach -> compact ->
spill-to-sharded -> host-pinned), so the contract is byte-identity: every
sharded-store route must return bit-identical packed-CSR dep sets and
identical attributed builder output vs. the host oracle AND vs. the
single-device route over the same registrations.  A seeded run_property
sweep drives registration interleavings, compaction mid-stream, point+range
queries, and attribution drops through all three builds; satellite legs pin
the spill rung, the un-terminal host-pin recovery, the escape hatch, and the
c_shard routing coefficient."""

import os

import numpy as np
import pytest

from accord_tpu.local.commands_for_key import InternalStatus
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
from accord_tpu.utils.random_source import RandomSource

from tests.conftest import make_device_state
from tests.proptest import case_budget, run_property
from tests.test_routing import _attributed, _reference
from tests.test_device_faults import _register_n

# under the ACCORD_TPU_STORE_SHARD=off canary run the spill rung is dormant
# by contract (the ladder behaves exactly pre-r21), so every leg here —
# including the hatch legs, which monkeypatch the same env — stands down
pytestmark = pytest.mark.skipif(
    os.environ.get("ACCORD_TPU_STORE_SHARD", "").lower()
    in ("off", "0", "false", "no"),
    reason="ACCORD_TPU_STORE_SHARD=off canary run: spill rung dormant")

SPILL_BUDGET = 64   # small enough that every case's grow breaches it


def _mk_txn(i, hlc, kind, dom, nslot):
    return TxnId.create(1, hlc, kind, dom, nslot)


# ---------------------------------------------------------------------------
# seeded case: an op log (register / invalidate / floor) + mixed queries
# ---------------------------------------------------------------------------
class ShardCase:
    def __init__(self, rng: RandomSource):
        self.keyspace = 2_000 + rng.next_int(3_000)
        n = 150 + rng.next_int(80)
        self.ops = []
        hlcs = iter(range(1, 40 * n, 7))
        floor_at = 40 + rng.next_int(n - 60) if rng.decide(0.5) else None
        for i in range(n):
            hlc = next(hlcs)
            kind = TxnKind.Write if rng.decide(0.7) else TxnKind.Read
            r = rng.next_int(100)
            if r < 40:
                spec = ("keys", [rng.next_int(self.keyspace)
                                 for _ in range(1 + rng.next_int(3))])
            else:
                s = rng.next_int(self.keyspace - 80)
                spec = ("range", s, s + 1 + rng.next_int(80))
            dom = Domain.Range if spec[0] == "range" else Domain.Key
            self.ops.append(("reg", hlc, kind, dom, spec,
                             1 + rng.next_int(5)))
            if rng.decide(0.08):          # attribution drop
                self.ops.append(("inval", hlc))
            if floor_at is not None and i == floor_at:
                # mid-stream compaction trigger: everything so far becomes
                # redundant; the next budget breach compacts, not grows
                self.ops.append(("floor", 50 * n))
        self.queries = []
        for _ in range(8):
            bound = TxnId.create(1, 60 * n + rng.next_int(40 * n),
                                 TxnKind.Write, Domain.Key, 1)
            toks, rngs = [], []
            for _ in range(1 + rng.next_int(3)):
                if rng.decide(0.6):
                    toks.append(rng.next_int(self.keyspace))
                else:
                    s = rng.next_int(self.keyspace - 80)
                    rngs.append(Range(s, s + 1 + rng.next_int(80)))
            self.queries.append((bound, bound, bound.kind().witnesses(),
                                 toks, rngs))

    def describe(self):
        regs = sum(1 for o in self.ops if o[0] == "reg")
        return (f"ShardCase(regs={regs}, invals="
                f"{sum(1 for o in self.ops if o[0] == 'inval')}, "
                f"floor={any(o[0] == 'floor' for o in self.ops)}, "
                f"queries={len(self.queries)}, keyspace={self.keyspace})")


def _replay(case, mode):
    """Apply the case's op log on a fresh store; returns (dev, safe,
    csr, attributed).  mode: 'sharded' (budget breach -> spill rung),
    'host' (oracle), 'single' (mesh=None dense route)."""
    store, dev, safe = make_device_state(mesh=None if mode == "single"
                                         else "auto")
    dev.route_override = "host" if mode == "host" else "dense"
    if mode == "sharded":
        dev.device_budget_slots = SPILL_BUDGET
    for op in case.ops:
        if op[0] == "reg":
            _, hlc, kind, dom, spec, nslot = op
            tid = _mk_txn(0, hlc, kind, dom, nslot)
            keys = Keys([IntKey(t) for t in spec[1]]) \
                if spec[0] == "keys" else Ranges.of(Range(spec[1], spec[2]))
            dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
        elif op[0] == "inval":
            _, hlc = op
            # re-derive the id the matching reg op created
            reg = next(o for o in case.ops if o[0] == "reg" and o[1] == hlc)
            dev.update_status(_mk_txn(0, reg[1], reg[2], reg[3], reg[5]),
                              int(InternalStatus.INVALIDATED))
        else:
            floor = TxnId.create(1, op[1], TxnKind.ExclusiveSyncPoint,
                                 Domain.Range, 1)
            store.redundant_before.add_redundant(
                Ranges.of(Range(-(1 << 60), 1 << 60)), floor)
    attr = _attributed(dev, safe, case.queries)
    assert attr == _reference(dev, safe, case.queries), mode
    return dev, safe, attr


def _shrink(case):
    for frac in (2, 4):
        if len(case.ops) > 8:
            c = ShardCase.__new__(ShardCase)
            c.keyspace = case.keyspace
            c.ops = case.ops[:len(case.ops) // frac]
            c.queries = case.queries
            yield c
    if len(case.queries) > 1:
        c = ShardCase.__new__(ShardCase)
        c.keyspace = case.keyspace
        c.ops = case.ops
        c.queries = case.queries[:len(case.queries) // 2]
        yield c


def _check_case(case):
    dev, _safe, got_attr = _replay(case, "sharded")
    # a case whose floor compacted below the budget may legitimately never
    # breach again; every OTHER case must have spilled, not pinned
    assert not dev.host_pinned, "spill rung skipped: store pinned to host"
    if dev.store_shards is not None and dev.store_shards.active:
        assert dev.n_store_sharded_flushes >= 1
    _d2, _s2, host_attr = _replay(case, "host")
    _d3, _s3, one_attr = _replay(case, "single")
    assert got_attr == host_attr, "sharded attributed != host oracle"
    assert one_attr == host_attr, "single-device attributed != host oracle"


def test_property_sharded_routes_byte_identical():
    """Seeded sweep: registration interleavings, compaction mid-stream,
    point+range queries, attribution drops — the sharded-store route is
    byte-identical to the host oracle and to the single-device route."""
    run_property(case_budget(4), base_seed=0x57A6D,
                 make_case=ShardCase, check=_check_case,
                 shrink_candidates=_shrink,
                 replay_hint="pytest tests/test_store_shard.py")


@pytest.mark.slow
def test_property_sharded_routes_byte_identical_soak():
    run_property(case_budget(64), base_seed=0x57A6D,
                 make_case=ShardCase, check=_check_case,
                 shrink_candidates=_shrink,
                 replay_hint="pytest tests/test_store_shard.py")


# ---------------------------------------------------------------------------
# the spill rung itself
# ---------------------------------------------------------------------------
def test_budget_breach_spills_to_sharded_not_host():
    """With a mesh available, a budget breach that compaction cannot fix
    activates sliced residency (effective budget x n_devices) instead of
    pinning to host — the r21 rung between compact and host-pinned."""
    store, dev, safe = make_device_state()
    dev.route_override = "dense"
    dev.device_budget_slots = 128
    _register_n(dev, 300, hlc_base=1)       # no floor: compaction can't help
    assert not dev.host_pinned
    assert dev.store_shards is not None and dev.store_shards.active
    assert dev.deps.capacity > 128          # grew past the single-dev budget
    assert dev.n_oom_degraded == 0
    d = dev.store_shards.d
    assert d == 8                           # the virtual test mesh
    assert dev.deps.capacity <= 128 * d


def test_sharded_store_breaching_mesh_budget_pins_to_host():
    """The sharded budget is budget x n_devices; breaching THAT still ends
    on the host rung — the ladder terminates, it does not recurse."""
    store, dev, safe = make_device_state()
    dev.route_override = "dense"
    dev.device_budget_slots = 32
    _register_n(dev, 300, hlc_base=1)       # needs 512 slots > 32*8
    assert dev.host_pinned and dev.n_oom_degraded == 1


def test_escape_hatch_disables_spill(monkeypatch):
    """ACCORD_TPU_STORE_SHARD=off: the ladder behaves exactly pre-r21 —
    breach -> compact -> host-pinned, no shards object ever activates."""
    monkeypatch.setenv("ACCORD_TPU_STORE_SHARD", "off")
    from accord_tpu.parallel.store_shard import store_shard_enabled
    assert not store_shard_enabled()
    store, dev, safe = make_device_state()
    dev.route_override = "dense"
    dev.device_budget_slots = 128
    _register_n(dev, 300, hlc_base=1)
    assert dev.host_pinned and dev.n_oom_degraded == 1
    assert dev.store_shards is None or not dev.store_shards.active


def test_sharded_survives_capacity_growth_waves():
    """Growth redistributes slots across slices (slot // slice_n changes
    with capacity): query between growth waves, identity must hold at
    every capacity."""
    store, dev, safe = make_device_state()
    dev.route_override = "dense"
    dev.device_budget_slots = SPILL_BUDGET
    store2, dev2, safe2 = make_device_state(mesh=None)
    dev2.route_override = "dense"
    bound = TxnId.create(1, 10_000_000, TxnKind.Write, Domain.Key, 1)
    qs = [(bound, bound, bound.kind().witnesses(), [(i * 37) % 4096], [])
          for i in range(6)]
    base = 1
    for wave in range(3):
        _register_n(dev, 120, hlc_base=base)
        _register_n(dev2, 120, hlc_base=base)
        base += 10_000
        got = _attributed(dev, safe, qs)
        expect = _attributed(dev2, safe2, qs)
        assert got == expect, f"wave {wave}: sharded != single-device"
    assert dev.store_shards is not None and dev.store_shards.active
    assert dev.n_store_sharded_flushes >= 2
    assert dev.n_shard_merge_bytes > 0


# ---------------------------------------------------------------------------
# un-terminal host_pinned (satellite): recovery back off the floor
# ---------------------------------------------------------------------------
def _drain_recheck(dev, safe, qs, limit=200):
    ref = _attributed(dev, safe, qs)
    for _ in range(limit):
        if not dev.host_pinned:
            break
        assert _attributed(dev, safe, qs) == ref
    return ref


def test_host_pin_recovers_after_budget_raise():
    """host_pinned is no longer terminal: once the budget is raised past
    capacity, the periodic recheck unpins the store (loud one-shot
    recovery, counter oom_recovered) and flushes return to the device."""
    store, dev, safe = make_device_state(mesh=None)
    dev.route_override = "dense"
    dev.device_budget_slots = 128
    _register_n(dev, 200, hlc_base=1)
    assert dev.host_pinned
    dev.device_budget_slots = 1 << 16
    bound = TxnId.create(1, 10_000_000, TxnKind.Write, Domain.Key, 1)
    qs = [(bound, bound, bound.kind().witnesses(), [(i * 37) % 4096], [])
          for i in range(4)]
    ref = _drain_recheck(dev, safe, qs)
    assert not dev.host_pinned
    assert dev.n_oom_recovered == 1
    dev_q_before = dev.n_dense_queries + dev.n_mesh_queries
    assert _attributed(dev, safe, qs) == ref
    assert dev.n_dense_queries + dev.n_mesh_queries > dev_q_before


def test_host_pin_recovers_by_spilling_to_sharded():
    """A pinned store whose capacity fits budget x n_devices recovers by
    ACTIVATING shards at the recheck — the recovery path walks back up the
    same ladder it came down."""
    store, dev, safe = make_device_state()
    dev.route_override = "dense"
    dev.device_budget_slots = 32
    _register_n(dev, 300, hlc_base=1)      # 512 slots > 32*8 -> pinned
    assert dev.host_pinned
    dev.device_budget_slots = 128          # 512 <= 128*8: shards now fit
    bound = TxnId.create(1, 10_000_000, TxnKind.Write, Domain.Key, 1)
    qs = [(bound, bound, bound.kind().witnesses(), [(i * 37) % 4096], [])
          for i in range(4)]
    ref = _drain_recheck(dev, safe, qs)
    assert not dev.host_pinned
    assert dev.n_oom_recovered == 1
    assert dev.store_shards is not None and dev.store_shards.active
    assert _attributed(dev, safe, qs) == ref
    assert dev.n_store_sharded_flushes >= 1


def test_host_pin_recovery_respects_escape_hatch(monkeypatch):
    """With the hatch off and capacity above the single-device budget,
    the recheck must NOT unpin (there is nowhere to recover to)."""
    monkeypatch.setenv("ACCORD_TPU_STORE_SHARD", "off")
    store, dev, safe = make_device_state()
    dev.route_override = "dense"
    dev.device_budget_slots = 32
    _register_n(dev, 300, hlc_base=1)
    assert dev.host_pinned
    dev.device_budget_slots = 64           # still < capacity 512
    bound = TxnId.create(1, 10_000_000, TxnKind.Write, Domain.Key, 1)
    qs = [(bound, bound, bound.kind().witnesses(), [37], [])]
    for _ in range(130):                   # past the first recheck window
        _attributed(dev, safe, qs)
    assert dev.host_pinned and dev.n_oom_recovered == 0


# ---------------------------------------------------------------------------
# routing coefficient: priced, never a device-count threshold
# ---------------------------------------------------------------------------
def test_c_shard_measured_when_mesh_present():
    store, dev, safe = make_device_state()
    calib = dev._calibration()
    assert "c_shard" in calib and calib["c_shard"] > 0.0


def test_slice_bookkeeping_unit():
    """quarantined_slot_mask maps global slots to their owning slice."""
    store, dev, safe = make_device_state()
    dev.route_override = "dense"
    dev.device_budget_slots = 128
    _register_n(dev, 300, hlc_base=1)
    sh = dev.store_shards
    assert sh.active and not sh.any_quarantined()
    sn = sh.slice_n()
    assert sn * sh.d == dev.deps.capacity
    sh.quar[3] = 5
    cj = np.array([0, sn - 1, 3 * sn, 4 * sn - 1, 4 * sn], np.int64)
    np.testing.assert_array_equal(
        sh.quarantined_slot_mask(cj),
        np.array([False, False, True, True, False]))
    assert sh.quarantined_slices() == [3]
    sh.quar[3] = 0
