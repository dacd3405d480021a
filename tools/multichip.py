"""The revived MULTICHIP harness (r21).

    python tools/multichip.py [--devices 8] [--out MULTICHIP_r11.json]

The MULTICHIP_r*.json trajectory froze at r05 with a vestigial pass/fail
schema ({n_devices, rc, ok, tail}) — the driver shelled into
``__graft_entry__.dryrun_multichip`` and recorded only whether it lived.
This harness reruns that r01-r05 leg AND the r21 sharded-store legs in one
process, emitting a real metrics artifact:

- ``dryrun_protocol``: the original leg — jit + run the sharded protocol
  step (store-axis sharding, all-gather deps merge, frontier exchange,
  live sim-cluster slice) with its bit-exactness asserts intact.
- ``store_shard``: ONE store scaled past a single device's budget through
  the ladder's spill rung — slots/device, merge wall per flush, download
  bytes, and ``vs_single_device`` (the same registrations served by the
  unbudgeted single-device dense route), with the sharded flush's built
  Deps asserted equal to both the host route's and the single-device
  route's.
- ``slice_fault``: one injected device fault during a sliced flush — the
  fault must quarantine exactly ONE slice (not the node), results stay
  byte-identical, and the slice probes back in.

Exit status: 0 = every leg ok (artifact written either way)."""

import argparse
import json
import os
import sys
import time


def _force_cpu_mesh(n_devices):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices but jax initialized with "
            f"{len(jax.devices())}; run in a fresh process")


def _store_and_safe():
    from accord_tpu.local.redundant import RedundantBefore

    class Store:
        def __init__(self):
            self.commands_for_key = {}
            self.redundant_before = RedundantBefore()

        class node:
            scheduler = None

    store = Store()

    class Safe:
        @staticmethod
        def redundant_before():
            return store.redundant_before

    Safe.store = store
    return store, Safe()


def _bulk_fill(dev, n, keyspace, seed):
    """Vectorized registration fill to exactly ``n`` live slots: walks the
    capacity ladder through _approve_grow (so a budgeted store exercises
    the real spill rung), then writes the same column layout alloc does."""
    import numpy as np
    from accord_tpu.ops import deps_kernel as dk
    from accord_tpu.primitives.timestamp import Domain, TxnKind

    m = dev.deps
    while m.capacity < n:
        m.free_slots.clear()
        m._grow_capacity()
    rng = np.random.default_rng(seed)
    hlc = rng.choice(np.arange(1, 4 * n, dtype=np.int64), size=n,
                     replace=False)
    flags = np.int64((int(TxnKind.Write) << 1) | int(Domain.Key))
    m.msb[:] = np.int64(1) << 16
    m.lsb[:] = (hlc << 16) | flags
    m.node[:] = (np.arange(n) % 5 + 1).astype(np.int32)
    m.kind[:] = int(TxnKind.Write)
    m.domain[:] = int(Domain.Key)
    # a grade that emits: a TRANSITIVE key dep is elided by every flush,
    # and an empty answer compares equal to anything
    m.status[:] = dk.SLOT_PREACCEPTED
    toks = rng.integers(0, keyspace, size=n).astype(np.int64)
    m.lo[:, 0] = toks
    m.hi[:, 0] = toks
    m.free_slots = []
    m.n_live = n
    m.version += 1
    m.mut_version += 1
    m._snap = None
    m._device = None
    m._device_sh = None
    m._dirty.clear()
    m._dirty_sh.clear()
    m._attr_dirty_sh.clear()


def _queries(n, keyspace, seed):
    import numpy as np
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        bound = TxnId.create(1, int(rng.integers(10**7, 2 * 10**7)),
                             TxnKind.Write, Domain.Key, 1)
        out.append((bound, bound, bound.kind().witnesses(),
                    [int(rng.integers(0, keyspace))], []))
    return out


def leg_dryrun_protocol(n_devices):
    """The r01-r05 leg, asserts intact (raises on any divergence)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__
    t0 = time.time()
    __graft_entry__.dryrun_multichip(n_devices)
    return {"ok": True, "wall_s": round(time.time() - t0, 2)}


def leg_store_shard(n_devices):
    """One store past the single-device budget on the sliced route."""
    from accord_tpu.local.device_index import DeviceState
    from accord_tpu.primitives.deps import DepsBuilder

    # the per-device budget that makes the table fit ONLY as d slices
    N, B, KEYS = 1 << 18, 64, 1 << 20
    BUDGET = N // n_devices
    store, safe = _store_and_safe()
    dev = DeviceState(store)
    assert dev.mesh is not None, "store_shard leg needs the mesh"
    dev.device_budget_slots = BUDGET
    dev.route_override = "dense"
    _bulk_fill(dev, N, KEYS, seed=13)
    assert dev.store_shards is not None and dev.store_shards.active, \
        "budget breach never spilled to the sharded store"
    assert not dev.host_pinned
    qs = _queries(B, KEYS, seed=17)

    def csr(d):
        """The built Deps of one flush, as comparable CSR columns."""
        builders = [DepsBuilder() for _ in qs]
        d.deps_query_batch_end_attributed(
            safe, d.deps_query_batch_begin(qs, immediate=True), builders)
        return [(b.key_deps.to_csr(), b.key_deps.txn_ids)
                for b in (bd.build() for bd in builders)]

    dev.route_override = "host"
    host = csr(dev)
    assert any(ids for _csr, ids in host), "no dep to compare"
    dev.route_override = "dense"
    csr(dev)                               # slice upload + compile
    reps = 3
    bytes0 = dev.download_bytes
    t0 = time.time()
    for _ in range(reps):
        got = csr(dev)
    shard_dt = (time.time() - t0) / reps
    download_bytes = (dev.download_bytes - bytes0) // reps
    assert got == host, "sharded flush != host route"
    # the same registrations on the unbudgeted SINGLE-DEVICE dense route
    store1, _safe1 = _store_and_safe()
    dev1 = DeviceState(store1)
    dev1.mesh = None
    dev1.route_override = "dense"
    _bulk_fill(dev1, N, KEYS, seed=13)
    csr(dev1)                              # upload + compile
    t0 = time.time()
    for _ in range(reps):
        one = csr(dev1)
    single_dt = (time.time() - t0) / reps
    assert one == host, "single-device flush != host route"
    sh = dev.store_shards
    return {
        "ok": True, "byte_identical": True,
        "live_slots": N, "device_budget_slots": BUDGET,
        "slots_per_device": N // sh.d,
        "merge_ms_per_flush": round(1e3 * shard_dt, 1),
        "single_device_ms_per_flush": round(1e3 * single_dt, 1),
        "vs_single_device": round(single_dt / shard_dt, 2),
        "download_bytes": int(download_bytes),
        "shard_merge_bytes": int(dev.n_shard_merge_bytes),
        "store_sharded_flushes": int(dev.n_store_sharded_flushes),
    }


def leg_slice_fault(n_devices):
    """One injected fault during a sliced flush: slice quarantine, not a
    node quarantine; byte-identical; probes back in."""
    import numpy as np
    from accord_tpu.local.device_index import DeviceState
    from accord_tpu.primitives.deps import DepsBuilder
    from accord_tpu.utils import faults
    from accord_tpu.utils.random_source import RandomSource

    N, B, KEYS = 1 << 16, 32, 1 << 18
    BUDGET = N // n_devices
    store, safe = _store_and_safe()
    dev = DeviceState(store)
    assert dev.mesh is not None
    dev.device_budget_slots = BUDGET
    dev.route_override = "dense"
    _bulk_fill(dev, N, KEYS, seed=29)
    assert dev.store_shards is not None and dev.store_shards.active
    qs = _queries(B, KEYS, seed=31)

    def attributed():
        builders = [DepsBuilder() for _ in qs]
        h = dev.deps_query_batch_begin(qs, immediate=True)
        dev.deps_query_batch_end_attributed(safe, h, builders)
        return [sorted((k, tuple(d.key_deps.txn_ids_for(k)))
                       for k in d.key_deps.keys.tokens())
                for d in (b.build() for b in builders)]

    expect = attributed()
    with faults.device_fault("transfer", 1.0, RandomSource(0xDEC0)):
        got = attributed()
    assert got == expect, "faulted flush diverged"
    assert dev.n_slice_quarantines == 1, dev.n_slice_quarantines
    assert dev.n_quarantines == 0, "whole-device quarantine fired"
    sh = dev.store_shards
    quarantined = sh.quarantined_slices()
    assert len(quarantined) == 1
    # hybrid flushes while quarantined, then drain to the probe/restore
    hybrid = 0
    while sh.any_quarantined():
        assert attributed() == expect
        hybrid += 1
    assert attributed() == expect          # the probe
    assert dev.n_slice_restores >= 1
    assert attributed() == expect          # healthy again
    return {
        "ok": True, "byte_identical": True,
        "fault_kind": "transfer", "quarantined_slice": quarantined[0],
        "slice_quarantines": int(dev.n_slice_quarantines),
        "whole_device_quarantines": int(dev.n_quarantines),
        "hybrid_flushes": hybrid,
        "slice_restores": int(dev.n_slice_restores),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description="multichip harness (r21)")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "MULTICHIP_r11.json"))
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from accord_tpu.ops.packing import startup
    startup()
    _force_cpu_mesh(args.devices)
    import jax

    legs = {}
    rc = 0
    for name, fn in (("dryrun_protocol", leg_dryrun_protocol),
                     ("store_shard", leg_store_shard),
                     ("slice_fault", leg_slice_fault)):
        t0 = time.time()
        try:
            legs[name] = fn(args.devices)
            legs[name]["wall_s"] = round(time.time() - t0, 2)
            print(f"# {name}: ok {json.dumps(legs[name])}")
        except Exception as e:  # noqa: BLE001 — legs are independent
            rc = 1
            legs[name] = {"ok": False, "error": repr(e),
                          "wall_s": round(time.time() - t0, 2)}
            print(f"# {name}: FAILED {e!r}", file=sys.stderr)
    doc = {
        "n_devices": args.devices,
        "rc": rc,
        "ok": rc == 0,
        "skipped": False,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "legs": legs,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"# wrote {args.out} rc={rc}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
