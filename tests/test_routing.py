"""Routing equivalence: the regime-adaptive dispatch layer must be invisible
to the protocol.  Property-style seeded runs generate mixed point/range
footprints over live + redundant (below-floor) + invalidated tables and
assert that every route of the ONE flush — host, bucketed, dense, and the
mesh-sharded kernels — builds bit-identical Deps, equal to the reference
passes of tests/deps_oracle.py (floors + elision + key/range attribution,
with and without the batch-global floor in the candidate scan).  A host
brute force anchors the shared answer so an error common to all routes
and the reference cannot hide."""

import numpy as np
import pytest

from accord_tpu.local.commands_for_key import InternalStatus
from accord_tpu.primitives.deps import DepsBuilder
from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind

from tests import deps_oracle
from tests.conftest import make_device_state

ROUTES = ("host", "device", "dense")


def _build(seed, n=220, keyspace=6_000):
    rng = np.random.default_rng(seed)
    store, dev, safe = make_device_state()
    entries = []
    hlcs = rng.choice(np.arange(1, 40 * n), size=n, replace=False)
    for i in range(n):
        kind = TxnKind.Write if rng.random() < 0.7 else TxnKind.Read
        r = rng.random()
        if r < 0.12:       # straggler: wide interval
            s = int(rng.integers(0, keyspace // 2))
            toks, rngs = [], [Range(s, s + keyspace // 3)]
            dom = Domain.Range
        elif r < 0.5:
            toks = [int(t) for t in rng.integers(0, keyspace,
                                                 rng.integers(1, 4))]
            rngs, dom = [], Domain.Key
        else:
            s = int(rng.integers(0, keyspace - 70))
            toks = []
            rngs = [Range(s, s + int(rng.integers(1, 70)))]
            dom = Domain.Range
        tid = TxnId.create(1, int(hlcs[i]), kind, dom,
                           1 + int(rng.integers(0, 5)))
        keys = Ranges.of(*rngs) if rngs else Keys([IntKey(t) for t in toks])
        dev.register(tid, int(InternalStatus.PREACCEPTED), keys)
        alive = True
        if rng.random() < 0.08:
            dev.update_status(tid, int(InternalStatus.INVALIDATED))
            alive = False
        if alive:
            entries.append((tid, toks, rngs))
    # a floor covering the WHOLE key space so min_floor_over engages the
    # device prune and the host route's structural floor
    floor = TxnId.create(1, int(10 * n), TxnKind.ExclusiveSyncPoint,
                         Domain.Range, 1)
    store.redundant_before.add_redundant(
        Ranges.of(Range(-(1 << 60), 1 << 60)), floor)
    qs = []
    for _ in range(28):
        bound = TxnId.create(1, int(rng.integers(40 * n, 80 * n)),
                             TxnKind.Write, Domain.Key, 1)
        toks, rngs = [], []
        for _ in range(int(rng.integers(1, 4))):
            r = rng.random()
            if r < 0.15:    # wide query (dense sub-batch fallback)
                s = int(rng.integers(0, keyspace // 2))
                rngs.append(Range(s, s + keyspace // 3))
            elif r < 0.6:
                toks.append(int(rng.integers(0, keyspace)))
            else:
                s = int(rng.integers(0, keyspace - 70))
                rngs.append(Range(s, s + int(rng.integers(1, 70))))
        qs.append((bound, bound, bound.kind().witnesses(), toks, rngs))
    return store, dev, safe, entries, floor, qs


def _brute(entries, q, floor=None):
    bound, _self_id, witnesses, toks, rngs = q
    out = set()
    for tid, etoks, erngs in entries:
        if not (tid < bound):
            continue
        if floor is not None and tid < floor:
            continue
        if not witnesses.test(tid.kind()):
            continue
        hit = any(t in etoks or any(r.contains_token(t) for r in erngs)
                  for t in toks)
        if not hit:
            for r in rngs:
                if any(r.contains_token(t) for t in etoks) or \
                        any(er.start < r.end and r.start < er.end
                            for er in erngs):
                    hit = True
                    break
        if hit:
            out.add(tid)
    return sorted(out)


def _unpack_builders(builders):
    out = []
    for b in builders:
        deps = b.build()
        out.append(([(k, tuple(deps.key_deps.txn_ids_for(k)))
                     for k in deps.key_deps.keys.tokens()],
                    [(r.start, r.end, tuple(deps.range_deps.txn_ids[j]
                                            for j in row))
                     for r, row in zip(deps.range_deps.ranges,
                                       deps.range_deps._per_range)]))
    return out


def _reference(dev, safe, qs, prune=True):
    """The REFERENCE answer (tests/deps_oracle.py), unpacked."""
    return _unpack_builders(
        deps_oracle.reference_builders(dev, safe, qs, prune))


def _attributed(dev, safe, qs):
    """The product flush on the pinned route, unpacked."""
    return _unpack_builders(deps_oracle.flush_builders(dev, safe, qs))


def _enqueue_flush(dev, qs):
    """Enqueue one store's queries through the coalescing path (the node
    dispatcher decides fused vs solo); returns (builders, failures)."""
    builders = [DepsBuilder() for _ in qs]
    failures = []

    def done(failure, _safe):
        if failure is not None:
            failures.append(failure)

    for q, b in zip(qs, builders):
        dev.enqueue_query(q, b, done)
    return builders, failures


@pytest.mark.parametrize("seed", [11, 23, 47])
def test_all_routes_bit_identical(seed):
    """host == bucketed/dense split == dense == sharded (mesh) flush output
    on random mixed footprints — anchored by a host brute force over the
    live entries above the floor."""
    store, dev, safe, entries, floor, qs = _build(seed)
    outs = {}
    for route in ROUTES:
        dev.route_override = route
        outs["mesh_" + route] = deps_oracle.flush_builders(dev, safe, qs)
    if dev.mesh is not None:    # single-device kernels as well
        saved = dev.mesh
        dev.mesh = None
        for route in ROUTES:
            dev.route_override = route
            outs["single_" + route] = deps_oracle.flush_builders(dev, safe,
                                                                 qs)
        dev.mesh = saved
    base_name = "mesh_host"
    base = _unpack_builders(outs[base_name])
    for name, got in outs.items():
        assert _unpack_builders(got) == base, \
            f"seed={seed} {name} != {base_name}"
    # anchor against brute force (dedupe route-common bugs)
    for b, (q, got) in enumerate(zip(qs, deps_oracle.dep_ids(
            outs[base_name]))):
        assert got == _brute(entries, q, floor), f"seed={seed} query {b}"


@pytest.mark.parametrize("seed", [7, 31, 11, 47])
def test_attributed_routes_bit_identical(seed):
    """Every route's flush — host filter, dense/bucketed in-kernel
    attribution, mesh-merged variants — builds byte-equal Deps to the
    reference passes (tests/deps_oracle.py), whether or not the
    reference's candidate scan used the batch-global floor."""
    store, dev, safe, entries, floor, qs = _build(seed)
    oracle = _reference(dev, safe, qs)
    assert oracle == _reference(dev, safe, qs, prune=False)
    for mesh in (dev.mesh, None):
        dev.mesh = mesh
        for route in ROUTES:
            dev.route_override = route
            assert _attributed(dev, safe, qs) == oracle, \
                f"seed={seed} route={route} mesh={mesh is not None}"


@pytest.mark.parametrize("seed_set", [(11, 23), (31, 47, 7)])
def test_fused_vs_solo_bit_identical(seed_set):
    """r08 launch coalescing must be invisible: ANY interleaving of fused
    and solo flushes — every subset of the node's stores flushing in the
    same event-loop step, fused when >=2 are device-routed — yields the
    byte-identical attributed output of the pinned solo launches."""
    import itertools

    from tests.conftest import make_dispatch_node
    node, stores = make_dispatch_node(seed_set, fusion=True)
    expected = [_reference(dev, safe, qs)
                for dev, safe, qs in stores]
    for r in range(1, len(stores) + 1):
        for combo in itertools.combinations(range(len(stores)), r):
            results = {}
            for i in combo:
                dev, _safe, qs = stores[i]
                results[i] = _enqueue_flush(dev, qs)
            node.scheduler.run()
            for i in combo:
                builders, failures = results[i]
                assert not failures, (seed_set, combo, failures)
                assert _unpack_builders(builders) == expected[i], \
                    f"seeds={seed_set} fused-combo={combo} store {i}"
    assert node.dispatcher.n_fused_launches >= 1
    # interleaved mutation: register fresh txns into one store between
    # rounds — the next fused launch must serve the NEW solo answer
    from accord_tpu.local.commands_for_key import InternalStatus
    from accord_tpu.primitives.keys import IntKey, Keys
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    dev0, safe0, qs0 = stores[0]
    for i in range(16):
        tid = TxnId.create(1, 500_000 + i, TxnKind.Write, Domain.Key, 1)
        dev0.register(tid, int(InternalStatus.PREACCEPTED),
                      Keys([IntKey((i * 131) % 6000)]))
    expected0 = _reference(dev0, safe0, qs0)
    results = {i: _enqueue_flush(stores[i][0], stores[i][2])
               for i in range(len(stores))}
    node.scheduler.run()
    assert _unpack_builders(results[0][0]) == expected0
    for i in range(1, len(stores)):
        assert _unpack_builders(results[i][0]) == expected[i]


def test_fused_unequal_capacities_bit_identical():
    """Stores of different table capacities (128 vs 512 slots) fuse by
    padding inside the kernel — the padded free slots must never surface
    and each store's answer must equal its solo launch."""
    from tests.conftest import DispatchTestNode, DispatchTestStoreShim
    node = DispatchTestNode(fusion=True)
    stores = []
    for i, (seed, n) in enumerate(((31, 120), (47, 500))):
        store, dev, safe, entries, floor, qs = _build(seed, n=n)
        dev.store = DispatchTestStoreShim(store, node, i)
        dev.route_override = "dense"
        stores.append((dev, safe, qs))
    assert len({dev.deps.capacity for dev, _s, _q in stores}) == 2
    expected = [_reference(dev, safe, qs)
                for dev, safe, qs in stores]
    results = [_enqueue_flush(dev, qs) for dev, _s, qs in stores]
    node.scheduler.run()
    assert node.dispatcher.n_fused_launches == 1
    for i in range(len(stores)):
        builders, failures = results[i]
        assert not failures
        assert _unpack_builders(builders) == expected[i], f"store {i}"


def test_fusion_off_pins_solo_launches():
    """The ACCORD_TPU_FUSION escape hatch: with fusion disabled the
    dispatcher still coalesces SCHEDULING (one event per step) but every
    launch is solo — and results are unchanged."""
    from tests.conftest import make_dispatch_node
    node, stores = make_dispatch_node((11, 23), fusion=False)
    expected = [_reference(dev, safe, qs)
                for dev, safe, qs in stores]
    results = [_enqueue_flush(dev, qs) for dev, _safe, qs in stores]
    node.scheduler.run()
    assert node.dispatcher.n_fused_launches == 0
    assert node.dispatcher.n_solo_flushes == len(stores)
    for i, (builders, failures) in enumerate(results):
        assert not failures
        assert _unpack_builders(builders) == expected[i]


@pytest.mark.parametrize("seed", [13, 61])
def test_exact_kernels_match_host_geometry_property(seed):
    """r10 tentpole contract: the entries every device kernel ships equal
    the reference geometry over its own pair list (with the in-kernel
    key-domain first-column dedupe: deps_oracle.attributed_entries) — on
    the mixed point/range footprints of the routing property generator
    (the reference is the executable spec of the emit order)."""
    store, dev, safe, entries, floor, qs = _build(seed)
    for route in ("device", "dense"):
        for mesh in (dev.mesh, None):
            saved = dev.mesh
            dev.mesh = mesh
            dev.route_override = route
            h = dev.deps_query_batch_begin(qs, immediate=True)
            tb, tj, tm, tq, _ids, ivs, qnp, q_m, _q = \
                dev._batch_collect_attr(h)
            dev.mesh = saved
            b_d, j_d, _p = deps_oracle.entry_pairs(tb, tj)
            rb, rj, rm, rq = deps_oracle.attributed_entries(
                b_d.copy(), j_d.copy(), ivs, qnp, q_m)
            got = set(zip(tb.tolist(), tj.tolist(), tm.tolist(),
                          tq.tolist()))
            ref = set(zip(rb.tolist(), rj.tolist(), rm.tolist(),
                          rq.tolist()))
            assert len(got) == len(tb), "a kernel shipped a duplicate"
            assert got == ref, f"seed={seed} route={route} mesh={mesh}"


def test_adaptive_route_is_invisible():
    """Whatever the adaptive chooser picks (route_override=None) must equal
    the pinned routes — the router can only change cost, never results."""
    store, dev, safe, entries, floor, qs = _build(97)
    dev.route_override = "dense"
    want = _attributed(dev, safe, qs)
    dev.route_override = None
    assert _attributed(dev, safe, qs) == want
    assert dev.n_queries == len(qs) * 2


def test_attributed_fused_matches_solo():
    """Fused launches (the dispatcher's coalesced path, running
    fused_flat_attr / sharded_fused_attr with the on-device merge) build
    the same bytes as the reference for every member."""
    from tests.conftest import make_dispatch_node
    node, stores = make_dispatch_node((11, 23, 47), fusion=True)
    oracles = [_reference(dev, safe, qs)
               for dev, safe, qs in stores]
    outs = []
    for dev, _safe, qs in stores:
        builders, failures = _enqueue_flush(dev, qs)
        outs.append((builders, failures))
    node.scheduler.run()
    assert node.dispatcher.n_fused_launches >= 1
    for (builders, failures), oracle in zip(outs, oracles):
        assert not failures
        assert _unpack_builders(builders) == oracle


# -- one flush path (PR 28) ---------------------------------------------------

@pytest.mark.parametrize("flag", ["prune_floors", "attributed"])
def test_begin_refuses_the_options_of_the_deleted_raw_path(flag):
    """The flush always prunes by the batch floor and always attributes:
    the two keywords of the deleted raw-CSR family are accepted only as
    True (the benchmark's store driver still passes them; ROADMAP D11)."""
    store, dev, safe, entries, floor, qs = _build(11, n=40)
    with pytest.raises(TypeError):
        dev.deps_query_batch_begin(qs, immediate=True, **{flag: False})
    builders = [DepsBuilder() for _ in qs]
    dev.deps_query_batch_end_attributed(
        safe, dev.deps_query_batch_begin(qs, immediate=True,
                                         prune_floors=True,
                                         attributed=True), builders)
    assert _unpack_builders(builders) == _reference(dev, safe, qs)


def _scan_entry_points():
    """{name: module} of the public compiled SCAN entry points of the two
    kernel modules: a jitted function, or a function that builds one
    (``jax.jit`` in its body), over a packed query matrix (``qmat`` /
    ``qmats``).  The un-jitted inner phases (flat_csr_local,
    bucketed_flat, ...) are traced into these and are not entry points."""
    import inspect

    from accord_tpu.ops import deps_kernel
    from accord_tpu.parallel import sharded
    out = {}
    for mod in (deps_kernel, sharded):
        for name, obj in vars(mod).items():
            if name.startswith("_") or not callable(obj) \
                    or getattr(obj, "__module__", mod.__name__) \
                    != mod.__name__:
                continue
            fn = getattr(obj, "__wrapped__", None)
            jitted = fn is not None and hasattr(obj, "lower")
            try:
                src = inspect.getsource(fn if jitted else obj)
            except (OSError, TypeError):
                continue
            if (jitted or "jax.jit(" in src) and "qmat" in src:
                out[name] = mod.__name__
    return out


def test_every_scan_entry_point_has_a_launcher():
    """ROADMAP D4 cannot regrow unseen: every public compiled scan entry
    point of ops/deps_kernel.py and parallel/sharded.py is referenced from
    accord_tpu/local/ (the flush, the fused launch, or — for
    calculate_deps_flat — the route calibration that times it)."""
    import inspect
    import os
    import re

    import accord_tpu.local as local_pkg
    from accord_tpu.local.device_index import DeviceState
    found = _scan_entry_points()
    # the rule sees the programs the flush is known to launch
    assert set(found) >= {"calculate_deps_flat", "calculate_deps_flat_attr",
                          "bucketed_attr_jit", "fused_flat_attr",
                          "sharded_flat_attr", "sharded_bucketed_attr",
                          "sharded_fused_attr"}, found
    local_dir = os.path.dirname(local_pkg.__file__)
    text = ""
    for fname in sorted(os.listdir(local_dir)):
        if fname.endswith(".py"):
            with open(os.path.join(local_dir, fname)) as f:
                text += f.read()
    orphans = [f"{mod}.{name}" for name, mod in sorted(found.items())
               if not re.search(rf"\b{name}\(", text)]
    assert not orphans, f"scan entry points nothing launches: {orphans}"
    assert "calculate_deps_flat(" in inspect.getsource(
        DeviceState._measure_route_calibration)
