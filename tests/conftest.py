"""Test config: force JAX onto a virtual 8-device CPU mesh so sharding tests
run anywhere (the driver separately dry-runs the multi-chip path)."""

import contextlib
import faulthandler
import os
import signal
import threading

import pytest

# Hard override: the ambient environment may point JAX at a real accelerator;
# unit tests always run on the virtual CPU mesh.  The env var alone is not
# enough — an installed accelerator plugin can still win platform selection —
# so also force it through jax.config before any backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "true"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-horizon tier-2 tests (excluded from the "
        "tier-1 gate via -m 'not slow')")
    config.addinivalue_line(
        "markers", "faults: device-fault injection matrix (quarantine / "
        "host fallback / HBM backpressure; tools/run_fault_matrix.sh "
        "sweeps these under fixed seeds)")
    # ACCORD_TPU_FUSION=off canary: running tier-1 with the escape hatch
    # set must (a) actually disable fusion — assert the knob is honored
    # here, where every test run passes through — and (b) stay green,
    # proving launch fusion never became load-bearing for correctness.
    if os.environ.get("ACCORD_TPU_FUSION", "").lower() in ("off", "0",
                                                           "false", "no"):
        from accord_tpu.local.dispatch import fusion_enabled
        assert not fusion_enabled(), \
            "ACCORD_TPU_FUSION=off set but dispatch.fusion_enabled() is True"
    # ACCORD_TPU_PROTO_FASTPATH=off canary (r18, same contract as the
    # fusion knob): with the escape hatch set every protocol fast-path
    # cache must actually stand down and tier-1 must stay green — no
    # hot-loop rewrite may become load-bearing for correctness.
    if os.environ.get("ACCORD_TPU_PROTO_FASTPATH", "").lower() in (
            "off", "0", "false", "no"):
        from accord_tpu.local.fastpath import proto_fastpath_enabled
        assert not proto_fastpath_enabled(), \
            "ACCORD_TPU_PROTO_FASTPATH=off set but proto_fastpath_enabled()"
    # ACCORD_TPU_STORE_GROUP=off canary (r20, same contract): with the
    # escape hatch set every CommandStore must drain per-op (opaque
    # closures, one SafeCommandStore per op) and every batch envelope
    # must route sub-bodies one at a time — store-grouped execution is a
    # perf layer, never load-bearing for correctness.
    if os.environ.get("ACCORD_TPU_STORE_GROUP", "").lower() in (
            "off", "0", "false", "no"):
        from accord_tpu.local.fastpath import store_group_enabled
        assert not store_group_enabled(), \
            "ACCORD_TPU_STORE_GROUP=off set but store_group_enabled()"
    # ACCORD_TPU_DRAIN=fixpoint canary (r19, same contract as the fusion
    # knob): with the escape hatch set every routed drain must run the
    # fixpoint oracle (no log-depth kernel, no widened tick wavefront) and
    # tier-1 must stay green — the log-depth drain is a perf layer, never
    # load-bearing for correctness.
    if os.environ.get("ACCORD_TPU_DRAIN", "").lower() in ("fixpoint", "fix",
                                                          "off", "0",
                                                          "false", "no"):
        from accord_tpu.ops.drain_kernel import drain_logdepth_enabled
        assert not drain_logdepth_enabled(), \
            "ACCORD_TPU_DRAIN=fixpoint set but drain_logdepth_enabled()"
    # ACCORD_TPU_STORE_SHARD=off canary (r21, same contract as the fusion
    # knob): with the escape hatch set the budget ladder must skip the
    # spill-to-sharded rung (breach goes compact -> host-pinned exactly as
    # pre-r21) and tier-1 must stay green — sliced residency is a scaling
    # layer, never load-bearing for correctness.
    if os.environ.get("ACCORD_TPU_STORE_SHARD", "").lower() in ("off", "0",
                                                                "false",
                                                                "no"):
        from accord_tpu.parallel.store_shard import store_shard_enabled
        assert not store_shard_enabled(), \
            "ACCORD_TPU_STORE_SHARD=off set but store_shard_enabled() is True"
    # ACCORD_TPU_OBS=off canary (r09, same contract as the fusion knob):
    # with the escape hatch set the obs subsystem must actually stand down
    # (no span recording, no device profiler) and tier-1 must stay green —
    # observability is never load-bearing for correctness.
    if os.environ.get("ACCORD_TPU_OBS", "").lower() in ("off", "0",
                                                        "false", "no"):
        from accord_tpu import obs
        assert not obs.enabled(), \
            "ACCORD_TPU_OBS=off set but obs.enabled() is True"


# -- one time limit for every test ----------------------------------------
# A hang costs one failed test, with every thread's stack in its captured
# stderr — not a dead xdist worker and the files queued behind it.  Raise
# it only for an honest test above 60 s (the slowest when it was set:
# test_drain_routes_vs_host_oracle, 44 s under the driver's six workers).

TEST_LIMIT_S = 120.0


@contextlib.contextmanager
def time_limit(seconds):
    """Fail the calling test once ``seconds`` have passed: SIGALRM dumps
    every thread's stack and raises pytest's Failed — a BaseException, so
    no ``except Exception`` of the program swallows it — in the main
    thread, out of ``asyncio.run`` too.  It fires again every second
    after, in case something did absorb it (a task other than the one
    ``run_until_complete`` waits for).  A main thread stuck outside the
    interpreter never runs the handler: for that case alone faulthandler's
    own thread dumps the stacks a little later."""
    if (not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def expired(_signum, _frame):
        faulthandler.dump_traceback()
        pytest.fail(f"test exceeded {seconds:g} s")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, seconds, 1.0)
    faulthandler.dump_traceback_later(seconds + 5.0, exit=False)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def _test_time_limit(request):
    if request.node.get_closest_marker("slow"):   # tier-2: long by design
        yield
        return
    with time_limit(TEST_LIMIT_S):
        yield


# -- shared DeviceState test fixture --------------------------------------
# The routing/mesh/perf tiers all drive a bare DeviceState against the
# minimal store surface its attribution touches; one definition here keeps
# the store contract in a single place (a new required store attribute is
# a one-line change, not a five-file hunt).


class DeviceTestStore:
    def __init__(self):
        from accord_tpu.local.redundant import RedundantBefore
        self.commands_for_key = {}
        self.redundant_before = RedundantBefore()

    class node:
        scheduler = None


class DeviceTestSafe:
    def __init__(self, store):
        self.store = store

    def redundant_before(self):
        return self.store.redundant_before


@pytest.fixture
def drain_ticks_on_device():
    """For a test that means the device: the live sets of a sim are a few
    slots, which the router sweeps on the host (DeviceState._host_tick_pays).
    Prices that sweep out, on top of the process's own calibration, so that
    every drain tick meets the device boundary: its launches, its fused
    launches and the faults armed there."""
    from accord_tpu.local.device_index import DeviceState
    saved = DeviceState._CALIB
    DeviceState._CALIB = {
        **(saved or DeviceState._measure_route_calibration()),
        "c_sweep": 1.0}
    try:
        yield
    finally:
        DeviceState._CALIB = saved


def make_device_state(mesh="auto"):
    """(store, DeviceState, safe) — ``mesh=None`` pins the single-device
    path under the test mesh; "auto" keeps DeviceState's own choice."""
    from accord_tpu.local.device_index import DeviceState
    store = DeviceTestStore()
    dev = DeviceState(store)
    if mesh is None:
        dev.mesh = None
    return store, dev, DeviceTestSafe(store)


# -- dispatcher (fused cross-store launch) test harness --------------------
# A minimal deterministic node: a FIFO scheduler, a DeviceDispatcher, and
# store shims that give each DeviceState the store surface the dispatcher
# and its harvest tasks touch (store_id ordering, execute -> scheduler).


class DispatchTestScheduler:
    def __init__(self):
        self.q = []

    def now(self, fn):
        self.q.append(fn)

    def once(self, _delay_micros, fn):
        self.q.append(fn)

    def run(self):
        while self.q:
            self.q.pop(0)()


class DispatchTestNode:
    node_id = 1
    alive = True

    def __init__(self, fusion=None):
        from accord_tpu.local.dispatch import DeviceDispatcher
        self.scheduler = DispatchTestScheduler()
        self.dispatcher = DeviceDispatcher(self)
        if fusion is not None:
            self.dispatcher.fusion = fusion


class DispatchTestStoreShim:
    """Presents a DeviceTestStore as the CommandStore surface the
    dispatcher needs (store_id, node, execute-with-safe)."""

    def __init__(self, inner, node, store_id):
        self.inner = inner
        self.node = node
        self.store_id = store_id
        self.commands_for_key = inner.commands_for_key
        self.redundant_before = inner.redundant_before

    def execute(self, _ctx, fn):
        shim = self

        class Safe:
            store = shim

            @staticmethod
            def redundant_before():
                return shim.redundant_before

        self.node.scheduler.now(lambda: fn(Safe()))


def make_dispatch_node(seeds, fusion=None, route="dense"):
    """(node, [(dev, safe, qs), ...]) — one DeviceState per seed, built
    with tests.test_routing._build and attached to a shared
    DispatchTestNode so enqueue_query / schedule_tick flow through the
    node's DeviceDispatcher."""
    from tests.test_routing import _build
    node = DispatchTestNode(fusion=fusion)
    out = []
    for i, seed in enumerate(seeds):
        store, dev, safe, entries, floor, qs = _build(seed)
        dev.store = DispatchTestStoreShim(store, node, i)
        dev.route_override = route
        out.append((dev, safe, qs))
    return node, out
