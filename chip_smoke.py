#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that accord-tpu still starts on the chip.

    python3 chip_smoke.py [--seed N]          one TPU chip (what the driver runs)
    python3 chip_smoke.py --chips 4           only the four-chip phase

ONE process owns the chip for the whole run; nothing here starts a child that
needs JAX or sets the platform.  It drives the main path through the entry
points a user calls, checks every result against the repo's plain reference,
and FAILS (non-zero exit, no ``"ok": true``) when JAX finds no TPU, a phase
raises, a comparison differs, or any fault-ladder counter moved — the ladder
serves a refused kernel from the host bit-identically, so a counter is the
only place a broken device path shows.

Default run, one JSON line per phase (sizes, route counts, wall seconds with
compile apart from run, peak device bytes), then the contract's last line:

- ``store``       the device data plane at BASELINE.json configs[2] size:
                  100,000 in-flight txns x 8 intervals over a 1M-key space,
                  registered through DeviceState.register() with
                  RedundantBefore floors + CommandsForKey state, then 10,240
                  queries in batches of 2048 through
                  deps_query_batch_begin/end_attributed on the "device" and
                  "dense" routes, byte-compared with the "host" route.
- ``store.drain`` a 100k-slot ELL DAG and the 4096-deep chain through
                  drain_ell_auto / drain_auto vs a host Kahn pass and the
                  fixpoint kernels.
- ``protocol``    a seeded in-process sim Cluster(device_mode=True), 3 nodes
                  rf 3, key + range txns through Node.coordinate, the
                  composite strict-serializability + Elle verifier, and a
                  read-back of every acknowledged write.
- ``serve``       three net.server.NodeServers in THIS process on one asyncio
                  loop, device on, loopback TCP between them, journal on,
                  binary codec, driven by net.client.ClusterClient.

``--chips 4`` runs only ``multichip``: the sharded protocol step on the real
4-device mesh vs the single-device kernels, an auto-mesh DeviceState at the
store phase's size vs its own mesh=None run, and the store-shard and
slice-fault legs of tools/multichip.py.

All data is generated from ``--seed``.  Writes only under
``chiprun_out/chip_smoke/`` beside this script and the compile cache
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/``).
"""

import argparse
import asyncio
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from typing import NamedTuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")


class Sizes(NamedTuple):
    """Every size of the run in one place.  REAL is what the chip runs; the
    sandbox rehearsal test passes a tiny instance to the same phases."""

    n_txns: int = 100_000       # in-flight txns registered in the store
    keyspace: int = 1_000_000
    max_iv: int = 8             # intervals per txn / per query
    batch: int = 2048
    n_queries: int = 10_240
    drain_slots: int = 100_000  # ELL DAG
    drain_chains: int = 512
    chain_depth: int = 4096     # the deep dense chain
    proto_txns: int = 300
    proto_keys: int = 40
    serve_txns: int = 300
    serve_keys: int = 32
    serve_limit_s: float = 600.0   # a stalled cluster fails, never hangs


REAL = Sizes()

LADDER = ("n_device_faults", "n_quarantines", "n_fallback_queries",
          "n_shadow_mismatches")
ROUTE_COUNTERS = ("n_bucketed_queries", "n_dense_queries", "n_mesh_queries",
                  "n_fused_queries", "n_host_queries", "n_dispatches")


# -- compile accounting -------------------------------------------------------

class _CompileClock:
    """Seconds jax spent tracing/lowering/compiling and persistent-cache
    hits, read from jax.monitoring — so each phase reports compile apart
    from run without guessing from a second pass."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def install(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return self.seconds, self.hits, self.misses


COMPILE = _CompileClock()


def _peak_bytes():
    import jax
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out[0] if len(out) == 1 else out


def run_phase(name, fn, *args):
    """Run one phase at the boundary that must keep going: a raise is
    recorded with its traceback and reported as the phase's failure."""
    c0 = COMPILE.snap()
    t0 = time.time()
    try:
        report = fn(*args)
    except Exception as e:   # noqa: BLE001 — phase boundary, reported below
        traceback.print_exc()
        report = {"ok": False, "problems": [f"raised {e!r}"]}
    wall = time.time() - t0
    c1 = COMPILE.snap()
    report = {"phase": name, **report,
              "wall_s": round(wall, 2),
              "compile_s": round(c1[0] - c0[0], 2),
              "run_s": round(wall - (c1[0] - c0[0]), 2),
              "compile_cache_hits": c1[1] - c0[1],
              "compile_cache_misses": c1[2] - c0[2],
              "peak_bytes_in_use": _peak_bytes()}
    print(json.dumps(report), flush=True)
    return report


# -- what every device-backed phase reports and is gated on ------------------

def device_counters(devs):
    """Summed route + ladder counters and merged kernel_times over a set of
    DeviceStates."""
    rep = {k: int(sum(getattr(d, k) for d in devs))
           for k in ROUTE_COUNTERS + LADDER}
    rep["host_pinned"] = any(d.host_pinned for d in devs)
    kt = {}
    for d in devs:
        for kind, (calls, secs) in d.kernel_times.items():
            cell = kt.setdefault(kind, [0, 0.0])
            cell[0] += calls
            cell[1] += secs
    rep["kernel_times"] = {k: [c, round(s, 3)] for k, (c, s) in
                           sorted(kt.items())}
    return rep


def device_gate(rep, expect_host_queries=None):
    """The no-hidden-fallback gate: ladder counters all zero, nothing served
    by the host beyond what the phase itself asked for (None = the phase
    runs the host route itself, as the reference), and the device routes
    visibly ran (query counts AND launch-boundary timings)."""
    problems = [f"{k}={rep[k]}" for k in LADDER if rep[k]]
    if rep["host_pinned"]:
        problems.append("host_pinned")
    on_device = (rep["n_bucketed_queries"] + rep["n_dense_queries"]
                 + rep["n_mesh_queries"] + rep["n_fused_queries"])
    if on_device <= 0:
        problems.append("no query ran on a device route")
    if expect_host_queries is not None \
            and rep["n_host_queries"] != expect_host_queries:
        problems.append(f"n_host_queries={rep['n_host_queries']} "
                        f"(expected {expect_host_queries})")
    kinds = set(rep["kernel_times"])
    if not any((k.startswith("dispatch_")
                and not k.startswith("dispatch_host"))
               or k == "wait_attr_fused" for k in kinds):
        problems.append("kernel_times has no device dispatch_* entry")
    if not any(k.startswith("wait_") for k in kinds):
        problems.append("kernel_times has no wait_* entry")
    return problems


# -- phase: store -------------------------------------------------------------

def _deps_digest(built):
    """Canonical bytes of a batch of built Deps (CSR columns + packed ids),
    hashed: equal digests == byte-equal answers."""
    h = hashlib.sha256()
    n_rel = 0
    for d in built:
        kd, rd = d.key_deps, d.range_deps
        doc = (kd.to_csr(), [(t.msb, t.lsb, t.node) for t in kd.txn_ids],
               rd.to_csr(), [(t.msb, t.lsb, t.node) for t in rd.txn_ids])
        h.update(repr(doc).encode())
        n_rel += kd.relation_count() + rd.relation_count()
    return h.hexdigest(), n_rel


def build_store(seed, sz):
    import bench
    rng = np.random.default_rng(seed)
    entries = bench.build_workload(rng, sz.n_txns, sz.keyspace, sz.max_iv)
    store, dev, safe = bench.build_headline_store(entries, sz.keyspace)
    n_batches = -(-sz.n_queries // sz.batch)
    batches = [[(q[0], q[0], q[1], q[2], q[3])
                for q in bench.make_queries(seed * 1000 + i, sz.batch,
                                            sz.keyspace, sz.max_iv)]
               for i in range(n_batches)]
    return dev, safe, batches


def scan_pass(dev, safe, batches, route):
    """All batches through begin/end_attributed on one pinned route,
    double-buffered as bench.py's headline path is.  Returns
    ([digest per batch], relations, seconds)."""
    from accord_tpu.primitives.deps import DepsBuilder
    dev.route_override = route
    digests, n_rel, pending = [], 0, []
    t0 = time.time()

    def collect(handle, batch):
        builders = [DepsBuilder() for _ in batch]
        dev.deps_query_batch_end_attributed(safe, handle, builders)
        return _deps_digest([b.build() for b in builders])

    for batch in batches:
        pending.append((dev.deps_query_batch_begin(batch), batch))
        if len(pending) >= 2:
            dg, n = collect(*pending.pop(0))
            digests.append(dg)
            n_rel += n
    while pending:
        dg, n = collect(*pending.pop(0))
        digests.append(dg)
        n_rel += n
    return digests, n_rel, time.time() - t0


def device_route_passes(dev, safe, batches, want, reference):
    """Both pinned device routes, two passes each (the first compiles and
    learns the s/k budgets, the second is steady), every batch compared
    with ``want``.  Returns ({route: report}, problems)."""
    routes, problems = {}, []
    for route in ("device", "dense"):
        before = {k: getattr(dev, k) for k in ROUTE_COUNTERS}
        first, _n, first_s = scan_pass(dev, safe, batches, route)
        again, _n, again_s = scan_pass(dev, safe, batches, route)
        for label, got in (("first", first), ("steady", again)):
            bad = [i for i, (a, b) in enumerate(zip(want, got)) if a != b]
            if bad or len(got) != len(want):
                problems.append(f"route {route} ({label} pass) differs from "
                                f"{reference} in batches {bad}")
        routes[route] = {
            "first_pass_s": round(first_s, 2),
            "steady_pass_s": round(again_s, 2),
            "learned_s": dev._batch_flat, "learned_k": dev._batch_k,
            **{k: getattr(dev, k) - before[k] for k in ROUTE_COUNTERS}}
    return routes, problems


def phase_store(seed, sz):
    t0 = time.time()
    dev, safe, batches = build_store(seed, sz)
    dev.mesh = None          # this phase is the ONE-device data plane
    build_s = time.time() - t0
    want, n_rel, host_s = scan_pass(dev, safe, batches, "host")
    routes, differs = device_route_passes(dev, safe, batches, want,
                                          "the host route")
    # what the adaptive router would do with this store on this machine,
    # and the calibration it priced that with (information for S4/S7)
    picked = []
    dev.on_route = lambda route, nq: picked.append(route)
    scan_pass(dev, safe, batches[:1], None)
    dev.on_route = None
    rep = device_counters([dev])
    problems = differs + device_gate(rep)
    return {
        "ok": not problems, "problems": problems,
        "sizes": {"n_txns": sz.n_txns, "capacity": dev.deps.capacity,
                  "intervals_per_txn": dev.deps.max_intervals,
                  "keyspace": sz.keyspace, "batch": sz.batch,
                  "n_queries": sz.batch * len(batches),
                  "bucket_keff": dev.deps.bucket_keff(),
                  "wide_entries": len(dev.deps.wide_entries)},
        "byte_equal_to_host_route": not differs,
        "relations": n_rel, "digest": hashlib.sha256(
            "".join(want).encode()).hexdigest()[:16],
        "build_s": round(build_s, 2), "host_pass_s": round(host_s, 2),
        "routes": routes, "adaptive_route_picked": picked,
        "route_calibration": {k: float(v) for k, v in
                              dev._calibration().items()},
        **rep}


# -- phase: store.drain -------------------------------------------------------

def host_kahn(dep_rows, stable):
    """The plain reference: a queue-based Kahn drain (the walk in
    bench.host_kahn_drain_rate) that also honours non-Stable slots — they
    never execute, so everything downstream of them stays blocked.  Valid
    where every edge points at an earlier executeAt (true of both graphs
    here), so every edge gates.  Returns applied bool[n]."""
    from collections import deque
    n = len(dep_rows)
    rdeps = [[] for _ in range(n)]
    indeg = [0] * n
    for i, deps in enumerate(dep_rows):
        indeg[i] = len(deps)
        for j in deps:
            rdeps[j].append(i)
    applied = np.zeros(n, bool)
    q = deque(i for i in range(n) if indeg[i] == 0 and stable[i])
    while q:
        j = q.popleft()
        applied[j] = True
        for i in rdeps[j]:
            indeg[i] -= 1
            if indeg[i] == 0 and stable[i]:
                q.append(i)
    return applied


def _packed_ids(n):
    from accord_tpu.ops.packing import pack_timestamps
    from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
    return pack_timestamps([TxnId.create(1, 10 + i, TxnKind.Write,
                                         Domain.Key, 1) for i in range(n)])


def _status_with_stuck(rng, n):
    """All Stable, except a few Committed-not-Stable slots in the last
    tenth: they never execute, so the expected applied set is not 'all'."""
    from accord_tpu.ops.deps_kernel import SLOT_COMMITTED, SLOT_STABLE
    status = np.full(n, SLOT_STABLE, np.int32)
    tail = np.arange(n - max(n // 10, 2), n)
    stuck = rng.choice(tail, size=max(len(tail) // 50, 1), replace=False)
    status[stuck] = SLOT_COMMITTED
    return status, status == SLOT_STABLE


def phase_drain(seed, sz):
    import jax.numpy as jnp
    from accord_tpu.ops import drain_kernel as drk
    rng = np.random.default_rng(seed + 1)
    problems = []
    before = dict(drk.drain_counters())

    def same(name, got, want):
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            problems.append(f"{name} differs")

    # (a) the wide ELL DAG: `chains` hot chains with local fan-in
    n, chains, d = sz.drain_slots, sz.drain_chains, 8
    i = np.arange(chains, n)
    lo = np.maximum(0, i - 3 * chains)
    room = i - 1 - lo
    picks = lo[:, None] + (rng.random((len(i), d - 1))
                           * room[:, None]).astype(np.int64)
    extra = rng.integers(1, d, len(i))
    adj_idx = np.full((n, d), -1, np.int32)
    adj_idx[i, 0] = i - chains
    adj_idx[i, 1:] = np.where((np.arange(d - 1)[None, :] < extra[:, None])
                              & (room > 0)[:, None], picks, -1)
    status, stable = _status_with_stuck(rng, n)
    em, el, en = _packed_ids(n)
    ell = drk.EllDrainState(jnp.asarray(adj_idx), jnp.asarray(status),
                            jnp.asarray(em), jnp.asarray(el),
                            jnp.asarray(en), jnp.zeros(n, bool))
    want = host_kahn([[int(j) for j in row if j >= 0] for row in adj_idx],
                     stable)
    ell_routes = []
    for _ in range(2):      # 2nd call is priced from the 1st's depth/rounds
        applied, newly, sweeps, route = drk.drain_ell_auto(ell)
        ell_routes.append([route, int(sweeps)])
        same(f"ELL DAG applied set ({route}) vs host Kahn", applied, want)
        same(f"ELL DAG drained set ({route}) vs host Kahn", newly, want)
    fa, fn_, fsweeps = drk.drain_ell_levels(ell)
    same("ELL DAG fixpoint kernel vs host Kahn", fa, want)
    same("ELL DAG fixpoint drained set", fn_, want)

    # (b) the deep chain, dense and ELL forms of the same edges
    nd = sz.chain_depth
    adj = np.zeros((nd, nd), bool)
    r = np.arange(1, nd)
    adj[r, r - 1] = True
    for back in range(2, 9):
        rows = r[r - back >= 0]
        adj[rows, rows - back] = rng.random(len(rows)) < 0.5
    status_d, stable_d = _status_with_stuck(rng, nd)
    edges = [np.nonzero(adj[k])[0].tolist() for k in range(nd)]
    want_d = host_kahn(edges, stable_d)
    em2, el2, en2 = em[:nd], el[:nd], en[:nd]
    dense = drk.DrainState(jnp.asarray(adj), jnp.asarray(status_d),
                           jnp.asarray(em2), jnp.asarray(el2),
                           jnp.asarray(en2), jnp.zeros(nd, bool))
    deep_routes = []
    for state in (dense, dense, drk.dense_to_ell(dense)):
        applied, newly, sweeps, route = drk.drain_auto(state)
        deep_routes.append([route, int(sweeps)])
        same(f"deep chain applied set ({route}) vs host Kahn",
             applied, want_d)
        same(f"deep chain drained set ({route}) vs host Kahn",
             newly, want_d)
    fa, fn_, dsweeps = drk.drain_levels(dense)
    same("deep chain dense fixpoint vs host Kahn", fa, want_d)
    sa, sn, _sq = drk.drain_dense_logsq(dense)
    same("deep chain dense log-squaring vs host Kahn", sa, want_d)
    same("deep chain dense log-squaring drained set", sn, want_d)

    counters = {k: v - before.get(k, 0)
                for k, v in drk.drain_counters().items()}
    if counters["drain_logdepth_failovers"]:
        problems.append("drain_logdepth_failovers="
                        f"{counters['drain_logdepth_failovers']}")
    if not counters["drain_logdepth"]:
        problems.append("the log-depth drain never ran")
    return {
        "ok": not problems, "problems": problems,
        "sizes": {"ell_slots": n, "ell_degree": d, "chains": chains,
                  "chain_depth": nd},
        "ell_drained": int(want.sum()), "ell_stuck": int((~want).sum()),
        "ell_routes": ell_routes, "ell_fixpoint_sweeps": int(fsweeps),
        "deep_drained": int(want_d.sum()), "deep_routes": deep_routes,
        "deep_fixpoint_sweeps": int(dsweeps),
        "drain_calibration": {k: float(v) for k, v in
                              drk.drain_calibration().items()},
        **counters}


# -- phase: protocol ----------------------------------------------------------

def _verifier():
    from accord_tpu.sim.elle import CompositeVerifier, ListAppendCycleChecker
    from accord_tpu.sim.verifier import StrictSerializabilityVerifier
    return CompositeVerifier(StrictSerializabilityVerifier(),
                             ListAppendCycleChecker())


def _missing_acks(acked, finals):
    """Acknowledged appends that a final read of their key does not hold."""
    return [(k, v) for k, vals in sorted(acked.items()) for v in vals
            if v not in finals.get(k, ())]


def _pin_device_path(devs):
    """Pin every store to the device routes of ONE device: the adaptive
    router serves scans this small from the host tail, and these phases
    exist to prove the DEVICE path.  (mesh=None is a no-op on the one-chip
    machine; it keeps the sandbox rehearsal, which has 8 virtual devices, on
    the programs the chip runs.)"""
    devs = list(devs)
    for dev in devs:
        dev.route_override = "device"
        dev.mesh = None
    return devs


def phase_protocol(seed, sz):
    from accord_tpu.primitives.keys import Range, Ranges
    from accord_tpu.sim.cluster import Cluster
    from accord_tpu.sim.kvstore import KVDataStore, kv_range_read, kv_txn
    from accord_tpu.sim.topology_factory import build_topology
    from accord_tpu.utils.random_source import RandomSource

    cluster = Cluster(topology=build_topology(1, (1, 2, 3), 3, 4),
                      seed=seed, data_store_factory=KVDataStore,
                      device_mode=True)
    devs = _pin_device_path(s.device for node in cluster.nodes.values()
                            for s in node.command_stores.stores)
    verifier = _verifier()
    wl = RandomSource(seed)
    n_keys = sz.proto_keys
    acked, failed, done = {}, [], [0]
    kinds = {"key": 0, "range": 0}

    def submit(i):
        window = None
        if wl.decide(0.12):
            lo = wl.next_int(n_keys)
            hi = min(n_keys, lo + 1 + wl.next_int(4))
            window = [k * 10 for k in range(lo, hi)]
            writes = {}
            txn = kv_range_read(Ranges.of(Range(lo * 10, hi * 10)))
            kinds["range"] += 1
        else:
            keys = sorted({wl.next_int(n_keys) * 10
                           for _ in range(wl.next_int(3) + 1)})
            writes = {k: (f"s{i}k{k}",) for k in keys if wl.decide(0.6)}
            txn = kv_txn(keys, writes)
            kinds["key"] += 1
        op_id, start = verifier.begin(), cluster.queue.now

        def on_done(res, failure):
            done[0] += 1
            if failure is not None:
                failed.append(repr(failure))
                return
            reads = res.reads
            if window is not None:
                reads = {t: res.reads.get(t, ()) for t in window}
            verifier.on_result(op_id, start, cluster.queue.now, reads,
                               res.appends)
            for k, vals in writes.items():
                acked.setdefault(k, []).extend(vals)

        cluster.nodes[1 + wl.next_int(3)].coordinate(txn).begin(on_done)

    window_micros = 40_000 * sz.proto_txns      # ~25 txn/s of sim time
    for i in range(sz.proto_txns):
        cluster.queue.add(wl.next_int(window_micros), lambda i=i: submit(i))
    cluster.run_for(window_micros)
    cluster.run_until_quiescent()

    problems = []
    if done[0] != sz.proto_txns or failed:
        problems.append(f"{done[0]}/{sz.proto_txns} txns resolved, "
                        f"{len(failed)} failed: {failed[:3]}")
    finals = {}
    for k in range(n_keys):
        out = []
        cluster.nodes[1].coordinate(kv_txn([k * 10], {})).begin(
            lambda r, f: out.append((r, f)))
        cluster.run_until_quiescent()
        if not out or out[0][1] is not None:
            problems.append(f"final read of key {k * 10} failed: {out}")
            continue
        finals[k * 10] = out[0][0].reads[k * 10]
        verifier.set_final(k * 10, finals[k * 10])
    missing = _missing_acks(acked, finals)
    if missing:
        problems.append(f"acknowledged writes not read back: {missing[:5]}")
    if cluster.failures:
        problems.append(f"node-level failures: {cluster.failures[:3]}")
    try:
        verifier.verify()
        verified = True
    except AssertionError as e:
        verified = False
        problems.append(f"verifier: {e}")
    rep = device_counters(devs)
    problems += device_gate(rep, expect_host_queries=0)
    return {
        "ok": not problems, "problems": problems,
        "sizes": {"nodes": 3, "rf": 3, "shards": 4, "stores": len(devs),
                  "txns": sz.proto_txns, "key_txns": kinds["key"],
                  "range_txns": kinds["range"], "keys": n_keys},
        "resolved": done[0], "failed": len(failed),
        "verifier_passed": verified,
        "acked_writes": sum(len(v) for v in acked.values()),
        "acked_writes_read_back": not missing,
        "n_ticks": int(sum(d.n_ticks for d in devs)),
        "n_host_ticks": int(sum(d.n_host_ticks for d in devs)),
        **rep}


# -- phase: serve -------------------------------------------------------------

async def _serve(seed, sz, out_dir):
    import random
    from accord_tpu.maelstrom.node import token_of
    from accord_tpu.net.admission import Overloaded
    from accord_tpu.net.client import ClusterClient, TxnFailed
    from accord_tpu.net.harness import TOKEN_SPACE, free_ports
    from accord_tpu.net.server import NodeServer

    names = ["n1", "n2", "n3"]
    addrs = {n: ("127.0.0.1", p) for n, p in zip(names, free_ports(3))}
    servers = [NodeServer(n, *addrs[n], dict(addrs), device_mode=True,
                          journal_dir=os.path.join(out_dir, "journal", n),
                          # background durability rounds off, as the serving
                          # harness spawns its nodes (net.harness.ServeCluster)
                          durability=False,
                          # the first flush of each shape compiles for
                          # seconds on this one shared loop: the default
                          # inter-node timeout and latency target would read
                          # that as a dead peer / an overload
                          request_timeout_ms=300_000,
                          target_p99_ms=300_000,
                          wire_codec_name="binary")
               for n in names]
    client = ClusterClient([(n, *addrs[n]) for n in names], timeout=300.0,
                           codec="binary")
    problems = []
    try:
        for s in servers:
            await s.start()
            if s.wire_codec != "binary" or s.journal is None:
                problems.append(f"{s.name}: codec={s.wire_codec} "
                                f"journal={s.journal is not None}")
        devs = _pin_device_path(st.device for s in servers for st in
                                s.proc.node.command_stores.stores)
        # init's warm-up scan ran before the pin, on the adaptive route
        warm_host_q = sum(d.n_host_queries for d in devs)
        await client.connect()
        for n in names:
            await client.ping(n, timeout=60.0)

        verifier = _verifier()
        rng = random.Random(seed)
        stride = TOKEN_SPACE // sz.serve_keys
        keys = [k * stride for k in range(sz.serve_keys)]
        acked, failed, attempt_failures = {}, [], []
        counter = [0]

        def now():
            return time.monotonic_ns() // 1_000

        async def one(node=None):
            shape = [(key, rng.random() < 0.6)
                     for key in rng.sample(keys, rng.randint(1, 2))]
            op_id, start = verifier.begin(), now()
            attempts = 0
            while True:
                # fresh append values per attempt, as the burn's client
                # retries: a failed attempt is indeterminate (its write may
                # still land unacknowledged, which the verifier allows)
                writes, ops = {}, []
                for key, is_append in shape:
                    if is_append:
                        counter[0] += 1
                        ops.append(["append", key, counter[0]])
                        writes[token_of(key)] = (counter[0],)
                    else:
                        ops.append(["r", key, None])
                try:
                    body = await client.submit(ops, node=node)
                    break
                except Overloaded as shed:    # not acknowledged: retry
                    await asyncio.sleep(shed.retry_after_ms / 1000.0)
                except (TxnFailed, asyncio.TimeoutError) as e:
                    # Preempted / Exhausted / Timeout: what a coordinator
                    # that stalled (here: in a compile on the shared loop)
                    # tells its client; the client retries elsewhere
                    attempts += 1
                    attempt_failures.append(repr(e))
                    if attempts >= 8:
                        failed.append(repr(e))
                        return
                    node = None
                    # outlast the stall: one cold compile is several seconds
                    await asyncio.sleep(1.0 * attempts)
            reads = {}
            for op in body["txn"]:
                if op[0] == "r":
                    t = token_of(op[1])
                    vals = tuple(op[2])
                    own = writes.get(t, ())
                    if own and vals[-len(own):] == own:
                        vals = vals[:len(vals) - len(own)]
                    reads[t] = vals
            verifier.on_result(op_id, start, now(), reads, writes)
            for t, vals in writes.items():
                acked.setdefault(t, []).extend(vals)

        for n in names:            # compile on each node before the burst
            await one(node=n)
        sem = asyncio.Semaphore(4)

        async def bounded():
            async with sem:
                await one()

        await asyncio.gather(*(bounded()
                               for _ in range(sz.serve_txns - len(names))))
        if failed:
            problems.append(f"{len(failed)} txns failed: {failed[:3]}")
        finals = {}
        for key in keys:
            for attempt in range(1, 9):    # a read is safe to repeat
                try:
                    body = await client.submit([["r", key, None]])
                    break
                except (TxnFailed, Overloaded, asyncio.TimeoutError):
                    if attempt == 8:
                        raise
                    await asyncio.sleep(1.0 * attempt)
            finals[token_of(key)] = tuple(body["txn"][0][2])
            verifier.set_final(token_of(key), finals[token_of(key)])
        missing = _missing_acks(acked, finals)
        if missing:
            problems.append(f"acknowledged appends not read back: "
                            f"{missing[:5]}")
        try:
            verifier.verify()
            verified = True
        except AssertionError as e:
            verified = False
            problems.append(f"verifier: {e}")
        rep = device_counters(devs)
        problems += device_gate(rep, expect_host_queries=warm_host_q)
        journal_bytes = sum(
            os.path.getsize(os.path.join(root, f))
            for root, _d, files in os.walk(os.path.join(out_dir, "journal"))
            for f in files)
        if journal_bytes <= 0:
            problems.append("the journal wrote nothing")
        return {
            "ok": not problems, "problems": problems,
            "sizes": {"nodes": 3, "stores": len(devs),
                      "txns": sz.serve_txns, "keys": sz.serve_keys},
            "codec": servers[0].wire_codec, "journal_bytes": journal_bytes,
            "ok_txns": client.n_ok, "overloaded": client.n_overloaded,
            "failed": len(failed), "attempt_failures": len(attempt_failures),
            "attempt_failure_kinds": sorted(set(attempt_failures)),
            "verifier_passed": verified,
            "acked_appends": sum(len(v) for v in acked.values()),
            "acked_appends_read_back": not missing,
            "duplicate_replies": client.duplicate_replies(),
            "n_ticks": int(sum(d.n_ticks for d in devs)),
            "n_host_ticks": int(sum(d.n_host_ticks for d in devs)),
            **rep}
    finally:
        await client.close()
        for s in servers:
            await s.close()


def phase_serve(seed, sz, out_dir=None):
    import gc
    out_dir = out_dir or os.path.join(OUT_DIR, "serve")
    shutil.rmtree(out_dir, ignore_errors=True)   # a kept journal would replay
    os.makedirs(out_dir, exist_ok=True)
    thresholds = gc.get_threshold()
    try:
        return asyncio.run(asyncio.wait_for(_serve(seed, sz, out_dir),
                                            sz.serve_limit_s))
    finally:
        # NodeServer.start() retunes the collector for a serving process
        gc.unfreeze()
        gc.set_threshold(*thresholds)


# -- phase: multichip (--chips 4 only) ---------------------------------------

def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _placement(cols):
    """{column: sorted device ids its addressable shards sit on}."""
    return {name: sorted({sh.device.id for sh in arr.addressable_shards})
            for name, arr in cols._asdict().items()}


def phase_multichip(seed, sz, n_chips=4):
    import jax
    import __graft_entry__ as graft
    multichip = _load(os.path.join(HERE, "tools", "multichip.py"),
                      "accord_tools_multichip")
    problems = []

    # (a) sharded_protocol_step on a mesh of the real devices vs the
    # single-device kernels, bit for bit (+ the live mesh cluster slice)
    t0 = time.time()
    graft.run_multichip(n_chips)
    step_s = time.time() - t0

    # (b) an auto-mesh DeviceState at the store phase's size vs its own
    # mesh=None run
    dev, safe, batches = build_store(seed, sz)
    mesh = dev.mesh
    if mesh is None or len(mesh.devices.flat) != n_chips:
        raise RuntimeError(f"DeviceState auto-mesh is {mesh}, "
                           f"expected {n_chips} devices")
    dev.mesh = None
    want, n_rel, _s = scan_pass(dev, safe, batches, "device")
    dev.mesh = mesh
    routes, differs = device_route_passes(dev, safe, batches, want,
                                          "the mesh=None run")
    problems += differs
    problems += [f"mesh route {route} never ran a mesh scan"
                 for route, r in routes.items() if r["n_mesh_queries"] <= 0]
    rep = device_counters([dev])
    problems += device_gate(rep)
    placement = {
        **_placement(dev.deps.device_table_sharded(mesh)),
        **{"attr." + k: v for k, v in _placement(
            dev.deps.device_attr_cols_sharded(mesh)).items()}}
    for col, ids in placement.items():
        if len(ids) != n_chips:
            problems.append(f"column {col} sits on devices {ids}, "
                            f"not on {n_chips} distinct ones")

    # (c) one store past the single-device budget (the spill rung) and one
    # injected fault confined to one slice
    legs = {"store_shard": multichip.leg_store_shard(n_chips),
            "slice_fault": multichip.leg_slice_fault(n_chips)}
    memory = {str(d.id): {k: v for k, v in (d.memory_stats() or {}).items()
                          if k in ("bytes_in_use", "peak_bytes_in_use",
                                   "bytes_limit")}
              for d in jax.devices()}
    return {
        "ok": not problems, "problems": problems,
        "sizes": {"chips": n_chips, "n_txns": sz.n_txns,
                  "capacity": dev.deps.capacity, "batch": sz.batch,
                  "n_queries": sz.batch * len(batches)},
        "sharded_step_bit_equal": True, "sharded_step_s": round(step_s, 2),
        "mesh_equal_to_single_device": not differs, "relations": n_rel,
        "routes": routes, "placement": placement, "legs": legs,
        "memory_stats": memory, **rep}


# -- driver -------------------------------------------------------------------

def _cache_entries(path):
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def run(seed, chips, sizes, out_dir=None):
    """Run the phases for ``chips`` and return their reports (no platform
    check: main() owns that).  The rehearsal test calls this directly."""
    if chips == 4:
        return [run_phase("multichip", phase_multichip, seed, sizes)]
    return [run_phase("store", phase_store, seed, sizes),
            run_phase("store.drain", phase_drain, seed, sizes),
            run_phase("protocol", phase_protocol, seed, sizes),
            run_phase("serve", phase_serve, seed, sizes, out_dir)]


def main(argv=None, sizes=REAL, rehearsal=False):
    """``sizes``/``rehearsal`` are for the sandbox rehearsal test only (no
    command-line spelling): a rehearsal runs on whatever devices jax has and
    says so in its last line, so it cannot be mistaken for a chip pass."""
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)
    try:
        from accord_tpu.ops.packing import startup
    except ImportError as e:
        print(f"chip_smoke: the accord_tpu package is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache_dir = startup()
    import jax
    devices = jax.devices()
    if not rehearsal and (devices[0].platform != "tpu"
                          or len(devices) != args.chips):
        print(f"chip_smoke: needs {args.chips} TPU chip(s), jax found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    COMPILE.install()
    entries_before = _cache_entries(cache_dir)
    print(json.dumps({"phase": "start", "seed": args.seed,
                      "chips": args.chips, "compile_cache_dir": cache_dir,
                      "compile_cache_entries": entries_before}), flush=True)
    t0 = time.time()
    reports = run(args.seed, args.chips, sizes)
    ok = all(r["ok"] for r in reports)
    summary = {"phase": "end", "wall_s": round(time.time() - t0, 2),
               "failed_phases": [r["phase"] for r in reports if not r["ok"]],
               "compile_cache_dir": cache_dir,
               "compile_cache_entries_before": entries_before,
               "compile_cache_entries": _cache_entries(cache_dir)}
    print(json.dumps(summary), flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"report_chips{args.chips}.json"),
              "w") as f:
        json.dump(reports + [summary], f, indent=1)
    last = {"ok": ok, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}
    if rehearsal:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
