"""Driver ``store``: ONE replica CommandStore's device data plane under a
closed loop of PreAccept flushes, one flush in flight.

Each flush asks the store for the deps of ``batch`` arriving txns
(deps_query_batch_begin -> deps_query_batch_end_attributed ->
DepsBuilder.build()), then registers them as PreAccepted with their
CommandsForKey updates and truncates the ``batch`` oldest txns, so the store
stays at its configured number in flight and every flush meets a changed
table.  Everything is generated from the seed in set-up."""

import collections
import gc
import random
import time

import numpy as np

from ..lib import checks, gen, replica_store
from ..lib.compile_clock import COMPILE, delta
from ..lib.stats import percentile
from ..lib.tracer import NoTracer

PROBES = 256            # queries of the correctness check
WARM_QUIET = 3          # consecutive flushes that compile nothing
WARM_MAX = 20


class Driver:
    annotations = ("flush.begin", "flush.collect", "deps.build",
                   "store.register")

    def __init__(self, config, traffic, seed, scratch_dir):
        self.sizes = dict(config["sizes"])
        self.batch = int(traffic["batch"])
        self.stream_txns = int(traffic["stream_txns"])
        self.trace_slice_s = float(traffic.get("trace_slice_s", 4.0))
        self.seed = seed
        self.problems = []
        self.info = {}
        self._gc = gc.get_threshold()

    # -- set-up ---------------------------------------------------------
    def setup(self):
        """The POPULATION (the stored txns, the arrivals, which arrivals
        share a flush, the probes) is drawn from the configuration's
        ``population_seed`` and is the same in every run; ``--seed`` draws
        the ORDER: in which the store witnesses its initial txns (so, their
        slots) and in which each flush's arrivals stand.  Every seed thus
        meets the same sizes and arrivals in another order, the store holds
        the same txns at every flush, and the budgets it learns (s, k, the
        bucket width) — each a separate compiled program — do not depend on
        the seed."""
        sz = self.sizes
        pop = np.random.default_rng(int(sz["population_seed"]))
        order = random.Random(self.seed)
        n, span = int(sz["n_txns"]), int(sz["hlc_span"])
        t0 = time.perf_counter()
        hlcs = np.sort(pop.permutation(span - 1)[:n] + 1)
        txns = gen.make_txns(pop, hlcs, sz)
        arrivals = gen.make_txns(
            pop, span + 1 + np.arange(self.stream_txns), sz)
        hi = span + 1 + self.stream_txns
        self.probes = gen.make_probe_queries(pop, PROBES, sz, hi,
                                             hi + 1_000_000)
        # (arrivals of one flush in this seed's order, the same in id order)
        self.stream = collections.deque()
        for i in range(0, len(arrivals) - self.batch + 1, self.batch):
            by_id = arrivals[i:i + self.batch]
            self.stream.append((order.sample(by_id, len(by_id)), by_id))
        t1 = time.perf_counter()
        self.store, self.dev, self.safe = replica_store.new_store(
            sz["floors"])
        for txn in order.sample(txns, n):
            replica_store.register(self.store, self.dev, txn)
        self.live = collections.deque(txns)      # truncated oldest id first
        self.info["setup"] = {
            "generate_s": t1 - t0, "register_s": time.perf_counter() - t1,
            "in_flight": self.dev.index_size(),
            "capacity": self.dev.deps.capacity,
            "intervals_per_slot": self.dev.deps.max_intervals,
            "bucket_keff": self.dev.deps.bucket_keff(),
            "stream_txns": self.stream_txns}
        if self.dev.deps.capacity != int(sz["capacity"]):
            self.problems.append(
                f"capacity {self.dev.deps.capacity} is not the "
                f"configuration's {sz['capacity']}")

    def _take(self):
        return self.stream.popleft() if self.stream else None

    def _answer(self, queries, tracer):
        from accord_tpu.primitives.deps import DepsBuilder
        dev = self.dev
        with tracer.span("flush.begin"):
            handle = dev.deps_query_batch_begin(
                queries, prune_floors=True, attributed=True)
        builders = [DepsBuilder() for _ in queries]
        with tracer.span("flush.collect"):
            dev.deps_query_batch_end_attributed(self.safe, handle, builders)
        with tracer.span("deps.build"):
            return [b.build() for b in builders]

    def _flush(self, arrivals, tracer):
        """One PreAccept flush; returns the seconds from begin to built
        deps (the registration that follows is in the window's clock, not
        in this one)."""
        batch, by_id = arrivals
        t0 = time.perf_counter()
        self._answer([t.query() for t in batch], tracer)
        t1 = time.perf_counter()
        with tracer.span("store.register"):
            store, dev, live = self.store, self.dev, self.live
            for txn in batch:
                replica_store.register(store, dev, txn)
            for _ in batch:
                replica_store.truncate(store, dev, live.popleft())
            live.extend(by_id)
        return t1 - t0

    def warm(self):
        """Untimed flushes at the cell's own batch until WARM_QUIET in a row
        compile nothing, then the first correctness probe."""
        quiet = flushes = 0
        tracer = NoTracer()
        while quiet < WARM_QUIET and flushes < WARM_MAX:
            batch = self._take()
            if batch is None:
                break
            before = COMPILE.events
            self._flush(batch, tracer)
            flushes += 1
            quiet = quiet + 1 if COMPILE.events == before else 0
        self.info["warm"] = {"flushes": flushes, "quiet": quiet,
                             **self._learned()}
        self._probe("before the window")
        # a CommandStore lives in a serving process, whose start-up
        # (NodeServer.start) freezes what set-up built out of the cyclic
        # collector and raises its thresholds; the same here, so that the
        # harness's own pre-built stream is not walked inside the window
        gc.collect()
        gc.freeze()
        gc.set_threshold(50_000, 25, 25)

    def _learned(self):
        # read, never written: the budgets the flushes taught the store
        return {"learned_s": int(self.dev._batch_flat),
                "learned_k": int(self.dev._batch_k),
                "bucket_keff": int(self.dev.deps.bucket_keff())}

    # -- the check ------------------------------------------------------
    def _probe(self, when):
        """The seeded probe batch on the route the store picks itself and
        on the host route, on the same store state: byte-equal or not
        correct."""
        step = min(PROBES, self.batch)
        chunks = [self.probes[i:i + step]
                  for i in range(0, len(self.probes), step)]
        off = NoTracer()
        got = {}
        for route in (None, "host"):
            self.dev.route_override = route
            try:
                got[route] = [checks.deps_digest(self._answer(c, off))
                              for c in chunks]
            finally:
                self.dev.route_override = None
        if got[None] != got["host"]:
            bad = [i for i, (a, b) in enumerate(zip(got[None], got["host"]))
                   if a != b]
            self.problems.append(f"probe {when}: chunks {bad} differ from "
                                 f"the host route")

    def check(self):
        self._probe("after the window")
        rep = checks.device_counters([self.dev])
        self.problems += checks.ladder_problems(rep)
        self.info["totals"] = {k: v for k, v in rep.items()
                               if k != "kernel_times"}
        self.info["calibration"] = {k: float(v) for k, v in
                                    self.dev._calibration().items()}
        return not self.problems

    # -- the window -----------------------------------------------------
    def window(self, seconds, tracer):
        c0 = checks.device_counters([self.dev])
        k0 = COMPILE.snap()
        lat, slice_from, raised = [], None, 0
        t_begin = time.perf_counter()
        while True:
            left = seconds - (time.perf_counter() - t_begin)
            if left <= 0:
                break
            batch = self._take()
            if batch is None:
                self.info["stream_ran_out_after_s"] = \
                    time.perf_counter() - t_begin
                break
            if tracer.due(left):
                tracer.start()
                slice_from = len(lat)
            try:
                lat.append(self._flush(batch, tracer))
            except Exception as e:   # noqa: BLE001 — counted, then reported
                raised = self.batch
                self.problems.append(f"a flush raised {e!r}")
                break
        t_end = time.perf_counter()
        tracer.stop()
        c1 = checks.device_counters([self.dev])
        window_s = t_end - t_begin
        done = len(lat) * self.batch
        return {
            "driver": "store", "window_s": window_s, "batch": self.batch,
            "flushes": len(lat), "flush_s": lat,
            "attempted": done + raised, "failed": raised,
            "slice_flushes": (len(lat) - slice_from
                              if slice_from is not None else 0),
            "counters": checks.counters_delta(c1, c0),
            "compile": delta(COMPILE.snap(), k0),
            "live_slots": self.dev.index_size(),
            "intervals_per_slot": int(self.dev.deps.max_intervals),
            "query_intervals": int(self.sizes["max_iv"]),
            "end_to_end": {
                "preaccept_rate": done / window_s if lat else None,
                "flush_p95": (percentile(lat, 0.95) * 1e3 if lat else None),
            }, **self._learned()}

    def close(self):
        gc.unfreeze()
        gc.set_threshold(*self._gc)
