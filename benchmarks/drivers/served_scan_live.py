"""Driver ``served_scan_live``: drivers/served_scan.py's cluster, records and
traffic, on replica stores that each hold a deployment's live window of
untruncated txns before the first client.

The history.  ``history_per_store`` x ``stores`` earlier txns of the same
traffic, drawn from ``population_seed`` by the same generator (so the same
in every run): with probability ``scan_share`` a scan (served_scan's draw:
a Zipf-drawn start record, 1 .. ``max_scan_length`` keys), else an insert,
which takes the next of the LAST loaded records in record order (YCSB
inserts in record order, so the newest records are the history's inserts
and their writes are in the loaded records).  Each has a TxnId of epoch 1
above the load's and below every txn a client can start (the nodes' clocks
run in microseconds since the epoch), coordinated by a node drawn uniformly,
executed at its own id, applied.  Every node installs all of it through
``Node.install_history``, the node-level entry beside the data store's
``install_snapshot``: each store keeps its ranges' share (about
``history_per_store`` txns, as ``setup`` prints), command records and
conflict indexes and its device mirror loaded, before the window's traffic.

The check adds, to served_scan's gates, the route probe at the window's
end: every store answers ``probe_queries`` window-shaped queries (scans and
inserts in the traffic's mix, above every txn) through the host route, the
solo device flush and, with the other stores of its node, the fused
cross-store launch, in flushes of the window's sizes (PROBE_SIZES).  Each
device route's built Deps must be byte-equal to the host route's, each
answer must name exactly the txns lib/store_deps.py's brute force finds
over the driver's own history rows and the store's command records (never
its conflict indexes or its device mirror), and a device route the window
used must have answered some of the probe's queries itself: a flush that no
loaded program could run is answered by the host (``n_budget_host_queries``)
and compares nothing of that route."""

import random
import time

from ..lib import checks, store_deps
from . import served_scan

# the route probe's flush sizes, in turn: a serving flush holds the queries
# one scheduler step delivered to a store (1-2 in the window, more under
# load), and each size is a program the nodes loaded before traffic
PROBE_SIZES = (1, 2, 4, 8, 16)
# the route counters a solo device flush's queries count to (a fused
# member's count to n_dense_queries or n_mesh_queries too)
DEVICE_KINDS = ("n_dense_queries", "n_bucketed_queries", "n_mesh_queries")
# what the window's snapshot adds from each node's coordination stats
_COORDINATION = ("deps_entries", "deps_txns")


class _Slice:
    """The tracer, with the device counters read where its slice starts
    and stops (what ``scan_hbm_share.serve`` divides the slice by)."""

    def __init__(self, tracer, read):
        self._tracer, self._read = tracer, read
        self.at_start = self.at_stop = None

    def __getattr__(self, name):
        return getattr(self._tracer, name)

    def start(self):
        self._tracer.start()
        self.at_start = self._read()

    def stop(self):
        if self._tracer.active:
            self.at_stop = self._read()
        self._tracer.stop()


class Driver(served_scan.Driver):

    def __init__(self, config, traffic, seed, scratch_dir):
        super().__init__(config, traffic, seed, scratch_dir)
        self.history_per_store = int(self.sz["history_per_store"])
        self.probe_queries = int(self.sz["probe_queries"])
        # the read-back's scans, each a txn whose deps query meets the
        # history: the configuration's width, the traffic's rows
        self.traffic["check_keys_per_scan"] = int(
            self.sz["check_keys_per_scan"])
        self.probes = []
        self._data = {}

    def _datum(self, n):
        """served_scan's record ``n``, made once: the load, every replica's
        copy and the read-back's comparison share it."""
        datum = self._data.get(n)
        if datum is None:
            datum = self._data[n] = super()._datum(n)
        return datum

    def setup(self):
        from accord_tpu.local.node import Node
        if not hasattr(Node, "install_history"):
            # a program without the entry cannot hold the configuration:
            # fail before a node starts
            raise RuntimeError("the program has no Node.install_history")
        super().setup()
        self.info["setup"].update(self.history_info)

    # -- the history ----------------------------------------------------
    def _draw_history(self):
        """The (txn_id, execute_at, keys) rows, ascending by id."""
        from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
        from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
        rng = random.Random(f"{self.population_seed}/history")
        n = self.history_per_store * int(self.sz["stores"])
        scan_share = float(self.traffic["scan_share"])
        scans = [rng.random() < scan_share for _ in range(n)]
        inserts = iter(range(self.n_loaded - scans.count(False),
                             self.n_loaded))
        nodes = [s.proc.node.node_id for s in self.servers]
        rows = []
        for i, scan in enumerate(scans):
            coordinator = nodes[rng.randrange(len(nodes))]
            if scan:
                lo, hi = self._draw_scan(rng)
                txn_id = TxnId.create(1, 2 + i, TxnKind.Read, Domain.Range,
                                      coordinator)
                keys = Ranges.of(Range(lo, hi))
            else:
                txn_id = TxnId.create(1, 2 + i, TxnKind.Write, Domain.Key,
                                      coordinator)
                keys = Keys([IntKey(self._token(next(inserts)))])
            rows.append((txn_id, txn_id, keys))
        return rows

    async def _start(self):
        await super()._start()
        t0 = time.perf_counter()
        rows = self.history = self._draw_history()
        t1 = time.perf_counter()
        planted = self.traffic.get("test_history_without_mirror")
        for s in self.servers:
            # the rehearsal's planted fault: one replica store's mirror is
            # loaded without the history (its records hold it all the same)
            store = s.proc.node.command_stores.stores[0]
            dev = store.device
            if planted and s is self.servers[1]:
                store.device = None
            s.install_history(rows)
            store.device = dev
        t2 = time.perf_counter()
        per_store = [d.n_live_slots for d in self.devs]
        self.history_info = {
            "history_txns": len(rows), "history_draw_s": t1 - t0,
            "history_install_s": t2 - t1,
            "history_per_store_min": min(per_store),
            "history_per_store_max": max(per_store),
            "capacity": max(d.deps.capacity for d in self.devs),
            "loaded_batch": max(d.loaded_batch for d in self.devs)}

    # -- the window -----------------------------------------------------
    def _snapshot(self):
        """served_scan's, plus the serving flushes the host answered past
        the stores' loaded programs and the deps the coordinators made
        stable (each left out where the program has no such counter)."""
        snap = super()._snapshot()
        if all(hasattr(d, "n_budget_host_queries") for d in self.devs):
            snap["device"]["n_budget_host_queries"] = sum(
                d.n_budget_host_queries for d in self.devs)
        coord = [s.stats().get("coordination") for s in self.servers]
        if all(c and all(k in c for k in _COORDINATION) for c in coord):
            for key in _COORDINATION:
                snap["server"]["coordination_" + key] = sum(
                    c[key] for c in coord)
        return snap

    def _live_per_store(self):
        return sum(d.n_live_slots for d in self.devs) / len(self.devs)

    def _device_read(self):
        """Store-level device flushes (solo device parts and fused
        members), the queries they answered, their download bytes."""
        kinds = checks.device_counters(self.devs)["kernel_times"]
        solo = sum(calls for kind, (calls, _s) in kinds.items()
                   if kind.startswith("dispatch_attr_"))
        return {"flushes": solo + sum(d.n_fused_flushes for d in self.devs),
                "queries": sum(getattr(d, k) for d in self.devs
                               for k in DEVICE_KINDS),
                "download_bytes": sum(d.download_bytes for d in self.devs),
                "live": self._live_per_store()}

    async def _window(self, seconds, tracer):
        live0 = self._live_per_store()
        traced = _Slice(tracer, self._device_read)
        record = await super()._window(seconds, traced)
        record["live_slots_per_store"] = (live0 + self._live_per_store()) / 2
        c = record["counters"]
        # the device routes the window's flushes took: a fused member
        # counts to n_fused_queries and to its device kind both
        fused = c.get("n_fused_queries", 0)
        self.window_routes = {
            "solo": sum(c.get(k, 0) for k in DEVICE_KINDS) - fused,
            "fused": fused}
        a, b = traced.at_start, traced.at_stop
        if a is not None and b is not None:
            record["slice_device"] = {
                "flushes": b["flushes"] - a["flushes"],
                "queries": b["queries"] - a["queries"],
                "download_bytes": b["download_bytes"] - a["download_bytes"],
                "live_slots": (a["live"] + b["live"]) / 2,
                "intervals_per_slot": max(d.deps.max_intervals
                                          for d in self.devs),
                "query_intervals": 1}
        return record

    # -- the check ------------------------------------------------------
    def _probe_queries(self, store, rng):
        """``probe_queries`` window-shaped queries this store owns, each
        above every txn the cluster holds."""
        from accord_tpu.local.command_store import (PreLoadContext,
                                                    SafeCommandStore)
        from accord_tpu.primitives.keys import IntKey, Keys, Range, Ranges
        from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
        safe = SafeCommandStore(store, PreLoadContext.empty())
        top = max(s.proc.node._hlc for s in self.servers)
        scan_share = float(self.traffic["scan_share"])
        out = []
        while len(out) < self.probe_queries:
            hlc = top + 1 + len(out)
            if rng.random() < scan_share:
                lo, hi = self._draw_scan(rng)
                tid = TxnId.create(1, hlc, TxnKind.Read, Domain.Range, 1)
                keys = Ranges.of(Range(lo, hi))
            else:
                token = self.known[rng.randrange(len(self.known))]
                tid = TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1)
                keys = Keys([IntKey(token)])
            q = store.device.build_query(safe, tid, keys, tid,
                                         tid.kind().witnesses())
            if q is not None:
                out.append(q)
        return safe, out

    def _probe_flushes(self, n):
        """(at, end) of each probe flush over ``n`` queries, of PROBE_SIZES
        in turn."""
        out, at, i = [], 0, 0
        while at < n:
            size = PROBE_SIZES[i % len(PROBE_SIZES)]
            out.append((at, min(at + size, n)))
            at, i = at + size, i + 1
        return out

    @staticmethod
    def _solo(dev, safe, qs, flushes, route):
        """The store's solo flushes of ``qs`` on ``route``: one built Deps
        a query."""
        from accord_tpu.primitives.deps import DepsBuilder
        builders = [DepsBuilder() for _ in qs]
        dev.route_override = route
        try:
            for at, end in flushes:
                handle = dev.deps_query_batch_begin(qs[at:end],
                                                    immediate=True)
                dev.deps_query_batch_end_attributed(safe, handle,
                                                    builders[at:end])
        finally:
            dev.route_override = None
        return [b.build() for b in builders]

    def _fused(self, node, members, flushes):
        """The node's stores' flushes of their ``qs`` through ONE fused
        launch a flush size, as the dispatcher plans, launches and
        harvests a serving tick's flushes: one built Deps a query, a store
        ({store_id: [Deps]}); None where the node has no fused path."""
        from accord_tpu.primitives.deps import DepsBuilder
        disp = node.dispatcher
        if disp is None or not disp.fusion or len(members) < 2:
            return None
        built = {store.store_id: [DepsBuilder() for _ in qs]
                 for store, _safe, qs in members}
        errors = []

        def done(err, _safe):
            if err is not None:
                errors.append(err)

        for store, _safe, _qs in members:
            store.device.route_override = "device"
        try:
            for at, end in flushes:
                hints = []
                for store, safe, qs in members:
                    h = store.device.fused_eligible(qs[at:end])
                    if h is None:
                        # no fused program for it: the solo flush's rule
                        store.device._flush_batch(safe, [
                            (q, b, done) for q, b in zip(
                                qs[at:end], built[store.store_id][at:end])])
                        continue
                    h["batch"] = [(q, b, done) for q, b in zip(
                        qs[at:end], built[store.store_id][at:end])]
                    hints.append((store, safe, h))
                if len(hints) < 2:
                    # a lone member: the dispatcher runs it solo
                    for store, safe, h in hints:
                        store.device._flush_batch(safe, h["batch"])
                    continue
                launch = disp._launch_fused_flush([h for _s, _f, h in hints])
                for store, safe, h in hints:
                    store.device.fused_harvest(safe, h, launch)
        finally:
            for store, _safe, _qs in members:
                store.device.route_override = None
        if errors:
            raise errors[0]
        return {sid: [b.build() for b in bs] for sid, bs in built.items()}

    def _route_probe(self):
        """Every store's probe through the host route, the solo device
        flush and the node's fused launch, against each other and the
        reference."""
        rng = random.Random(f"{self.seed}/probe")
        history = store_deps.History(self.history)
        for s in self.servers:
            stores = s.proc.node.command_stores.unsafe_all_stores()
            members = [(store, *self._probe_queries(store, rng))
                       for store in stores]
            flushes = self._probe_flushes(self.probe_queries)
            host, solo = {}, {}
            for store, safe, qs in members:
                host[store.store_id] = self._solo(store.device, safe, qs,
                                                  flushes, "host")
            mid = {store.store_id: self._route_counts(store.device)
                   for store in stores}
            for store, safe, qs in members:
                solo[store.store_id] = self._solo(store.device, safe, qs,
                                                  flushes, "device")
            after_solo = {store.store_id: self._route_counts(store.device)
                          for store in stores}
            fused = self._fused(s.proc.node, members, flushes)
            after = {store.store_id: self._route_counts(store.device)
                     for store in stores}
            for store, _safe, qs in members:
                sid = store.store_id
                name = f"{s.name}/{sid}"
                index = store_deps.StoreIndex(store, history)
                want = [index.deps(q) for q in qs]
                answers = {"host": host[sid], "solo": solo[sid]}
                if fused is not None:
                    answers["fused"] = fused[sid]
                a0, a1 = mid[sid], after_solo[sid]
                a2 = after[sid]
                probe = {
                    "store": name, "queries": len(qs),
                    "deps": sum(len(store_deps.named(d))
                                for d in host[sid]),
                    "solo_device": a1["device"] - a0["device"],
                    "solo_budget_host": a1["budget"] - a0["budget"],
                    "fused_device": a2["fused"] - a1["fused"],
                    "fused_budget_host": a2["budget"] - a1["budget"]}
                for route, built in answers.items():
                    unlike = sum(1 for d, w in zip(built, want)
                                 if store_deps.named(d) != w)
                    probe[f"{route}_unlike_reference"] = unlike
                    if unlike:
                        self.problems.append(
                            f"route probe {name}: {unlike} of {len(qs)} "
                            f"{route} answers unlike the reference")
                    if route != "host":
                        same = checks.deps_digest(built) == \
                            checks.deps_digest(host[sid])
                        probe[f"{route}_equals_host"] = same
                        if not same:
                            self.problems.append(
                                f"route probe {name}: the {route} and host "
                                f"routes answer differently")
                self.probes.append(probe)
        # a device route the window used has to have answered some of the
        # probe's queries itself, or the probe compared nothing of it
        used = getattr(self, "window_routes", {})
        for route in ("solo", "fused"):
            if used.get(route) and not sum(p[f"{route}_device"]
                                           for p in self.probes):
                self.problems.append(
                    f"route probe: the window used the {route} device "
                    f"route and the probe's {route} flushes answered none "
                    f"of its queries on the device")

    @staticmethod
    def _route_counts(dev):
        return {"device": sum(getattr(dev, k) for k in DEVICE_KINDS)
                - dev.n_fused_queries,
                "fused": dev.n_fused_queries,
                "budget": dev.n_budget_host_queries}

    async def _check(self):
        t0 = time.perf_counter()
        self._route_probe()
        self.info["route_probe"] = {"seconds": time.perf_counter() - t0,
                                    "window_routes": getattr(
                                        self, "window_routes", None),
                                    "stores": self.probes}
        await super()._check()
