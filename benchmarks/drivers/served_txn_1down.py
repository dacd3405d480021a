"""Driver ``served_txn_1down``: drivers/served_txn.py's deployment, traffic,
window and check, with one replica crash-stopped between warm-up and window.

After the traffic mix's own warm-up the closed loop goes one more round over
ALL the nodes and, with attempts in flight on every one of them, the
configuration's ``crash_node`` is crash-stopped (``NodeServer.crash_stop``:
sockets reset with no goodbye, timers dead, nothing flushed, the journal as
it lies).  The node never returns and the topology is left alone.  From then
on the clients draw their coordinators from the survivors (a client library
routes around a host that refuses connections); the attempts that were in
flight on the crashed node are indeterminate, as served_txn records every
unanswered attempt.  The driver then waits until no survivor holds an
undecided txn that the crashed node coordinated (``settle_max_s`` at most),
runs ``post_crash_warm_txns`` more untimed txns on the survivors, and only
then opens the window: the window measures the steady state AFTER the loss.

An in-process crash is an emulation (one process owns the chip): the node
object's memory stays where it is and nothing reads its journal again.

Beyond served_txn's check (read-back of every key through the client path
over the survivors, composite verifier, serial replay, no duplicate reply,
no node failure, no ladder counter) the run is ``correct`` only if the
orphans settled in time and stay settled, every acknowledged append is in
the data store of a slow quorum of its shard's replicas once the cluster is
quiet (with one replica of three down: of every live one), and no attempt of
the WINDOW was shed, failed or timed out: ``failed`` is 0.

The record keeps ``"driver": "served"``; it gains ``peer_failures`` (how the
survivors' callbacks failed), ``down_peer_enqueued`` (frames their links
took for the crashed peer) and ``latency_cuts`` (the admission gates' cuts)
where ``NodeServer.stats()`` has them."""

import asyncio
import time

from accord_tpu.net.server import NodeServer

from ..lib.tracer import NoTracer
from . import served_txn
from .served import _Sink

if not hasattr(NodeServer, "crash_stop"):
    # a program from before this driver: say so now, not after a warm-up
    raise ImportError("served_txn_1down needs NodeServer.crash_stop, which "
                      "this program has not")

PEER_FAILURE_KEYS = ("failed_at_once", "failed_by_drop", "timed_out",
                     "down_drops", "reads_to_down_replica")
QUIET_MAX_S = 10.0      # the wait for Apply to reach every live replica


class Driver(served_txn.Driver):

    def __init__(self, config, traffic, seed, scratch_dir):
        super().__init__(config, traffic, seed, scratch_dir)
        self.crash_node = config["crash_node"]
        self.settle_max_s = float(config["settle_max_s"])
        self.post_crash_warm_txns = int(config["post_crash_warm_txns"])

    def survivors(self):
        return [s for s in self.servers if s.name != self.crash_node]

    # -- warm-up, crash, settle, warm-up again --------------------------
    async def _warm(self):
        await super()._warm()
        crash = await self._crash_under_load()
        crash["settle_s"] = await self._settle()
        crash["post_crash"] = await self._closed_loop_for(
            "post-crash", self.post_crash_warm_txns)
        self.info["warm"]["crash"] = crash

    async def _closed_loop_for(self, phase, txns, at_submitted=None):
        """The clients' loop, untimed, until ``txns`` were submitted;
        ``at_submitted`` = (n, fn): fn() runs as the n-th is drawn."""
        sink, submitted = _Sink(), [0]

        def go_on():
            if submitted[0] >= txns:
                return False
            submitted[0] += 1
            if at_submitted is not None and submitted[0] == at_submitted[0]:
                at_submitted[1]()
            return True

        t0 = self.loop.time()
        await asyncio.gather(*self._clients(phase, go_on, sink, NoTracer()))
        return {"seconds": self.loop.time() - t0, "acked": len(sink.done),
                "failed": len(sink.failed),
                "failed_kinds": sorted(set(sink.failed))[:6]}

    async def _crash_under_load(self):
        """Three rounds of the closed loop over all the nodes (3 x clients
        untimed txns beyond the mix's own warm-up: that one ends with every
        client finished, and a crash on an idle node leaves no orphan); the
        crash falls as the third begins, with every client's attempt in
        flight."""
        clients = int(self.traffic["clients"])
        seen = {}

        def crash():
            server = next(s for s in self.servers
                          if s.name == self.crash_node)
            seen["in_flight_on_crashed"] = len(
                self.client.conns[self.crash_node]._pending)
            server.crash_stop()
            self.names = [n for n in self.names if n != self.crash_node]

        out = await self._closed_loop_for("crash", 3 * clients,
                                          (2 * clients, crash))
        await self.client.remove_node(self.crash_node)
        return {**out, **seen, "orphans_at_crash": len(self._orphans())}

    def _orphans(self):
        """(survivor, store, txn, status) of every txn the crashed node
        coordinated that a survivor holds undecided: neither committed nor
        invalidated."""
        from accord_tpu.local.status import Status
        from accord_tpu.maelstrom.node import node_name_to_id
        crashed = node_name_to_id(self.crash_node)
        return [(s.name, store.store_id, str(txn_id),
                 cmd.save_status.status.name)
                for s in self.survivors()
                for store in s.proc.node.command_stores.stores
                for txn_id, cmd in store.commands.items()
                if txn_id.node == crashed
                and not cmd.save_status.status.has_been(Status.Committed)]

    async def _settle(self):
        t0 = self.loop.time()
        while self._orphans():
            if self.loop.time() - t0 >= self.settle_max_s:
                self.problems.append(
                    f"orphans of {self.crash_node} undecided after "
                    f"{self.settle_max_s} s: {self._orphans()[:5]}")
                break
            await asyncio.sleep(0.05)
        return self.loop.time() - t0

    # -- the window -----------------------------------------------------
    def _snapshot(self):
        """served_txn's, plus the survivors' ``peer_failures`` and what
        their links took for the crashed peer, where the program's stats()
        has them (a program without: the keys are left out and their
        readers find nothing)."""
        snap = super()._snapshot()
        stats = [s.stats() for s in self.survivors()]
        failures = [st.get("peer_failures") for st in stats]
        if all(failures):
            for key in PEER_FAILURE_KEYS:
                snap["server"]["peer_" + key] = sum(f[key] for f in failures)
        links = [st["links"].get(self.crash_node, {}) for st in stats]
        if all("enqueued" in link for link in links):
            snap["server"]["down_peer_enqueued"] = sum(
                link["enqueued"] for link in links)
        snap["server"]["latency_cuts"] = sum(
            st["admission"]["latency_cuts"] for st in stats)
        return snap

    # -- the check ------------------------------------------------------
    async def _check(self):
        await super()._check()
        if self.info.get("window_failed_kinds"):
            self.problems.append(
                f"attempts of the window were shed, failed or timed out: "
                f"{self.info['window_failed_kinds']}")
        t0 = time.perf_counter()
        thin = await self._under_replicated()
        if thin:
            self.problems.append(f"acknowledged appends on fewer than a "
                                 f"slow quorum of replicas: {thin[:5]}")
        orphans = self._orphans()
        if orphans:
            self.problems.append(f"undecided orphans of {self.crash_node} "
                                 f"at the end: {orphans[:5]}")
        self.info["check"]["replica_check_s"] = time.perf_counter() - t0
        stats = {s.name: s.stats() for s in self.survivors()}
        self.info["check"]["survivor_links"] = {
            name: st["links"].get(self.crash_node)
            for name, st in stats.items()}
        self.info["check"]["peer_failures"] = {
            name: st.get("peer_failures") for name, st in stats.items()}
        self.info["check"]["admission"] = {
            name: st["admission"] for name, st in stats.items()}
        self.info["check"]["phases"] = {
            s.name: s.proc.obs.metrics.phase_percentiles()
            for s in self.survivors()}

    async def _under_replicated(self):
        """(token, value, holders) of every acknowledged append that fewer
        than a slow quorum of its shard's replicas hold in their data
        stores, the crashed one counted as holding nothing; polled until the
        Applys in flight have landed."""
        by_id = {s.proc.node.node_id: s.proc.node.data_store
                 for s in self.survivors()}
        topology = self.survivors()[0].proc.node.topology().current()
        wanted = []
        for token, values in self.acked.items():
            shard = next(sh for sh in topology if sh.contains_token(token))
            stores = [by_id[n] for n in shard.nodes if n in by_id]
            wanted.append((token, values, stores,
                           shard.slow_path_quorum_size))
        t0 = self.loop.time()
        while True:
            thin = []
            for token, values, stores, quorum in wanted:
                held = [set(store.get(token)) for store in stores]
                for value in values:
                    holders = sum(value in h for h in held)
                    if holders < quorum:
                        thin.append((token, value, holders))
            if not thin or self.loop.time() - t0 >= QUIET_MAX_S:
                return thin
            await asyncio.sleep(0.1)
