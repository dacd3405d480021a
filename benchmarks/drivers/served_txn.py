"""Driver ``served_txn``: drivers/served.py's deployment and window under
multi-key txns on a skewed key space.

Each closed-loop client submits txns of ``keys_per_txn`` DISTINCT keys; a key
is drawn as a rank ~ Zipf(``zipf``) over the configuration's keys and mapped
through a rank -> key table that the configuration's ``population_seed``
fixes (a permutation: integer keys are their own tokens, so without it the
hot ranks would be neighbours in ONE shard).  Each op appends a unique
integer with probability ``append_share``, else reads.

The check reads EVERY key back through the client path, in read-only txns of
``check_keys_per_read`` keys, ``check_in_flight`` at a time, and then holds
the run to served.py's gates plus the serial replay of lib/serial_kv.py: the
plain reference has to reproduce every read of every acknowledged txn and
the final lists.

The record keeps ``"driver": "served"``: what the ``.serve`` metrics read is
all there, under the same names."""

import asyncio
import bisect
import itertools
import random
import time

from ..lib import checks, serial_kv
from ..lib.compile_clock import COMPILE
from ..lib.tracer import NoTracer
from . import served
from .served import _Sink, _now_us


class Driver(served.Driver):

    def __init__(self, config, traffic, seed, scratch_dir):
        super().__init__(config, traffic, seed, scratch_dir)
        from accord_tpu.utils import invariants
        self._paranoid = invariants.PARANOID
        self.population_seed = int(config["population_seed"])
        self.answered = []       # (start, end, reads, appends) per txn_ok
        self.unanswered = []     # (start, appends) of attempts that failed

    def setup(self):
        # a serving process stands the deep structural checks down
        # (net/server.py main(): O(n) sortedness scans of maps that here
        # hold a boundary per key); hosting the nodes in this process has to
        # say so itself
        from accord_tpu.local.fastpath import proto_fastpath_enabled
        from accord_tpu.utils import invariants
        if proto_fastpath_enabled():
            invariants.PARANOID = False
        super().setup()

    def close(self):
        from accord_tpu.utils import invariants
        try:
            super().close()
        finally:
            invariants.PARANOID = self._paranoid

    async def _start(self):
        await super()._start()
        random.Random(self.population_seed).shuffle(self.keys)  # rank -> key
        skew = float(self.traffic["zipf"])
        self.zipf_cdf = list(itertools.accumulate(
            (rank + 1) ** -skew for rank in range(len(self.keys))))

    def _draw_keys(self, rng):
        """``keys_per_txn`` distinct keys, in the order drawn: a rank drawn
        again is drawn anew, so the txn keeps its width."""
        cdf, chosen = self.zipf_cdf, []
        while len(chosen) < int(self.traffic["keys_per_txn"]):
            key = self.keys[bisect.bisect_left(cdf, rng.random() * cdf[-1])]
            if key not in chosen:
                chosen.append(key)
        return chosen

    # -- the closed loop ------------------------------------------------
    async def _client_loop(self, rng, go_on, sink, tracer):
        from accord_tpu.maelstrom.node import token_of
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        loop, client, verifier = self.loop, self.client, self.verifier
        append_share = float(self.traffic["append_share"])
        while go_on():
            node = self.names[rng.randrange(len(self.names))]
            ops, writes = [], {}
            for key in self._draw_keys(rng):
                if rng.random() < append_share:
                    self.counter += 1
                    ops.append(["append", key, self.counter])
                    writes[token_of(key)] = (self.counter,)
                else:
                    ops.append(["r", key, None])
            op_id, start = verifier.begin(), _now_us()
            t0 = loop.time()
            try:
                with tracer.span("client.submit"):
                    body = await client.submit(ops, node=node)
            except Overloaded as shed:
                sink.failed.append("Overloaded")
                self._unanswered(start, writes)
                await asyncio.sleep(shed.retry_after_ms / 1e3)
                continue
            except (TxnFailed, asyncio.TimeoutError, ConnectionError) as e:
                # indeterminate: its appends may still land, all or none
                sink.failed.append(repr(e)[:120])
                self._unanswered(start, writes)
                continue
            sink.done.append((t0, loop.time()))
            end = _now_us()
            reads = {token_of(op[1]): tuple(op[2])
                     for op in body["txn"] if op[0] == "r"}
            verifier.on_result(op_id, start, end, reads, writes)
            self.answered.append((start, end, reads, writes))
            for t, vals in writes.items():
                self.acked.setdefault(t, []).extend(vals)

    def _unanswered(self, start, writes):
        if writes:
            self.unanswered.append((start, writes))

    async def _warm(self):
        """served.py's warm-up (the closed loop, untimed, until
        ``warm_quiet_s`` pass with no compile event, bounded by
        ``warm_max_s``), which then goes on until ``warm_txns`` txns have
        been submitted in all: on a warm cache every run's window starts
        after the same number of txns, from lists of the same lengths."""
        quiet_s = float(self.traffic["warm_quiet_s"])
        max_s = float(self.traffic["warm_max_s"])
        want = int(self.traffic["warm_txns"])
        sink, quiet, submitted = _Sink(), [False], [0]

        def go_on():
            if quiet[0] and submitted[0] >= want:
                return False
            submitted[0] += 1
            return True

        tasks = self._clients("warm", go_on, sink, NoTracer())
        t0 = last_change = self.loop.time()
        events = COMPILE.events
        while not all(t.done() for t in tasks):
            await asyncio.sleep(0.25)
            now = self.loop.time()
            if COMPILE.events != events:
                events, last_change = COMPILE.events, now
            if now - t0 >= max_s:
                quiet[0], want = True, 0
            elif not quiet[0] and now - last_change >= quiet_s:
                quiet[0] = True
                self.info["warm"] = {"quiet_after_s": now - t0}
        await asyncio.gather(*tasks)
        self.info["warm"] = {**self.info.get("warm", {}),
                             "seconds": self.loop.time() - t0,
                             "submitted": submitted[0],
                             "acked": len(sink.done),
                             "failed": len(sink.failed),
                             "failed_kinds": sorted(set(sink.failed))[:6]}

    # -- the window -----------------------------------------------------
    def _snapshot(self):
        """served.py's, plus the nodes' ``coordination`` counters where the
        program has them (NodeServer.stats() of this PR's parent has not:
        the keys are then left out and their readers find nothing)."""
        snap = super()._snapshot()
        coord = [s.stats().get("coordination") for s in self.servers]
        if all(coord):
            for key in ("fast", "slow", "recoveries"):
                snap["server"]["coordination_" + key] = sum(
                    c[key] for c in coord)
        return snap

    # -- the check ------------------------------------------------------
    async def _read_back(self, keys, finals):
        """One read-only txn over ``keys``; safe to repeat."""
        from accord_tpu.maelstrom.node import token_of
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        ops = [["r", key, None] for key in keys]
        for attempt in range(1, 9):
            start = _now_us()
            try:
                body = await self.client.submit(ops)
                break
            except (TxnFailed, Overloaded, asyncio.TimeoutError):
                if attempt == 8:
                    raise
                await asyncio.sleep(0.5 * attempt)
        reads = {token_of(op[1]): tuple(op[2]) for op in body["txn"]}
        self.answered.append((start, _now_us(), reads, {}))
        finals.update(reads)

    async def _check(self):
        t0 = time.perf_counter()
        width = int(self.traffic["check_keys_per_read"])
        chunks = [self.keys[i:i + width]
                  for i in range(0, len(self.keys), width)]
        finals = {}

        async def reader():
            while chunks:
                await self._read_back(chunks.pop(), finals)

        await asyncio.gather(*[reader() for _ in range(
            int(self.traffic["check_in_flight"]))])
        read_back_s = time.perf_counter() - t0
        for token, final in finals.items():
            self.verifier.set_final(token, final)
        if len(finals) != len(self.keys):
            self.problems.append(f"read back {len(finals)} of "
                                 f"{len(self.keys)} keys")
        missing = checks.missing_acks(self.acked, finals)
        if missing:
            self.problems.append(f"acknowledged appends not read back: "
                                 f"{missing[:5]}")
        t1 = time.perf_counter()
        try:
            self.verifier.verify()
        except AssertionError as e:
            self.problems.append(f"verifier: {e}")
        t2 = time.perf_counter()
        try:
            serial_kv.replay(self.answered, self.unanswered, finals)
        except serial_kv.NotSerial as e:
            self.problems.append(f"serial_kv: {e}")
        t3 = time.perf_counter()
        if self.client.duplicate_replies():
            self.problems.append(
                f"duplicate_replies={self.client.duplicate_replies()}")
        node_failures = sum(len(s.proc.failures) for s in self.servers)
        if node_failures:
            self.problems.append(f"node-level failures: {node_failures}")
        rep = checks.device_counters(self.devs)
        self.problems += checks.ladder_problems(rep)
        self.info["totals"] = {k: v for k, v in rep.items()
                               if k != "kernel_times"}
        self.info["kernel_times"] = rep["kernel_times"]
        self.info["check"] = {
            "read_back_s": read_back_s, "verify_s": t2 - t1,
            "replay_s": t3 - t2, "keys_read_back": len(finals),
            "txns_replayed": len(self.answered),
            "unanswered_with_appends": len(self.unanswered),
            "acked_appends": sum(len(v) for v in self.acked.values()),
            "longest_list": max(map(len, finals.values()), default=0),
            "client": {"ok": self.client.n_ok,
                       "overloaded": self.client.n_overloaded,
                       "failed": self.client.n_failed,
                       "timeout": self.client.n_timeout}}
        if self.devs:
            self.info["calibration"] = {
                k: float(v) for k, v in self.devs[0]._calibration().items()}
