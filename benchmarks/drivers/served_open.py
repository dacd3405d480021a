"""Driver ``served_open``: drivers/served.py's deployment and check under an
OPEN loop: requests arrive on a schedule and do not wait for each other.

Arrivals are a Poisson process at the traffic mix's ``rate`` txn/s, drawn
from ``--seed`` before the window (the schedule is fixed before the first
request leaves).  Each arrival is its own task whether or not earlier ones
were answered, carries served.py's op (one append or read, ``append_share``,
uniform on the configuration's keys, coordinator uniform) and is never
retried: a shed, a TxnFailed or a timeout is one failed attempt.

The clock.  A latency runs from the instant the request was DUE to its
``txn_ok``, not from when its task got the loop: a generator that runs late
(it shares the loop with the three nodes) charges the lateness to the
request, as a client would feel it; ``generator_late_ms`` (sent - due, p50
/ p95 / max) goes on the window's info line beside it, so that a starved
generator is not read as a fast or a slow server.  ``commit_rate`` is the
txn_ok that arrived inside the window per second: about the offered rate
while the system keeps up, which is why ``commit_p95``, the tail under
queueing, is what this mix judges.

The warm-up is served.py's closed loop, untimed.  The record keeps
``"driver": "served"``: what the ``.serve`` metrics read is all there."""

import asyncio
import random

from ..lib import checks
from ..lib.compile_clock import delta
from ..lib.stats import percentile
from . import served
from .served import _Sink, _now_us


class Driver(served.Driver):

    def _schedule(self, seconds):
        """Every arrival of the window as ``(due, key, node, append)``,
        ``due`` in seconds from its start."""
        rng = random.Random(f"{self.seed}/arrivals")
        rate = float(self.traffic["rate"])
        append_share = float(self.traffic["append_share"])
        out, due = [], rng.expovariate(rate)
        while due < seconds:
            out.append((due, self.keys[rng.randrange(len(self.keys))],
                        self.names[rng.randrange(len(self.names))],
                        rng.random() < append_share))
            due += rng.expovariate(rate)
        return out

    async def _one(self, due, key, node, append, sink, tracer):
        from accord_tpu.maelstrom.node import token_of
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        writes = {}
        if append:
            self.counter += 1
            ops = [["append", key, self.counter]]
            writes[token_of(key)] = (self.counter,)
        else:
            ops = [["r", key, None]]
        op_id, start = self.verifier.begin(), _now_us()
        try:
            with tracer.span("client.submit"):
                body = await self.client.submit(ops, node=node)
        except Overloaded:
            sink.failed.append("Overloaded")
            return
        except (TxnFailed, asyncio.TimeoutError, ConnectionError) as e:
            # indeterminate: its write may still land unacknowledged,
            # which the verifier allows
            sink.failed.append(repr(e)[:120])
            return
        sink.done.append((due, self.loop.time()))
        reads = {token_of(op[1]): tuple(op[2])
                 for op in body["txn"] if op[0] == "r"}
        self.verifier.on_result(op_id, start, _now_us(), reads, writes)
        for t, vals in writes.items():
            self.acked.setdefault(t, []).extend(vals)

    async def _window(self, seconds, tracer):
        loop = self.loop
        sink, late, tasks = _Sink(), [], []
        schedule = self._schedule(seconds)
        s0 = self._snapshot()
        t0 = loop.time()
        for due, key, node, append in schedule:
            if tracer.due(seconds - due):        # a traced run: the last slice
                tracer.start()
            wait = t0 + due - loop.time()
            if wait > 0:
                await asyncio.sleep(wait)
            late.append((loop.time() - t0 - due) * 1e3)
            tasks.append(loop.create_task(self._one(
                t0 + due, key, node, append, sink, tracer)))
        await asyncio.sleep(max(seconds - (loop.time() - t0), 0.0))
        t1 = loop.time()
        s1 = self._snapshot()
        tracer.stop()
        await asyncio.gather(*tasks)     # in flight at t1: answered or timed out
        acked = sum(1 for _d, a in sink.done if a <= t1)
        lat = [(a - d) * 1e3 for d, a in sink.done]
        self.info["window_failed_kinds"] = sorted(set(sink.failed))[:6]
        self.info["open_loop"] = {
            "offered_rate": float(self.traffic["rate"]),
            "arrivals": len(schedule),
            "generator_late_ms": {"p50": percentile(late, 0.5),
                                  "p95": percentile(late, 0.95),
                                  "max": max(late, default=None)}}
        return {
            "driver": "served", "window_s": t1 - t0, "acked": acked,
            "answered_after_window": len(sink.done) - acked,
            "attempted": len(sink.done) + len(sink.failed),
            "failed": len(sink.failed),
            "server": {k: s1["server"][k] - s0["server"][k]
                       for k in s1["server"]},
            "cpu_s": s1["cpu_s"] - s0["cpu_s"],
            "counters": checks.counters_delta(s1["device"], s0["device"]),
            "compile": delta(s1["compile"], s0["compile"]),
            "latency_ms": {"n": len(lat), "p50": percentile(lat, 0.5),
                           "p95": percentile(lat, 0.95),
                           "p99": percentile(lat, 0.99)},
            "end_to_end": {
                "commit_rate": acked / (t1 - t0) if acked else None,
                "commit_p95": percentile(lat, 0.95),
            }}
