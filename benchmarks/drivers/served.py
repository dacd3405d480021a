"""Driver ``served``: N net.server.NodeServers in THIS process (the one that
owns the chip) on one asyncio loop, real loopback TCP between them and to the
client, driven by a closed loop of clients through net.client.ClusterClient.

Hosting the nodes in-process, the start order and the close order are
chip_smoke.py's ``_serve`` (copied); the closed loop is net/harness.py's
``saturation_probe`` (copied), with one op per txn, the coordinator drawn per
txn and no retry inside the window.  Unlike the smoke, nothing is pinned: the
stores route adaptively and every timeout and admission setting is the
product's default."""

import asyncio
import gc
import os
import random
import shutil
import socket
import time

from ..lib import checks
from ..lib.compile_clock import COMPILE, delta
from ..lib.stats import percentile
from ..lib.tracer import NoTracer

TOKEN_SPACE = 1 << 32        # net/harness.py's key ring


def free_ports(n):
    """n distinct ephemeral ports (bind-then-release; net/harness.py)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _now_us():
    return time.monotonic_ns() // 1_000


class _Sink:
    """What one phase's clients saw."""

    def __init__(self):
        self.done = []       # (submitted, answered) loop times of txn_ok
        self.failed = []     # reprs of what a refused / failed attempt raised


class Driver:
    annotations = ("client.submit",)

    def __init__(self, config, traffic, seed, scratch_dir):
        self.sz = dict(config["sizes"])
        self.traffic = dict(traffic)
        self.trace_slice_s = float(traffic.get("trace_slice_s", 4.0))
        self.seed = seed
        self.journal_root = os.path.join(scratch_dir, "journal")
        self.problems = []
        self.info = {}
        self.loop = asyncio.new_event_loop()
        self.servers, self.client, self.devs = [], None, []
        self.acked = {}
        self.counter = 0
        self._gc = gc.get_threshold()

    def _run(self, coro):
        return self.loop.run_until_complete(coro)

    # -- set-up ---------------------------------------------------------
    def setup(self):
        t0 = time.perf_counter()
        asyncio.set_event_loop(self.loop)
        self._run(self._start())
        self.info["setup"] = {"start_s": time.perf_counter() - t0,
                              "nodes": len(self.servers),
                              "stores": len(self.devs)}

    async def _start(self):
        from accord_tpu.net.client import ClusterClient
        from accord_tpu.net.server import NodeServer
        sz = self.sz
        self.names = [f"n{i}" for i in range(1, int(sz["nodes"]) + 1)]
        addrs = {n: ("127.0.0.1", p)
                 for n, p in zip(self.names, free_ports(len(self.names)))}
        self.servers = [NodeServer(
            n, *addrs[n], dict(addrs),
            stores=int(sz["stores"]), shards=int(sz["shards"]),
            device_mode=bool(sz["device_mode"]),
            durability=bool(sz["durability"]),
            admit_max=int(sz["admit_max"]),
            target_p99_ms=int(sz["target_p99_ms"]),
            request_timeout_ms=sz["request_timeout_ms"],
            journal_dir=os.path.join(self.journal_root, n),
            journal_sync=sz["journal_sync"],
            wire_codec_name=sz["wire_codec"]) for n in self.names]
        self.client = ClusterClient([(n, *addrs[n]) for n in self.names],
                                    timeout=float(sz["client_timeout_s"]),
                                    codec=sz["wire_codec"])
        for s in self.servers:
            await s.start()
            if s.wire_codec != sz["wire_codec"] or s.journal is None:
                self.problems.append(f"{s.name}: codec={s.wire_codec} "
                                     f"journal={s.journal is not None}")
        self.devs = [st.device for s in self.servers
                     for st in s.proc.node.command_stores.stores]
        await self.client.connect()
        for n in self.names:
            await self.client.ping(n, timeout=60.0)
        stride = TOKEN_SPACE // int(sz["keys"])
        self.keys = [k * stride for k in range(int(sz["keys"]))]
        self.verifier = checks.verifier()

    # -- the closed loop ------------------------------------------------
    async def _client_loop(self, rng, go_on, sink, tracer):
        """One closed-loop client: the next txn when the last is answered.
        A shed, a TxnFailed or a timeout is one failed attempt; the client
        moves on to a NEW txn (after a shed, when the server's retry_after
        says it may)."""
        from accord_tpu.maelstrom.node import token_of
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        loop, client, verifier = self.loop, self.client, self.verifier
        append_share = float(self.traffic["append_share"])
        while go_on():
            key = self.keys[rng.randrange(len(self.keys))]
            node = self.names[rng.randrange(len(self.names))]
            writes = {}
            if rng.random() < append_share:
                self.counter += 1
                ops = [["append", key, self.counter]]
                writes[token_of(key)] = (self.counter,)
            else:
                ops = [["r", key, None]]
            op_id, start = verifier.begin(), _now_us()
            t0 = loop.time()
            try:
                with tracer.span("client.submit"):
                    body = await client.submit(ops, node=node)
            except Overloaded as shed:
                sink.failed.append("Overloaded")
                await asyncio.sleep(shed.retry_after_ms / 1e3)
                continue
            except (TxnFailed, asyncio.TimeoutError, ConnectionError) as e:
                # indeterminate: its write may still land unacknowledged,
                # which the verifier allows
                sink.failed.append(repr(e)[:120])
                continue
            sink.done.append((t0, loop.time()))
            reads = {token_of(op[1]): tuple(op[2])
                     for op in body["txn"] if op[0] == "r"}
            verifier.on_result(op_id, start, _now_us(), reads, writes)
            for t, vals in writes.items():
                self.acked.setdefault(t, []).extend(vals)

    def _clients(self, phase, go_on, sink, tracer):
        n = int(self.traffic["clients"])
        return [self.loop.create_task(self._client_loop(
            random.Random(f"{self.seed}/{phase}/{i}"), go_on, sink, tracer))
            for i in range(n)]

    def warm(self):
        self._run(self._warm())

    async def _warm(self):
        """The same closed loop, untimed, until ``warm_quiet_s`` pass with
        no compile event (bounded by ``warm_max_s``)."""
        quiet_s = float(self.traffic["warm_quiet_s"])
        max_s = float(self.traffic["warm_max_s"])
        sink, going = _Sink(), [True]
        tasks = self._clients("warm", lambda: going[0], sink, NoTracer())
        t0 = last_change = self.loop.time()
        events = COMPILE.events
        while True:
            await asyncio.sleep(0.25)
            now = self.loop.time()
            if COMPILE.events != events:
                events, last_change = COMPILE.events, now
            if now - last_change >= quiet_s or now - t0 >= max_s:
                break
        going[0] = False
        await asyncio.gather(*tasks)
        self.info["warm"] = {"seconds": self.loop.time() - t0,
                             "quiet_s": self.loop.time() - last_change,
                             "acked": len(sink.done),
                             "failed": len(sink.failed),
                             "failed_kinds": sorted(set(sink.failed))[:6]}

    # -- the window -----------------------------------------------------
    def _snapshot(self):
        stats = [s.stats() for s in self.servers]
        disp = [st["dispatch"] or {} for st in stats]
        return {
            "server": {
                "links_sent": sum(l["sent"] for st in stats
                                  for l in st["links"].values()),
                "wire_bytes_tx": sum(st["wire_bytes_tx"] for st in stats),
                "flush_events": sum(d.get("flush_events", 0) for d in disp),
                "flush_queries": sum(d.get("flush_queries", 0)
                                     for d in disp),
                "fused_launches": sum(d.get("fused_launches", 0)
                                      for d in disp),
                "journal_bytes": sum(st["journal"]["wal"]["bytes"]
                                     for st in stats),
                "journal_flushes": sum(st["journal"]["commit"]["flushes"]
                                       for st in stats),
                "client_replies": sum(st["client_replies"] for st in stats),
            },
            "cpu_s": time.process_time(),
            "device": checks.device_counters(self.devs),
            "compile": COMPILE.snap()}

    def window(self, seconds, tracer):
        return self._run(self._window(seconds, tracer))

    async def _window(self, seconds, tracer):
        loop = self.loop
        sink = _Sink()
        s0 = self._snapshot()
        t0 = loop.time()
        tasks = self._clients("window", lambda: loop.time() - t0 < seconds,
                              sink, tracer)
        if tracer.due(0.0):              # a traced run: the last slice
            await asyncio.sleep(max(seconds - tracer.slice_s, 0.0))
            tracer.start()
        await asyncio.sleep(max(seconds - (loop.time() - t0), 0.0))
        t1 = loop.time()
        s1 = self._snapshot()
        tracer.stop()
        await asyncio.gather(*tasks)     # in flight at t1: answered or timed out
        acked = sum(1 for _s, a in sink.done if a <= t1)
        lat = [(a - s) * 1e3 for s, a in sink.done]
        self.info["window_failed_kinds"] = sorted(set(sink.failed))[:6]
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

    # -- the check ------------------------------------------------------
    def check(self):
        self._run(self._check())
        return not self.problems

    async def _check(self):
        from accord_tpu.maelstrom.node import token_of
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        finals = {}
        for key in self.keys:
            for attempt in range(1, 9):      # a read is safe to repeat
                try:
                    body = await self.client.submit([["r", key, None]])
                    break
                except (TxnFailed, Overloaded, asyncio.TimeoutError):
                    if attempt == 8:
                        raise
                    await asyncio.sleep(0.5 * attempt)
            finals[token_of(key)] = tuple(body["txn"][0][2])
            self.verifier.set_final(token_of(key), finals[token_of(key)])
        missing = checks.missing_acks(self.acked, finals)
        if missing:
            self.problems.append(f"acknowledged appends not read back: "
                                 f"{missing[:5]}")
        t0 = time.perf_counter()
        try:
            self.verifier.verify()
        except AssertionError as e:
            self.problems.append(f"verifier: {e}")
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
            "verify_s": time.perf_counter() - t0,
            "acked_appends": sum(len(v) for v in self.acked.values()),
            "client": {"ok": self.client.n_ok,
                       "overloaded": self.client.n_overloaded,
                       "failed": self.client.n_failed,
                       "timeout": self.client.n_timeout}}
        if self.devs:
            self.info["calibration"] = {
                k: float(v) for k, v in self.devs[0]._calibration().items()}

    # -- close ----------------------------------------------------------
    def close(self):
        try:
            self._run(self._close())
        finally:
            self.loop.close()
            asyncio.set_event_loop(None)
            # NodeServer.start() retunes the collector for a serving process
            gc.unfreeze()
            gc.set_threshold(*self._gc)
            shutil.rmtree(self.journal_root, ignore_errors=True)

    async def _close(self):
        # every outbound link first: a FrameServer's close waits for its
        # inbound connections, which in one process are the OTHER servers'
        # links (separate processes just exit)
        if self.client is not None:
            await self.client.close()
        for s in self.servers:
            for link in s.links.values():
                await link.close()
        for s in self.servers:
            if s.frame_server is not None:
                await asyncio.wait_for(s.close(), 30.0)
