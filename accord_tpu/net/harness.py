"""Serving-cluster harness: spawn real node processes, drive open-loop load.

Three consumers share this module (ISSUE r12 satellite: one harness, not
three): ``tests/test_net.py`` (tier-1 loopback smoke, kill-9 recovery,
slow overload sweep), ``tools/serve_bench.py`` (the 3-point offered-load
sweep that lands in the BENCH artifact) and ``tools/run_fault_matrix.sh``
(the socket-fault legs: ``python -m accord_tpu.net.harness --smoke
--net-faults conn_reset:0.08:5``).

The load generator is OPEN-LOOP: arrivals follow a seeded Poisson process
at the offered rate regardless of completions — the regime where a server
without admission control collapses (every arrival joins a queue that only
grows) and a shedding server keeps its goodput.  Each arrival is submitted
without retry; sheds/timeouts/failures are counted, latency is recorded
for admitted txns only (the admitted-p99 the graceful-overload assertion
bounds).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from .admission import Overloaded
from .client import ClusterClient, TxnFailed

TOKEN_SPACE = 1 << 32


def free_ports(n: int) -> List[int]:
    """n distinct ephemeral ports (bind-then-release; the tiny reuse race
    is acceptable for a test harness)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class ServeCluster:
    """N ``accord_tpu.net.server`` OS processes on loopback ports."""

    def __init__(self, n_nodes: int = 3, stores: int = 2,
                 admit_max: int = 64, target_p99_ms: int = 1000,
                 request_timeout_ms: Optional[int] = 4000,
                 durability: bool = False,
                 net_faults: Optional[str] = None,
                 log_dir: Optional[str] = None,
                 extra_args: Optional[List[str]] = None,
                 journal_root: Optional[str] = None,
                 wire_codec: str = "binary",
                 hosts: Optional[List[str]] = None,
                 pin_cpus: Optional[List[int]] = None):
        self.names = [f"n{i}" for i in range(1, n_nodes + 1)]
        ports = free_ports(n_nodes)
        # multi-box spread (r20, ROADMAP item 4): ``hosts`` assigns listen
        # addresses round-robin across the given host IPs (they must be
        # locally-bindable interfaces — the harness spawns local
        # processes; loopback is the default single-box topology) and
        # ``pin_cpus`` pins node i to cpu pin_cpus[i % len] via taskset —
        # the honest separate-core equivalent of separate boxes on one
        # machine.  Both are recorded in ``topology()`` so bench rows
        # carry the spread in-row.
        self.hosts = list(hosts) if hosts else ["127.0.0.1"]
        self.pin_cpus = list(pin_cpus) if pin_cpus else None
        self.addrs: List[Tuple[str, str, int]] = [
            (name, self.hosts[i % len(self.hosts)], port)
            for i, (name, port) in enumerate(zip(self.names, ports))]
        # epoch-1 membership is frozen at construction: nodes added later
        # (add_node) spawn with --members = this list so every node's
        # epoch-1 topology byte-matches; membership then changes only
        # through proposed epochs (the elastic serving path)
        self.initial_members = list(self.names)
        self.stores = stores
        self.admit_max = admit_max
        self.target_p99_ms = target_p99_ms
        self.request_timeout_ms = request_timeout_ms
        self.durability = durability
        self.net_faults = net_faults
        self.wire_codec = wire_codec
        self.extra_args = extra_args or []
        # per-node durable journal dirs (<root>/<name>): a kill -9'd node
        # respawned with the same name recovers its pre-crash state
        self.journal_root = journal_root
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="accord_serve_")
        self.procs: Dict[str, subprocess.Popen] = {}
        self._logs: Dict[str, object] = {}

    def _peers_arg(self) -> str:
        return ",".join(f"{n}={h}:{p}" for n, h, p in self.addrs)

    def _pin_for(self, name: str) -> Optional[int]:
        """The cpu this node pins to (taskset), or None (unpinned)."""
        if not self.pin_cpus:
            return None
        import shutil
        if shutil.which("taskset") is None:
            return None
        try:
            idx = self.names.index(name)
        except ValueError:
            return None
        return self.pin_cpus[idx % len(self.pin_cpus)]

    def topology(self) -> dict:
        """The in-row spread record (ROADMAP item 4): which hosts the
        cluster spans, the box's core count, and any per-node cpu
        pinning — so a bench row is honest about whether its numbers
        came from N processes time-sharing one core or truly separate
        cores/boxes."""
        pinning = {n: self._pin_for(n) for n in self.names}
        return {
            "hosts": sorted({h for _n, h, _p in self.addrs}),
            "host_cpus": os.cpu_count(),
            "pinning": (pinning if any(v is not None
                                       for v in pinning.values()) else None),
        }

    def spawn(self, name: str,
              env_extra: Optional[Dict[str, str]] = None
              ) -> subprocess.Popen:
        """(Re)start one node process (used for initial spawn AND the
        kill-9 rejoin leg — same name, same port, fresh state).
        ``env_extra`` arms per-node knobs (e.g. the deterministic
        mid-propose crash point)."""
        _, host, port = next(a for a in self.addrs if a[0] == name)
        env = dict(os.environ)
        if env_extra:
            env.update(env_extra)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_ENABLE_X64"] = "true"
        env.setdefault("ACCORD_TPU_DEVICE", "0")   # host route: fast start
        if self.net_faults:
            env["ACCORD_TPU_NET_FAULTS"] = self.net_faults
        cmd = []
        cpu = self._pin_for(name)
        if cpu is not None:
            cmd += ["taskset", "-c", str(cpu)]
        cmd += [sys.executable, "-m", "accord_tpu.net.server",
               "--name", name, "--listen", f"{host}:{port}",
               "--peers", self._peers_arg(),
               "--members", ",".join(self.initial_members),
               "--stores", str(self.stores),
               "--admit-max", str(self.admit_max),
               "--target-p99-ms", str(self.target_p99_ms),
               "--wire-codec", self.wire_codec]
        if self.request_timeout_ms is not None:
            cmd += ["--request-timeout-ms", str(self.request_timeout_ms)]
        if not self.durability:
            cmd.append("--no-durability")
        if self.journal_root:
            cmd += ["--journal-dir",
                    os.path.join(self.journal_root, name)]
        cmd += self.extra_args
        log = open(os.path.join(self.log_dir, f"{name}.log"), "ab")
        self._logs[name] = log
        proc = subprocess.Popen(cmd, stdout=log, stderr=log,
                                cwd=os.path.dirname(os.path.dirname(
                                    os.path.dirname(
                                        os.path.abspath(__file__)))),
                                env=env)
        self.procs[name] = proc
        return proc

    def spawn_all(self) -> None:
        for name in self.names:
            self.spawn(name)

    def alive(self) -> Dict[str, bool]:
        return {n: (p.poll() is None) for n, p in self.procs.items()}

    # -- dynamic membership (r17, elastic serving) ----------------------------
    def add_node(self, name: Optional[str] = None) -> str:
        """Spawn one EXTRA node as a non-member observer (--members = the
        frozen epoch-1 list): it dials the cluster and waits for the
        epoch that admits it (client.reconfigure(op="add")).  Mutates
        ``addrs`` in place so clients sharing the list see the new
        node."""
        if name is None:
            taken = {int(n[1:]) for n in self.names if n[1:].isdigit()}
            name = f"n{max(taken) + 1 if taken else 1}"
        port = free_ports(1)[0]
        self.names.append(name)
        self.addrs.append((name, "127.0.0.1", port))
        self.spawn(name)
        return name

    def node_addr(self, name: str) -> Tuple[str, int]:
        _, host, port = next(a for a in self.addrs if a[0] == name)
        return host, port

    def remove_node(self, name: str, kill: bool = True) -> None:
        """Forget one node (after the epoch removing it settled): the
        process is terminated (the operator's final step of a drain) and
        the addr book entry removed in place."""
        proc = self.procs.pop(name, None)
        if proc is not None and kill and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        self.names = [n for n in self.names if n != name]
        self.addrs[:] = [a for a in self.addrs if a[0] != name]

    def kill9(self, name: str) -> None:
        self.procs[name].send_signal(signal.SIGKILL)
        self.procs[name].wait(timeout=10)

    def shutdown(self) -> None:
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.terminate()
        deadline = time.time() + 10
        for proc in self.procs.values():
            try:
                proc.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                proc.kill()
        for log in self._logs.values():
            try:
                log.close()
            except Exception:
                pass

    def node_log(self, name: str) -> str:
        path = os.path.join(self.log_dir, f"{name}.log")
        try:
            with open(path, "r", errors="replace") as f:
                return f.read()
        except OSError:
            return ""


async def wait_ready(cluster: ServeCluster, client: ClusterClient,
                     timeout: float = 60.0) -> None:
    """Connect + ping every node (retrying: process startup pays the jax
    import).  Raises on deadline with each node's log tail."""
    deadline = time.time() + timeout
    for name, host, port in cluster.addrs:
        fresh = False
        while True:
            try:
                if name not in client.conns or not fresh:
                    # always re-dial once per node: after a kill/restart
                    # the client may hold a stale conn to the old process
                    await client.reconnect(name)
                    fresh = True
                await client.ping(name, timeout=2.0)
                break
            except Exception:
                fresh = False
                if time.time() > deadline:
                    tails = {n: cluster.node_log(n)[-800:]
                             for n in cluster.names}
                    raise TimeoutError(
                        f"cluster not ready within {timeout}s: {tails}")
                if cluster.procs.get(name) is not None \
                        and cluster.procs[name].poll() is not None:
                    raise RuntimeError(
                        f"node {name} exited rc={cluster.procs[name].poll()}"
                        f": {cluster.node_log(name)[-800:]}")
                await asyncio.sleep(0.25)


def percentile(sorted_xs: List[float], q: float) -> Optional[float]:
    if not sorted_xs:
        return None
    return sorted_xs[min(len(sorted_xs) - 1, int(len(sorted_xs) * q))]


def _r2(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 2)


class LoadPointResult:
    """One offered-load point's census."""

    def __init__(self, offered: float, duration: float):
        self.offered = offered
        self.duration = duration
        self.sent = 0
        self.ok = 0
        self.shed = 0
        self.failed = 0
        self.timeout = 0
        self.latencies_ms: List[float] = []

    @property
    def goodput(self) -> float:
        return self.ok / self.duration if self.duration else 0.0

    @property
    def shed_rate(self) -> float:
        return self.shed / self.sent if self.sent else 0.0

    def latency_ms(self, q: float) -> Optional[float]:
        return percentile(sorted(self.latencies_ms), q)

    def row(self) -> dict:
        lat = sorted(self.latencies_ms)
        return {
            "offered_txns_per_sec": round(self.offered, 1),
            "duration_s": round(self.duration, 1),
            "sent": self.sent, "ok": self.ok, "shed": self.shed,
            "failed": self.failed, "timeout": self.timeout,
            "goodput_txns_per_sec": round(self.goodput, 1),
            "shed_rate": round(self.shed_rate, 4),
            "p50_ms": _r2(percentile(lat, 0.50)),
            "p99_ms": _r2(percentile(lat, 0.99)),
            "p999_ms": _r2(percentile(lat, 0.999)),
        }


def _mk_ops(rng: random.Random, counter: List[int], n_keys: int) -> list:
    """1-2 key list-append ops, keys strided across the whole token ring
    (multi-shard by construction, same policy as the sim runner)."""
    stride = TOKEN_SPACE // n_keys
    ops = []
    for _ in range(rng.randint(1, 2)):
        key = rng.randrange(n_keys) * stride
        if rng.random() < 0.6:
            counter[0] += 1
            ops.append(["append", key, counter[0]])
        else:
            ops.append(["r", key, None])
    return ops


async def open_loop(client: ClusterClient, rate: float, duration: float,
                    seed: int = 0, n_keys: int = 64,
                    txn_timeout: float = 8.0) -> LoadPointResult:
    """Open-loop Poisson load at ``rate`` txn/s for ``duration`` seconds.
    Arrivals never wait for completions; every arrival is submitted once
    (no retry — the shed/timeout census IS the measurement).  A latency
    runs from the instant the request was DUE, not from when its task got
    the loop: a generator that runs late charges its lateness to the
    request, as a client would feel it."""
    rng = random.Random(seed)
    counter = [0]
    res = LoadPointResult(rate, duration)
    tasks: List[asyncio.Task] = []
    loop = asyncio.get_event_loop()
    t0 = loop.time()
    t_next = t0

    async def one(ops, due):
        res.sent += 1
        try:
            await client.submit(ops, timeout=txn_timeout)
            res.ok += 1
            res.latencies_ms.append((loop.time() - due) * 1e3)
        except Overloaded:
            res.shed += 1
        except asyncio.TimeoutError:
            res.timeout += 1
        except (TxnFailed, ConnectionError):
            res.failed += 1

    while True:
        t_next += rng.expovariate(rate)
        now = loop.time()
        if t_next - t0 > duration:
            break
        if t_next > now:
            await asyncio.sleep(t_next - now)
        tasks.append(loop.create_task(one(_mk_ops(rng, counter, n_keys),
                                          t_next)))
    if tasks:
        await asyncio.wait(tasks, timeout=txn_timeout + 5.0)
    for t in tasks:
        if not t.done():
            t.cancel()
    # measure over the actual window the arrivals spanned
    res.duration = max(duration, 1e-9)
    return res


async def saturation_probe(client: ClusterClient, workers: int = 16,
                           duration: float = 4.0, seed: int = 42,
                           n_keys: int = 64) -> dict:
    """Closed-loop saturation measurement: ``workers`` back-to-back
    submitters for ``duration`` seconds.  Closed loop saturates BY
    CONSTRUCTION whatever speed the box happens to run at (workers simply
    complete slower), so both readouts are true at-saturation values: the
    rate anchors the open-loop sweep's 0.5x/1x/3x offered points, and the
    admitted-txn latency percentiles anchor the graceful-overload p99
    bound on a box whose speed oscillates between sweep points."""
    rng = random.Random(seed)
    counter = [0]
    done = [0]
    lat_ms: List[float] = []
    loop = asyncio.get_event_loop()
    stop_at = loop.time() + duration

    async def worker(wseed: int):
        wrng = random.Random(wseed)
        backoff = random.Random(wseed ^ 0x5EED)
        while loop.time() < stop_at:
            ops = _mk_ops(wrng, counter, n_keys)
            # per-ATTEMPT timing: a shed's retry-backoff sleep must not
            # land in the latency census — the percentile here anchors
            # the graceful-overload bound, so it must be ADMITTED-txn
            # commit latency, commensurable with the open-loop points'
            # bare submit() measurement
            t0 = loop.time()
            try:
                await client.submit(ops, timeout=6.0)
                done[0] += 1
                lat_ms.append((loop.time() - t0) * 1e3)
            except Overloaded as exc:
                await asyncio.sleep(
                    (exc.retry_after_ms + backoff.randrange(25)) / 1e3)
            except (TxnFailed, asyncio.TimeoutError, ConnectionError):
                pass

    await asyncio.gather(*(worker(seed + i) for i in range(workers)))
    lat = sorted(lat_ms)
    return {"rate": done[0] / duration,
            "p50_ms": _r2(percentile(lat, 0.50)),
            "p99_ms": _r2(percentile(lat, 0.99))}


async def cluster_net_stats(client: ClusterClient,
                            names: List[str]) -> dict:
    """Aggregate serving stats across nodes: reconnect counters, sheds,
    admission state — the bench-row columns."""
    agg = {"reconnects": 0, "dial_failures": 0, "dropped_frames": 0,
           "shed_total": 0, "admitted": 0,
           # the r16 serving counters (cluster totals; the bench rows and
           # the # index: line quote these)
           "wire_bytes_tx": 0, "wire_bytes_rx": 0, "frames_coalesced": 0,
           "batched_fanouts": 0, "batched_ops": 0, "fast_sheds": 0,
           "batch_occupancy_p50": 0,
           # the r20 store-grouped execution counters
           "grouped_ops": 0, "group_fallbacks": 0,
           "store_group_occupancy_p50": 0, "per_node": {}}
    occupancy = []
    group_occupancy = []
    for name in names:
        try:
            s = await client.stats(name)
        except Exception:
            agg["per_node"][name] = None
            continue
        agg["per_node"][name] = s
        for link in (s.get("links") or {}).values():
            agg["reconnects"] += link.get("reconnects", 0)
            agg["dial_failures"] += link.get("dial_failures", 0)
            agg["dropped_frames"] += link.get("dropped", 0)
        adm = s.get("admission") or {}
        agg["shed_total"] += adm.get("shed_total", 0)
        agg["admitted"] += adm.get("admitted", 0)
        agg["wire_bytes_tx"] += s.get("wire_bytes_tx", 0)
        agg["wire_bytes_rx"] += s.get("wire_bytes_rx", 0)
        agg["frames_coalesced"] += s.get("frames_coalesced", 0)
        b = s.get("batching") or {}
        agg["batched_fanouts"] += b.get("batched_fanouts", 0)
        agg["batched_ops"] += b.get("batched_ops", 0)
        agg["fast_sheds"] += b.get("fast_sheds", 0)
        agg["grouped_ops"] += b.get("grouped_ops", 0)
        agg["group_fallbacks"] += b.get("group_fallbacks", 0)
        if b.get("batch_occupancy_p50"):
            occupancy.append(b["batch_occupancy_p50"])
        if b.get("store_group_occupancy_p50"):
            group_occupancy.append(b["store_group_occupancy_p50"])
    if occupancy:
        agg["batch_occupancy_p50"] = sorted(occupancy)[len(occupancy) // 2]
    if group_occupancy:
        agg["store_group_occupancy_p50"] = \
            sorted(group_occupancy)[len(group_occupancy) // 2]
    return agg


# ---------------------------------------------------------------------------
# elastic serving helpers (r17): epoch convergence + the reconfig smoke
# ---------------------------------------------------------------------------

async def await_epoch(client: ClusterClient, names: List[str], epoch: int,
                      timeout: float = 60.0,
                      settled: bool = True) -> Dict[str, dict]:
    """Poll until every named node reports ``epoch_current >= epoch``
    (and, with ``settled``, the epoch synced + no bootstrap in flight).
    Returns the final per-node reconfig stats blocks; raises on
    deadline with the stragglers' state."""
    deadline = time.time() + timeout
    last: Dict[str, dict] = {}
    while True:
        pending = []
        for name in names:
            try:
                s = await client.stats(name, timeout=3.0)
            except Exception as exc:
                pending.append((name, repr(exc)))
                continue
            rc = s.get("reconfig") or {}
            last[name] = rc
            if rc.get("epoch_current", 0) < epoch:
                pending.append((name, f"epoch={rc.get('epoch_current')}"))
            elif settled and rc.get("epoch_current", 0) == epoch \
                    and not rc.get("epoch_synced"):
                pending.append((name, "unsynced"))
            elif settled and rc.get("bootstrapping_now"):
                pending.append((name, "bootstrapping"))
        if not pending:
            return last
        if time.time() > deadline:
            raise TimeoutError(
                f"epoch {epoch} never settled within {timeout}s: {pending}")
        await asyncio.sleep(0.25)


async def propose_with_retry(client: ClusterClient, via: str, op: str,
                             timeout: float = 30.0, **fields) -> dict:
    """Propose, retrying the verb's transient rejections (the
    no-stacking guard requires EVERY member's ack for the current epoch
    and no local rebalance — both settle within seconds)."""
    deadline = time.time() + timeout
    while True:
        rep = await client.reconfigure(via, op, **fields)
        if rep.get("type") == "reconfigure_ok":
            return rep
        text = rep.get("text", "")
        if rep.get("code") == 11 and ("syncing" in text
                                      or "rebalance" in text) \
                and time.time() < deadline:
            await asyncio.sleep(0.5)
            continue
        return rep


async def _reconfig_scenario(cluster: ServeCluster, n_txns: int,
                             kill_joiner: bool, kill_proposer: bool,
                             note) -> dict:
    client = ClusterClient(cluster.addrs, timeout=8.0,
                           codec=cluster.wire_codec)
    rng = random.Random(11)
    counter = [0]
    ok = [0]
    try:
        await wait_ready(cluster, client)

        async def burst(n, nodes):
            for i in range(n):
                await client.submit_retry(_mk_ops(rng, counter, 32),
                                          retries=16, timeout=6.0,
                                          node=nodes[i % len(nodes)])
                ok[0] += 1

        base = list(cluster.names)
        await burst(n_txns, base)
        # -- join: spawn the observer, propose the admitting epoch ------
        joiner = cluster.add_node()
        jhost, jport = cluster.node_addr(joiner)
        # cluster.addrs is shared with the client (mutated in place), so
        # wait_ready dials the joiner with startup retries included
        await wait_ready(cluster, client)
        if kill_proposer:
            # TRUE mid-propose crash: re-arm the proposer with the
            # deterministic crash point (ACCORD_TPU_RECONFIG_CRASH) — it
            # journals epoch N+1 durable and _exits BEFORE ingesting or
            # broadcasting it, so it dies holding an epoch NO peer has
            # ever seen.  Recovery must re-ingest the journaled doc and
            # the hello-epoch gossip must propagate it cluster-wide, or
            # the epoch is lost — the exact window the
            # durable-before-broadcast write exists for.
            note(f"arming mid-propose crash on {base[0]}")
            cluster.kill9(base[0])
            cluster.spawn(base[0], env_extra={
                "ACCORD_TPU_RECONFIG_CRASH": "after-flush"})
            await wait_ready(cluster, client)
            epoch = 2
            crashed = False
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    rep = await client.reconfigure(base[0], "add",
                                                   node=joiner,
                                                   addr=f"{jhost}:{jport}",
                                                   timeout=8.0)
                except (ConnectionError, asyncio.TimeoutError):
                    crashed = True   # died before replying: the armed
                    break            # crash point fired after the flush
                if rep.get("type") == "reconfigure_ok":
                    raise AssertionError("proposer survived the armed "
                                         "mid-propose crash")
                # transient no-stacking rejection (acks still arriving
                # at the freshly-respawned proposer): retry
                await asyncio.sleep(0.5)
            assert crashed, "armed mid-propose crash never fired"
            note(f"proposer {base[0]} died mid-propose holding "
                 f"journaled epoch {epoch}; respawning clean")
            deadline = time.time() + 10
            while cluster.procs[base[0]].poll() is None \
                    and time.time() < deadline:
                await asyncio.sleep(0.1)
            assert cluster.procs[base[0]].poll() is not None, \
                "armed proposer never exited"
            cluster.spawn(base[0])
            await wait_ready(cluster, client)
        else:
            rep = await propose_with_retry(client, base[0], "add",
                                           node=joiner,
                                           addr=f"{jhost}:{jport}")
            assert rep.get("type") == "reconfigure_ok", rep
            epoch = rep["epoch"]
        if kill_joiner:
            # kill -9 the JOINING node mid-bootstrap: its fence/snapshot
            # fetch dies with it; the respawned incarnation recovers its
            # epoch ledger (journal) or refetches it (hello-epoch gossip)
            # and re-runs the bootstrap to completion
            note(f"kill -9 joiner {joiner} mid-bootstrap")
            cluster.kill9(joiner)
            await burst(max(4, n_txns // 4), base)   # survivors serve on
            cluster.spawn(joiner)
            await wait_ready(cluster, client)
        await await_epoch(client, cluster.names, epoch, timeout=90.0)
        await burst(n_txns, cluster.names)
        # -- leave: retire one original member ---------------------------
        leaver = base[-1]
        via = base[0]
        rep = await propose_with_retry(client, via, "remove", node=leaver)
        assert rep.get("type") == "reconfigure_ok", rep
        survivors = [n for n in cluster.names if n != leaver]
        await await_epoch(client, survivors, rep["epoch"], timeout=90.0)
        # stop routing to the leaver, then terminate it (operator drain)
        await client.remove_node(leaver)
        cluster.remove_node(leaver)
        await burst(n_txns, survivors)
        # epoch lifecycle TAIL: the oldest epoch retires once the whole
        # prefix is sync-complete cluster-wide (the ack re-gossip's
        # grace window + duplicate-ack replies close any straggler)
        deadline = time.time() + 25.0
        while time.time() < deadline:
            stats = await cluster_net_stats(client, survivors)
            retired = [((stats["per_node"].get(n) or {})
                        .get("reconfig") or {}).get("epochs_retired", 0)
                       for n in survivors]
            if all(r >= 1 for r in retired):
                break
            await asyncio.sleep(0.5)
        stats = await cluster_net_stats(client, survivors)
        recon = {n: (stats["per_node"].get(n) or {}).get("reconfig")
                 for n in survivors}
        return {"ok": ok[0], "expected": ok[0],
                "duplicate_replies": client.duplicate_replies(),
                "alive": cluster.alive(), "joiner": joiner,
                "left": leaver, "reconfig": recon, "net": stats}
    finally:
        await client.close()


def run_reconfig_smoke(n_txns: int = 12, kill_joiner: bool = False,
                       kill_proposer: bool = False,
                       out_dir: Optional[str] = None,
                       wire_codec: str = "binary") -> dict:
    """The fault-matrix reconfig leg: a 3-node journaled cluster runs a
    join AND a leave under load — optionally killing -9 the joining node
    mid-bootstrap or the epoch proposer mid-propose — and must converge
    into one consistent epoch with every client op succeeding and zero
    duplicate replies."""
    def note(msg):
        print(f"  [reconfig-smoke] {msg}", flush=True)

    cluster = ServeCluster(n_nodes=3, request_timeout_ms=1000,
                           journal_root=tempfile.mkdtemp(
                               prefix="accord_reconf_jr_"),
                           wire_codec=wire_codec)
    cluster.spawn_all()
    try:
        result = asyncio.run(_reconfig_scenario(
            cluster, n_txns, kill_joiner, kill_proposer, note))
        problems = []
        if result["duplicate_replies"]:
            problems.append(
                f"{result['duplicate_replies']} duplicate client replies")
        if not all(result["alive"].values()):
            problems.append(f"dead nodes: {result['alive']}")
        epochs = {n: (rc or {}).get("epoch_current")
                  for n, rc in result["reconfig"].items()}
        if len(set(epochs.values())) != 1:
            problems.append(f"divergent final epochs: {epochs}")
        if problems:
            tag = ("reconfig"
                   + ("_killjoiner" if kill_joiner else "")
                   + ("_killproposer" if kill_proposer else ""))
            path = None
            if out_dir:
                path = asyncio.run(_dump_postmortems(cluster, out_dir, tag))
            raise AssertionError(
                f"reconfig smoke failed ({'; '.join(problems)})"
                + (f" [post-mortem: {path}]" if path else ""))
        return result
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# the 2-process smoke (tier-1 + the fault-matrix socket legs)
# ---------------------------------------------------------------------------

async def _smoke_async(cluster: ServeCluster, n_txns: int,
                       concurrency: int = 8) -> dict:
    client = ClusterClient(cluster.addrs, timeout=8.0,
                           codec=cluster.wire_codec)
    try:
        await wait_ready(cluster, client)
        rng = random.Random(7)
        counter = [0]
        sem = asyncio.Semaphore(concurrency)
        ok = [0]
        errors: List[str] = []

        async def one():
            async with sem:
                # NEVER raise out of the gather: a failed txn must reach
                # the caller's census so the post-mortem dump runs — the
                # forensic bundle is the whole point of the fault legs
                try:
                    await client.submit_retry(_mk_ops(rng, counter, 32),
                                              retries=16, timeout=6.0)
                    ok[0] += 1
                except Exception as exc:
                    errors.append(repr(exc))

        await asyncio.gather(*(one() for _ in range(n_txns)))
        stats = await cluster_net_stats(client, cluster.names)
        return {"ok": ok[0], "n_txns": n_txns, "errors": errors[:8],
                "duplicate_replies": client.duplicate_replies(),
                "alive": cluster.alive(), "net": stats}
    finally:
        await client.close()


async def _dump_postmortems(cluster: ServeCluster, out_dir: str,
                            tag: str) -> Optional[str]:
    """Fetch every reachable node's flight/metrics dump + harness-side
    stats into one forensic bundle under ``out_dir``."""
    client = ClusterClient(cluster.addrs, timeout=5.0, src="c-dump")
    bundle = {"tag": tag, "alive": cluster.alive(), "nodes": {}}
    for name, host, port in cluster.addrs:
        try:
            await client.reconnect(name)
            bundle["nodes"][name] = {
                "dump": await client.dump(name),
                "stats": await client.stats(name),
            }
        except Exception as exc:
            bundle["nodes"][name] = {"unreachable": repr(exc),
                                     "log_tail": cluster.node_log(name)[-2000:]}
    await client.close()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"net_smoke_{tag}.json")
    with open(path, "w") as f:
        json.dump(bundle, f, sort_keys=True, indent=1)
    return path


def run_smoke(n_txns: int = 100, n_nodes: int = 2,
              net_faults: Optional[str] = None,
              out_dir: Optional[str] = None,
              admit_max: int = 32,
              wire_codec: str = "binary") -> dict:
    """Spawn an ``n_nodes`` cluster, run ``n_txns`` client txns (bounded
    concurrency, retry-with-backoff), assert full success and cluster
    liveness.  On failure under a fault leg, dumps flight post-mortems to
    ``out_dir`` before raising."""
    # tight inter-node timeout: under injected socket faults the sink's
    # timeout owns recovery, and a lost frame must cost ~1s, not the
    # Maelstrom adapter's cold-compile-sized 20s
    cluster = ServeCluster(n_nodes=n_nodes, net_faults=net_faults,
                           admit_max=admit_max,
                           request_timeout_ms=800,
                           wire_codec=wire_codec)
    cluster.spawn_all()
    try:
        result = asyncio.run(_smoke_async(cluster, n_txns))
        problems = []
        if result["ok"] != n_txns:
            problems.append(f"{n_txns - result['ok']} txns never succeeded "
                            f"(first errors: {result['errors']})")
        if result["duplicate_replies"]:
            problems.append(
                f"{result['duplicate_replies']} duplicate client replies")
        if not all(result["alive"].values()):
            problems.append(f"dead nodes: {result['alive']}")
        if problems:
            tag = (net_faults or "clean").replace(":", "_").replace(",", "+")
            path = None
            if out_dir:
                path = asyncio.run(_dump_postmortems(cluster, out_dir, tag))
            raise AssertionError(
                f"serving smoke failed ({'; '.join(problems)})"
                + (f" [post-mortem: {path}]" if path else ""))
        return result
    finally:
        cluster.shutdown()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="serving-cluster smoke harness (fault-matrix legs)")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--reconfig-smoke", action="store_true",
                   help="elastic-serving leg: join + leave under load on "
                        "a journaled 3-node cluster")
    p.add_argument("--kill-joiner", action="store_true",
                   help="(reconfig) kill -9 the joining node mid-bootstrap")
    p.add_argument("--kill-proposer", action="store_true",
                   help="(reconfig) kill -9 the epoch proposer mid-propose")
    p.add_argument("--txns", type=int, default=100)
    p.add_argument("--nodes", type=int, default=2)
    p.add_argument("--net-faults", default=None,
                   help="kind:prob:seed[,...] armed in every node process")
    p.add_argument("--wire-codec", choices=("json", "binary"),
                   default="binary",
                   help="cluster + client wire codec for this smoke (the "
                        "fault-matrix net leg sweeps both)")
    p.add_argument("--out", default=os.environ.get("FAULT_MATRIX_OUT",
                                                   "/tmp"))
    args = p.parse_args(argv)
    if args.reconfig_smoke:
        t0 = time.time()
        result = run_reconfig_smoke(n_txns=max(8, args.txns // 8),
                                    kill_joiner=args.kill_joiner,
                                    kill_proposer=args.kill_proposer,
                                    out_dir=args.out,
                                    wire_codec=args.wire_codec)
        epochs = {n: (rc or {}).get("epoch_current")
                  for n, rc in result["reconfig"].items()}
        print(f"reconfig smoke ok: {result['ok']} txns, joined "
              f"{result['joiner']}, removed {result['left']}, epochs "
              f"{epochs} kill_joiner={args.kill_joiner} "
              f"kill_proposer={args.kill_proposer} "
              f"dup_replies={result['duplicate_replies']} in "
              f"{time.time() - t0:.1f}s")
        return 0
    if not args.smoke:
        p.error("--smoke or --reconfig-smoke required")
    t0 = time.time()
    result = run_smoke(n_txns=args.txns, n_nodes=args.nodes,
                       net_faults=args.net_faults, out_dir=args.out,
                       wire_codec=args.wire_codec)
    net = result["net"]
    print(f"smoke ok: {result['ok']}/{result['n_txns']} txns in "
          f"{time.time() - t0:.1f}s faults={args.net_faults or 'none'} "
          f"codec={args.wire_codec} "
          f"reconnects={net['reconnects']} sheds={net['shed_total']} "
          f"dup_replies={result['duplicate_replies']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
