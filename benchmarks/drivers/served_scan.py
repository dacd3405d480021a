"""Driver ``served_scan``: drivers/served.py's deployment and window under
YCSB core workload E: range scans and inserts over loaded records.

The data.  Record number n has the key ``_token(n)``, a seeded hash into the
token ring (YCSB's hashed insert order: neighbours in record order are
scattered in key order, and an insert lands anywhere, inside ranges being
scanned), and ONE datum: 1,000 ASCII characters, ten 100-character fields
concatenated, made from ``population_seed`` and n, so the check regenerates
what it expects.  ``records`` of them are loaded before traffic through
``KVDataStore.install_snapshot`` on every replica (the route a bootstrapping
replica's snapshot takes), each replica its own copy, at a timestamp below
every txn.

The traffic.  Each closed-loop client submits one op a txn: with probability
``scan_share`` a scan, else an insert.  An insert appends the NEXT record
number's datum to its key, which holds nothing.  A scan starts at the key of
a record drawn as YCSB draws it: a rank ~ Zipf(``zipf``) over ``records`` +
``expected_new_records`` items, scrambled through a permutation that
``population_seed`` fixes, drawn again until it names a record that is
loaded or whose insert was acknowledged; its length L is uniform on 1 ..
``max_scan_length``; it is sent as the bounded token range ``[s, e)``, e the
key after the L-th key at or above s in the sorted list of every key loaded
or issued so far.

The check holds the run to served_txn.py's gates with scans expanded (a scan
is a read of EVERY known key in its range; one it did not return was read as
empty), to the serial replay of lib/serial_scan_kv.py, and reads every
record back, byte for byte, by scans that cover the whole token space
through the client path.

The record keeps ``"driver": "served"``: what the ``.serve`` metrics read is
all there, under the same names."""

import asyncio
import base64
import bisect
import gc
import hashlib
import itertools
import os
import random
import time

from ..lib import checks, serial_scan_kv
from ..lib.tracer import NoTracer
from . import served, served_txn
from .served import TOKEN_SPACE, _now_us

# the nodes' counters this PR's program added: a program without them (the
# parent) leaves the keys out and their readers find nothing
_COORDINATION = ("range_txns", "key_txns", "scan_rows")
_DATA = ("scan_calls", "scan_host_s")
_DEVICE = ("n_range_queries", "n_range_device_queries")


class Driver(served_txn.Driver):

    def __init__(self, config, traffic, seed, scratch_dir):
        # served.Driver lays sizes["keys"] keys over the ring for its own
        # traffic; here the keys are the records', so the configuration
        # carries none
        config = dict(config, sizes=dict(config["sizes"], keys=1))
        super().__init__(config, traffic, seed, scratch_dir)
        self.n_loaded = int(self.sz["records"])
        self.record_bytes = int(self.sz["record_bytes"])
        self.tokens = []         # record number -> key
        self.record_of = {}      # key -> record number
        self.known = []          # every key loaded or issued, ascending
        self.acked_inserts = set()       # record numbers, acknowledged
        self.dropped_row = None  # the test hook's injected phantom

    # -- the data -------------------------------------------------------
    def _token(self, n):
        """The key of record ``n``: records are numbered as they are
        loaded and inserted, and keyed by a seeded hash (distinct: a taken
        token is hashed again)."""
        while len(self.tokens) <= n:
            at, salt = len(self.tokens), 0
            while True:
                digest = hashlib.blake2b(
                    f"{self.population_seed}/{at}/{salt}".encode(),
                    digest_size=8).digest()
                token = int.from_bytes(digest, "big") % TOKEN_SPACE
                if token not in self.record_of:
                    break
                salt += 1
            self.record_of[token] = at
            self.tokens.append(token)
        return self.tokens[n]

    def _datum(self, n):
        """Record ``n``'s value: ten fields of 100 characters."""
        raw = random.Random(f"{self.population_seed}/{n}").randbytes(
            self.record_bytes * 3 // 4)
        return base64.b64encode(raw).decode("ascii")

    async def _start(self):
        await served.Driver._start(self)
        from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
        t0 = time.perf_counter()
        load_id = TxnId.create(1, 1, TxnKind.Write, Domain.Key, 1)
        loaded = [self._token(n) for n in range(self.n_loaded)]
        self.known = sorted(loaded)
        op_id, start = self.verifier.begin(), _now_us()
        for s in self.servers:       # each replica its own copy
            s.proc.node.data_store.install_snapshot(
                {token: [((self._datum(n),), load_id, load_id)]
                 for n, token in enumerate(loaded)})
        # the initial state, for the reference and the verifier: one txn
        # answered before every other began
        self.initial = {token: (self._datum(n),)
                        for n, token in enumerate(loaded)}
        self.verifier.on_result(op_id, start, _now_us(), {}, self.initial)
        items = self.n_loaded + int(self.traffic["expected_new_records"])
        self.scrambled = list(range(items))          # rank -> record number
        random.Random(self.population_seed).shuffle(self.scrambled)
        skew = float(self.traffic["zipf"])
        self.zipf_cdf = list(itertools.accumulate(
            (rank + 1) ** -skew for rank in range(items)))
        self.next_record = self.n_loaded
        # the load is start-up state, as what NodeServer.start() froze
        # before it: out of the collector's walks (close() unfreezes)
        gc.collect()
        gc.freeze()
        self.load_s = time.perf_counter() - t0

    def setup(self):
        super().setup()
        self.info["setup"].update(load_s=self.load_s, records=self.n_loaded,
                                  record_bytes=self.record_bytes)

    def _draw_scan(self, rng):
        """``(lo, hi)``: from the key of a Zipf-drawn record that is
        there, over the next L known keys."""
        cdf = self.zipf_cdf
        while True:
            n = self.scrambled[bisect.bisect_left(cdf,
                                                  rng.random() * cdf[-1])]
            if n < self.n_loaded or n in self.acked_inserts:
                break
        lo = self._token(n)
        end = bisect.bisect_left(self.known, lo) + rng.randint(
            1, int(self.traffic["max_scan_length"]))
        return lo, self.known[end] if end < len(self.known) else TOKEN_SPACE

    # -- one op ---------------------------------------------------------
    async def _scan(self, lo, hi, node, tracer, sink=None):
        """One scan txn through the client path, recorded for the checks.
        Raises what ``submit`` raises."""
        op_id, start = self.verifier.begin(), _now_us()
        t0 = self.loop.time()
        with tracer.span("client.submit"):
            body = await self.client.submit([["scan", [lo, hi], None]],
                                            node=node)
        if sink is not None:
            sink.done.append((t0, self.loop.time()))
        end = _now_us()
        rows = [(k, tuple(v)) for k, v in body["txn"][0][2]]
        if self.traffic.get("test_drop_scan_row") and sink is not None \
                and self.dropped_row is None and rows:
            # the rehearsal's injected phantom: one record leaves one reply
            self.dropped_row = rows.pop(len(rows) // 2)
        seen = dict.fromkeys(
            self.known[bisect.bisect_left(self.known, lo):
                       bisect.bisect_left(self.known, hi)], ())
        seen.update(rows)
        self.verifier.on_result(op_id, start, end, seen, {})
        self.answered.append((start, end, {}, {}, [((lo, hi), rows)]))
        return rows

    async def _insert(self, node, tracer, sink):
        n = self.next_record
        self.next_record += 1
        token, datum = self._token(n), self._datum(n)
        bisect.insort(self.known, token)
        writes = {token: (datum,)}
        op_id, start = self.verifier.begin(), _now_us()
        t0 = self.loop.time()
        try:
            with tracer.span("client.submit"):
                await self.client.submit([["append", token, datum]],
                                         node=node)
        except BaseException:
            # indeterminate: it may still land, unacknowledged
            self.unanswered.append((start, writes))
            raise
        sink.done.append((t0, self.loop.time()))
        end = _now_us()
        self.verifier.on_result(op_id, start, end, {}, writes)
        self.answered.append((start, end, {}, writes))
        self.acked_inserts.add(n)

    # -- the closed loop ------------------------------------------------
    async def _client_loop(self, rng, go_on, sink, tracer):
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        scan_share = float(self.traffic["scan_share"])
        while go_on():
            node = self.names[rng.randrange(len(self.names))]
            try:
                if rng.random() < scan_share:
                    await self._scan(*self._draw_scan(rng), node, tracer,
                                     sink)
                else:
                    await self._insert(node, tracer, sink)
            except Overloaded as shed:
                sink.failed.append("Overloaded")
                await asyncio.sleep(shed.retry_after_ms / 1e3)
            except TxnFailed as e:
                if e.body.get("code") == 10:
                    # a program without the scan op: fail the run at the
                    # first scan, never loop on refusals
                    raise RuntimeError(f"the nodes refuse the op: "
                                       f"{e.body.get('text')}") from e
                sink.failed.append(repr(e)[:120])
            except (asyncio.TimeoutError, ConnectionError) as e:
                sink.failed.append(repr(e)[:120])

    # -- the warm-up ----------------------------------------------------
    async def _warm(self):
        """served_txn.py's warm-up.  This traffic's txns seldom wait on
        each other (an insert lands inside a range being scanned about once
        in two thousand), so the closed loop schedules few drain ticks; a
        store's first tick loads the tick's program on the serving loop,
        and the nodes run it at start (DeviceState.audit_route), so none
        is left for the window: ``stores_ticked`` says so."""
        await super()._warm()
        self.info["warm"]["stores_ticked"] = sum(
            1 for d in self.devs if d.n_ticks)

    # -- the window -----------------------------------------------------
    def _drift_sample(self):
        """What a quarter of the window is read from: txns answered, the
        data store's scan clock, three kinds of the stores' host clocks,
        and the process (collector pauses, resident memory)."""
        data = [s.proc.node.data_store for s in self.servers]
        kinds = checks.device_counters(self.devs)["kernel_times"]
        with open("/proc/self/statm") as f:
            resident = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        return {"t": self.loop.time(), "answered": len(self.answered),
                "scan": [sum(getattr(d, "scan_calls", 0) for d in data),
                         sum(getattr(d, "scan_host_s", 0.0) for d in data)],
                **{k: list(kinds.get(k, (0, 0.0)))
                   for k in ("dispatch_host", "range_index_sync",
                             "host_attr_filter")},
                "gc_s": self._gc_s[0], "gc_full": gc.get_stats()[2][
                    "collections"], "resident_mb": resident / 1e6}

    async def _window(self, seconds, tracer):
        """served.Driver's window, read in quarters besides: whether the
        rate or a per-call cost drifts INSIDE the window (with durability
        off nothing truncates, and the rate falls with history: PERF.md
        §6 has a 120 s window read this way)."""
        samples, self._gc_s, began = [], [0.0], [0.0]

        def on_gc(phase, _info):
            if phase == "start":
                began[0] = time.perf_counter()
            else:
                self._gc_s[0] += time.perf_counter() - began[0]

        async def sample():
            t0 = self.loop.time()
            for i in range(5):
                await asyncio.sleep(max(t0 + i * seconds / 4
                                        - self.loop.time(), 0.0))
                samples.append(self._drift_sample())

        gc.callbacks.append(on_gc)
        sampler = self.loop.create_task(sample())
        try:
            record = await super()._window(seconds, tracer)
            await sampler
        finally:
            gc.callbacks.remove(on_gc)

        def per_call(a, b, key):
            calls, secs = b[key][0] - a[key][0], b[key][1] - a[key][1]
            return secs * 1e6 / calls if calls else None

        self.info["window_quarters"] = [
            {"txn_per_s": (b["answered"] - a["answered"]) / (b["t"] - a["t"]),
             **{key + "_us": per_call(a, b, key)
                for key in ("scan", "dispatch_host", "range_index_sync",
                            "host_attr_filter")},
             "gc_s": b["gc_s"] - a["gc_s"],
             "gc_full": b["gc_full"] - a["gc_full"],
             "resident_mb": b["resident_mb"]}
            for a, b in zip(samples, samples[1:])]
        return record

    def _snapshot(self):
        snap = super()._snapshot()
        stats = [s.stats() for s in self.servers]
        coord = [st.get("coordination") for st in stats]
        if all(c and all(k in c for k in _COORDINATION) for c in coord):
            for key in _COORDINATION:
                snap["server"]["coordination_" + key] = sum(
                    c[key] for c in coord)
        data = [st.get("data") for st in stats]
        if all(data):
            for key in _DATA:
                snap["server"]["data_" + key] = sum(d[key] for d in data)
        if all(hasattr(d, k) for d in self.devs for k in _DEVICE):
            for key in _DEVICE:
                snap["device"][key] = sum(getattr(d, key) for d in self.devs)
        return snap

    # -- the check ------------------------------------------------------
    async def _read_back_scan(self, lo, hi, finals):
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        for attempt in range(1, 9):          # a scan is safe to repeat
            try:
                rows = await self._scan(lo, hi, None, NoTracer())
                break
            except (TxnFailed, Overloaded, asyncio.TimeoutError):
                if attempt == 8:
                    raise
                await asyncio.sleep(0.5 * attempt)
        finals.update(rows)

    async def _check(self):
        t0 = time.perf_counter()
        # scans that cover the whole token space, check_keys_per_scan
        # known keys each
        width = int(self.traffic["check_keys_per_scan"])
        cuts = [0] + self.known[width::width] + [TOKEN_SPACE]
        chunks = list(zip(cuts, cuts[1:]))
        n_scans = len(chunks)
        finals = {}

        async def reader():
            while chunks:
                await self._read_back_scan(*chunks.pop(), finals)

        await asyncio.gather(*[reader() for _ in range(
            int(self.traffic["check_in_flight"]))])
        read_back_s = time.perf_counter() - t0
        for token in self.known:
            self.verifier.set_final(token, finals.get(token, ()))
        # byte for byte: every record read back is the one the generator
        # makes for its key, once; every loaded and every acknowledged
        # record is there
        wrong = [k for k, v in finals.items()
                 if k not in self.record_of
                 or v != (self._datum(self.record_of[k]),)]
        if wrong:
            self.problems.append(f"records read back unlike the "
                                 f"generator's: keys {wrong[:5]}")
        lost = [n for n in itertools.chain(range(self.n_loaded),
                                           sorted(self.acked_inserts))
                if self.tokens[n] not in finals]
        if lost:
            self.problems.append(f"loaded or acknowledged records not read "
                                 f"back: numbers {lost[:5]}")
        landed = sum(1 for n in range(self.n_loaded, self.next_record)
                     if self.tokens[n] in finals)
        if len(finals) != self.n_loaded + landed:
            self.problems.append(f"read back {len(finals)} records, loaded "
                                 f"{self.n_loaded} and {landed} inserts "
                                 f"landed")
        t1 = time.perf_counter()
        try:
            self.verifier.verify()
        except AssertionError as e:
            self.problems.append(f"verifier: {str(e)[:300]}")
        t2 = time.perf_counter()
        try:
            serial_scan_kv.replay(self.answered, self.unanswered, finals,
                                  self.initial)
        except serial_scan_kv.NotSerial as e:
            self.problems.append(f"serial_scan_kv: {str(e)[:300]}")
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
        scans = [t[4][0] for t in self.answered if len(t) == 5]
        self.info["check"] = {
            "read_back_s": read_back_s, "read_back_scans": n_scans,
            "verify_s": t2 - t1, "replay_s": t3 - t2,
            "records_read_back": len(finals), "inserts_landed": landed,
            "inserts_acked": len(self.acked_inserts),
            "unanswered_inserts": len(self.unanswered),
            "txns_replayed": len(self.answered),
            "scans": len(scans),
            "scan_rows": sum(len(rows) for _bounds, rows in scans),
            "dropped_row_key": self.dropped_row and self.dropped_row[0],
            "client": {"ok": self.client.n_ok,
                       "overloaded": self.client.n_overloaded,
                       "failed": self.client.n_failed,
                       "timeout": self.client.n_timeout}}
        if self.devs:
            self.info["calibration"] = {
                k: float(v) for k, v in self.devs[0]._calibration().items()}
