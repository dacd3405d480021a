"""Driver ``served_tpcc``: drivers/served.py's deployment and window under
TPC-C New-Order and Payment (TPC-C rev. 5.11 clauses 2.4 and 2.5) at one
warehouse.

The data.  ``lib/serial_tpcc.populate`` builds clause 4.3.3.1's population
from ``population_seed``; every row is loaded before traffic through
``KVDataStore.install_snapshot`` on every replica (the route a bootstrapping
replica's snapshot takes), at a timestamp below every txn.  A row lives on
the key ``blake2b(row_id) mod 2**32`` (the program's ``sim/tpcc.row_token``,
computed here the same way); its value is a list of versions (a register)
or of entries (ORDER, NEW-ORDER, HISTORY), as ``lib/serial_tpcc.py`` says.

The traffic.  Each closed-loop client submits one procedure call a txn,
``["call", "new_order" | "payment", args]``, to a coordinator drawn
uniformly, its kind the next card of the run's deck (``deck``: New-Order
and Payment cards in the mix's proportion, shuffled, drawn by the terminals
in turn and reshuffled when spent: clause 5.2.4.2's card deck, one for all
the terminals, so that a window's mix is the deck's and not a coin's);
the district uniform; the customer NURand(1023, 1, 3000); a New-Order's
``ol_cnt`` uniform, its items NURand(8191, 1, items) and distinct, each
quantity uniform, and with probability ``rollback_share`` its last item the
unused id (it rolls back); a Payment's amount uniform in cents.  NURand's
constants are drawn from ``population_seed``.  Each attempt carries a
unique ``tag``, which the versions it writes hold.

The check reads back, through the client path, every row any attempt may
have written plus every WAREHOUSE, DISTRICT, ORDER and NEW-ORDER row, in
read-only txns of ``check_keys_per_read`` keys, ``check_in_flight`` at a
time, and holds the run to served.py's gates, the strict-serializability
verifier over the rows' version chains (ww / wr / rw edges and real time,
the population one op before all), and ``serial_tpcc.replay``: the serial
execution exists, the reference reproduces every answered txn's outputs
and every row read back, and the consistency conditions 1-4 hold.

The record keeps ``"driver": "served"``: what the ``.serve`` metrics read is
all there, under the same names."""

import asyncio
import gc
import hashlib
import random
import time

from ..lib import checks, serial_tpcc
from . import served, served_txn
from .served import TOKEN_SPACE, _now_us

# the program's counters this configuration reads where they exist (a
# program without them leaves the keys out and their readers find nothing)
_COORDINATION = ("deps_entries", "deps_txns")
_DEVICE = ("n_interval_grows", "n_wide_spills")


def row_token(row_id):
    digest = hashlib.blake2b(row_id.encode(), digest_size=8).digest()
    return int.from_bytes(digest, "big") % TOKEN_SPACE


def _tuple(x):
    """A value as the client reply carries it (lists) back as stored."""
    return tuple(_tuple(v) for v in x) if isinstance(x, list) else x


class Driver(served_txn.Driver):

    def __init__(self, config, traffic, seed, scratch_dir):
        # served.Driver lays sizes["keys"] keys over the ring for its own
        # traffic; the keys here are the rows'
        config = dict(config, sizes=dict(config["sizes"], keys=1))
        super().__init__(config, traffic, seed, scratch_dir)
        self.history = []        # one dict an attempt (serial_tpcc.replay)
        self.population = None
        crng = random.Random(f"tpcc-constants/{self.population_seed}")
        self.c_cid, self.c_olid = crng.randint(0, 1023), crng.randint(0, 8191)
        self.deck, self.deck_rng = [], random.Random(f"{seed}/deck")

    # -- set-up ---------------------------------------------------------
    async def _start(self):
        """served.Driver's cluster (not served_txn's Zipf key table), then
        the population."""
        await served.Driver._start(self)
        self._load()

    def _load(self):
        from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
        sz = self.sz
        t0 = time.perf_counter()
        self.population = serial_tpcc.populate(
            self.population_seed, items=int(sz["items"]),
            districts=int(sz["districts"]),
            customers=int(sz["customers"]), orders=int(sz["orders"]),
            new_orders=int(sz["new_orders"]))
        t1 = time.perf_counter()
        load_id = TxnId.create(1, 1, TxnKind.Write, Domain.Key, 1)
        snapshot = {}
        for rid, values in self.population.items():
            if values:
                snapshot.setdefault(row_token(rid), []).append(
                    (tuple(values), load_id, load_id))
        self.load_start = _now_us()
        for s in self.servers:       # each replica its own log of them
            s.proc.node.data_store.install_snapshot(
                {t: list(entries) for t, entries in snapshot.items()})
        self.load_end = _now_us()
        self.districts = int(sz["districts"])
        self.items = int(sz["items"])
        self.customers = int(sz["customers"])
        # the load is start-up state, as what NodeServer.start() froze
        # before it: out of the collector's walks (close() unfreezes)
        gc.collect()
        gc.freeze()
        self.load_s = {"populate_s": t1 - t0,
                       "install_s": time.perf_counter() - t1,
                       "rows": len(self.population), "keys": len(snapshot)}

    def setup(self):
        super().setup()
        self.info["setup"].update(load=self.load_s)

    # -- the traffic ----------------------------------------------------
    def _card(self):
        if not self.deck:
            cards = self.traffic["deck"]
            self.deck = [name for name in sorted(cards)
                         for _ in range(int(cards[name]))]
            self.deck_rng.shuffle(self.deck)
        return self.deck.pop()

    def _draw(self, rng):
        """(procedure, args) of one attempt, its tag the next number."""
        tr = self.traffic
        self.counter += 1
        tag = self.counter
        d = rng.randint(1, self.districts)
        c = serial_tpcc.nurand(rng, 1023, 1, self.customers, self.c_cid)
        date = serial_tpcc.POPULATED_AT + 86_400 + tag
        if self._card() == "new_order":
            n, items = rng.randint(*tr["ol_cnt"]), []
            while len(items) < n:
                i = serial_tpcc.nurand(rng, 8191, 1, self.items, self.c_olid)
                if i not in items:
                    items.append(i)
            if rng.random() < float(tr["rollback_share"]):
                items[-1] = serial_tpcc.UNUSED_ITEM
            return "new_order", {
                "w": 1, "d": d, "c": c, "date": date, "tag": tag,
                "lines": [[i, 1, rng.randint(*tr["quantity"])]
                          for i in items]}
        return "payment", {"w": 1, "d": d, "c_w": 1, "c_d": d, "c": c,
                           "amount": rng.randint(*tr["amount_cents"]),
                           "date": date, "tag": tag}

    async def _client_loop(self, rng, go_on, sink, tracer):
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        loop, client = self.loop, self.client
        while go_on():
            node = self.names[rng.randrange(len(self.names))]
            name, args = self._draw(rng)
            attempt = {"name": name, "args": args, "start": _now_us(),
                       "end": None}
            self.history.append(attempt)
            t0 = loop.time()
            try:
                with tracer.span("client.submit"):
                    body = await client.submit([["call", name, args]],
                                               node=node)
            except Overloaded as shed:
                sink.failed.append("Overloaded")
                await asyncio.sleep(shed.retry_after_ms / 1e3)
                continue
            except TxnFailed as e:
                if e.body.get("code") == 10:
                    # a program without the call op: fail the run at the
                    # first call, never loop on refusals
                    raise RuntimeError(f"the nodes refuse the op: "
                                       f"{e.body.get('text')}") from e
                sink.failed.append(repr(e)[:120])
                continue
            except (asyncio.TimeoutError, ConnectionError) as e:
                # indeterminate: it may still take effect, whole
                sink.failed.append(repr(e)[:120])
                continue
            sink.done.append((t0, loop.time()))
            reply = body["txn"][0][2]
            attempt.update(end=_now_us(), out=reply["out"],
                           seen=reply["seen"])

    # -- the window -----------------------------------------------------
    def _snapshot(self):
        snap = super()._snapshot()
        coord = [s.stats().get("coordination") for s in self.servers]
        if all(c and all(k in c for k in _COORDINATION) for c in coord):
            for key in _COORDINATION:
                snap["server"]["coordination_" + key] = sum(
                    c[key] for c in coord)
        if all(hasattr(d, k) for d in self.devs for k in _DEVICE):
            for key in _DEVICE:
                snap["device"][key] = sum(getattr(d, key) for d in self.devs)
        return snap

    # -- the check ------------------------------------------------------
    async def _read_tokens(self, tokens, finals):
        """One read-only txn over ``tokens``; every value read is filed
        under the row it names.  Safe to repeat."""
        from accord_tpu.net.admission import Overloaded
        from accord_tpu.net.client import TxnFailed
        ops = [["r", t, None] for t in tokens]
        for attempt in range(1, 9):
            try:
                body = await self.client.submit(ops)
                break
            except (TxnFailed, Overloaded, asyncio.TimeoutError):
                if attempt == 8:
                    raise
                await asyncio.sleep(0.5 * attempt)
        for _r, _t, values in body["txn"]:
            for v in values:
                v = _tuple(v)
                finals.setdefault(v[0], []).append(v)

    def _rows_to_read(self):
        rows = {"W/1"}
        for d in range(1, self.districts + 1):
            rows.update((f"D/1/{d}", f"O/1/{d}", f"NO/1/{d}", f"H/1/{d}"))
        for t in self.history:
            rows.update(serial_tpcc.rows_of(t["name"], t["args"])[1])
        return {rid for rid in rows if rid in self.population}

    async def _check(self):
        # the mirrors as the window left them (the read-back's wide
        # read-only txns widen them further)
        mirrors = {
            "max_intervals": max((d.deps.max_intervals for d in self.devs),
                                 default=None),
            "wide_entries": max((len(d.deps.wide_entries)
                                 for d in self.devs), default=None),
            "bucket_max_len": max((d.deps.bucket_max_len
                                   for d in self.devs), default=None)}
        t0 = time.perf_counter()
        rows = self._rows_to_read()
        tokens = sorted({row_token(rid) for rid in rows})
        width = int(self.traffic["check_keys_per_read"])
        chunks = [tokens[i:i + width] for i in range(0, len(tokens), width)]
        n_reads = len(chunks)
        got = {}

        async def reader():
            while chunks:
                await self._read_tokens(chunks.pop(), got)

        await asyncio.gather(*[reader() for _ in range(
            int(self.traffic["check_in_flight"]))])
        finals = {rid: tuple(got.get(rid, ())) for rid in rows}
        read_back_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        self._verify(finals)
        t2 = time.perf_counter()
        try:
            serial_tpcc.replay(self.population, self.history, finals)
        except serial_tpcc.NotTPCC as e:
            self.problems.append(f"serial_tpcc: {str(e)[:300]}")
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
        answered = [t for t in self.history if t["end"] is not None]
        self.info["check"] = {
            "read_back_s": read_back_s, "verify_s": t2 - t1,
            "replay_s": t3 - t2, "rows_read_back": len(finals),
            "read_back_txns": n_reads,
            "txns": len(self.history), "answered": len(answered),
            "new_orders": sum(t["name"] == "new_order" for t in answered),
            "rollbacks": sum("rollback" in t["out"] for t in answered),
            "warehouse_versions": len(finals.get("W/1", ())),
            **mirrors,
            "client": {"ok": self.client.n_ok,
                       "overloaded": self.client.n_overloaded,
                       "failed": self.client.n_failed,
                       "timeout": self.client.n_timeout}}
        if self.devs:
            self.info["calibration"] = {
                k: float(v) for k, v in self.devs[0]._calibration().items()}

    def _verify(self, finals):
        """The composite verifier over the rows' version chains: a row is
        a list of the tags that wrote it, a read the prefix up to the
        version it saw; the population is one op before every other."""
        chains = {rid: serial_tpcc.version_chain(v)
                  for rid, v in finals.items()}
        v = self.verifier
        v.on_result(v.begin(), self.load_start, self.load_end, {},
                    {rid: (0,) for rid, chain in chains.items()
                     if chain[:1] == (0,)})
        for t in self.history:
            if t["end"] is None:
                continue
            read_rows, write_rows = serial_tpcc.rows_of(t["name"], t["args"])
            reads = {}
            for rid in read_rows:
                chain, seen = chains.get(rid), t["seen"].get(rid)
                if chain is not None and seen in chain:
                    reads[rid] = chain[:chain.index(seen) + 1]
            appends = ({rid: (t["args"]["tag"],) for rid in write_rows
                        if rid in chains}
                       if "rollback" not in t["out"] else {})
            v.on_result(v.begin(), t["start"], t["end"], reads, appends)
        for rid, chain in chains.items():
            v.set_final(rid, chain)
        try:
            v.verify()
        except AssertionError as e:
            self.problems.append(f"verifier: {str(e)[:300]}")
