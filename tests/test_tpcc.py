"""TPC-C New-Order and Payment as stored procedures (sim/tpcc.py) against
the plain reference, benchmarks/lib/serial_tpcc.py, on seeded data: one
warehouse cut to 1,000 items and stock rows, 300 customers and 300 orders a
district (this file's cut only), over an in-process three-node cluster.

What is compared: every txn's outputs and every row's versions, the stock
rule's wrap, a rollback, a BC customer's C_DATA, the consistency conditions
1-4, the wire codec of each new type, and that a recovered or re-decoded
procedure computes byte-equal writes.  Then the device index under the
footprints TPC-C brings: a 35-key txn widens a deps mirror to 64 intervals
and a hot key spills past BUCKET_K, with the host and device routes
byte-equal in both."""

import random

import pytest

from accord_tpu import wire
from accord_tpu.local.commands_for_key import InternalStatus
from accord_tpu.net import codec
from accord_tpu.primitives.keys import IntKey, Keys
from accord_tpu.primitives.timestamp import Domain, TxnId, TxnKind
from accord_tpu.sim import tpcc
from accord_tpu.sim.cluster import Cluster
from accord_tpu.sim.kvstore import KVData, KVDataStore
from accord_tpu.sim.topology_factory import build_topology
from benchmarks.lib import serial_tpcc

from tests.conftest import make_device_state

SIZES = dict(items=1000, customers=300, orders=300, new_orders=90)
LOAD = TxnId.create(1, 1, TxnKind.Write, Domain.Key, 1)


@pytest.fixture(scope="module")
def population():
    return serial_tpcc.populate(5, **SIZES)


def _cluster(population, seed=1):
    cluster = Cluster(topology=build_topology(1, (1, 2, 3), 3, 4,
                                              max_token=1 << 32),
                      seed=seed, data_store_factory=KVDataStore)
    snapshot = {}
    for rid, values in population.items():
        if values:
            snapshot.setdefault(tpcc.row_token(rid), []).append(
                (tuple(values), LOAD, LOAD))
    for node in cluster.nodes.values():
        node.data_store.install_snapshot(
            {t: list(e) for t, e in snapshot.items()})
    return cluster


def _rows(store, rids):
    """Every value each row holds in ``store``, oldest first."""
    out = {}
    for rid in rids:
        out[rid] = tuple(v for v in store.get(tpcc.row_token(rid))
                         if v[0] == rid)
    return out


def _draws(population, n, seed):
    """``n`` (procedure, args): the clause 5.2.3 mix, and among them a
    stock row that wraps, a rollback and a BC customer's payment."""
    rng = random.Random(seed)
    low = next(i for i in range(1, SIZES["items"] + 1)
               if population[f"S/1/{i}"][-1][serial_tpcc.S_QUANTITY] < 19)
    bc = next(int(rid.rsplit("/", 1)[1]) for rid, v in population.items()
              if rid.startswith("C/1/3/")
              and v[-1][serial_tpcc.C_CREDIT] == "BC")
    out = []
    for tag in range(1, n + 1):
        d = rng.randint(1, 10)
        c = rng.randint(1, SIZES["customers"])
        if tag == 1:            # the wrap: S_QUANTITY < 19 takes 10 more
            out.append(("new_order", {"w": 1, "d": d, "c": c, "date": tag,
                                      "tag": tag,
                                      "lines": [[low, 1, 10], [7, 1, 3]]}))
        elif tag == 2:          # a BC customer's payment
            out.append(("payment", {"w": 1, "d": 3, "c_w": 1, "c_d": 3,
                                    "c": bc, "amount": 123456, "date": tag,
                                    "tag": tag}))
        elif tag == 3:          # an unused item on the last line: rollback
            out.append(("new_order", {"w": 1, "d": d, "c": c, "date": tag,
                                      "tag": tag,
                                      "lines": [[5, 1, 2], [0, 1, 1]]}))
        elif rng.random() < 0.511:
            items = rng.sample(range(1, SIZES["items"] + 1),
                               rng.randint(5, 15))
            out.append(("new_order", {"w": 1, "d": d, "c": c, "date": tag,
                                      "tag": tag,
                                      "lines": [[i, 1, rng.randint(1, 10)]
                                                for i in items]}))
        else:
            out.append(("payment", {"w": 1, "d": d, "c_w": 1, "c_d": d,
                                    "c": c, "amount": rng.randint(100, 500000),
                                    "date": tag, "tag": tag}))
    return out


def _submit(cluster, node, name, args):
    out = []
    cluster.nodes[node].coordinate(tpcc.procedure_txn(name, args)).begin(
        lambda r, f: out.append((r, f)))
    return out


def _finals(population, draws):
    """The rows a check reads back: every row the draws may write, and
    every WAREHOUSE, DISTRICT, ORDER, NEW-ORDER and HISTORY list."""
    rows = {"W/1", *(f"{t}/1/{d}" for t in ("D", "O", "NO", "H")
                     for d in range(1, 11))}
    for name, args in draws:
        rows.update(serial_tpcc.rows_of(name, args)[1])
    return {rid for rid in rows if rid in population}


def test_one_at_a_time_the_system_answers_as_the_reference(population):
    """Each txn alone, in turn: its outputs and what it saw, then every row
    it may have written, version by version, on every replica."""
    cluster = _cluster(population)
    state = {rid: list(v) for rid, v in population.items()}
    draws = _draws(population, 24, seed=3)
    results = []
    for k, (name, args) in enumerate(draws):
        reads, _writes = serial_tpcc.rows_of(name, args)
        seen = {rid: state[rid][-1][1] if rid in state else None
                for rid in sorted(set(reads))}
        out = _submit(cluster, 1 + k % 3, name, args)
        cluster.run_until_quiescent()
        (result, failure), = out
        assert failure is None
        want = serial_tpcc.TRANSACTIONS[name](state, args)
        assert serial_tpcc.as_json(result.out) == serial_tpcc.as_json(want)
        assert result.seen == seen
        results.append(result.out)
    assert cluster.failures == []
    # the three cases the draws were made for
    wrapped = state[f"S/1/{draws[0][1]['lines'][0][0]}"][-1]
    assert wrapped[serial_tpcc.S_QUANTITY] >= 81        # q - 10 + 91
    assert results[2]["rollback"] == "Item number is not valid"
    bc = state[f"C/1/3/{draws[1][1]['c']}"][-1]
    assert bc[serial_tpcc.C_DATA].startswith(f"{draws[1][1]['c']} 3 1 3 1 "
                                             f"1234.56|")
    rows = _finals(population, draws)
    for node in cluster.nodes.values():
        got = _rows(node.data_store, rows)
        assert got == {rid: tuple(state[rid]) for rid in rows}
    assert serial_tpcc.consistency(state) == []


def test_concurrent_txns_replay_serially_on_the_reference(population):
    """Forty txns at once over three coordinators: the history (all
    concurrent in real time) has a serial order that the reference
    reproduces, output by output and row by row."""
    cluster = _cluster(population, seed=9)
    draws = _draws(population, 40, seed=17)
    outs = [_submit(cluster, 1 + k % 3, name, args)
            for k, (name, args) in enumerate(draws)]
    cluster.run_until_quiescent()
    assert cluster.failures == []
    history = []
    for (name, args), out in zip(draws, outs):
        (result, failure), = out
        assert failure is None
        history.append({"name": name, "args": args, "start": 0, "end": 1,
                        "out": serial_tpcc.as_json(result.out),
                        "seen": result.seen})
    finals = _rows(cluster.nodes[2].data_store, _finals(population, draws))
    order = serial_tpcc.replay(population, history, finals)
    assert sorted(order) == list(range(len(draws)))


def test_a_wrong_answer_fails_the_replay(population):
    cluster = _cluster(population, seed=9)
    draws = _draws(population, 6, seed=17)
    outs = [_submit(cluster, 1, name, args) for name, args in draws]
    cluster.run_until_quiescent()
    history = [{"name": name, "args": args, "start": k, "end": k + 0.5,
                "out": serial_tpcc.as_json(out[0][0].out),
                "seen": out[0][0].seen}
               for k, ((name, args), out) in enumerate(zip(draws, outs))]
    finals = _rows(cluster.nodes[1].data_store, _finals(population, draws))
    serial_tpcc.replay(population, history, finals)
    history[1]["out"]["c_balance"] += 1
    with pytest.raises(serial_tpcc.NotTPCC) as e:
        serial_tpcc.replay(population, history, finals)
    assert e.value.kind == "result-mismatch"


@pytest.mark.parametrize("condition", [1, 2, 3, 4])
def test_each_consistency_condition_is_checked(population, condition):
    """The population satisfies clause 3.3.2's conditions 1-4; breaking
    what each one reads makes exactly that one fail."""
    state = {rid: list(v) for rid, v in population.items()}
    assert serial_tpcc.consistency(state) == []
    d = state["D/1/4"][-1]
    if condition == 1:
        state["D/1/4"].append(d[:9] + (d[9] + 1,) + d[10:])
    elif condition == 2:
        state["D/1/4"].append(d[:10] + (d[10] + 1,))
    elif condition == 3:
        state["NO/1/4"].pop(40)
    else:
        o = state["O/1/4/7"][-1]
        state["O/1/4/7"].append(o[:6] + (o[6] + 1,) + o[7:])
    bad = serial_tpcc.consistency(state)
    assert [b.split(":")[0] for b in bad] == [str(condition)]


def _samples(population):
    txn = tpcc.procedure_txn("new_order", {
        "w": 1, "d": 2, "c": 9, "date": 1, "tag": 4,
        "lines": [[3, 1, 2], [8, 1, 5]]})
    data = KVData({rid: population[rid][-1] if rid in population else None
                   for rid in serial_tpcc.rows_of(
                       "new_order", txn.update.args)[0]})
    at = TxnId.create(1, 9, TxnKind.Write, Domain.Key, 1)
    return {"RowRead": txn.read, "ProcUpdate": txn.update,
            "ProcQuery": txn.query,
            "ProcResult": txn.result(at, at, data),
            "KVWrite": txn.update.apply(at, data),
            "Txn": txn, "KVData": data}


@pytest.mark.parametrize("kind", ["RowRead", "ProcUpdate", "ProcQuery",
                                  "ProcResult", "KVWrite", "Txn", "KVData"])
def test_each_new_type_round_trips_the_codecs(population, kind):
    """wire.encode -> the binary frame (msgpack) and the JSON frame ->
    wire.decode gives back a byte-equal document."""
    doc = wire.encode(_samples(population)[kind])
    for name in ("binary", "json"):
        packet = {"src": "n1", "dest": "n2",
                  "body": {"type": "accord_req", "msg_id": 1,
                           "payload": doc}}
        back = codec.decode_payload(codec.encode_packet(packet, name))
        assert wire.encode(wire.decode(back["body"]["payload"])) == doc


@pytest.mark.parametrize("path", ["recovery", "journal"])
def test_a_procedure_recomputes_byte_equal_writes(population, path):
    """``recovery``: the coordinator's Commit is lost, so another node
    recovers the txn and re-executes it: every replica holds the versions a
    direct evaluation over the same reads gives.  ``journal``: the txn and
    its reads rebuilt from their wire documents, as the journal keeps them,
    compute byte-equal writes and result."""
    name, args = "new_order", {"w": 1, "d": 6, "c": 11, "date": 1,
                               "tag": 1, "lines": [[4, 1, 3], [9, 1, 7]]}
    reads = serial_tpcc.rows_of(name, args)[0]
    data = KVData({rid: population[rid][-1] for rid in reads})
    txn = tpcc.procedure_txn(name, args)
    at = TxnId.create(1, 9, TxnKind.Write, Domain.Key, 1)
    want = txn.execute(at, at, data)
    if path == "journal":
        again = wire.decode(wire.encode(txn))
        data2 = wire.decode(wire.encode(data))
        assert wire.encode(again.execute(at, at, data2).write) \
            == wire.encode(want.write)
        assert wire.encode(again.result(at, at, data2)) \
            == wire.encode(txn.result(at, at, data))
        return
    from accord_tpu.messages.commit import Commit
    cluster = _cluster(population, seed=11)
    cluster.message_filter = lambda src, dst, req: \
        isinstance(req, Commit) and src == 1
    out = _submit(cluster, 1, name, args)
    cluster.run_until_quiescent()
    cluster.message_filter = None
    cluster.run_until_quiescent()
    assert cluster.failures == []
    written = {t: vals for t, vals in want.write.appends.items()}
    for node in cluster.nodes.values():
        log = node.data_store.log
        got = {t: next(v for v, _at, _tid in reversed(log[t])
                       if v[0][1] == 1) for t in written}
        assert wire.encode(got) == wire.encode(written)


# -- calls outside a procedure's schema --------------------------------------

_PAYMENT = {"w": 1, "d": 2, "c_w": 1, "c_d": 2, "c": 5, "amount": 700,
            "date": 1, "tag": 1}
_NEW_ORDER = {"w": 1, "d": 2, "c": 7, "date": 2, "tag": 2,
              "lines": [[4, 1, 3], [9, 1, 1]]}
_MALFORMED = {
    "unknown procedure": ("delivery", _PAYMENT),
    "args not an object": ("payment", [1, 2, 3]),
    "payment without amount": ("payment", {k: v for k, v in _PAYMENT.items()
                                           if k != "amount"}),
    "amount a string": ("payment", dict(_PAYMENT, amount="7.00")),
    "tag a bool": ("payment", dict(_PAYMENT, tag=True)),
    "date past 2**31": ("payment", dict(_PAYMENT, date=1 << 40)),
    "quantity a string": ("new_order", dict(_NEW_ORDER,
                                            lines=[[4, 1, "3"]])),
    "no lines": ("new_order", dict(_NEW_ORDER, lines=[])),
    "16 lines": ("new_order", dict(_NEW_ORDER,
                                   lines=[[i, 1, 1] for i in range(1, 17)])),
    "a line of two": ("new_order", dict(_NEW_ORDER, lines=[[4, 1]])),
    "new_order without c": ("new_order", {k: v for k, v in _NEW_ORDER.items()
                                          if k != "c"}),
}


@pytest.mark.parametrize("case", sorted(_MALFORMED))
def test_a_call_outside_the_schema_is_refused_before_coordination(case):
    """Arguments an evaluation could not take raise ValueError when the txn
    is built: evaluated inside the execution, they would raise on every
    replica and in every recovery, and every later txn on the warehouse
    row would wait behind the one that never applies."""
    name, args = _MALFORMED[case]
    with pytest.raises(ValueError):
        tpcc.procedure_txn(name, args)
    tpcc.procedure_txn("payment", _PAYMENT)
    tpcc.procedure_txn("new_order", _NEW_ORDER)


def test_a_malformed_call_is_refused_with_code_10_and_the_cluster_goes_on(
        population):
    """Through the Maelstrom front end of a three-node cluster: a Payment
    without ``amount`` and a New-Order whose quantity is a string are
    answered error code 10, and the calls after them, on the same
    warehouse and district rows, commit."""
    from accord_tpu.maelstrom.runner import MaelstromRunner
    runner = MaelstromRunner(3, seed=4, device_mode=False)
    snapshot = {}
    for rid, values in population.items():
        if values:
            snapshot.setdefault(tpcc.row_token(rid), []).append(
                (tuple(values), LOAD, LOAD))
    for proc in runner.processes.values():
        proc.node.data_store.install_snapshot(
            {t: list(e) for t, e in snapshot.items()})
    replies = {}

    def call(msg_id, node, name, args):
        runner.client_handlers[msg_id] = \
            lambda body: replies.__setitem__(msg_id, body)
        runner._deliver(node, {"src": "c1", "dest": node, "body": {
            "type": "txn", "msg_id": msg_id,
            "txn": [["call", name, args]]}})
        runner.queue_drain()

    call(1, "n1", *_MALFORMED["payment without amount"])
    call(2, "n2", *_MALFORMED["quantity a string"])
    assert [(replies[m]["type"], replies[m]["code"]) for m in (1, 2)] \
        == [("error", 10)] * 2
    call(3, "n3", "payment", _PAYMENT)
    call(4, "n1", "new_order", _NEW_ORDER)
    call(5, "n2", "payment", dict(_PAYMENT, tag=5))
    assert [replies[m]["type"] for m in (3, 4, 5)] == ["txn_ok"] * 3
    (_call, _name, body), = replies[5]["txn"]
    assert body["seen"]["W/1"] == 1 and body["seen"]["D/1/2"] == 2
    for proc in runner.processes.values():
        store = proc.node.data_store
        assert store.read_row(tpcc.row_token("W/1"), "W/1",
                              proc.node.unique_now())[1] == 5


# -- the device index under TPC-C's footprints ------------------------------

def _register(dev, hlc, tokens):
    tid = TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1 + hlc % 3)
    dev.register(tid, int(InternalStatus.PREACCEPTED),
                 Keys([IntKey(t) for t in sorted(set(tokens))]))
    return tid


def _routes_agree(dev, safe, qs):
    from tests.test_routing import _attributed, _reference
    oracle = _reference(dev, safe, qs)
    for mesh in (dev.mesh, None):
        dev.mesh = mesh
        for route in ("host", "device"):
            dev.route_override = route
            assert _attributed(dev, safe, qs) == oracle, (route, mesh)
    return oracle


def _query(hlc, tokens):
    bound = TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1)
    return (bound, bound, bound.kind().witnesses(), sorted(set(tokens)), [])


def test_a_35_key_footprint_widens_a_mirror_and_the_routes_agree():
    """New-Orders of 5 + 2 x 15 keys: the mirror doubles 4 -> 64 (four
    grows, counted, all under the first) and the host and device routes
    build byte-equal deps."""
    rng = random.Random(35)
    _store, dev, safe = make_device_state()
    new_orders = [[rng.randrange(1 << 20) for _ in range(35)]
                  for _ in range(24)]
    for hlc, tokens in enumerate(new_orders, 1):
        _register(dev, hlc, tokens)
    assert dev.deps.max_intervals == 64 and dev.n_interval_grows == 4
    qs = [_query(100 + i, rng.sample(tokens, 20) + [rng.randrange(1 << 20)])
          for i, tokens in enumerate(new_orders[::3])]
    deps = _routes_agree(dev, safe, qs)
    assert all(keys for keys, _ranges in deps)
    _store, fresh, _safe = make_device_state()
    _register(fresh, 1, new_orders[0])
    assert fresh.deps.max_intervals == 64 and fresh.n_interval_grows == 4
    for hlc, tokens in enumerate(new_orders[1:], 2):
        _register(fresh, hlc, tokens)
    assert fresh.n_interval_grows == 4


def test_a_hot_key_past_bucket_k_spills_and_the_routes_agree():
    """Every txn on the warehouse row: past BUCKET_K entries its bucket
    spills into ``wide_entries`` (counted), and both routes still give the
    deps the reference gives."""
    rng = random.Random(128)
    _store, dev, safe = make_device_state()
    hot = 77_777
    k = dev.deps.BUCKET_K
    for hlc in range(1, k + 21):
        _register(dev, hlc, [hot] + [rng.randrange(1 << 20)
                                     for _ in range(3)])
    assert dev.n_wide_spills == 20 and len(dev.deps.wide_entries) >= 20
    qs = [_query(1000 + i, [hot, rng.randrange(1 << 20)]) for i in range(6)]
    deps = _routes_agree(dev, safe, qs)
    for keys, _ranges in deps:
        hot_deps = dict(keys)[hot]
        assert len(hot_deps) == k + 20


def test_a_drain_takes_one_state_size_to_its_floor_and_doubles_past_it():
    """A store's drain state is padded to its floor, 64 live slots, and
    past it to the next power of two (a new program size for the tick);
    a scatter of dirty rows keeps the size the state was built at."""
    _store, dev, _safe = make_device_state()
    drain = dev.drain
    assert drain.MIN_STATE_SLOTS == 64
    slots = [drain.alloc(TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1))
             for hlc in range(1, 61)]
    state, live = drain.state()
    assert len(state.status) == 64 and len(live) == 60
    for slot in slots:
        drain.set_status(slot, 3, None)
    state, _live = drain.state()               # a scatter of 60 dirty rows
    assert len(state.status) == 64
    for hlc in range(61, 101):
        drain.alloc(TxnId.create(1, hlc, TxnKind.Write, Domain.Key, 1))
    assert len(drain.state()[0].status) == 128


def test_a_warmed_drain_meets_no_new_program_in_a_widened_tick():
    """``warm_drain`` loads the dirty-row scatter, the frontier sweep and
    the level pass of a widened tick at the empty mirror's state size (the
    floor): later ticks over a live set under the floor, built or
    scattered, find every program already there."""
    from accord_tpu.local.device_index import _scatter_drain_scalars
    from accord_tpu.ops import drain_kernel as drk
    programs = (drk.ready_frontier, drk.level_assign_dense,
                _scatter_drain_scalars)
    _store, dev, _safe = make_device_state()
    dev.warm_drain()
    sizes = [p._cache_size() for p in programs]
    slots = [dev.drain.alloc(TxnId.create(1, hlc, TxnKind.Write,
                                          Domain.Key, 1))
             for hlc in range(1, 51)]
    built, live = dev.drain.state()
    assert len(built.status) == 64 and len(live) == 50
    for slot in slots[:10]:
        dev.drain.set_status(slot, 3, None)
    scattered, _live = dev.drain.state()
    for state in (built, scattered):
        drk.ready_frontier(state)
        drk.level_assign_dense(state)
    assert [p._cache_size() for p in programs] == sizes
