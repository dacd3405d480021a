"""CPU rehearsal of the cell tpcc-w1-3n.closed16 (drivers/served_tpcc.py)
at tiny sizes, through run.main's ``rehearsal`` argument: the contract line,
the names it reports, the check's bookkeeping, that a fault reads
``correct: false``, that a wrong answer is caught by the reference, and that
a broken guarantee is caught by the check: a stale read and a replica that
loses acknowledged writes.

The two planted faults (``plant_stale_reads``, ``plant_dropped_applies``)
take a ``pytest.MonkeyPatch``, so that a run of the cell at its own size
can plant them too:

    from _pytest.monkeypatch import MonkeyPatch
    from benchmarks import run
    from benchmarks.tests.test_rehearsal_tpcc import plant_stale_reads
    plant_stale_reads(MonkeyPatch())
    run.main(["--workload", "tpcc-w1-3n.closed16", "--seed", "7",
              "--seconds", "30", "--trace", "0"])
"""

import json
import os

import pytest

from benchmarks import run

CELL = "tpcc-w1-3n.closed16"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = {"sizes": {"items": 1000, "customers": 300, "orders": 300,
                  "new_orders": 90},
        "traffic": {"clients": 4, "warm_quiet_s": 1.0, "warm_max_s": 8.0,
                    "warm_txns": 30, "trace_slice_s": 1.0}}
NEW = {"proc_eval_per_txn.serve", "deps_per_txn.serve"}


def _names(kind):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return {m["name"] for m in json.load(f)[kind]
                if "workloads" not in m or CELL in m["workloads"]}


def _run(capsys, trace, **traffic):
    rehearsal = {"sizes": TINY["sizes"],
                 "traffic": {**TINY["traffic"], **traffic}}
    rc = run.main(["--workload", CELL, "--seed", "2147483999",
                   "--seconds", "5", "--trace", str(trace)],
                  rehearsal=rehearsal)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()
             if ln.startswith("{")]
    return rc, lines[-1], lines      # a run that raised ends with its info


def _info(lines):
    return next(ln for ln in lines if ln.get("line") == "info")


def test_cell_rehearses_and_prints_the_contract_line(capsys):
    rc, last, earlier = _run(capsys, 0)
    assert rc == 0 and last["correct"] is True, (last,
                                                 _info(earlier)["problems"])
    assert last.pop("rehearsal") is True      # never mistaken for a chip run
    assert set(last) == CONTRACT_KEYS
    assert set(last["metrics"]) == _names("end_to_end") == {
        "commit_rate", "commit_p95", "setup_s"}
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    check = _info(earlier)["check"]
    assert check["answered"] == check["txns"] > last["attempted"]
    assert 0 < check["new_orders"] < check["answered"]
    # the New-Orders widened the deps mirrors (8 intervals at start) to a
    # store's share of up to 35 keys, and the window line counts it
    assert 8 <= check["max_intervals"] <= 32
    window = next(ln for ln in earlier if ln.get("line") == "window")
    assert {"n_interval_grows", "n_wide_spills"} <= set(window["counters"])
    setup = next(ln for ln in earlier if ln.get("line") == "setup")
    assert setup["load"]["rows"] == 1000 * 2 + 1 + 10 * (1 + 300 + 300 + 3)


def test_traced_run_reports_per_layer_metrics(capsys):
    rc, last, earlier = _run(capsys, 1)
    assert rc == 0 and last["correct"] is True, (last,
                                                 _info(earlier)["problems"])
    want = _names("per_layer")
    assert NEW <= want
    assert set(last["metrics"]) <= want
    # every one that has something to read on a CPU
    assert want - set(last["metrics"]) <= {"flush_occupancy.serve"}
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert metrics["proc_eval_per_txn.serve"] > 0
    assert metrics["deps_per_txn.serve"] >= 1


def test_an_armed_launch_fault_makes_the_cell_incorrect(capsys):
    from accord_tpu.utils import faults
    from accord_tpu.utils.random_source import RandomSource
    faults.inject_device_fault("kernel_launch", 1.0, RandomSource(7))
    try:
        rc, last, earlier = _run(capsys, 0)
    finally:
        faults.clear_device_faults()
    assert rc != 0 and last["correct"] is False
    assert any(p.startswith("n_device_faults=")
               for p in _info(earlier)["problems"])


def test_a_wrong_answer_is_caught_by_the_reference(capsys, monkeypatch):
    """The program's New-Order computes from a wrong S_QUANTITY (1000 for
    every stock row): its replies and the stock rows it wrote agree with
    each other, so only the reference can tell."""
    from accord_tpu.sim import tpcc
    real = tpcc.NewOrder.evaluate

    def no_wrap(args, reads):
        reads = {rid: (v[:2] + (1000,) + v[3:]
                       if v is not None and rid.startswith("S/") else v)
                 for rid, v in reads.items()}
        return real(args, reads)

    monkeypatch.setattr(tpcc.NewOrder, "evaluate", staticmethod(no_wrap))
    rc, last, earlier = _run(capsys, 0)
    assert rc != 0 and last["correct"] is False
    assert any(p.startswith("serial_tpcc: ")
               for p in _info(earlier)["problems"])


def plant_stale_reads(mp):
    """Every row read returns the version below the newest one below
    ``executeAt`` (a row with one version reads that one): the stale read
    of a replica that missed the last write."""
    from accord_tpu.sim.kvstore import KVDataStore

    def stale(self, token, row_id, execute_at):
        seen = []
        for vals, at, _tid in reversed(self.log.get(token, ())):
            if at < execute_at:
                seen += [v for v in reversed(vals) if v[0] == row_id]
                if len(seen) > 1:
                    return seen[1]
        return seen[0] if seen else None

    mp.setattr(KVDataStore, "read_row", stale)


def plant_dropped_applies(mp, node="n2"):
    """One replica, ``node``, applies no write after the load: every
    acknowledged write is lost there, and held by the other two."""
    from accord_tpu.maelstrom.node import node_name_to_id
    from accord_tpu.sim.kvstore import KVDataStore
    real, lost = KVDataStore.apply_append, node_name_to_id(node)

    def apply_append(self, token, values, execute_at, txn_id):
        if self.node_id != lost:
            real(self, token, values, execute_at, txn_id)

    mp.setattr(KVDataStore, "apply_append", apply_append)


@pytest.mark.parametrize("fault", [plant_stale_reads, plant_dropped_applies])
def test_a_broken_guarantee_makes_the_cell_incorrect(capsys, monkeypatch,
                                                     fault):
    """A stale read, or one replica's lost acknowledged writes: the
    verifier and the reference both read what the run itself produced."""
    fault(monkeypatch)
    rc, last, earlier = _run(capsys, 0)
    assert rc != 0 and last["correct"] is False
    assert any(p.startswith(("verifier: ", "serial_tpcc: "))
               for p in _info(earlier)["problems"])


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_readers_find_nothing_on_a_program_without_the_counters(name):
    """The parent's stats() has no ``deps_*`` and no ``srv.proc`` span: the
    record lacks the keys, and the readers return None and do not raise."""
    record = {"driver": "served", "acked": 10,
              "server": {"coordination_fast": 9, "coordination_slow": 1},
              "counters": {"kernel_times": {}, "n_host_queries": 5}}
    assert run._metric_reader(name).read(record) is None
    assert run._metric_reader(name).read({"driver": "some-later-driver"}) \
        is None
