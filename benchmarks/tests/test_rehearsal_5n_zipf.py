"""CPU rehearsal of the cell lin-kv-5n-zipf.closed16 (drivers/served_txn.py)
at tiny sizes, through run.main's ``rehearsal`` argument: the contract line,
the names it reports, and that a fault or a lost append reads ``correct:
false``."""

import json
import os

import pytest

from benchmarks import run
from benchmarks.drivers import served_txn

CELL = "lin-kv-5n-zipf.closed16"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = {"sizes": {"keys": 400},
        "traffic": {"clients": 4, "warm_quiet_s": 1.0, "warm_max_s": 8.0,
                    "warm_txns": 20,
                    "trace_slice_s": 1.0}}


def _names(kind):
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return {m["name"] for m in json.load(f)[kind]
                if "workloads" not in m or CELL in m["workloads"]}


def _run(capsys, trace):
    rc = run.main(["--workload", CELL, "--seed", "2147483777",
                   "--seconds", "5", "--trace", str(trace)], rehearsal=TINY)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()
             if ln.startswith("{")]
    return rc, lines[-1], lines      # a run that raised ends with its info


def _problems(lines):
    return next(ln for ln in lines if ln.get("line") == "info")["problems"]


def test_cell_rehearses_and_prints_the_contract_line(capsys):
    rc, last, earlier = _run(capsys, 0)
    assert rc == 0 and last["correct"] is True, (last, _problems(earlier))
    assert last.pop("rehearsal") is True      # never mistaken for a chip run
    assert set(last) == CONTRACT_KEYS
    assert set(last["metrics"]) == _names("end_to_end") == {
        "commit_rate", "commit_p95", "setup_s"}
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    check = next(ln for ln in earlier if ln.get("line") == "info")["check"]
    assert check["keys_read_back"] == 400
    # warm-up, window and the read-back's own txns all went through the replay
    assert check["txns_replayed"] >= last["attempted"] + 400 // 50


def test_traced_run_reports_per_layer_metrics(capsys):
    rc, last, earlier = _run(capsys, 1)
    assert rc == 0 and last["correct"] is True, (last, _problems(earlier))
    assert set(last) == CONTRACT_KEYS | {"breakdown", "rehearsal"}
    want = _names("per_layer")
    assert len(want) == 10
    assert set(last["metrics"]) <= want
    # every one that has something to read on a CPU
    assert want - set(last["metrics"]) <= {"flush_occupancy.serve"}
    share = last["metrics"]["fast_path_share.serve"]
    assert share["unit"] == "%" and 0 < share["value"] <= 100
    assert last["metrics"]["recoveries_per_ktxn.serve"]["value"] >= 0
    assert last["metrics"]["drain_tick_per_txn.serve"]["value"] >= 0
    assert last["device"]["window_s"] > 0


def test_an_armed_launch_fault_makes_the_cell_incorrect(capsys):
    from accord_tpu.utils import faults
    from accord_tpu.utils.random_source import RandomSource
    faults.inject_device_fault("kernel_launch", 1.0, RandomSource(7))
    try:
        rc, last, earlier = _run(capsys, 0)
    finally:
        faults.clear_device_faults()
    assert rc != 0 and last["correct"] is False
    assert any(p.startswith("n_device_faults=") for p in _problems(earlier))


def test_a_dropped_acknowledged_append_makes_the_cell_incorrect(
        capsys, monkeypatch):
    """The read-back of one key loses its last value, as if the store had
    dropped an acknowledged append: missing_acks, the verifier and the serial
    replay each say so."""
    read_back = served_txn.Driver._read_back

    async def lossy(self, keys, finals):
        await read_back(self, keys, finals)
        token = next((t for t in sorted(self.acked) if finals.get(t)), None)
        if token is not None and not getattr(self, "dropped", False):
            self.dropped = True
            finals[token] = finals[token][:-1]
            start, end, reads, _ = self.answered[-1]
            self.answered[-1] = (start, end, {**reads, token: finals[token]},
                                 {})

    monkeypatch.setattr(served_txn.Driver, "_read_back", lossy)
    rc, last, earlier = _run(capsys, 0)
    assert rc != 0 and last["correct"] is False
    problems = _problems(earlier)
    assert any(p.startswith("acknowledged appends not read back")
               for p in problems), problems
    assert any(p.startswith("serial_kv: ") for p in problems), problems


@pytest.mark.parametrize("name", ["fast_path_share.serve",
                                  "recoveries_per_ktxn.serve"])
def test_new_readers_find_nothing_where_stats_has_no_coordination(name):
    """The parent's program has no ``coordination`` in stats(): the record
    then lacks the keys, and the readers return None and do not raise."""
    record = {"driver": "served", "acked": 10, "server": {},
              "counters": {"kernel_times": {}}}
    assert run._metric_reader(name).read(record) is None


def test_drain_tick_reader_reads_the_counters_the_parent_has_too():
    reader = run._metric_reader("drain_tick_per_txn.serve")
    record = {"driver": "served", "acked": 10, "server": {}, "counters": {
        "kernel_times": {"drain_tick_dispatch": [4, 0.03],
                         "drain_tick_wait": [4, 0.01], "dispatch_host": [9, 1.0]}}}
    assert reader.read(record) == pytest.approx(4.0)
    assert reader.read(dict(record, acked=0)) is None
