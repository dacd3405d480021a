"""CPU rehearsal of the cell ycsb-e-3n.closed16 (drivers/served_scan.py) at
tiny sizes, through run.main's ``rehearsal`` argument: the contract line, the
names it reports, that a fault reads ``correct: false``, and that a record
dropped from one scan reply (the driver's test hook) is caught by BOTH the
verifier and the serial replay."""

import json
import os

import pytest

from benchmarks import run

CELL = "ycsb-e-3n.closed16"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = {"sizes": {"records": 2000},
        "traffic": {"clients": 4, "warm_quiet_s": 1.0, "warm_max_s": 8.0,
                    "warm_txns": 30, "trace_slice_s": 1.0,
                    "expected_new_records": 100}}
NEW = {"range_txn_share.serve", "scan_rows_per_txn.serve",
       "scan_read_per_txn.serve", "range_index_per_txn.serve",
       "device_range_query_share.serve"}


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
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    warm = next(ln for ln in earlier if ln.get("line") == "warm")
    assert warm["stores_ticked"] == 6     # no first tick left for the window
    quarters = _info(earlier)["window_quarters"]
    assert len(quarters) == 4 and all(q["txn_per_s"] > 0 and q["scan_us"] > 0
                                      for q in quarters)
    check = _info(earlier)["check"]
    # every loaded record and every insert came back through the scans
    assert check["records_read_back"] == 2000 + check["inserts_landed"]
    assert check["inserts_landed"] == check["inserts_acked"]
    assert check["read_back_scans"] >= 2000 // 100
    # warm-up, window and the read-back's own scans all went through the replay
    assert check["txns_replayed"] >= last["attempted"] + 20
    assert check["dropped_row_key"] is None


def test_traced_run_reports_per_layer_metrics(capsys):
    rc, last, earlier = _run(capsys, 1)
    assert rc == 0 and last["correct"] is True, (last,
                                                 _info(earlier)["problems"])
    assert set(last) == CONTRACT_KEYS | {"breakdown", "rehearsal"}
    want = _names("per_layer")
    assert NEW <= want
    assert set(last["metrics"]) <= want
    # every one that has something to read on a CPU
    assert want - set(last["metrics"]) <= {"flush_occupancy.serve"}
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert 60 <= metrics["range_txn_share.serve"] <= 100
    assert 1 <= metrics["scan_rows_per_txn.serve"] <= 110
    assert metrics["scan_read_per_txn.serve"] > 0
    assert metrics["range_index_per_txn.serve"] > 0
    assert 0 <= metrics["device_range_query_share.serve"] <= 100
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
    assert any(p.startswith("n_device_faults=")
               for p in _info(earlier)["problems"])


def test_an_injected_phantom_is_caught_by_the_verifier_and_the_replay(capsys):
    """One record leaves one scan reply (``test_drop_scan_row``, a traffic
    parameter with no file that sets it): the scan read a key that held a
    record as empty."""
    rc, last, earlier = _run(capsys, 0, test_drop_scan_row=1)
    assert rc != 0 and last["correct"] is False
    info = _info(earlier)
    assert info["check"]["dropped_row_key"] is not None
    assert any(p.startswith("verifier: ") for p in info["problems"]), info
    assert any(p.startswith("serial_scan_kv: ") for p in info["problems"])


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_readers_find_nothing_on_a_program_without_the_counters(name):
    """The parent's stats() has no ``data``, no ``range_txns`` and its
    DeviceStates no ``n_range_queries`` or ``range_index_sync``: the record
    lacks the keys, and the readers return None and do not raise."""
    record = {"driver": "served", "acked": 10,
              "server": {"coordination_fast": 9, "coordination_slow": 1},
              "counters": {"kernel_times": {}, "n_host_queries": 5}}
    assert run._metric_reader(name).read(record) is None
    assert run._metric_reader(name).read({"driver": "some-later-driver"}) \
        is None
