"""CPU rehearsal of the cell lin-kv-5n-1down.closed16
(drivers/served_txn_1down.py) at tiny sizes, through run.main's ``rehearsal``
argument: the contract line, the crash and what the driver saw of it, both
new metrics in a traced run, an armed fault reads ``correct: false``, the new
readers on a record without their keys, and that no file of the benchmark
that was there before this cell changed."""

import json
import os
import subprocess

import pytest

from benchmarks import run

CELL = "lin-kv-5n-1down.closed16"
TWIN = "lin-kv-5n-zipf.closed16"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NEW_METRICS = {"peer_down_fast_fail_share.serve",
               "down_peer_frames_per_txn.serve"}
NEW_FILES = {"benchmarks/configs/lin-kv-5n-1down.json",
             "benchmarks/drivers/served_txn_1down.py",
             "benchmarks/metrics/peer_down_fast_fail_share.serve.py",
             "benchmarks/metrics/down_peer_frames_per_txn.serve.py",
             "benchmarks/tests/test_rehearsal_1down.py"}
TINY = {"sizes": {"keys": 400},
        "traffic": {"clients": 4, "warm_quiet_s": 1.0, "warm_max_s": 8.0,
                    "warm_txns": 20, "trace_slice_s": 1.0}}


def _bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _names(kind, cell=CELL):
    return {m["name"] for m in _bench()[kind]
            if "workloads" not in m or cell in m["workloads"]}


def _run(capsys, trace):
    rc = run.main(["--workload", CELL, "--seed", "2147483877",
                   "--seconds", "5", "--trace", str(trace)], rehearsal=TINY)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()
             if ln.startswith("{")]
    return rc, lines[-1], lines      # a run that raised ends with its info


def _line(lines, kind):
    return next(ln for ln in lines if ln.get("line") == kind)


def test_cell_rehearses_and_prints_the_contract_line(capsys):
    rc, last, earlier = _run(capsys, 0)
    problems = _line(earlier, "info")["problems"]
    assert rc == 0 and last["correct"] is True, (last, problems)
    assert last.pop("rehearsal") is True      # never mistaken for a chip run
    assert set(last) == CONTRACT_KEYS
    assert set(last["metrics"]) == _names("end_to_end") == {
        "commit_rate", "commit_p95", "setup_s"}
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    crash = _line(earlier, "warm")["crash"]
    assert crash["settle_s"] < 20 and crash["post_crash"]["acked"] == 200
    assert crash["post_crash"]["failed"] == 0
    # the crash fell with the loop running: what was in flight on the
    # crashed node failed, nothing else did
    assert crash["failed"] == crash["in_flight_on_crashed"]
    check = _line(earlier, "info")["check"]
    assert check["keys_read_back"] == 400
    assert all(link["down"] for link in check["survivor_links"].values())
    window = _line(earlier, "window")
    assert window["end_to_end"]["commit_rate"] > 0


def test_record_keeps_the_served_driver_and_reports_the_twins_metrics():
    """The cell is on every list its twin is on, plus its own two."""
    assert _names("per_layer") == _names("per_layer", TWIN) | NEW_METRICS
    assert _names("end_to_end") == _names("end_to_end", TWIN)
    cell = next(w for w in _bench()["workloads"] if w["name"] == CELL)
    twin = next(w for w in _bench()["workloads"] if w["name"] == TWIN)
    assert (cell["traffic"], cell["chips"]) == (twin["traffic"], 1)
    with open(os.path.join(run.HERE, "configs", "lin-kv-5n-1down.json")) as f:
        config = json.load(f)
    with open(os.path.join(run.HERE, "configs", "lin-kv-5n-zipf.json")) as f:
        twin_config = json.load(f)
    assert config["sizes"] == twin_config["sizes"]
    assert config["population_seed"] == twin_config["population_seed"]
    assert config["driver"] == "served_txn_1down"


def test_traced_run_reports_both_new_metrics(capsys):
    rc, last, earlier = _run(capsys, 1)
    problems = _line(earlier, "info")["problems"]
    assert rc == 0 and last["correct"] is True, (last, problems)
    assert set(last) == CONTRACT_KEYS | {"breakdown", "rehearsal"}
    want = _names("per_layer")
    assert set(last["metrics"]) <= want
    assert want - set(last["metrics"]) <= {"flush_occupancy.serve"}
    share = last["metrics"]["peer_down_fast_fail_share.serve"]
    assert share == {"value": 100.0, "unit": "%"}
    frames = last["metrics"]["down_peer_frames_per_txn.serve"]
    assert frames == {"value": 0.0, "unit": "frame/txn"}
    # most txns touch a shard of the crashed node: the slow path carries them
    assert last["metrics"]["fast_path_share.serve"]["value"] < 50
    assert last["metrics"]["recoveries_per_ktxn.serve"]["value"] >= 0
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
               for p in _line(earlier, "info")["problems"])


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_readers_find_nothing_on_a_program_without_the_counters(name):
    """The parent's stats() has no ``peer_failures`` and its links no
    ``enqueued``: the record then lacks the keys, and the readers return None
    and do not raise; nor on another driver's record."""
    reader = run._metric_reader(name)
    record = {"driver": "served", "acked": 10, "server": {"links_sent": 5},
              "counters": {"kernel_times": {}}}
    assert reader.read(record) is None
    assert reader.read({"driver": "store"}) is None


def test_new_readers_read_the_window():
    record = {"driver": "served", "acked": 50, "server": {
        "peer_failed_at_once": 90, "peer_timed_out": 10,
        "down_peer_enqueued": 25}}
    assert run._metric_reader("peer_down_fast_fail_share.serve").read(
        record) == pytest.approx(90.0)
    assert run._metric_reader("down_peer_frames_per_txn.serve").read(
        record) == pytest.approx(0.5)
    assert run._metric_reader("down_peer_frames_per_txn.serve").read(
        dict(record, acked=0)) is None


def test_the_cell_added_files_and_changed_none():
    """Against the commit before this cell (the first parent of the commit
    that added its configuration, else HEAD): nothing under benchmarks/ was
    modified or deleted, the new files are the five named, and BENCHMARK.json
    lost nothing."""
    def git(*args):
        return subprocess.run(["git", "-C", run.ROOT, *args], check=True,
                              capture_output=True, text=True).stdout
    try:
        added_in = git("log", "--diff-filter=A", "--format=%H", "--",
                       "benchmarks/configs/lin-kv-5n-1down.json").split()
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history here")
    base = added_in[-1] + "^" if added_in else "HEAD"
    changes = [ln.split("\t") for ln in git(
        "diff", "--name-status", "--no-renames", base, "--",
        "benchmarks").splitlines()]
    untracked = git("ls-files", "--others", "--exclude-standard", "--",
                    "benchmarks").split()
    assert {path for status, path in changes if status != "A"} == set()
    assert {path for _s, path in changes} | set(untracked) >= NEW_FILES
    before = json.loads(git("show", base + ":BENCHMARK.json"))
    now = _bench()
    assert (now["command"], now["paths"], now["run_seconds"]) == (
        before["command"], before["paths"], before["run_seconds"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert now[group][:len(before[group])] != [] and all(
            {k: v for k, v in new.items() if k != "workloads"}
            == {k: v for k, v in old.items() if k != "workloads"}
            and new.get("workloads", [])[:len(old.get("workloads", []))]
            == old.get("workloads", [])
            for old, new in zip(before[group], now[group])), group
