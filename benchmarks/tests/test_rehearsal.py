"""CPU rehearsal of the benchmark command: every driver sets up, warms, runs
a short window and passes its check in-process, through run.main's
``rehearsal`` argument (which has no command-line spelling)."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import run

ROOT = run.ROOT
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# small, yet large enough that the adaptive router keeps the scans on the
# device route whatever this machine's calibration probe reads, and a stream
# that outlasts the window
TINY_STORE = {"sizes": {"n_txns": 3000, "keyspace": 30000, "max_iv": 4,
                        "capacity": 4096, "hlc_span": 40000,
                        "floors": {"hlc": 5000, "width": 1500, "every": 3000,
                                   "below": 15000}},
              "traffic": {"stream_txns": 40000, "trace_slice_s": 1.0}}
QUICK_SERVED = {"sizes": {},
                "traffic": {"clients": 4, "warm_quiet_s": 1.0,
                            "warm_max_s": 8.0, "trace_slice_s": 1.0}}
CELLS = {
    "store-100k.scan-b2048": {**TINY_STORE, "traffic": {
        **TINY_STORE["traffic"], "batch": 64}},
    "store-100k.scan-b64": {**TINY_STORE, "traffic": {
        **TINY_STORE["traffic"], "batch": 32}},
    "lin-kv-3n.closed16": QUICK_SERVED,
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _names(kind, cell):
    return {m["name"] for m in _bench()[kind]
            if "workloads" not in m or cell in m["workloads"]}


def _run(capsys, cell, trace, rehearsal=None, seconds="2"):
    rc = run.main(["--workload", cell, "--seed", "2147483777",
                   "--seconds", seconds, "--trace", str(trace)],
                  rehearsal=rehearsal or CELLS[cell])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), [json.loads(ln) for ln in lines[:-1]
                                       if ln.startswith("{")]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearses_and_prints_the_contract_line(capsys, cell):
    rc, last, earlier = _run(capsys, cell, 0)
    assert rc == 0 and last["correct"] is True, (last, earlier[-2:])
    assert last.pop("rehearsal") is True      # never mistaken for a chip run
    assert set(last) == CONTRACT_KEYS
    assert set(last["metrics"]) == _names("end_to_end", cell)
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in last["metrics"].values())
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_run_reports_per_layer_metrics(capsys, cell):
    rc, last, earlier = _run(capsys, cell, 1)
    problems = [ln.get("problems") for ln in earlier if ln.get("line") == "info"]
    assert rc == 0 and last["correct"] is True, (problems, last)
    assert set(last) == CONTRACT_KEYS | {"breakdown", "rehearsal"}
    # every per-layer metric of the cell that has something to read on a
    # CPU (no device plane: the busy-time metrics read 0 or nothing)
    want = _names("per_layer", cell)
    assert set(last["metrics"]) <= want
    assert want - set(last["metrics"]) <= {"scan_hbm_share.store",
                                           "flush_occupancy.serve"}
    assert {"busy_s", "window_s"} <= set(last["device"])
    assert last["device"]["window_s"] > 0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(last["breakdown"]["idle_gaps"]) <= 10


def test_without_a_tpu_the_command_fails_and_prints_no_metric(capsys):
    rc = run.main(["--workload", "store-100k.scan-b64", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "metrics" not in out.out and "needs 1 TPU" in out.err


def test_an_armed_launch_fault_makes_the_store_cell_incorrect(capsys):
    """The ladder serves a refused kernel from the host bit-identically, so
    the probe still agrees; the counters are what says ``correct: false``."""
    from accord_tpu.utils import faults
    from accord_tpu.utils.random_source import RandomSource
    faults.inject_device_fault("kernel_launch", 1.0, RandomSource(7))
    try:
        rc, last, earlier = _run(capsys, "store-100k.scan-b2048", 0)
    finally:
        faults.clear_device_faults()
    assert rc != 0 and last["correct"] is False
    info = next(ln for ln in earlier if ln.get("line") == "info")
    assert any(p.startswith("n_device_faults=") for p in info["problems"])


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def test_a_later_pr_adds_a_cell_with_new_files_only(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell added
    as NEW files plus new BENCHMARK.json entries, in a copy of the
    benchmark: picked up with no edit to a file that was there."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: _sha(p) for p in map(str, copy.rglob("*")) if os.path.isfile(p)}
    bench = _bench()

    with open(copy / "benchmarks/configs/store-100k.json") as f:
        config = json.load(f)
    config["name"] = "store-tiny"
    config["sizes"].update(TINY_STORE["sizes"])
    (copy / "benchmarks/configs/store-tiny.json").write_text(
        json.dumps(config))
    (copy / "benchmarks/traffic/preaccept-churn-b16.json").write_text(
        json.dumps({"name": "preaccept-churn-b16", "driver": "store",
                    "batch": 32, "stream_txns": 20000,
                    "trace_slice_s": 0.5}))
    (copy / "benchmarks/metrics/flushes_in_window.tiny.py").write_text(
        'LAYER = "device dispatch"\nUNIT = "flush"\n'
        'SOURCE = "program_counter"\nMOVES = "preaccept_rate"\n\n\n'
        'def read(record):\n'
        '    if record.get("driver") != "store":\n        return None\n'
        '    return record["flushes"]\n')
    cell = "store-tiny.scan-b16"
    bench["configs"].append({
        "name": "store-tiny", "source": config["source"],
        "file": "benchmarks/configs/store-tiny.json", "reduced": ["n_txns"],
        "why": "rehearsal only"})
    bench["workloads"].append({
        "name": cell, "config": "store-tiny",
        "traffic": "preaccept-churn-b16", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "store-100k.scan-b64" in m.get("workloads", ()):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "flushes_in_window.tiny", "unit": "flush", "better": "higher",
        "source": "program_counter", "layer": "device dispatch",
        "moves": "preaccept_rate", "workloads": [cell]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    code = ("import sys; sys.path.insert(0, %r); from benchmarks import run; "
            "raise SystemExit(run.main(['--workload', %r, '--seed', '5', "
            "'--seconds', '1', '--trace', '1'], rehearsal={'sizes': {}}))"
            % (str(copy), cell))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=copy,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"]["flushes_in_window.tiny"]["value"] > 0
    assert "device_query_share.store" in last["metrics"]
    assert "flush_p50.b2048" not in last["metrics"]
    after = {p: _sha(p) for p in before}
    assert after == before                    # nothing that was there changed
