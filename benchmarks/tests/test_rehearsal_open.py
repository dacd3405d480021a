"""CPU rehearsal of the open-loop driver (drivers/served_open.py) with the
traffic mix open-poisson-90, as the cell ``lin-kv-3n.open-08knee`` would run
it.  run.py finds a cell's driver by its CONFIGURATION's ``driver`` key, so a
second driver on the configuration ``lin-kv-3n`` cannot be reached with new
files alone (PERF.md, Open questions).  These tests therefore run in a
temporary copy of the benchmark in which run.py takes the driver from the
traffic mix where it names one (``traffic.get("driver", config["driver"])``:
every traffic file has carried the key since PR 23) and BENCHMARK.json has
the cell: the one-line edit and the entries a ``benchmark`` PR has to make."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import run

ROOT = run.ROOT
CELL = "lin-kv-3n.open-08knee"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
LINE = 'f"benchmarks.drivers.{config[\'driver\']}")'
QUICK = {"sizes": {}, "traffic": {"rate": 25.0, "clients": 4,
                                  "warm_quiet_s": 1.0, "warm_max_s": 8.0,
                                  "trace_slice_s": 1.0}}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    copy = tmp_path_factory.mktemp("open") / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    text = (copy / "benchmarks/run.py").read_text()
    assert text.count(LINE) == 1
    (copy / "benchmarks/run.py").write_text(text.replace(
        LINE, 'f"benchmarks.drivers."\n        '
              'f"{traffic.get(\'driver\', config[\'driver\'])}")'))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": CELL, "config": "lin-kv-3n", "traffic": "open-poisson-90",
        "chips": 1, "why": "open loop at 0.8 x the closed loop's rate"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lin-kv-3n.closed16" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    return copy


def _run(checkout, trace, fault=False):
    code = ("import sys; sys.path.insert(0, %r); from benchmarks import run\n"
            "if %r:\n"
            "    from accord_tpu.utils import faults\n"
            "    from accord_tpu.utils.random_source import RandomSource\n"
            "    faults.inject_device_fault('kernel_launch', 1.0, "
            "RandomSource(7))\n"
            "raise SystemExit(run.main(['--workload', %r, '--seed', "
            "'2147484111', '--seconds', '4', '--trace', %r], rehearsal=%r))"
            % (str(checkout), fault, CELL, str(trace), QUICK))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_ENABLE_X64="true")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=checkout,
                         capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in res.stdout.strip().splitlines()
             if ln.startswith("{")]
    return res, lines[-1], lines


def _info(lines):
    return next(ln for ln in lines if ln.get("line") == "info")


def test_open_loop_rehearses_and_prints_the_contract_line(checkout):
    res, last, earlier = _run(checkout, 0)
    assert res.returncode == 0 and last["correct"] is True, (
        res.stderr[-2000:], _info(earlier)["problems"])
    assert last.pop("rehearsal") is True
    assert set(last) == CONTRACT_KEYS
    assert set(last["metrics"]) == {"commit_rate", "commit_p95", "setup_s"}
    assert all(v["value"] > 0 for v in last["metrics"].values())
    open_loop = _info(earlier)["open_loop"]
    # 4 s at 25 txn/s: the schedule is the seed's, whatever the system does
    assert open_loop["offered_rate"] == 25.0
    assert 60 <= open_loop["arrivals"] <= 140
    assert last["attempted"] == open_loop["arrivals"]
    late = open_loop["generator_late_ms"]
    assert 0 <= late["p50"] <= late["p95"] <= late["max"]
    # a latency is timed from when the request was due: never below the
    # generator's own lateness
    window = next(ln for ln in earlier if ln.get("line") == "window")
    assert window["end_to_end"]["commit_p95"] >= late["p50"]


def test_traced_open_loop_reports_the_serve_metrics(checkout):
    res, last, earlier = _run(checkout, 1)
    assert res.returncode == 0 and last["correct"] is True, (
        res.stderr[-2000:], _info(earlier)["problems"])
    assert set(last) == CONTRACT_KEYS | {"breakdown", "rehearsal"}
    assert {"msgs_per_txn.serve", "host_cpu_per_txn.serve",
            "device_idle_share.serve", "journal_bytes_per_txn.serve",
            "window_compile_s.serve"} <= set(last["metrics"])
    assert last["device"]["window_s"] > 0


def test_an_armed_launch_fault_makes_the_open_loop_incorrect(checkout):
    res, last, earlier = _run(checkout, 0, fault=True)
    assert res.returncode != 0 and last["correct"] is False
    assert any(p.startswith("n_device_faults=")
               for p in _info(earlier)["problems"])


def test_the_schedule_is_the_seeds_and_poisson():
    from benchmarks.drivers import served_open
    d = served_open.Driver.__new__(served_open.Driver)
    d.seed, d.traffic = 2147484111, {"rate": 90.0, "append_share": 0.5}
    d.keys, d.names = list(range(100)), ["n1", "n2", "n3"]
    schedule = d._schedule(30.0)
    assert schedule == d._schedule(30.0)
    d.seed += 1
    assert schedule != d._schedule(30.0)
    dues = [due for due, *_rest in schedule]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 30.0
    assert 0.9 * 2700 <= len(schedule) <= 1.1 * 2700
    assert 0.4 <= sum(1 for *_x, append in schedule if append) \
        / len(schedule) <= 0.6
