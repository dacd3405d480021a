"""CPU rehearsal of the cell ycsb-e-3n-live100k.closed16
(drivers/served_scan_live.py) at tiny sizes, through run.main's
``rehearsal`` argument: the contract line and the names it reports, the
history every store holds, the route probe, one planted control (a replica
store whose mirror is loaded without the history: the probe must read
``correct: false``), and a program without ``Node.install_history`` failing
at once."""

import json
import os
import time

import pytest

from benchmarks import run

CELL = "ycsb-e-3n-live100k.closed16"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = {"sizes": {"records": 3000, "history_per_store": 500,
                  "probe_queries": 16},
        "traffic": {"clients": 4, "warm_quiet_s": 1.0, "warm_max_s": 8.0,
                    "warm_txns": 30, "trace_slice_s": 1.0,
                    "expected_new_records": 100}}
NEW = {"live_entries_per_store.serve", "scan_hbm_share.serve"}


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
    return rc, lines[-1], lines


def _line(lines, kind):
    return next(ln for ln in lines if ln.get("line") == kind)


def test_cell_rehearses_and_prints_the_contract_line(capsys):
    rc, last, earlier = _run(capsys, 0)
    info = _line(earlier, "info")
    assert rc == 0 and last["correct"] is True, (last, info["problems"])
    assert last.pop("rehearsal") is True
    assert set(last) == CONTRACT_KEYS
    assert set(last["metrics"]) == _names("end_to_end") == {
        "commit_rate", "commit_p95", "setup_s"}
    assert last["attempted"] > 0 and last["failed"] == 0
    setup = _line(earlier, "setup")
    assert setup["history_txns"] == 2 * 500
    assert 350 <= setup["history_per_store_min"] \
        <= setup["history_per_store_max"] <= 700
    probes = info["route_probe"]["stores"]
    assert len(probes) == 6
    assert all(p["solo_equals_host"] and p["fused_equals_host"]
               and p["queries"] == 16 for p in probes)
    assert all(p[f"{route}_unlike_reference"] == 0 for p in probes
               for route in ("host", "solo", "fused"))
    assert sum(p["deps"] for p in probes) > 0
    # the solo flush and the fused launch each answered probe queries on
    # the device
    assert sum(p["solo_device"] for p in probes) > 0
    assert sum(p["fused_device"] for p in probes) > 0
    check = info["check"]
    assert check["records_read_back"] == 3000 + check["inserts_landed"]


def test_traced_run_reports_per_layer_metrics(capsys):
    rc, last, earlier = _run(capsys, 1)
    assert rc == 0 and last["correct"] is True, (
        last, _line(earlier, "info")["problems"])
    want = _names("per_layer")
    assert NEW <= want
    assert set(last["metrics"]) <= want
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert 350 <= metrics["live_entries_per_store.serve"] <= 800
    # a CPU's calibration may price every flush to either route: the share
    # is read only where the slice held a device flush
    assert want - set(last["metrics"]) <= {"flush_occupancy.serve",
                                           "scan_hbm_share.serve"}
    if "scan_hbm_share.serve" in metrics:
        assert metrics["scan_hbm_share.serve"] > 0


def test_a_mirror_without_the_history_makes_the_cell_incorrect(capsys):
    """The planted control (``test_history_without_mirror``, a traffic
    parameter no file sets): n2's first store holds the history in its
    records but not in its device mirror.  Both routes read the mirror and
    agree; the reference reads the driver's history and does not."""
    rc, last, earlier = _run(capsys, 0, test_history_without_mirror=1)
    assert rc != 0 and last["correct"] is False
    info = _line(earlier, "info")
    bad = [p for p in info["route_probe"]["stores"]
           if p["host_unlike_reference"]]
    assert [p["store"] for p in bad] == ["n2/0"]
    assert bad[0]["solo_unlike_reference"] \
        and bad[0]["fused_unlike_reference"]
    assert any("answers unlike the reference" in p
               for p in info["problems"])


def test_a_program_without_the_entry_fails_at_once(capsys, monkeypatch):
    from accord_tpu.local.node import Node
    monkeypatch.delattr(Node, "install_history")
    t0 = time.perf_counter()
    rc = run.main(["--workload", CELL, "--seed", "7", "--seconds", "5",
                   "--trace", "0"], rehearsal=TINY)
    assert rc != 0 and time.perf_counter() - t0 < 30
    out = capsys.readouterr().out
    assert "no Node.install_history" not in out        # said on stderr
    assert '"line": "setup"' not in out


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_readers_find_nothing_on_a_program_without_the_counters(name):
    record = {"driver": "served", "acked": 10, "trace": {"busy_s": 1.0},
              "counters": {"kernel_times": {}, "n_host_queries": 5}}
    assert run._metric_reader(name).read(record) is None
    assert run._metric_reader(name).read({"driver": "store"}) is None
