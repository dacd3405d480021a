"""Sandbox rehearsal of ``chip_smoke.py``: the same phase functions the chip
runs, at tiny sizes, in-process on the conftest's cpu devices — so the script
cannot rot between chip runs.  Nothing here claims a TPU: the only way to an
``"ok": true`` last line without one is ``main(rehearsal=True)``, which has no
command-line spelling and labels its output ``"rehearsal": true``.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

from accord_tpu.utils import faults  # noqa: E402
from accord_tpu.utils.random_source import RandomSource  # noqa: E402

TINY = chip_smoke.Sizes(n_txns=600, keyspace=6000, max_iv=4, batch=32,
                        n_queries=96, drain_slots=2000, drain_chains=16,
                        chain_depth=64, proto_txns=16, proto_keys=8,
                        serve_txns=12, serve_keys=8, serve_limit_s=120.0)


def _assert_ok(report):
    assert report["ok"], report["problems"]
    assert report["problems"] == []


def test_store_phase_rehearses():
    report = chip_smoke.run_phase("store", chip_smoke.phase_store, 21, TINY)
    _assert_ok(report)
    assert report["byte_equal_to_host_route"]
    assert report["sizes"]["n_queries"] == 96
    assert report["routes"]["device"]["n_bucketed_queries"] > 0
    assert report["routes"]["dense"]["n_dense_queries"] > 0
    assert report["n_device_faults"] == report["n_fallback_queries"] == 0


def test_drain_phase_rehearses():
    report = chip_smoke.run_phase("store.drain", chip_smoke.phase_drain,
                                  21, TINY)
    _assert_ok(report)
    assert report["drain_logdepth"] > 0
    assert report["drain_logdepth_failovers"] == 0
    assert 0 < report["ell_drained"] < TINY.drain_slots   # stuck slots bite


def test_protocol_phase_rehearses():
    report = chip_smoke.run_phase("protocol", chip_smoke.phase_protocol,
                                  21, TINY)
    _assert_ok(report)
    assert report["resolved"] == TINY.proto_txns
    assert report["verifier_passed"] and report["acked_writes_read_back"]
    assert report["n_host_queries"] == 0


def test_serve_phase_rehearses(tmp_path):
    report = chip_smoke.run_phase("serve", chip_smoke.phase_serve, 21, TINY,
                                  str(tmp_path))
    _assert_ok(report)
    assert report["codec"] == "binary" and report["journal_bytes"] > 0
    assert report["verifier_passed"] and report["acked_appends_read_back"]


@pytest.fixture
def only_store(monkeypatch, tmp_path):
    """main() with the store phase alone, writing under tmp_path, and the
    process's compile-cache setting put back afterwards (main() calls the
    start-up helper, which points jax at the checkout's cache)."""
    import jax
    monkeypatch.setattr(
        chip_smoke, "run", lambda seed, chips, sizes, out_dir=None: [
            chip_smoke.run_phase("store", chip_smoke.phase_store, seed,
                                 sizes)])
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_fault_ladder_gate_fails_the_phase_and_the_script(only_store,
                                                          capsys):
    """A refused kernel is served from the host bit-identically, so the
    comparison passes — the ladder counters are what must fail the run."""
    with faults.device_fault("kernel_launch", 1.0, RandomSource(7)):
        report = chip_smoke.run_phase("store", chip_smoke.phase_store,
                                      21, TINY)
        assert not report["ok"]
        assert report["byte_equal_to_host_route"]      # the ladder's promise
        assert any("n_device_faults" in p for p in report["problems"])
        assert report["n_fallback_queries"] > 0
        capsys.readouterr()
        rc = chip_smoke.main(["--seed", "21"], sizes=TINY, rehearsal=True)
    assert rc == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] is True


def test_rehearsal_last_line_is_labelled_and_names_the_cpu(only_store,
                                                           capsys):
    rc = chip_smoke.main(["--seed", "21"], sizes=TINY, rehearsal=True)
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"


def test_without_a_tpu_the_script_refuses_and_prints_no_result(only_store,
                                                               capsys):
    """As the driver runs it (no rehearsal keyword), on the cpu."""
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert capsys.readouterr().out == ""
