"""BENCHMARK.json and the files it names, against the rules of the
benchmark's contract that can be checked without a run."""

import importlib.util
import json
import os
import re

from benchmarks import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells_of(metric, cells):
    return set(metric.get("workloads", cells))


def test_keys_names_and_limits():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"] and b["command"][1].startswith(
        "benchmarks/")
    assert 1 <= b["run_seconds"] <= 51
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in b[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and 1 <= len(e["why"]) <= 200
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_every_cell_finds_its_files_and_reports_enough():
    b = _bench()
    cells = [w["name"] for w in b["workloads"]]
    configs = {c["name"]: c for c in b["configs"]}
    assert {w["config"] for w in b["workloads"]} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in b["workloads"]:
        assert w["chips"] in (1, 4)
        entry = configs[w["config"]]
        assert entry["file"].startswith("benchmarks/")
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "drivers", config["driver"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in b["end_to_end"])
    for cell in cells:
        e2e = {m["name"] for m in b["end_to_end"]
               if cell in _cells_of(m, cells)}
        assert len(e2e - {"setup_s"}) >= 1
        layer = [m for m in b["per_layer"] if cell in _cells_of(m, cells)]
        assert layer
        for m in layer:        # what a layer metric moves is reported there
            assert m["moves"] in e2e, (cell, m["name"])


def test_every_per_layer_metric_has_its_reader_and_they_agree():
    for m in _bench()["per_layer"]:
        path = os.path.join(ROOT, "benchmarks", "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
        # a reader that does not know the driver returns nothing
        assert mod.read({"driver": "some-later-driver"}) is None
