"""`BENCHMARK.json` against the parts of its contract that need no run:
the limits on names and lengths, and that everything it names is there."""

import os
import re

import pytest

import bench_contract
from benchmark.run import load_reader

BENCH = bench_contract.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(os.path.isdir(os.path.join(bench_contract.REPO, p))
               for p in BENCH["paths"])


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    held = bench_contract.load_benchmark(
        os.path.join(bench_contract.REPO, config["file"]))
    # every cut the entry lists is a key of the file, explained there
    assert set(config["reduced"]) == set(held["reduced"])
    assert all(key in held for key in config["reduced"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    for text in (config["source"], config["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = bench_contract.load_benchmark(os.path.join(
        bench_contract.BENCH, "traffic", cell["traffic"] + ".json"))
    assert os.path.isfile(os.path.join(bench_contract.BENCH, "kinds",
                                       traffic["kind"] + ".py"))
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if bench_contract.applies(m, cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(bench_contract.applies(m, cell["name"])
               for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    per_layer = metric in BENCH["per_layer"]
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) - {"workloads"} == want
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert all(w in CELLS for w in metric.get("workloads", CELLS))
    if per_layer:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        reader = load_reader(os.path.join(bench_contract.BENCH,
                                          "layer_metrics"), metric["name"])
        assert callable(reader.read)
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        # reported only where the metric it moves is
        assert all(bench_contract.applies(moved, w)
                   for w in metric.get("workloads", CELLS))
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25


def test_names_are_unique():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
