"""A tiny CPU rehearsal of the `fork_cold_rs14_2.degraded_get` cell, end
to end through the daemons: two small volumes sealed at RS(14,2), two of
B's data shards of each lost, Zipf GETs through A's degraded reads, and
the last line against the contract. (One file per cell: they run on
separate workers.)"""

import json
import os

import pytest

import bench_contract

CELL = "fork_cold_rs14_2.degraded_get"
# the per-layer entries of the serve cell whose readers read this cell too
SHARED = ("get_p50_ms", "get_p95_ms", "get_p99_ms", "gen_late_p99_ms",
          "http_queue_wait_share", "store_stage_us", "store_read_us",
          "read_pool_wait_us", "loop_stalls_over_50ms",
          "serve_compiles_in_window", "serve_device_idle_share")


def test_the_entries_of_the_cell_in_benchmark_json():
    bench = bench_contract.load_benchmark()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "fork_cold_rs14_2_degraded", "degraded_get", 1)
    (config,) = [c for c in bench["configs"]
                 if c["name"] == cell["config"]]
    assert config["file"] == "benchmark/configs/fork_cold_rs14_2_degraded.json"
    # the cuts of scale: volume size, volumes, holders, and with them the
    # key population
    assert set(config["reduced"]) == {"volume_limit_mb", "ec_volumes",
                                      "shard_holders", "recordcount"}
    # a deployment of its own: no other configuration's file has every
    # number it keeps uncut held here under the same key
    def numbers(entry):
        with open(os.path.join(bench_contract.REPO, entry["file"])) as f:
            return {k: v for k, v in json.load(f).items()
                    if isinstance(v, (int, float))}

    held = numbers(config)
    for other in bench["configs"]:
        if other is config:
            continue
        theirs = numbers(other)
        kept = {k: v for k, v in theirs.items() if k not in other["reduced"]}
        assert any(held.get(k) != v for k, v in kept.items()), other["name"]
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == "get_p99_1s_ms"]
    assert CELL in e2e["workloads"]
    reads = {m["name"] for m in bench["per_layer"]
             if bench_contract.applies(m, CELL)}
    assert reads == set(SHARED)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_contract_line(trace):
    bench = bench_contract.load_benchmark()
    line = bench_contract.rehearse(CELL, trace)
    bench_contract.check_line(line, bench, CELL, trace)
    if trace:
        got = {k: v["value"] for k, v in line["metrics"].items()}
        assert got["serve_compiles_in_window"] == 0
        # the degraded GETs run through A's HTTP loop, store stage and
        # read pool, so the serve cell's counters read them too
        assert {"http_queue_wait_share", "store_stage_us",
                "read_pool_wait_us", "loop_stalls_over_50ms"} <= set(got)
