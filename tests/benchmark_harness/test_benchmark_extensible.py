"""A later PR adds a cell, a traffic kind and a per-layer metric as new
files and new entries, editing none: dropped into temp copies of the
benchmark's directories, they run."""

import json
import os
import shutil

import bench_contract

NEW_KIND = '''
"""A traffic kind of a later PR: scrub sweeps of single volumes."""
from benchmark import stats
from benchmark.kinds import scrub_sweep

generate, install, verify = (scrub_sweep.generate, scrub_sweep.install,
                             scrub_sweep.verify)


def run(run):
    vids = [m.vid for m in run.samples["group"]]
    while True:
        op = scrub_sweep.sweep(run, vids[len(run.ops) % len(vids)])
        run.op_done({**op, "label": "scrub", "bytes": 1})
        if len(run.ops) >= run.traffic["sweeps"]:
            break
    return {"attempted": len(run.ops), "failed": 0,
            "metrics": {"scrub_GBps": stats.median(
                [op["bytes"] / op["wall_s"] / 1e9 for op in run.ops])}}
'''

NEW_METRIC = '''
def read(run):
    return float(len(run.ops))
'''


def test_a_new_cell_kind_and_metric_are_files_and_entries_only(tmp_path):
    base = tmp_path / "benchmark"
    for sub in ("traffic", "kinds", "layer_metrics"):
        shutil.copytree(os.path.join(bench_contract.BENCH, sub), base / sub)
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    (base / "kinds" / "scrub_one_by_one.py").write_text(NEW_KIND)
    (base / "traffic" / "scrub_single.json").write_text(json.dumps(
        {"kind": "scrub_one_by_one", "corrupt_per_volume": 0, "sweeps": 3,
         # traced as the scrub cell is: a slice, begun inside the window
         "trace_after_s": 0.3, "trace_seconds": 0.3, "trace_stop": "timer"}))
    (base / "layer_metrics" / "scrub_sweeps.py").write_text(NEW_METRIC)
    bench = bench_contract.load_benchmark()
    cell = "fork_cold_rs14_2.scrub_single"
    bench["workloads"].append(
        {"name": cell, "config": "fork_cold_rs14_2",
         "traffic": "scrub_single", "chips": 1, "why": "a later PR's cell"})
    for m in bench["end_to_end"]:
        if m["name"] == "scrub_GBps":
            m["workloads"].append(cell)
    bench["per_layer"].append(
        {"name": "scrub_sweeps", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "scrub",
         "moves": "scrub_GBps", "workloads": [cell]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    extra = ("--base", str(base), "--benchmark", str(path))
    line = bench_contract.rehearse(cell, 0, extra=extra)
    bench_contract.check_line(line, bench, cell, 0)
    line = bench_contract.rehearse(cell, 1, extra=extra)
    bench_contract.check_line(line, bench, cell, 1)
    assert line["metrics"]["scrub_sweeps"] == {"value": 3.0, "unit": "count"}
    assert line["attempted"] == 3
    # nothing that was there was edited
    assert all(p.read_bytes() == was for p, was in before.items())
