"""Helpers of the benchmark's tests: run one rehearsal, and hold its last
line to the contract the driver reads it by."""

import json
import os
import subprocess
import sys

from benchmark.run import applies  # noqa: F401 — the tests use it too

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


def load_benchmark(path: str = "") -> dict:
    with open(path or os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(cell: str, trace: int, seconds: float = 2, extra=()) -> dict:
    """One `--rehearse` run through the daemons; the parsed last line."""
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "7", "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse", *extra],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.splitlines()[-1])


def check_line(line: dict, bench: dict, cell: str, trace: int) -> None:
    """The keys the driver reads, and the metrics the cell owes."""
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    group = bench["per_layer" if trace else "end_to_end"]
    known = {m["name"]: m for m in group if applies(m, cell)}
    assert line["metrics"], "no metric reported"
    for name, got in line["metrics"].items():
        assert name in known, f"{name} is not a metric of {cell}"
        assert got["unit"] == known[name]["unit"]
        # a rehearsal runs on the CPU: only counts are numbers
        if known[name]["source"] == "program_counter":
            assert isinstance(got["value"], (int, float))
        else:
            assert got["value"] == "not measured"
    if not trace:
        assert set(line["metrics"]) == set(known)
