"""The trace reduction on a small trace recorded on a v5e chip
(`benchmark/testdata/small.xplane.pb`: two `encode_jit` calls and two CRC
scans, with sleeps between): busy union, per-program sums, gaps."""

import json
import os
import subprocess
import sys

import pytest

import bench_contract
from benchmark import xplane

TRACE = os.path.join(bench_contract.BENCH, "testdata", "small.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return xplane.read_planes(TRACE)


@pytest.fixture(scope="module")
def reduced(planes):
    return xplane.reduce_planes(planes)


def device_lines(planes):
    (device,) = [p for p in planes if p["name"].startswith("/device:TPU:")]
    return device["lines"]


def test_one_device_ran_operations(reduced):
    assert reduced["devices"] == 1


def test_program_sums(planes, reduced):
    modules = device_lines(planes)["XLA Modules"]
    assert [xplane.program_name(n) for n, _, _ in modules] == [
        "jit_encode_jit", "jit__lambda", "jit_encode_jit", "jit__lambda"]
    for name in ("jit_encode_jit", "jit__lambda"):
        runs = [d for n, _, d in modules if n.startswith(name + "(")]
        assert reduced["programs"][name] == [
            2, pytest.approx(sum(runs) / 1e9)]
    assert reduced["programs"]["jit_encode_jit"][1] == pytest.approx(3.429e-6)


def test_busy_is_the_union_of_the_operations(planes, reduced):
    ops = device_lines(planes)["XLA Ops"]
    # the slow way: mark every nanosecond an operation covers
    covered = set()
    for _, s, d in ops:
        covered.update(range(s, s + d))
    assert reduced["busy_s"] == pytest.approx(len(covered) / 1e9)
    # operations nest and overlap (a while and its body): the union is
    # less than their sum, and no more than the programs' time
    assert reduced["busy_s"] < sum(d for _, _, d in ops) / 1e9
    assert reduced["busy_s"] <= sum(t for _, t in
                                    reduced["programs"].values()) * 1.001


def test_gaps_lie_between_the_programs(planes, reduced):
    modules = sorted(device_lines(planes)["XLA Modules"],
                     key=lambda m: m[1])
    t0 = reduced["t0_ns"]
    # the sleeps of the recording script: 20 ms after each encode, 50 ms
    # after each scan; the longest gaps come first
    (_, s2, _), (_, s3, _) = modules[1], modules[2]
    between = [g for g in reduced["gaps"] if g[1] > 1e-3
               and abs(g[0] + g[1] - (s3 - t0) / 1e9) < 1e-4]
    assert len(between) == 1 and between[0][1] > 0.05
    assert between[0][0] == pytest.approx(
        (s2 + modules[1][2] - t0) / 1e9, abs=1e-4)
    assert reduced["gaps"] == sorted(reduced["gaps"], key=lambda g: -g[1])
    idle = sum(e - s for s, e in xplane.gaps(
        xplane.union([(s, s + d) for _, s, d in
                      device_lines(planes)["XLA Ops"]]),
        t0, t0 + round(reduced["span_s"] * 1e9))) / 1e9
    assert idle + reduced["busy_s"] == pytest.approx(reduced["span_s"])


def test_top_operations_are_short_names(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert "encode_jit.1" in names and "while.1" in names
    assert all(" " not in n for n in names) and len(names) <= xplane.TOP


def test_the_command_line_prints_the_same(reduced):
    r = subprocess.run([sys.executable, "-m", "benchmark.xplane", TRACE],
                       cwd=bench_contract.REPO, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.splitlines()[-1]) == json.loads(
        json.dumps(reduced))
