"""The reader of `rebuild_read_overlap` (PR 29): the loads' own seconds
over the read stage's wall, summed over the window's rebuilds; nothing,
and no error, from a program that books no `read_busy_s`."""

import os
import types

import pytest

import bench_contract
from benchmark.run import load_reader

READERS = os.path.join(bench_contract.BENCH, "layer_metrics")


def read(events):
    run = types.SimpleNamespace(journal=[
        {"type": "ec.rebuild.finish", "attrs": attrs} for attrs in events])
    run.events = lambda etype: [e["attrs"] for e in run.journal
                                if e["type"].startswith(etype)]
    return load_reader(READERS, "rebuild_read_overlap").read(run)


def test_the_ratio_is_of_the_sums_over_the_windows_rebuilds():
    assert read([
        {"duration_ms": 1500.0, "read_s": 1.0, "read_busy_s": 3.5,
         "read_local_busy_s": 1.0, "read_remote_busy_s": 2.5},
        {"duration_ms": 900.0, "read_s": 0.5, "read_busy_s": 1.0},
    ]) == pytest.approx(3.0)


@pytest.mark.parametrize("events", [
    [],
    # the parent's program: stages, and no sum of the loads beside them
    [{"duration_ms": 3000.0, "read_s": 2.6, "drain_s": 0.26}],
    # a failed rebuild's event carries neither
    [{"ok": False, "error": "short read of shard 3 at 0"}],
], ids=["no_rebuild", "no_read_busy_s", "failed_rebuild"])
def test_nothing_to_read_is_none(events):
    assert read(events) is None


def test_one_event_without_the_field_is_left_out_not_counted_as_zero():
    assert read([
        {"duration_ms": 3000.0, "read_s": 2.6},
        {"duration_ms": 1500.0, "read_s": 1.0, "read_busy_s": 2.5},
    ]) == pytest.approx(2.5)


def test_the_entry_in_benchmark_json():
    bench = bench_contract.load_benchmark()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "rebuild_read_overlap"]
    assert entry == {
        "name": "rebuild_read_overlap", "unit": "x", "better": "higher",
        "source": "program_span", "layer": "rebuild",
        "moves": "repair_GBps", "workloads": ["upstream_rs10_4.repair"]}
    assert bench["per_layer"][-1] == entry  # appended, nothing moved
