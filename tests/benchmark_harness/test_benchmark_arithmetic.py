"""The benchmark's metric arithmetic on fixed inputs: what a later PR's
numbers are compared by must not move unseen."""

import math

import pytest

import os
import types

import bench_contract
from benchmark import reference, roofline, stats, xplane
from benchmark.cluster import Failed
from benchmark.layer_metrics import _shared
from benchmark.run import load_reader

READERS = os.path.join(bench_contract.BENCH, "layer_metrics")


def fake_run(**fields):
    """What a reader reads, made by hand."""
    run = types.SimpleNamespace(
        ops=[], samples={}, traced=None, trace_window=None,
        device={"kind": "TPU v5 lite"}, config={}, journal=[])
    run.events = lambda etype: [e["attrs"] for e in run.journal
                                if e["type"].startswith(etype)]
    run.traced_ops = lambda: [
        op for op in run.ops if run.trace_window
        and op["t0"] >= run.trace_window[0]
        and op["t1"] <= run.trace_window[1]]
    vars(run).update(fields)
    return run


@pytest.mark.parametrize("xs, want", [
    ([3.0], 3.0), ([1, 9, 5], 5), ([4, 1, 3, 2], 2.5)])
def test_median(xs, want):
    assert stats.median(xs) == want


@pytest.mark.parametrize("q, want", [(50, 50), (99, 99), (100, 100), (1, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(list(range(1, 101)), q) == want


def test_percentile_of_a_small_sample_is_its_largest():
    assert stats.percentile([2.0, 7.0, 3.0], 99) == 7.0


def test_latency_counts_from_the_due_time_and_failures_are_infinite():
    # request 1 was due at 1.0 but a stall sent it late: it is charged
    # from 1.0; request 2 failed and is over any limit
    got = stats.due_latencies_ms([0.0, 1.0, 2.0], [0.25, 1.75, 2.25],
                                 [True, True, False])
    assert got[:2] == [250.0, 750.0] and got[2] == math.inf


def test_rs_ops_and_bytes_from_shapes():
    ops, nbytes = roofline.rs_ops_bytes(32, 14, 2, 1 << 20)
    cols = 32 << 20
    assert nbytes == cols * 16
    assert ops == 2 * (8 * 2) * (8 * 14) * cols


def test_crc_ops_and_bytes_from_shapes():
    ops, nbytes = roofline.crc_ops_bytes(2, 4096)
    assert nbytes == 8192
    assert ops == 2 * 2 * 8 * (32 * 32 + 8 * 512 * 32)


def test_roofline_share_names_the_roof():
    # RS(14,2) moves 16 bytes and 3584 int8 ops a column: the memory
    # roof is the higher on a v5e
    ops, nbytes = roofline.rs_ops_bytes(32, 14, 2, 1 << 20)
    least = nbytes / 819e9
    share, roof = roofline.share(ops, nbytes, 4 * least, "TPU v5 lite")
    assert roof == "memory" and share == pytest.approx(25.0)
    share, roof = roofline.share(1e15, 1.0, 10.0, "TPU v5 lite")
    assert roof == "compute" and share == pytest.approx(100 / 3.93)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")


def test_union_and_gaps():
    busy = xplane.union([(10, 20), (15, 30), (50, 60), (60, 65)])
    assert busy == [(10, 30), (50, 65)]
    assert xplane.gaps(busy, 0, 100) == [(0, 10), (30, 50), (65, 100)]


def test_reduce_planes_on_a_made_up_trace():
    planes = [
        {"name": "/host:CPU", "lines": {"main": [("x", 0, 1000)]}},
        {"name": "/device:TPU:0", "lines": {
            "XLA Modules": [("jit_encode_jit(123)", 100, 400),
                            ("jit_encode_jit(123)", 600, 300),
                            ("jit__lambda_(9)", 1500, 100)],
            "XLA Ops": [("fusion", 100, 100), ("custom-call", 250, 250),
                        ("custom-call", 600, 300), ("while", 1500, 100)]}}]
    got = xplane.reduce_planes(planes)
    assert got["devices"] == 1
    assert got["busy_s"] == pytest.approx(750e-9)
    assert got["span_s"] == pytest.approx(1600e-9)
    assert got["programs"]["jit_encode_jit"] == [2, pytest.approx(700e-9)]
    assert got["device_ops"][0] == ["custom-call", pytest.approx(550e-9)]
    # longest gap first: 900..1500, starts counted from the first event
    assert got["gaps"][0] == [pytest.approx(900e-9), pytest.approx(600e-9)]


def test_a_trace_without_device_events_has_no_devices():
    planes = [{"name": "/host:CPU", "lines": {"main": [("x", 0, 10)]}}]
    assert xplane.reduce_planes(planes) == {"devices": 0}


def test_gaps_are_labelled_by_the_phase_that_covers_most_of_them():
    phases = [[0.0, 4.0, "seal"], [4.0, 5.0, "check+restore"]]
    got = xplane.label_gaps([[0.5, 2.0], [3.5, 1.5], [9.0, 1.0]], phases)
    assert got == [["seal", 2.0], ["check+restore", 1.5], ["unlabelled", 1.0]]


def test_prometheus_samples_are_summed_by_label():
    text = ('# HELP x\n'
            'S_sum{type="get",stage="store"} 2.5\n'
            'S_sum{type="get",stage="queue_wait"} 1.5\n'
            'S_sum{type="post",stage="store"} 4\n'
            'S_count{type="get",stage="store"} 10\n')
    assert _shared.prom(text, "S_sum") == 8.0
    assert _shared.prom(text, "S_sum", stage="store") == 6.5
    assert _shared.prom(text, "S_sum", type="get", stage="store") == 2.5


@pytest.mark.parametrize("name, file", [
    ("crc_kernel_roofline", "crc_kernel_roofline"),
    ("seal_device_idle_share", "device_idle_share"),
    ("a_later_cell_compiles_in_window", "compiles_in_window"),
    ("scrub_verb_overhead_share", "verb_overhead_share"),
    ("scrub_needles_per_s", "scrub_needles_per_s")])
def test_a_reader_is_found_by_the_longest_tail_of_the_name(name, file):
    assert load_reader(READERS, name).__name__.endswith("_" + file)


def test_a_metric_without_a_reader_fails_the_run():
    with pytest.raises(Failed):
        load_reader(READERS, "no_such_reading")


def test_verb_overhead_is_what_the_programs_span_leaves_of_the_wall():
    reader = load_reader(READERS, "seal_verb_overhead_share")
    run = fake_run(
        ops=[{"label": "seal", "wall_s": 6.0}, {"label": "seal", "wall_s": 4.0}],
        journal=[{"type": "ec.encode.finish", "attrs": {"wall_s": 2.5}},
                 {"type": "ec.encode.finish", "attrs": {"wall_s": 1.5}}])
    assert reader.read(run) == pytest.approx(60.0)
    run = fake_run(
        ops=[{"label": "repair", "wall_s": 5.0}],
        journal=[{"type": "ec.rebuild.finish",
                  "attrs": {"duration_ms": 4000}}])
    assert reader.read(run) == pytest.approx(20.0)
    run = fake_run(ops=[{"label": "scrub", "wall_s": 10.0, "volumes": {
        1: {"elapsed_s": 4.0}, 2: {"elapsed_s": 5.0}}}])
    assert reader.read(run) == pytest.approx(10.0)
    assert reader.read(fake_run()) is None


def test_rs_roofline_counts_the_bytes_needed_not_the_batches_dispatched():
    # one seal of 14 MB at RS(14,2) wholly inside the trace, another cut
    # by its end: 1 MB a row, 16 MB moved; the kernel's programs took 4x
    # the least time for them, however many padded batches they were
    seal = {"label": "seal", "bytes": 14e6, "t0": 1.0, "t1": 2.0}
    least = 16e6 / 819e9
    run = fake_run(
        ops=[seal, {**seal, "t0": 2.0, "t1": 9.0}], trace_window=(0.5, 3.0),
        config={"data_shards": 14, "parity_shards": 2},
        traced={"programs": {"jit_encode_jit": [3, 3 * least],
                             "jit_encode_jit.1": [1, least],
                             "jit_other": [1, 1.0]}})
    got = load_reader(READERS, "rs_encode_kernel_roofline").read(run)
    assert got == pytest.approx(25.0)
    run.trace_window = (1.5, 3.0)  # no whole seal inside: nothing to read
    assert load_reader(READERS, "rs_encode_kernel_roofline").read(run) is None


def test_rebuild_roofline_reads_d_shards_and_writes_the_lost():
    d, dat = 10, 10 * (1 << 20)
    shard = reference.shard_file_size(dat, d)
    least = shard * (d + 2) / 819e9
    run = fake_run(
        ops=[{"label": "repair", "bytes": dat, "lost": [3, 7],
              "t0": 1.0, "t1": 2.0}], trace_window=(0.0, 3.0),
        config={"data_shards": d},
        traced={"programs": {"jit_reconstruct_jit": [1, 2 * least]}})
    got = load_reader(READERS, "rs_rebuild_kernel_roofline").read(run)
    assert got == pytest.approx(50.0)


def test_crc_roofline_takes_the_sweeps_bytes_by_the_slices_share():
    # a 0.5 s slice of a sweep that verified 80 MB in 8 s of scrubbing:
    # 5 MB needed, whatever the blocks were padded to
    run = fake_run(
        ops=[{"label": "scrub", "bytes": 80e6, "t0": 10.0, "t1": 19.0,
              "volumes": {1: {"elapsed_s": 3.0}, 2: {"elapsed_s": 5.0}}}],
        trace_window=(14.0, 14.5),
        traced={"programs": {"jit__lambda_": [9, 0.2]}})
    ops, _ = roofline.crc_ops_bytes(1, 5e6)
    want = 100.0 * max(5e6 / 819e9, ops / 393e12) / 0.2
    got = load_reader(READERS, "crc_kernel_roofline").read(run)
    assert got == pytest.approx(want)


def test_device_idle_share_and_percentiles():
    run = fake_run(traced={"busy_s": 0.5}, trace_window=(10.0, 12.0),
                   samples={"get_ms": list(range(1, 101))})
    assert load_reader(READERS, "x_device_idle_share").read(run) == 75.0
    assert load_reader(READERS, "get_p95_ms").read(run) == 95
    assert load_reader(READERS, "put_p99_ms").read(run) is None


def test_a_typical_seconds_tail_ignores_one_stall():
    # 5 seconds of 100 requests at 1..100 ms; a stall makes second 2 ten
    # times slower and sets the window's own p99
    at = [k + i / 100 for k in range(5) for i in range(100)]
    xs = [float(1 + i) * (10 if k == 2 else 1)
          for k in range(5) for i in range(100)]
    assert stats.percentile(xs, 99) == 950.0
    assert stats.sliced_percentile(at, xs, 99) == 99.0
    assert stats.sliced_percentile([0.2, 0.4], [5.0, 7.0], 99) == 7.0
