"""A tiny CPU rehearsal of the `upstream_rs10_4.serve_scrubbing` cell, end
to end through the daemons: the open-loop load on A while the scrub loop
sweeps A's volumes through the walker process, the seeded rot held to
every sweep, and the last line against the contract. Then the kind's
`verify` alone, on sweeps that missed or added a rotten needle, and the
window's sweep rate. (One file per cell: they run on separate workers.)"""

import types

import pytest

import bench_contract
from benchmark.run import load_module

CELL = "upstream_rs10_4.serve_scrubbing"
KIND = load_module(bench_contract.BENCH + "/kinds", "serve_scrubbing")
# name: (unit, better, source, layer); each moves get_p99_1s_ms
PER_LAYER = {
    "scrubbing_get_p50_ms": ("ms", "lower", "host_clock", "client"),
    "scrubbing_gen_late_p99_ms": ("ms", "lower", "host_clock", "client"),
    "scrubbing_loop_stalls_over_50ms": ("count", "lower", "program_counter",
                                        "HTTP loop"),
    "scrubbing_store_stage_us": ("us", "lower", "program_counter", "store"),
    "scrubbing_scrub_needles_per_s": ("needles/s", "higher", "program_span",
                                      "scrub"),
    "scrubbing_scrub_recv_share": ("%", "lower", "program_span", "scrub"),
    "scrubbing_crc_blocks_roofline": ("%", "higher", "device_trace",
                                      "kernels"),
    "scrubbing_device_idle_share": ("%", "lower", "device_trace", "device"),
    "scrubbing_compiles_in_window": ("count", "lower", "program_counter",
                                     "coders"),
    "scrubbing_get_p99_ms": ("ms", "lower", "host_clock", "client"),
    "scrubbing_put_p99_ms": ("ms", "lower", "host_clock", "client"),
    "scrubbing_store_read_us": ("us", "lower", "program_counter", "store"),
    "scrubbing_read_pool_wait_us": ("us", "lower", "program_counter",
                                    "HTTP loop"),
    "scrubbing_http_queue_wait_share": ("%", "lower", "program_counter",
                                        "HTTP loop"),
    "scrubbing_scrub_window_needles_per_s": ("needles/s", "higher",
                                             "host_clock", "scrub"),
}


def test_the_entries_of_the_cell_in_benchmark_json():
    """The cell runs its own configuration, the small-file server under
    its bit-rot cron, and reports `get_p99_1s_ms` and these per-layer
    entries, each moving it and for this cell alone."""
    bench = bench_contract.load_benchmark()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "upstream_scrub_cron", "serve_scrubbing", 1)
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == "get_p99_1s_ms"]
    assert CELL in e2e["workloads"]
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"] in PER_LAYER}
    assert set(entries) == set(PER_LAYER)
    for name, (unit, better, source, layer) in PER_LAYER.items():
        assert entries[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "get_p99_1s_ms", "workloads": [CELL]}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_contract_line(trace):
    bench = bench_contract.load_benchmark()
    line = bench_contract.rehearse(CELL, trace)
    bench_contract.check_line(line, bench, CELL, trace)
    if trace:  # counts, so numbers on the CPU too
        assert line["metrics"]["scrubbing_compiles_in_window"]["value"] == 0


ROTTEN = {(1, 0x11), (1, 0x2a), (2, 0x07)}


def _run(reported, needles=(1000, 1000), mode="xla-cpu"):
    """A finished window with one sweep, as the kind keeps it."""
    sweep = {"label": "scrub", "reported": reported,
             "volumes": {vid: {"needles": n, "elapsed_s": 0.5, "mode": mode}
                         for vid, n in zip((1, 2), needles)}}
    return types.SimpleNamespace(
        samples={"rotten": set(ROTTEN), "loaded": {1: 1000, 2: 1000},
                 "load": types.SimpleNamespace(puts=[])},
        ops=[sweep], rehearsal=False)


@pytest.mark.parametrize("case, run, want", [
    ("exact", _run(set(ROTTEN), mode="device"), True),
    ("missed_one", _run(ROTTEN - {(2, 0x07)}, mode="device"), False),
    ("one_too_many", _run(ROTTEN | {(2, 0x08)}, mode="device"), False),
    ("fewer_needles", _run(set(ROTTEN), (1000, 999), "device"), False),
    ("off_the_device", _run(set(ROTTEN)), False),
], ids=lambda x: x if isinstance(x, str) else "")
def test_verify_holds_every_sweep_to_the_seeded_rot(case, run, want):
    assert KIND.verify(run) is want


def test_verify_needs_a_sweep():
    run = _run(set(ROTTEN), mode="device")
    run.ops = []
    assert KIND.verify(run) is False


def test_the_window_rate_sees_the_pauses_between_sweeps():
    """`scrub_window_needles_per_s` reads the needles verified per second
    of the window, a sweep that runs past its end counted for its share
    inside: a pause between sweeps lowers it, where the rate over the
    sweeps' own elapsed time does not move."""
    reader = load_module(bench_contract.BENCH + "/layer_metrics",
                         "scrub_window_needles_per_s")
    per_sweep = load_module(bench_contract.BENCH + "/layer_metrics",
                            "scrub_needles_per_s")

    def run(starts, window_wall=100.0):
        ops = [{"label": "scrub", "t0": t0, "t1": t0 + 2.0,
                "volumes": {1: {"needles": 1000, "elapsed_s": 2.0}},
                "needles": 1000} for t0 in starts]
        return types.SimpleNamespace(
            ops=ops, seconds=10.0,
            samples={} if window_wall is None else {
                "window_wall": window_wall})

    back_to_back = run([100.0, 102.0, 104.0, 106.0, 108.0])
    paused = run([100.0, 104.0, 108.0])
    assert reader.read(back_to_back) == pytest.approx(500.0)
    assert reader.read(paused) == pytest.approx(300.0)
    assert per_sweep.read(back_to_back) == per_sweep.read(paused) == 500.0
    # half of the last sweep lies inside the window; one before it, none
    assert reader.read(run([97.0, 109.0])) == pytest.approx(50.0)
    assert reader.read(run([100.0], window_wall=None)) is None
