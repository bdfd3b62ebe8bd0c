"""The reduction of the program's stage annotations on a trace recorded
on a v5e chip (`benchmark/testdata/stages.xplane.pb`, by
`record_stages.py` beside it: a seal of two small volumes in three
`[4, 14, 1 MiB]` batches, a scrub of a volume of small needles, a rebuild
of one shard), and the readers of the metrics this PR added: arithmetic
on fixed inputs, and None — not an error — where a run lacks the source."""

import json
import os
import subprocess
import sys
import types

import pytest

import bench_contract
from benchmark import host_spans, roofline, xplane
from benchmark.run import load_reader

TRACE = os.path.join(bench_contract.BENCH, "testdata", "stages.xplane.pb")
READERS = os.path.join(bench_contract.BENCH, "layer_metrics")
# the metrics this file holds to their contract, by name: what a later PR
# appends to BENCHMARK.json is that PR's to test, and leaves this green
NAMES = (
    "feed_dispatch_share", "feed_drain_share", "feed_finish_share",
    "seal_rpc_generate_share", "seal_rpc_spread_share",
    "seal_outside_rpc_share", "repair_outside_rpc_share",
    "rebuild_read_share", "rebuild_drain_share", "rebuild_write_share",
    "scrub_walk_share", "scrub_pack_share", "scrub_device_wait_share",
    "scrub_pad_share", "crc_blocks_roofline", "seal_idle_explained_share",
    "repair_idle_explained_share", "scrub_idle_explained_share",
    "store_read_us", "read_pool_wait_us", "loop_stalls_over_50ms")
NEW = [m for m in bench_contract.load_benchmark()["per_layer"]
       if m["name"] in NAMES]


@pytest.fixture(scope="module")
def reduced():
    return host_spans.reduce_file(TRACE)


def spans(reduced, name):
    return [s for s in reduced["spans"] if s[0] == name]


def programs(reduced, name):
    return [p for p in reduced["programs"] if p[0] == name]


def test_the_trace_is_small():
    assert os.path.getsize(TRACE) < 2 << 20


def test_the_stages_of_all_three_verbs_are_found(reduced):
    names = {s[0] for s in reduced["spans"]}
    assert names == {
        "swtpu/ec.fill", "swtpu/ec.dispatch", "swtpu/ec.drain",
        "swtpu/ec.finish", "swtpu/scrub.walk", "swtpu/scrub.pack",
        "swtpu/scrub.device", "swtpu/scrub.compare", "swtpu/rebuild.read",
        "swtpu/rebuild.dispatch", "swtpu/rebuild.drain",
        "swtpu/rebuild.write"}
    for stage in ("fill", "dispatch", "drain"):
        assert [s[3] for s in spans(reduced, f"swtpu/ec.{stage}")] == [
            {"batch": 0}, {"batch": 1}, {"batch": 2}]
    assert len(spans(reduced, "swtpu/ec.finish")) == 2  # one a volume
    starts = [s[1] for s in reduced["spans"]]
    assert starts == sorted(starts)


@pytest.mark.parametrize("op, program", [
    ("ec", "jit_encode_jit"), ("rebuild", "jit_reconstruct_jit")])
def test_each_program_run_lies_between_its_dispatch_and_its_drain(
        reduced, op, program):
    """Host stages and device programs are on one clock: batch n's
    dispatch starts before the n-th run of the program, its drain ends
    after that run, and the copy to the device (tens of MiB) lies
    between dispatch and run."""
    runs = programs(reduced, program)
    dispatches = {s[3]["batch"]: s for s in spans(reduced,
                                                  f"swtpu/{op}.dispatch")}
    drains = {s[3]["batch"]: s for s in spans(reduced, f"swtpu/{op}.drain")}
    assert len(runs) == len(dispatches) == len(drains) >= 2
    for n, (_, start, dur) in enumerate(runs):
        _, d0, _, _ = dispatches[n]
        _, r0, rdur, _ = drains[n]
        assert d0 < start, f"batch {n}: the program began before dispatch"
        assert start + dur < r0 + rdur, f"batch {n}: the drain ended first"
        assert start - d0 < 50e6  # and not by much: within 50 ms
    worst = max(start - dispatches[n][1] for n, (_, start, _)
                in enumerate(runs))
    assert worst > 1e6  # the copy takes time: the clocks are not faked


def test_scrub_device_stages_carry_their_bytes_and_hold_their_program(
        reduced):
    stages = spans(reduced, "swtpu/scrub.device")
    assert [s[3]["L"] for s in stages] == [4096, 2048]
    for _, start, dur, stats in stages:
        assert stats["dispatched"] == 8 << 20
        assert 0 < stats["needed"] <= stats["dispatched"]
        inside = [p for p in programs(reduced, "jit__lambda")
                  if start <= p[1] and p[1] + p[2] <= start + dur]
        assert len(inside) == 1
    assert sum(s[3]["needed"] for s in stages) == 1049635  # every needle


def test_busy_agrees_with_the_shipped_reduction(reduced):
    shipped = xplane.reduce_planes(xplane.read_planes(TRACE))
    busy = sum(e - s for s, e in reduced["busy"])
    assert busy / 1e9 == pytest.approx(shipped["busy_s"])
    assert reduced["end_ns"] == shipped["t0_ns"] + round(
        shipped["span_s"] * 1e9)
    assert [p[0] for p in reduced["programs"]].count("jit_encode_jit") \
        == shipped["programs"]["jit_encode_jit"][0]


def test_idle_time_goes_to_the_innermost_stage():
    stages = [["swtpu/a.outer", 10, 80, {}], ["swtpu/a.inner", 30, 20, {}],
              ["swtpu/a.late", 95, 10, {}]]
    reduced = {"spans": stages, "busy": [[0, 5], [40, 45], [100, 101]],
               "end_ns": 120}
    idle = host_spans.idle(reduced)
    assert idle == [(5, 40), (45, 100), (101, 120)]
    got = host_spans.innermost(stages, idle)
    # [5,10) none; [10,30) outer; [30,40) inner; [45,50) inner;
    # [50,90) outer; [90,95) none; [95,100) late; [101,105) late;
    # [105,120) none
    assert got == {"": 5 + 5 + 15, "swtpu/a.outer": 20 + 40,
                   "swtpu/a.inner": 10 + 5, "swtpu/a.late": 5 + 4}
    assert sum(got.values()) == sum(e - s for s, e in idle)


def test_the_seal_in_the_trace_explains_its_idle_time(reduced):
    by_stage = host_spans.innermost(reduced["spans"],
                                    host_spans.idle(reduced))
    total = sum(by_stage.values())
    assert total == reduced["end_ns"] - sum(e - s for s, e in
                                            reduced["busy"])
    # the device idles under the host's fill and survivor reads
    top = max((k for k in by_stage if k), key=by_stage.get)
    assert top in ("swtpu/ec.fill", "swtpu/rebuild.read")
    assert 0.5 < 1 - by_stage[""] / total < 1.0


def test_the_command_line_prints_the_same(reduced):
    r = subprocess.run([sys.executable, "-m", "benchmark.host_spans", TRACE],
                       cwd=bench_contract.REPO, capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.splitlines()[-1]) == json.loads(
        json.dumps(reduced))


# -- the readers -------------------------------------------------------------

def fake_run(**fields):
    run = types.SimpleNamespace(
        ops=[], samples={}, traced=None, trace_window=None, trace_dir="",
        device={"kind": "TPU v5 lite"}, config={}, journal=[],
        metrics0="", metrics1="")
    run.events = lambda etype: [e["attrs"] for e in run.journal
                                if e["type"].startswith(etype)]
    vars(run).update(fields)
    return run


def read(name, run):
    return load_reader(READERS, name).read(run)


def test_the_21_metrics_are_entries_read_by_18_files():
    assert [m["name"] for m in NEW] == list(NAMES)  # each one an entry
    files = {load_reader(READERS, name).__spec__.origin for name in NAMES}
    assert len(files) == 18
    # a later PR may append its cells to a list, never empty one
    assert all(m.get("workloads") for m in NEW)


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_a_reader_without_its_source_returns_none(metric):
    """An untraced rehearsal, an earlier commit's program (no stage
    fields, no `timing` line, no new histogram), SWTPU_TRACE_SAMPLE=0."""
    old_events = [
        {"type": "ec.encode.finish",
         "attrs": {"ok": True, "wall_s": 4.0, "fill_s": 2.0, "mode": "sync"}},
        {"type": "ec.rebuild.finish",
         "attrs": {"ok": True, "duration_ms": 900.0, "bytes_read": 5}}]
    old_op = {"label": "seal", "wall_s": 9.0, "t0": 0.0, "t1": 9.0,
              "out": "ec encoded 3 volumes\n"}
    for run in (fake_run(),
                fake_run(journal=old_events, ops=[old_op],
                         metrics0="x 1\n", metrics1="x 2\n"),
                fake_run(traced={"programs": {}}, trace_dir="")):
        assert read(metric["name"], run) is None


def test_feed_shares_are_sums_over_the_walls():
    run = fake_run(journal=[
        {"type": "ec.encode.finish", "attrs": {
            "wall_s": 4.0, "dispatch_s": 0.4, "drain_block_s": 0.6,
            "finish_s": 1.0}},
        {"type": "ec.encode.finish", "attrs": {
            "wall_s": 6.0, "dispatch_s": 0.6, "drain_block_s": 1.4,
            "finish_s": 0.5}}])
    assert read("feed_dispatch_share", run) == pytest.approx(10.0)
    assert read("feed_drain_share", run) == pytest.approx(20.0)
    assert read("feed_finish_share", run) == pytest.approx(15.0)


OUT = """ec encoded 3 volumes
timing lock total=0.020 rpc=0.010 LeaseAdminToken=0.010/1
timing ec.encode total=7.500 rpc=7.000 VolumeEcShardsGenerateBatch=4.000/1 \
VolumeEcShardsCopy=2.500/3 VolumeEcShardsMount=0.300/6 VolumeList=0.200/2
timing unlock total=0.010 rpc=0.005 ReleaseAdminToken=0.005/1
"""


def test_verb_shares_come_from_the_timing_lines():
    ops = [{"label": "seal", "wall_s": 10.0, "out": OUT},
           {"label": "seal", "wall_s": 10.0, "out": OUT}]
    run = fake_run(ops=ops)
    assert read("seal_rpc_generate_share", run) == pytest.approx(40.0)
    assert read("seal_rpc_spread_share", run) == pytest.approx(25.0)
    assert read("seal_outside_rpc_share", run) == pytest.approx(
        100 * (1 - 7.015 / 10))
    for op in ops:
        op["label"] = "repair"
    assert read("repair_outside_rpc_share", run) == pytest.approx(29.85)


def test_rebuild_and_scrub_shares_and_the_pad_share():
    run = fake_run(journal=[
        {"type": "ec.rebuild.finish", "attrs": {
            "duration_ms": 2000.0, "read_s": 1.2, "drain_s": 0.1,
            "write_s": 0.3}},
        {"type": "volume.scrub.finish", "attrs": {
            "elapsed_s": 5.0, "walk_s": 1.0, "pack_s": 0.5, "device_s": 3.0,
            "compare_s": 0.4, "bytes_checked": 700, "bytes_dispatched": 1000}},
        {"type": "volume.scrub.finish", "attrs": {
            "elapsed_s": 5.0, "walk_s": 2.0, "pack_s": 0.5, "device_s": 2.0,
            "compare_s": 0.4, "bytes_checked": 800, "bytes_dispatched": 1000}},
    ])
    assert read("rebuild_read_share", run) == pytest.approx(60.0)
    assert read("rebuild_drain_share", run) == pytest.approx(5.0)
    assert read("rebuild_write_share", run) == pytest.approx(15.0)
    assert read("scrub_walk_share", run) == pytest.approx(30.0)
    assert read("scrub_pack_share", run) == pytest.approx(10.0)
    assert read("scrub_device_wait_share", run) == pytest.approx(50.0)
    assert read("scrub_pad_share", run) == pytest.approx(25.0)


def test_serve_counters_are_deltas_over_the_window():
    def text(reads, read_s, waits, wait_s, probes, under):
        return "\n".join([
            'SeaweedFS_volumeServer_store_read_seconds_sum{type="get"} '
            f"{read_s}",
            'SeaweedFS_volumeServer_store_read_seconds_count{type="get"} '
            f"{reads}",
            f'SeaweedFS_pool_queue_wait_seconds_sum{{pool="read"}} {wait_s}',
            f'SeaweedFS_pool_queue_wait_seconds_count{{pool="read"}} {waits}',
            f'SeaweedFS_pool_queue_wait_seconds_count{{pool="ec_read"}} 99',
            'SeaweedFS_event_loop_lag_seconds_bucket{loop="volume",'
            f'le="0.05"}} {under}',
            'SeaweedFS_event_loop_lag_seconds_bucket{loop="volume",'
            f'le="0.1"}} {probes}',
            f'SeaweedFS_event_loop_lag_seconds_count{{loop="volume"}} '
            f"{probes}", ""])
    run = fake_run(metrics0=text(100, 0.01, 100, 0.02, 40, 40),
                   metrics1=text(1100, 0.21, 1100, 0.52, 240, 237))
    assert read("store_read_us", run) == pytest.approx(200.0)
    assert read("read_pool_wait_us", run) == pytest.approx(500.0)
    assert read("loop_stalls_over_50ms", run) == pytest.approx(3.0)


def test_trace_readers_on_the_recorded_trace(reduced, capsys):
    """`crc_blocks_roofline` and `<cell>_idle_explained_share` from a
    run whose reduction is already there (`host_spans.of` keeps it on
    the run): the needed bytes are the device stages' own."""
    run = fake_run(traced={"programs": {}}, trace_dir="unused")
    run.host_spans = json.loads(json.dumps(reduced))
    seconds = sum(p[2] for p in programs(reduced, "jit__lambda")) / 1e9
    want, roof = roofline.share(*roofline.crc_ops_bytes(1, 1049635),
                                seconds, "TPU v5 lite")
    assert read("crc_blocks_roofline", run) == pytest.approx(want)
    assert 0 < want < 105 and roof == "compute"
    share = read("scrub_idle_explained_share", run)
    assert 50 < share < 100
    assert "[benchmark] idle by stage: " in capsys.readouterr().err
