"""The stage account (tracing/stages.py) and the three device verbs that
use it: sums and counts, exclusive nesting, `add`, and that on the CPU
the stage sums of a seal, a rebuild and a scrub partition their
operation."""

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import tracing
from seaweedfs_tpu.ec import encoder, files, stream
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ops import device
from seaweedfs_tpu.ops.coder import NumpyCoder, get_coder
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.scrub import scrub_volume
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.tracing import StageAccount, stages

GEO = EcGeometry(d=4, p=2, large_block=1 << 16, small_block=1 << 12)


def test_sums_and_counts():
    acct = StageAccount("op", ("fill", "drain"))
    assert acct.fields() == {"fill_s": 0.0, "drain_s": 0.0}
    for _ in range(3):
        with acct.stage("fill"):
            time.sleep(0.01)
    assert acct.count("fill") == 3 and acct.count("drain") == 0
    assert 0.03 <= acct.seconds("fill") < 0.3
    assert acct.seconds("never") == 0.0 and acct.count("never") == 0
    assert acct.names()[0] == "fill"
    assert set(acct.fields()) == {"fill_s", "drain_s"}


def test_nested_stages_are_exclusive():
    acct = StageAccount("op")
    t0 = time.perf_counter()
    with acct.stage("outer"):
        time.sleep(0.02)
        with acct.stage("inner"):
            time.sleep(0.03)
            with acct.stage("innermost"):
                time.sleep(0.01)
    wall = time.perf_counter() - t0
    assert acct.seconds("inner") >= 0.03
    assert acct.seconds("innermost") >= 0.01
    # the outer stage lost what its children took: the sums partition
    assert 0.02 <= acct.seconds("outer") < wall - 0.04 + 0.005
    assert sum(acct.seconds(n) for n in acct.names()) <= wall


def test_add_is_taken_out_of_the_open_stage():
    acct = StageAccount("op")
    acct.add("write_block", 0.5, n=2)  # no stage open: just booked
    with acct.stage("fill"):
        time.sleep(0.02)
        acct.add("write_block", 0.015)
    assert acct.seconds("write_block") == pytest.approx(0.515)
    assert acct.count("write_block") == 3
    assert 0.004 <= acct.seconds("fill") < 0.02


def test_a_stage_books_its_time_when_the_body_raises():
    acct = StageAccount("op")
    with pytest.raises(ValueError):
        with acct.stage("fill"):
            time.sleep(0.01)
            raise ValueError("boom")
    assert acct.count("fill") == 1 and acct.seconds("fill") >= 0.01
    with acct.stage("drain"):  # the stack was unwound
        pass
    assert acct.count("drain") == 1


def test_timed_wraps_a_callable():
    acct = StageAccount("op")
    read = acct.timed("read", lambda off, ln: bytes(ln))
    assert read(0, 5) == bytes(5) and read(5, 2) == bytes(2)
    assert acct.count("read") == 2


def test_adds_from_many_threads_are_all_booked():
    acct = StageAccount("shell/x")

    def worker():
        for _ in range(2000):
            acct.add("Lookup", 0.001)
    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert acct.count("Lookup") == 16000
    assert acct.seconds("Lookup") == pytest.approx(16.0)


def test_publish_sets_the_sums_on_a_span():
    acct = StageAccount("op", ("fill",))
    acct.add("fill", 1.25)
    with tracing.start_span("op") as sp:
        acct.publish(sp)
    assert sp.attrs == {"fill_s": 1.25}


def test_a_stage_is_a_trace_annotation_where_jax_is_loaded(monkeypatch):
    """With jax in the process, a stage opens `swtpu/<op>.<stage>` with
    its keyword arguments; nothing else about it changes."""
    import jax.profiler
    seen = []

    class Spy:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            seen.append("enter")

        def __exit__(self, *exc):
            seen.append("exit")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    acct = StageAccount("ec")
    with acct.stage("dispatch", batch=3):
        pass
    assert seen == [("swtpu/ec.dispatch", {"batch": 3}), "enter", "exit"]
    assert acct.count("dispatch") == 1


def test_no_annotation_without_jax(monkeypatch):
    monkeypatch.delitem(__import__("sys").modules, "jax", raising=False)
    assert stages._annotation("swtpu/ec.fill", {}) is None


def _dat(path, size, seed):
    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())


@pytest.mark.parametrize("coder_name", ["numpy", "jax"])
def test_seal_stages_partition_the_wall(tmp_path, coder_name):
    """Sync (host coder) and async (device coder, here on the CPU
    backend): fill + dispatch + drain + write_block + finish <= wall_s,
    every old key still there, `bytes` = the .dat bytes sealed."""
    device.info()
    coder = get_coder(coder_name, GEO.d, GEO.p)
    jobs, total = [], 0
    for i, size in enumerate([300_000, 0, 123_457]):
        base = str(tmp_path / f"v{i}")
        _dat(base + ".dat", size, i)
        jobs.append((base + ".dat", base, None))
        total += size
    stats: dict = {}
    stream.encode_volumes(jobs, GEO, coder, chunk=1 << 12, batch=8,
                          stats=stats)
    want = {"wall_s", "fill_s", "write_s", "write_block_s", "finish_s",
            "writers", "mode", "bytes", "write_overlap"}
    want |= ({"coder_s"} if coder_name == "numpy" else
             {"dispatch_s", "first_dispatch_s", "drain_block_s", "batches",
              "batch_bytes"})
    assert want <= set(stats), want - set(stats)
    assert "dispatch_ts" not in stats and "done_ts" not in stats
    assert stats["bytes"] == total
    assert stats["mode"] == ("sync" if coder_name == "numpy" else "async")
    parts = (stats["fill_s"] + stats["write_block_s"] + stats["finish_s"]
             + stats.get("dispatch_s", stats.get("coder_s", 0.0))
             + stats.get("drain_block_s", 0.0))
    # what is left of the wall: opening the plans, joining the writers
    assert 0 < stats["finish_s"] and 0 < parts <= stats["wall_s"]


def test_rebuild_stages_are_returned(tmp_path):
    base = str(tmp_path / "v")
    _dat(base + ".dat", 1 << 20, 5)
    coder = NumpyCoder(GEO.d, GEO.p)
    stream.encode_volumes([(base + ".dat", base, None)], GEO, coder)
    os.unlink(base + files.shard_ext(1))
    stats: dict = {}
    t0 = time.perf_counter()
    assert encoder.rebuild_shards(base, GEO, coder, chunk=1 << 12, batch=8,
                                  stats=stats) == [1]
    wall = time.perf_counter() - t0
    for key in ("bytes_read", "bytes_written", "codec", "path",
                "shard_size", "read_s", "dispatch_s", "drain_s", "write_s",
                "batches"):
        assert key in stats, key
    shard = stats["shard_size"]
    assert stats["path"] == "full"
    assert stats["batches"] == -(-shard // (8 << 12))
    four = (stats["read_s"] + stats["dispatch_s"] + stats["drain_s"]
            + stats["write_s"])
    assert 0.5 * wall <= four <= wall


def test_rebuild_books_its_loads_beside_the_read_stage(tmp_path):
    """`read_s` is the stage's wall on the rebuild's thread; the loads'
    own seconds are summed beside it, all and by kind of survivor, and
    taken out of no stage."""
    base = str(tmp_path / "v")
    _dat(base + ".dat", 1 << 20, 6)
    coder = NumpyCoder(GEO.d, GEO.p)
    stream.encode_volumes([(base + ".dat", base, None)], GEO, coder)
    held = {}
    for sid in (0, 2, 3, 4):  # 0 is lost; three answer from elsewhere
        with open(base + files.shard_ext(sid), "rb") as f:
            held[sid] = f.read()
        os.unlink(base + files.shard_ext(sid))

    def holder(sid, off, ln):
        time.sleep(0.02)
        return held[sid][off:off + ln]

    stats: dict = {}
    t0 = time.perf_counter()
    assert encoder.rebuild_shards(base, GEO, coder, wanted=[0],
                                  chunk=1 << 12, batch=8, stats=stats,
                                  shard_reader=holder,
                                  remote_shards=[2, 3, 4]) == [0]
    wall = time.perf_counter() - t0
    batches = stats["batches"]
    # three sleeping loads a batch overlap: their sum is over the wall
    # of the stage they ran under, and the stage lost none of it
    assert stats["read_remote_busy_s"] >= 3 * batches * 0.02
    assert stats["read_s"] >= batches * 0.02
    assert stats["read_busy_s"] >= stats["read_s"]
    assert stats["read_local_busy_s"] > 0
    assert stats["read_busy_s"] == pytest.approx(
        stats["read_local_busy_s"] + stats["read_remote_busy_s"], abs=2e-4)
    four = (stats["read_s"] + stats["dispatch_s"] + stats["drain_s"]
            + stats["write_s"])
    assert 0.5 * wall <= four <= wall
    with open(base + files.shard_ext(0), "rb") as f:
        assert f.read() == held[0]


@pytest.mark.parametrize("mode", ["auto", "off"])
def test_scrub_stages_partition_the_elapsed_time(tmp_path, mode):
    """The device path (the kernel on the CPU backend this process
    resolved) books recv, wait and compare on the sweep's thread, the
    enclosing recv taking whatever the other two do not, so the three add
    up to its elapsed time whatever the machine's speed; the walker's own
    walk and pack come back beside them. The host loop is its walk."""
    device.info()
    rng = np.random.default_rng(9)
    v = Volume(str(tmp_path), "", 1)
    sizes = [int(s) for s in rng.integers(1, 60_000, 400)] + [0, 700_000]
    for i, size in enumerate(sizes, start=1):
        v.write_needle(Needle(id=i, cookie=1, data=rng.integers(
            0, 256, size, dtype=np.uint8).tobytes()))
    scrub_volume(v, device=mode)  # the programs are compiled
    res = scrub_volume(v, device=mode)
    v.close()
    assert res.scanned == len(sizes) and res.corrupt == []
    assert res.bytes_checked == sum(sizes)
    three = res.recv_s + res.device_s + res.compare_s
    if mode == "off":
        assert res.mode == "cpu" and res.blocks == 0
        assert res.bytes_dispatched == 0 and three == 0.0
        assert res.walk_s == res.elapsed_s > 0 and res.pack_s == 0.0
    else:
        assert res.mode == "xla-cpu" and res.blocks > 0
        assert res.bytes_dispatched >= res.bytes_checked
        assert res.bytes_dispatched % (8 << 20) == 0
        assert three == pytest.approx(res.elapsed_s, rel=1e-9)
        assert res.recv_s > 0 and res.compare_s > 0
        assert res.device_busy_s > 0 and res.spawn_s > 0
        assert res.walker_busy_s >= res.walk_s + res.pack_s
        assert res.walk_s > 0 and res.pack_s > 0