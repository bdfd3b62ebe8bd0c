"""Parallel-writeback EC encode pipeline (ec/stream.py).

The writeback plane (WriterPool), writer-gated AsyncPipe recycling, the
mmap lifetime fix, and the fit_chunk divisor walk — asserted against a
straight-line reference encoder written HERE from the stripe definition
(locate.py's layout + the gf8 numpy oracle), independent of the pipeline
under test, across the nasty geometries: cross-volume batch spanning,
partial final batch, padded small-block tail, empty volume, and
chunk < small_block.
"""

import errno
import glob
import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ec import files, stream
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ops import gf8
from seaweedfs_tpu.ops.coder import NumpyCoder, get_coder
from seaweedfs_tpu.stats import EC_PIPELINE_SECONDS, EC_WRITER_QUEUE_DEPTH
from seaweedfs_tpu.tracing import StageAccount

GEO = EcGeometry(d=4, p=2, large_block=4096, small_block=512)

# empty volume / sub-block / exact small row / one byte past the large
# tier / two large rows + ragged padded tail / sub-small-block
NASTY_SIZES = [0, 77, GEO.small_block * GEO.d,
               GEO.large_block * GEO.d + 1,
               GEO.large_block * GEO.d * 2 + GEO.small_block * 3 + 123,
               GEO.small_block - 1]


def reference_encode(data: bytes, geo: EcGeometry) -> "list[bytes]":
    """Straight-line oracle: stripe the bytes row-major over d shards per
    the two-tier layout, zero-pad the tail row, then parity = the gf8
    numpy encode of the full shard columns (GF(2^8) is byte-pointwise, so
    whole-shard encode == per-stripe encode)."""
    ssize = geo.shard_file_size(len(data))
    shards = np.zeros((geo.n, ssize), np.uint8)
    src = np.frombuffer(data, np.uint8)
    pos = sofs = 0
    for _ in range(geo.large_rows(len(data))):
        for i in range(geo.d):
            shards[i, sofs:sofs + geo.large_block] = \
                src[pos:pos + geo.large_block]
            pos += geo.large_block
        sofs += geo.large_block
    while pos < len(src):
        for i in range(geo.d):
            take = max(0, min(geo.small_block, len(src) - pos))
            if take:
                shards[i, sofs:sofs + take] = src[pos:pos + take]
            pos += geo.small_block
        sofs += geo.small_block
    if ssize:
        shards[geo.d:] = gf8.np_encode(shards[:geo.d], geo.p)
    return [s.tobytes() for s in shards]


def _make_jobs(tmp_path, sizes, seed=7):
    rng = np.random.default_rng(seed)
    jobs, datas = [], []
    for i, size in enumerate(sizes):
        payload = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        dat = tmp_path / f"{i}.dat"
        dat.write_bytes(payload)
        jobs.append((str(dat), str(tmp_path / f"v{i}"), None))
        datas.append(payload)
    return jobs, datas


def _assert_identical(tmp_path, jobs, datas, geo):
    for i, payload in enumerate(datas):
        want = reference_encode(payload, geo)
        for s in range(geo.n):
            got = (tmp_path / f"v{i}{files.shard_ext(s)}").read_bytes()
            assert got == want[s], f"vol {i} shard {s} (size={len(payload)})"


@pytest.mark.parametrize("coder_name", ["numpy", "jax"])
@pytest.mark.parametrize("writers", [1, 3])
def test_parallel_writeback_byte_identical(tmp_path, coder_name, writers):
    """Every geometry in NASTY_SIZES through one shared stream (batch=3
    forces cross-volume spanning and a partial final batch; chunk=256 <
    small_block forces multi-chunk rows) must match the straight-line
    reference byte for byte, for both the sync and async drain paths."""
    jobs, datas = _make_jobs(tmp_path, NASTY_SIZES)
    coder = get_coder(coder_name, GEO.d, GEO.p)
    stream.encode_volumes(jobs, GEO, coder, chunk=256, batch=3,
                          writers=writers)
    _assert_identical(tmp_path, jobs, datas, GEO)
    assert EC_WRITER_QUEUE_DEPTH.value() == 0


def test_pipeline_stats_and_stage_histogram(tmp_path):
    jobs, datas = _make_jobs(tmp_path, [GEO.small_block * GEO.d * 3 + 11])
    before = {s: EC_PIPELINE_SECONDS.count(s)
              for s in ("fill", "dispatch", "drain", "write")}
    stats: dict = {}
    stream.encode_volumes(jobs, GEO, NumpyCoder(GEO.d, GEO.p), stats=stats,
                          writers=2)
    _assert_identical(tmp_path, jobs, datas, GEO)
    assert stats["mode"] == "sync" and stats["writers"] == 2
    for key in ("wall_s", "coder_s", "write_s", "write_block_s"):
        assert stats[key] >= 0.0
    assert 0.0 <= stats["write_overlap"] <= 1.0
    for s, n in before.items():
        assert EC_PIPELINE_SECONDS.count(s) == n + 1


def test_writer_pool_enospc_fails_cleanly(tmp_path, monkeypatch):
    """A writer hitting ENOSPC fails the job with the original OSError, no
    hung writer threads, and the partial shard outputs removed."""
    jobs, _ = _make_jobs(tmp_path, [5000, 6000], seed=3)

    def no_space(fd, data, off):
        raise OSError(errno.ENOSPC, "No space left on device")

    monkeypatch.setattr(stream.os, "pwrite", no_space)
    with pytest.raises(OSError) as ei:
        stream.encode_volumes(jobs, GEO, NumpyCoder(GEO.d, GEO.p),
                              chunk=512, batch=4, writers=2)
    assert ei.value.errno == errno.ENOSPC
    monkeypatch.undo()
    assert not [t for t in threading.enumerate()
                if t.name.startswith("swtpu-ec-writer")]
    assert glob.glob(str(tmp_path / "v*")) == []
    assert EC_WRITER_QUEUE_DEPTH.value() == 0


def test_writer_pool_error_skips_queued_runs_and_callbacks_fire(tmp_path):
    """After poison, queued runs are skipped but completion callbacks still
    run — the invariant that keeps buffer gating from hanging."""
    pool = stream.WriterPool(StageAccount("ec"), writers=1, queue_depth=4)
    path = tmp_path / "t.bin"
    fd = os.open(str(path), os.O_WRONLY | os.O_CREAT)
    fired = []
    try:
        pool.submit(0, fd, 0, np.full(8, 1, np.uint8), lambda: fired.append(1))
        pool.drain()
        pool.poison()
        # submit() on a poisoned pool raises; enqueue directly to prove the
        # writer loop itself skips the write but still fires the callback
        # (mirror submit()'s gauge increment — the writer decrements per
        # dequeued item, and the gauge is global delta accounting)
        EC_WRITER_QUEUE_DEPTH.add(amount=1)
        pool._queues[0].put((0, fd, 8, np.full(8, 2, np.uint8),
                             lambda: fired.append(2), None))
        pool._queues[0].join()
    finally:
        pool.close()
        os.close(fd)
    assert fired == [1, 2]
    assert path.read_bytes() == bytes([1] * 8)  # second run skipped


def test_reap_never_seals_behind_a_poisoned_pool(tmp_path):
    """writes_done() turns true even for SKIPPED runs (their callbacks fire
    so buffer gating can't hang) — _reap must not seal such a volume, or a
    mid-job ENOSPC leaves a valid-looking .vif over holed shards that
    _abort then keeps as "completed"."""
    from collections import deque
    jobs, _ = _make_jobs(tmp_path, [3000], seed=11)
    plan = stream._VolumePlan(jobs[0][0], jobs[0][1], None, GEO, 512)
    plan.open()
    acct = StageAccount("ec")
    pool = stream.WriterPool(acct, writers=1, queue_depth=2)
    try:
        plan.note_write()
        pool.poison()
        plan.write_done()  # the skipped run's callback
        finishing = deque([plan])
        stream._reap(finishing, acct, pool)
        assert not plan.finished  # left for _abort to clean up
        assert finishing  # still queued, not popped
        assert not os.path.exists(jobs[0][1] + ".vif")
        # a healthy pool (or the post-drain force path) still seals
        stream._reap(finishing, acct, pool, force=True)
        assert plan.finished
    finally:
        pool.close()


def test_writer_pool_routes_and_writes_runs(tmp_path):
    """Strided [k, chunk] runs land at consecutive chunk offsets; 1-D runs
    are a single pwrite."""
    pool = stream.WriterPool(StageAccount("ec"), writers=3, queue_depth=2)
    path = tmp_path / "shard.bin"
    fd = os.open(str(path), os.O_WRONLY | os.O_CREAT)
    try:
        base = np.arange(48, dtype=np.uint8).reshape(4, 3, 4)
        pool.submit(0, fd, 0, base[:, 1, :])      # strided rows
        pool.submit(5, fd, 16, np.full(4, 9, np.uint8))  # contiguous
        pool.drain()
    finally:
        pool.close()
        os.close(fd)
    got = np.frombuffer(path.read_bytes(), np.uint8)
    # rows 0..3 of shard column 1 at offsets 0,4,8,12; then the 1-D run
    expect = np.zeros(20, np.uint8)
    for r in range(4):
        expect[r * 4:(r + 1) * 4] = base[r, 1]
    expect[16:] = 9
    assert np.array_equal(got, expect)


def test_async_pipe_recycling_gated_on_writers():
    """next_buffer must not hand out a buffer a writer still reads."""
    acct = StageAccount("ec")
    pipe = stream.AsyncPipe((2, 2, 4), acct, depth=0)  # pool of 2 buffers
    first = pipe.next_buffer()
    pipe.retain(first)
    got = []

    def spin():
        pipe.next_buffer()          # the other buffer: free
        got.append(pipe.next_buffer())  # back to `first`: must block

    t = threading.Thread(target=spin, daemon=True)
    t.start()
    t.join(timeout=0.3)
    assert t.is_alive(), "recycle was not gated on the writer hold"
    pipe.release(first)
    t.join(timeout=2)
    assert not t.is_alive() and got and got[0] is first
    assert acct.seconds("write_block") > 0.0


def test_volume_plan_closes_source_mmap(tmp_path):
    """Satellite: finish() releases the region views and closes the source
    mapping explicitly — not at some future GC."""
    dat = tmp_path / "v.dat"
    dat.write_bytes(bytes(range(256)) * 64)
    plan = stream._VolumePlan(str(dat), str(tmp_path / "v"), None, GEO, 512)
    plan.open(open_fds=False)
    assert plan._mm is not None and not plan._mm.closed
    plan.finish()
    assert plan._mm is None and plan._arr is None and plan.regions == []


def test_encode_leaves_no_source_mappings(tmp_path):
    """A multi-volume job must not accumulate source-file mappings: after
    encode_volumes returns, /proc/self/maps has no entry for any .dat."""
    jobs, datas = _make_jobs(tmp_path, [3000, 70000, 12345], seed=11)
    stream.encode_volumes(jobs, GEO, NumpyCoder(GEO.d, GEO.p), batch=4)
    _assert_identical(tmp_path, jobs, datas, GEO)
    maps = open("/proc/self/maps").read()
    assert str(tmp_path) not in maps


def test_fit_chunk_divisor_walk():
    """fit_chunk = largest divisor of gcd(large, small) <= chunk, including
    odd gcds where the old decrement loop was O(chunk)."""
    def brute(geo, chunk):
        g = int(np.gcd(geo.large_block, geo.small_block))
        return max(c for c in range(1, min(chunk, g) + 1) if g % c == 0)

    cases = [
        (EcGeometry(d=4, p=2, large_block=4096, small_block=512), [1000, 100, 512, 1]),
        (EcGeometry(d=4, p=2, large_block=3645, small_block=315), [44, 45, 46, 300, 2]),
        (EcGeometry(d=4, p=2, large_block=7 * 11 * 13, small_block=7 * 13), [90, 91, 13, 12, 7, 6]),
    ]
    for geo, chunks in cases:
        for chunk in chunks:
            assert stream.fit_chunk(geo, chunk) == brute(geo, chunk), \
                (geo.large_block, geo.small_block, chunk)
    assert stream.fit_chunk(GEO, 10**9) == 512  # clamped to the gcd


def test_empty_job_list_and_single_empty_volume(tmp_path):
    assert stream.encode_volumes([], GEO, NumpyCoder(GEO.d, GEO.p)) == {}
    (tmp_path / "e.dat").write_bytes(b"")
    res = stream.encode_volumes([(str(tmp_path / "e.dat"),
                                  str(tmp_path / "v0"), None)],
                                GEO, NumpyCoder(GEO.d, GEO.p))
    for path in res[str(tmp_path / "e.dat")]:
        assert os.path.getsize(path) == 0
    assert (tmp_path / "v0.vif").exists()
