"""Hitchhiker-XOR single data-shard repair as ONE GF(2^8) matrix apply
(ops/piggyback.py: `repair_matrix`) under the rebuild's own loaders, pipe
and stages (ec/encoder.py: `_rebuild_batched`), held to the benchmark's
table-driven reference (benchmark/reference_hitchhiker.py, which imports
nothing of the program) at small sizes on the CPU."""

import os
import time

import numpy as np
import pytest

from benchmark import reference_hitchhiker as hh
from seaweedfs_tpu.ec import files as ecf
from seaweedfs_tpu.ec.encoder import encode_volume, rebuild_shards
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ops import device
from seaweedfs_tpu.ops.coder import NumpyCoder, get_coder
from seaweedfs_tpu.ops.piggyback import PiggybackCoder
from seaweedfs_tpu.stats import REPAIR_BYTES_READ, REPAIR_BYTES_WRITTEN

GEOMETRIES = [(10, 4), (6, 3)]


def _stripe(d, p, length, seed):
    """All n shards [n, length] of a seeded stripe, by the reference."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (d, length), dtype=np.uint8)
    return np.concatenate([data, hh.encode(data, p)])


def _halves(shards, f, d, p):
    half = shards.shape[1] // 2
    return {(s, ab): shards[s, :half] if ab == "a" else shards[s, half:]
            for s, ab in hh.reads(f, d, p)}


@pytest.mark.parametrize("d, p, f", [(d, p, f) for d, p in GEOMETRIES
                                     for f in range(d)])
def test_matrix_apply_is_the_two_step_repair(d, p, f):
    """M . x over the plan's ranges, in the plan's order, equals the
    paper's two-step repair and the lost shard, for every data shard."""
    length = 512
    shards = _stripe(d, p, length, seed=d * 100 + f)
    half = length // 2
    pb = PiggybackCoder(d, p)
    present = tuple(s for s in range(d + p) if s != f)
    plan = pb.repair_plan(present, (f,), length)
    # the plan reads what the reference says the repair reads
    assert [(s, "a" if off == 0 else "b") for s, off, _ in plan] \
        == hh.reads(f, d, p)
    assert {ln for _, _, ln in plan} == {half}
    x = np.stack([shards[s, off:off + ln] for s, off, ln in plan])
    matrix, targets, engine = pb.repair_linear((f,), length)
    assert matrix.shape == (2, d + len(pb.group_of(f)[1]))
    assert targets == [(f, 0), (f, half)] and engine is pb.inner
    got = np.concatenate(list(engine.apply_matrix(matrix, x)))
    assert np.array_equal(got, shards[f])
    assert np.array_equal(got, hh.repair(_halves(shards, f, d, p), f, d, p))


@pytest.mark.parametrize("backend", ["numpy", "native", "jax", "pallas"])
def test_every_backend_applies_the_matrix(backend):
    """The host tables, the C++ sidecar, the einsum program and the
    Pallas kernel (interpreted here) with the matrix as an operand, on
    [k, L] rows and on [B, k, L] slabs."""
    device.info()
    d, p, f = 10, 4, 3
    coder = get_coder(backend, d, p)
    matrix = PiggybackCoder(d, p).repair_matrix(f)
    rng = np.random.default_rng(11)
    x = rng.integers(0, 256, (3, matrix.shape[1], 384), dtype=np.uint8)
    want = np.stack([NumpyCoder.apply_matrix(coder, matrix, b) for b in x])
    assert np.array_equal(np.asarray(coder.apply_matrix(matrix, x)), want)
    assert np.array_equal(np.asarray(coder.apply_matrix(matrix, x[1])),
                          want[1])


def test_plain_rs_programs_keep_their_matrices_baked_in():
    """`matrix_apply_jit` is a new entry: encode and reconstruct still build
    their bit-matrix from the static key, so their programs and compile
    cache keys are what they were."""
    from seaweedfs_tpu.ops import gf8, rs_pallas
    key = ("rec", 10, 4, tuple(range(1, 11)), (0,))
    rec = gf8.decode_matrix(10, 4, list(range(1, 11)))[[0], :]
    assert np.array_equal(rs_pallas._plane_major_bitmatrix(key),
                          rs_pallas.matrix_operand(rec))
    assert rs_pallas.matrix_operand(rec) is rs_pallas.matrix_operand(rec)


# -- file level: rebuild_shards through the batched path ----------------------

D, P = 10, 4
# shard files of 21 small blocks of 512 B: a half of 5376 B is five
# batches of (4 x 256 B), more than the pipe has buffers, and an odd tail
# of 256 B: one chunk of a batch, in a buffer an earlier batch filled
GEO = EcGeometry(d=D, p=P, large_block=1 << 14, small_block=512)
CHUNK, BATCH = 256, 4


def _sealed(tmp_path, seed=21, size=D * 512 * 20 + 700):
    rng = np.random.default_rng(seed)
    dat = rng.integers(0, 256, size, dtype=np.uint8)
    base = str(tmp_path / "v")
    dat.tofile(base + ".dat")
    encode_volume(base + ".dat", base, GEO, PiggybackCoder(D, P),
                  chunk=CHUNK, batch=BATCH)
    shards = np.stack([np.fromfile(base + ecf.shard_ext(s), dtype=np.uint8)
                       for s in range(GEO.n)])
    assert shards.shape[1] == 21 * 512
    return base, shards


def test_sealed_volume_carries_the_reference_piggyback(tmp_path):
    _base, shards = _sealed(tmp_path)
    assert np.array_equal(shards[D:], hh.encode(shards[:D], P))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("survivors", ["local", "mixed"])
@pytest.mark.parametrize("f", [0, 2])
def test_rebuild_is_byte_identical_reads_the_plan_and_books_the_stages(
        tmp_path, f, survivors, backend):
    """A group of four and a group of three; every survivor on this disk,
    or half of them behind `shard_reader`; a host engine and the device
    engine (here the CPU backend)."""
    device.info()
    base, shards = _sealed(tmp_path)
    shard_size = shards.shape[1]
    os.unlink(base + ecf.shard_ext(f))
    held, calls = {}, []
    if survivors == "mixed":
        for sid in range(GEO.n):
            if sid % 2 == 0 and sid != f:
                held[sid] = shards[sid].tobytes()
                os.unlink(base + ecf.shard_ext(sid))

    def holder(sid, off, ln):
        calls.append((sid, off, ln))
        return held[sid][off:off + ln]

    pb = PiggybackCoder(D, P, backend=backend)
    read0 = REPAIR_BYTES_READ.value("piggyback")
    wrote0 = REPAIR_BYTES_WRITTEN.value("piggyback")
    stats: dict = {}
    t0 = time.perf_counter()
    assert rebuild_shards(base, GEO, pb, wanted=[f], chunk=CHUNK,
                          batch=BATCH, stats=stats, shard_reader=holder,
                          remote_shards=sorted(held)) == [f]
    wall = time.perf_counter() - t0
    # (ii) the lost shard, the reference's repair, the array path's
    rebuilt = np.fromfile(base + ecf.shard_ext(f), dtype=np.uint8)
    assert np.array_equal(rebuilt, shards[f])
    assert np.array_equal(rebuilt, hh.repair(_halves(shards, f, D, P),
                                             f, D, P))
    present = tuple(s for s in range(GEO.n) if s != f)
    assert np.array_equal(rebuilt, PiggybackCoder(D, P).reconstruct(
        shards[list(present[:D])], present, (f,))[0])
    # (iii) the plan's bytes and no more: the tail's padding is made here
    want = hh.read_bytes(f, D, P, shard_size)
    assert want == (D + len(pb.group_of(f)[1])) * shard_size // 2
    assert stats["bytes_read"] == want
    assert stats["bytes_written"] == shard_size
    assert REPAIR_BYTES_READ.value("piggyback") - read0 == want
    assert REPAIR_BYTES_WRITTEN.value("piggyback") - wrote0 == shard_size
    half = shard_size // 2
    plan = pb.repair_plan(present, (f,), shard_size)
    for sid, off, ln in calls:  # each fetch inside a range of the plan
        assert any(lo <= off and off + ln <= lo + half
                   for s, lo, _ in plan if s == sid), (sid, off, ln)
    assert sum(ln for _, _, ln in calls) == half * sum(
        1 for s, _, _ in plan if s in held)
    # (iv) the rebuild's own stages, and nothing under `codec`
    assert stats["path"] == "ranged" and stats["codec"] == "piggyback"
    assert "codec_s" not in stats
    assert stats["batches"] == -(-half // (CHUNK * BATCH)) == 6
    four = (stats["read_s"] + stats["dispatch_s"] + stats["drain_s"]
            + stats["write_s"])
    assert 0.5 * wall <= four <= wall
    assert stats["read_busy_s"] == pytest.approx(
        stats["read_local_busy_s"] + stats["read_remote_busy_s"], abs=2e-4)
    assert stats["read_local_busy_s"] > 0
    assert (stats["read_remote_busy_s"] > 0) == bool(held)


def test_remote_loads_of_a_batch_overlap(tmp_path):
    """PR 29's side-by-side loads reach the codec path: a batch waits for
    its slowest survivor, not for their sum."""
    base, shards = _sealed(tmp_path)
    held = {}
    for sid in range(1, GEO.n):
        held[sid] = shards[sid].tobytes()
        os.unlink(base + ecf.shard_ext(sid))
    os.unlink(base + ecf.shard_ext(0))

    def holder(sid, off, ln):
        time.sleep(0.02)
        return held[sid][off:off + ln]

    stats: dict = {}
    assert rebuild_shards(base, GEO, PiggybackCoder(D, P), wanted=[0],
                          chunk=CHUNK, batch=BATCH, stats=stats,
                          shard_reader=holder,
                          remote_shards=sorted(held)) == [0]
    assert np.array_equal(np.fromfile(base + ecf.shard_ext(0),
                                      dtype=np.uint8), shards[0])
    batches, loads = stats["batches"], D + 4
    assert stats["read_remote_busy_s"] >= loads * batches * 0.02
    assert stats["read_s"] < 0.5 * stats["read_remote_busy_s"]


@pytest.mark.parametrize("lost", [[0, 2], [D + 1], [D]])
def test_other_losses_still_take_the_general_path(tmp_path, lost):
    """(v) two shards, a piggybacked parity, the plain parity: no ranged
    plan, the window-by-window executor under the stage `codec`."""
    base, shards = _sealed(tmp_path)
    for sid in lost:
        os.unlink(base + ecf.shard_ext(sid))
    stats: dict = {}
    assert rebuild_shards(base, GEO, PiggybackCoder(D, P), chunk=CHUNK,
                          batch=BATCH, stats=stats) == lost
    for sid in lost:
        assert np.array_equal(np.fromfile(base + ecf.shard_ext(sid),
                                          dtype=np.uint8), shards[sid])
    assert stats["path"] == "general" and stats["codec_s"] > 0
    # d whole survivors at the least (and the groups' a-halves again)
    assert stats["bytes_read"] >= D * shards.shape[1]
