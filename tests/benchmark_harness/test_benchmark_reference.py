"""The benchmark's own reference against the program's host coders and
CRC, once; it is independent afterwards."""

import numpy as np
import pytest

from benchmark import reference
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ops import crc32c as crcmod
from seaweedfs_tpu.ops.coder import NumpyCoder
from seaweedfs_tpu.ops.native import NativeCoder

GEOMETRIES = [(14, 2), (10, 4)]


@pytest.mark.parametrize("d, p", GEOMETRIES)
def test_encode_matches_the_host_coders(d, p):
    rng = np.random.default_rng(d)
    data = rng.integers(0, 256, (d, 4096), dtype=np.uint8)
    want = NumpyCoder(d, p).encode(data)
    assert np.array_equal(reference.encode(data, p), want)
    assert np.array_equal(NativeCoder(d, p).encode(data), want)


@pytest.mark.parametrize("d, p", GEOMETRIES)
@pytest.mark.parametrize("n_lost", [1, 2])
def test_reconstruct_matches_the_host_coder(d, p, n_lost):
    rng = np.random.default_rng(d + n_lost)
    data = rng.integers(0, 256, (d, 1024), dtype=np.uint8)
    shards = np.concatenate([data, reference.encode(data, p)])
    lost = [0, 2 * n_lost][:n_lost]
    present = [s for s in range(d + p) if s not in lost]
    got = reference.reconstruct(shards[present[:d]], present, lost, d, p)
    assert np.array_equal(got, shards[lost])
    assert np.array_equal(
        NumpyCoder(d, p).reconstruct(shards[present[:d]], tuple(present),
                                     tuple(lost)), got)


@pytest.mark.parametrize("d, p", GEOMETRIES)
@pytest.mark.parametrize("dat_size", [1, (1 << 20) * 14, 5_000_123,
                                      1004 << 20])
def test_layout_matches_the_program(d, p, dat_size):
    geo = EcGeometry(d, p)
    assert reference.large_rows(dat_size, d) == geo.large_rows(dat_size)
    assert reference.small_rows(dat_size, d) == geo.small_rows(dat_size)
    assert reference.shard_file_size(dat_size, d) \
        == geo.shard_file_size(dat_size)


@pytest.mark.parametrize("d, p", GEOMETRIES)
def test_tail_row_is_zero_padded_like_the_program_encodes_it(d, p, tmp_path):
    """A small volume sealed by the program's host encoder: the
    reference's rows, the last one short, match every shard file."""
    from seaweedfs_tpu.ec import encoder, files
    small = 4096
    geo = EcGeometry(d, p, large_block=1 << 16, small_block=small)
    rng = np.random.default_rng(p)
    dat = rng.integers(0, 256, small * d * 2 + 777, dtype=np.uint8)
    base = str(tmp_path / "v")
    dat.tofile(base + ".dat")
    encoder.encode_volume(base + ".dat", base, geo, NumpyCoder(d, p))
    rows = reference.small_rows(dat.size, d, 1 << 16, small)
    assert rows == 3
    for row in range(rows):
        blocks, off = reference.small_row(dat, row, d, 1 << 16, small)
        want = np.concatenate([blocks, reference.encode(blocks, p)])
        for sid in range(d + p):
            shard = np.fromfile(base + files.shard_ext(sid), dtype=np.uint8)
            assert shard.size == reference.shard_file_size(
                dat.size, d, 1 << 16, small)
            assert np.array_equal(shard[off:off + small], want[sid]), \
                (row, sid)


@pytest.mark.parametrize("data", [b"", b"123456789", bytes(range(256)) * 5])
def test_crc32c_matches_the_store(data):
    assert reference.crc32c(data) == crcmod.crc32c(data)


def test_crc32c_check_value():
    assert reference.crc32c(b"123456789") == 0xE3069283
