"""The reference's Hitchhiker part against the program's `PiggybackCoder`,
once; it is independent afterwards (it imports `reference.py` alone)."""

import numpy as np
import pytest

from benchmark import reference
from benchmark import reference_hitchhiker as hh
from seaweedfs_tpu.ec import encoder, files
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ops.coder import repair_read_bytes
from seaweedfs_tpu.ops.piggyback import PiggybackCoder, partition_groups

GEOMETRIES = [(10, 4), (6, 3), (14, 3)]


@pytest.mark.parametrize("d, p", GEOMETRIES)
def test_groups_and_encode_match_the_coder(d, p):
    assert hh.groups(d, p) == partition_groups(d, p)
    rng = np.random.default_rng(d)
    data = rng.integers(0, 256, (d, 2048), dtype=np.uint8)
    want = PiggybackCoder(d, p).encode(data)
    got = hh.encode(data, p)
    assert np.array_equal(got, want)
    # parity 0 and every a-half are plain RS; the others' b-halves not
    plain = reference.encode(data, p)
    assert np.array_equal(got[0], plain[0])
    assert np.array_equal(got[:, :1024], plain[:, :1024])
    assert not np.array_equal(got[1:, 1024:], plain[1:, 1024:])


@pytest.mark.parametrize("d, p", GEOMETRIES)
def test_two_step_repair_matches_the_coder_and_its_plan(d, p):
    rng = np.random.default_rng(p)
    data = rng.integers(0, 256, (d, 1024), dtype=np.uint8)
    shards = np.concatenate([data, hh.encode(data, p)])
    pb = PiggybackCoder(d, p)
    for f in range(d):
        halves = {(s, ab): shards[s, :512] if ab == "a" else shards[s, 512:]
                  for s, ab in hh.reads(f, d, p)}
        got = hh.repair(halves, f, d, p)
        assert np.array_equal(got, shards[f])
        present = tuple(s for s in range(d + p) if s != f)
        assert np.array_equal(got, pb.reconstruct(
            shards[list(present[:d])], present, (f,))[0])
        plan = pb.repair_plan(present, (f,), 1024)
        assert sorted((s, "a" if off == 0 else "b") for s, off, _ in plan) \
            == sorted(hh.reads(f, d, p))
        assert hh.read_bytes(f, d, p, 1024) \
            == repair_read_bytes("piggyback", d, p, [f], 1024)


@pytest.mark.parametrize("d, p", [(10, 4), (6, 3)])
def test_sealed_rows_match_a_volume_the_program_sealed(d, p, tmp_path):
    """A small volume sealed by the program's host encoder under the
    codec: the reference's rows, the boundary row and the short last row
    among them, match every shard file."""
    small = 4096
    geo = EcGeometry(d, p, large_block=1 << 20, small_block=small)
    rng = np.random.default_rng(d + p)
    dat = rng.integers(0, 256, small * d * 4 + 999, dtype=np.uint8)
    base = str(tmp_path / "v")
    dat.tofile(base + ".dat")
    encoder.encode_volume(base + ".dat", base, geo, PiggybackCoder(d, p))
    assert files.read_vif(base + ".vif")["codec"] == "piggyback"
    rows = reference.small_rows(dat.size, d, 1 << 20, small)
    assert rows == 5  # an odd count: the boundary runs through row 2
    for row in range(rows):
        want, off = hh.sealed_row(dat, row, d, p, 1 << 20, small)
        for sid in range(d + p):
            shard = np.fromfile(base + files.shard_ext(sid), dtype=np.uint8)
            assert np.array_equal(shard[off:off + small], want[sid]), \
                (row, sid)
