"""Sharded EC pipeline over the 8-device virtual CPU mesh (2x4):
shard-parallel encode, all_gather rebuild, psum scrub, full ECPipeline step."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from seaweedfs_tpu.models.ec_pipeline import ECPipeline
from seaweedfs_tpu.ops import gf8
from seaweedfs_tpu.parallel import pipeline as pp
from seaweedfs_tpu.parallel.mesh import build_mesh


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() >= 8, "conftest must provision 8 CPU devices"
    return build_mesh(8)


def test_mesh_shape(mesh):
    assert dict(mesh.shape) == {"data": 2, "shard": 4}
    with pytest.raises(RuntimeError, match="only"):
        build_mesh(64)


def test_encode_sharded_matches_oracle(mesh):
    d, p = 10, 4
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (4, d, 128), dtype=np.uint8)
    gdata = jax.device_put(data, NamedSharding(mesh, P("data", None, None)))
    parity = np.asarray(pp.encode_sharded(mesh, gdata, d, p))
    assert parity.shape == (4, 4, 128)  # p_pad == p for shard=4
    for b in range(4):
        np.testing.assert_array_equal(parity[b, :p], gf8.np_encode(data[b], p))


def test_rebuild_sharded_all_patterns(mesh):
    d, p = 10, 4
    n, n_pad = 14, 16
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (2, d, 64), dtype=np.uint8)
    parity = np.stack([gf8.np_encode(b, p) for b in data])
    shards = np.zeros((2, n_pad, 64), dtype=np.uint8)
    shards[:, :d] = data
    shards[:, d:n] = parity
    for lost in [(0,), (13,), (0, 5, 10, 13), (1, 2, 3, 4)]:
        present = tuple(i for i in range(n) if i not in lost)
        wiped = shards.copy()
        wiped[:, list(lost)] = 0
        gw = jax.device_put(wiped, NamedSharding(mesh, P("data", "shard", None)))
        out = np.asarray(pp.rebuild_sharded(mesh, gw, present, d, p))
        np.testing.assert_array_equal(out[:, :n], shards[:, :n], err_msg=f"lost={lost}")


@pytest.mark.parametrize("L", [256, 768])  # chunk 256: T = 1, T = 3
def test_scrub_sharded_counts_corruption(mesh, L):
    from seaweedfs_tpu.ops import crc32c
    rng = np.random.default_rng(2)
    nb = 16
    lengths = rng.integers(1, L - 56, nb)
    blocks = np.zeros((nb, L), dtype=np.uint8)
    for i, ln in enumerate(lengths):
        blocks[i, L - ln:] = rng.integers(0, 256, ln, dtype=np.uint8)
    states = np.zeros(nb, dtype=np.uint32)
    for i, ln in enumerate(lengths):
        true = crc32c.crc32c(blocks[i, L - ln:].tobytes())
        corr = crc32c.zero_prefix_correction(np.array([ln]))[0]
        states[i] = np.uint32(true) ^ corr ^ np.uint32(0xFFFFFFFF)
    gb = jax.device_put(blocks, NamedSharding(mesh, P(("data", "shard"), None)))
    gs = jax.device_put(states, NamedSharding(mesh, P(("data", "shard"))))
    assert int(np.asarray(pp.scrub_sharded(mesh, gb, gs))) == 0
    # corrupt 3 blocks -> exactly 3 mismatches
    blocks[1, -1] ^= 0xFF
    blocks[7, L - 1] ^= 1
    blocks[12, L - 5] ^= 0x10
    gb = jax.device_put(blocks, NamedSharding(mesh, P(("data", "shard"), None)))
    assert int(np.asarray(pp.scrub_sharded(mesh, gb, gs))) == 3


def test_ec_pipeline_step(mesh):
    pipe = ECPipeline(d=10, p=4, mesh=mesh)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (4, 10, 256), dtype=np.uint8)
    gdata = jax.device_put(data, NamedSharding(mesh, P("data", None, None)))
    out = jax.jit(pipe.step, static_argnums=(1,))(gdata, (0, 5, 10, 13))
    assert int(np.asarray(out["rebuild_mismatch_bytes"])) == 0
