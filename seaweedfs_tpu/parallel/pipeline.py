"""Sharded EC compute steps over a ('data', 'shard') mesh via shard_map.

The multi-chip execution plan (SURVEY.md §5): stripe batches ride the
``data`` axis (pure data parallelism — volumes are independent), the n
output shards are partitioned along the ``shard`` axis (each device computes
and "owns" a subset of shards, like servers own shards in the reference), and
rebuild all_gathers survivors along ``shard`` over ICI before the masked
inverse matmul — the device-side analogue of store_ec.go:367-400's fan-out
shard fetch. Scrub reduces mismatch counts with a psum over the whole mesh.

All entry points take/return global arrays with NamedShardings; shapes are
static per (geometry, batch) so XLA compiles each once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gf8
from ..ops.coder import ErasureCoder, register_coder
from ..ops.crc32c import device_crc_states
from ..ops.rs_jax import pack_bits, unpack_bits


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# -- encode -----------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _padded_parity_bitmatrix(d: int, p: int, p_pad: int) -> np.ndarray:
    full = gf8.expand_to_bits(gf8.parity_matrix(d, p)).astype(np.int8)
    out = np.zeros((8 * p_pad, 8 * d), dtype=np.int8)
    out[: 8 * p, :] = full
    out.setflags(write=False)
    return out


def encode_sharded(mesh: Mesh, data: jax.Array, d: int, p: int) -> jax.Array:
    """data [B, d, L] -> parity [B, p_pad, L]; B over 'data', parity rows
    partitioned over 'shard' (p padded up to the shard-axis size)."""
    n_shard = mesh.shape["shard"]
    p_pad = _ceil_to(p, n_shard)
    rows_per = p_pad // n_shard
    bmat = jnp.asarray(_padded_parity_bitmatrix(d, p, p_pad))

    def kernel(x):  # x: [B_loc, d, L] replicated over 'shard'
        idx = jax.lax.axis_index("shard")
        sub = jax.lax.dynamic_slice_in_dim(bmat, idx * rows_per * 8, rows_per * 8, 0)
        bits = unpack_bits(x)  # [B_loc, 8d, L]
        acc = jnp.einsum("pk,bkl->bpl", sub, bits,
                         preferred_element_type=jnp.int32)
        return pack_bits(acc & 1)  # [B_loc, rows_per, L]

    fn = jax.shard_map(kernel, mesh=mesh,
                       in_specs=P("data", None, None),
                       out_specs=P("data", "shard", None))
    return fn(data)


# -- rebuild ----------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _padded_decode_bitmatrix(d: int, p: int, present: tuple[int, ...],
                             n_pad: int) -> np.ndarray:
    """Decode matrix producing ALL n_pad shard slots from d survivors."""
    rec = gf8.decode_matrix(d, p, list(present))  # [n, d]
    full = gf8.expand_to_bits(rec).astype(np.int8)
    out = np.zeros((8 * n_pad, 8 * d), dtype=np.int8)
    out[: 8 * (d + p), :] = full
    out.setflags(write=False)
    return out


def rebuild_sharded(mesh: Mesh, shards: jax.Array,
                    present: tuple[int, ...], d: int, p: int) -> jax.Array:
    """shards [B, n_pad, L] (shard axis partitioned over 'shard'; lost rows
    are garbage) -> all n_pad shards recomputed, same layout.

    Each device all_gathers the survivor rows along 'shard' (ICI) and then
    reconstructs only the shard rows it owns.
    """
    n = d + p
    n_shard = mesh.shape["shard"]
    n_pad = shards.shape[1]
    assert n_pad % n_shard == 0 and n_pad >= n
    rows_per = n_pad // n_shard
    use = tuple(sorted(present)[:d])
    bmat = jnp.asarray(_padded_decode_bitmatrix(d, p, use, n_pad))
    sel = jnp.asarray(np.array(use, dtype=np.int32))

    def kernel(x):  # x: [B_loc, rows_per, L] — this device's shard rows
        allsh = jax.lax.all_gather(x, "shard", axis=1, tiled=True)  # [B, n_pad, L]
        survivors = jnp.take(allsh, sel, axis=1)  # [B, d, L]
        idx = jax.lax.axis_index("shard")
        sub = jax.lax.dynamic_slice_in_dim(bmat, idx * rows_per * 8, rows_per * 8, 0)
        bits = unpack_bits(survivors)
        acc = jnp.einsum("pk,bkl->bpl", sub, bits,
                         preferred_element_type=jnp.int32)
        return pack_bits(acc & 1)

    fn = jax.shard_map(kernel, mesh=mesh,
                       in_specs=P("data", "shard", None),
                       out_specs=P("data", "shard", None))
    return fn(shards)


# -- scrub ------------------------------------------------------------------

def scrub_sharded(mesh: Mesh, blocks: jax.Array, expected_states: jax.Array,
                  chunk: int = 256) -> jax.Array:
    """Batched CRC scrub: blocks [B, L] (left-zero-padded needles), expected
    raw CRC states [B] uint32. Returns global mismatch count (replicated).

    B is sharded across the entire mesh (both axes) — scrub is pure dp; the
    reduction is one psum. Reference analogue: volume_checking.go:91 per
    needle, volume.check.disk over replicas.
    """

    def kernel(x, exp):
        states = device_crc_states(x, chunk)
        bad = jnp.sum((states != exp).astype(jnp.int32))
        return jax.lax.psum(bad, ("data", "shard"))

    fn = jax.shard_map(kernel, mesh=mesh,
                       in_specs=(P(("data", "shard"), None), P(("data", "shard"))),
                       out_specs=P())
    return fn(blocks, expected_states)


# -- helpers ----------------------------------------------------------------

def shard_put(mesh: Mesh, arr: np.ndarray, spec: P) -> jax.Array:
    return jax.device_put(arr, NamedSharding(mesh, spec))


class MeshCoder(ErasureCoder):
    """ErasureCoder over the mesh-sharded encode: the seam that lets
    the disk-fed streaming pipeline (ec/stream.encode_volumes) batch host
    slabs straight onto a multi-chip mesh. Batches ride the 'data' axis,
    parity rows the 'shard' axis — the same layout dryrun_multichip
    validates, now fed from real volume files (SURVEY §5 'sharded stripe
    pipelines over ICI with DCN fan-in')."""

    async_dispatch = True  # device arrays materialize on np.asarray

    def __init__(self, mesh: Mesh, d: int, p: int):
        super().__init__(d, p)
        self.mesh = mesh
        #: {device: bytes} of the last host batch put on the mesh — what
        #: each chip holds of one input batch (ec.encode.finish carries it)
        self.batch_bytes_by_device: "dict[str, int]" = {}

    def encode(self, data) -> jax.Array:
        b = data.shape[0]
        n_data = self.mesh.shape["data"]
        if b % n_data:  # pad batch to the data-axis multiple
            pad = _ceil_to(b, n_data) - b
            data = np.concatenate(
                [data, np.zeros((pad,) + data.shape[1:], np.uint8)])
            return encode_sharded(self.mesh, self._put(data),
                                  self.d, self.p)[:b, :self.p, :]
        return encode_sharded(self.mesh, self._put(data),
                              self.d, self.p)[:, :self.p, :]

    def _put(self, data) -> jax.Array:
        """Host batch -> mesh, split along 'data' at transfer time.

        An explicit NamedSharding device_put sends each device only its
        B/n_data batch rows (parallel host->device DMA); a plain
        jnp.asarray would land the whole array on one device and reshard
        over the interconnect inside the jit."""
        if isinstance(data, jax.Array):
            return data
        arr = jax.device_put(
            data, NamedSharding(self.mesh, P("data", None, None)))
        self.batch_bytes_by_device = {
            str(s.device): int(s.data.nbytes)
            for s in arr.addressable_shards}
        return arr

    def reconstruct(self, survivors, present, wanted):
        """survivors [B, d, L] (or one [d, L] stripe, as a degraded read
        hands over) = shard rows sorted(present)[:d]."""
        survivors = np.asarray(survivors)
        squeeze = survivors.ndim == 2
        if squeeze:
            survivors = survivors[None]
        present = tuple(sorted(present))[:self.d]
        b, _, l = survivors.shape
        n_pad = _ceil_to(self.n, self.mesh.shape["shard"])
        # the batch rides the 'data' axis: round it up with zero stripes
        wiped = np.zeros((_ceil_to(b, self.mesh.shape["data"]), n_pad, l),
                         dtype=np.uint8)
        wiped[:b, list(present), :] = survivors
        rebuilt = rebuild_sharded(self.mesh, jnp.asarray(wiped), present,
                                  self.d, self.p)[:b, list(wanted), :]
        return rebuilt[0] if squeeze else rebuilt


def _all_device_mesh_coder(d: int, p: int) -> MeshCoder:
    """Registry factory: MeshCoder over every visible device, so the volume
    server CLI can ask for multi-chip encode with `-coder mesh` exactly like
    any other coder name (ops.coder.get_coder lazily imports this module)."""
    from .mesh import build_mesh
    return MeshCoder(build_mesh(), d, p)


register_coder("mesh", _all_device_mesh_coder)
