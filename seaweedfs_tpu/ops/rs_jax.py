"""Reed-Solomon encode / reconstruct on device (pure JAX; XLA-fused).

Replaces the reference's CPU hot loop — klauspost/reedsolomon's AVX2
``Encode``/``Reconstruct`` called per 256 KB batch from
weed/storage/erasure_coding/ec_encoder.go:166-196 (`encodeDataOneBatch`) and
weed/storage/store_ec.go:402 (`ReconstructData`) — with one batched device
matmul over thousands of stripes.

Formulation (see ops/gf8.py): GF(2^8) shard arithmetic expands over GF(2) to

    out_bits[8m, N] = B[8m, 8k] @ in_bits[8k, N]   (mod 2)

where in_bits is the LSB-first bit-unpacking of the shard bytes. On TPU the
matmul runs on the MXU in int8 with int32 accumulation (sums <= 8k < 2^31, so
``& 1`` after accumulation is exact). The unpack (shift+and) and repack
(weighted sum over the bit axis, itself a tiny matmul) are elementwise VPU ops
XLA fuses around the dot. HBM traffic stays at (d+p)/d bytes per data byte —
the 8x bit expansion lives only in registers/VMEM.

All functions are shape-polymorphic in the batch/length axes and jitted by the
caller; matrices are compile-time constants baked in as literals.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf8

_BIT_SHIFTS = tuple(range(8))


def unpack_bits(data: jax.Array) -> jax.Array:
    """[..., k, L] uint8 -> [..., 8k, L] int8 bits, LSB-first per byte."""
    shifts = jnp.asarray(_BIT_SHIFTS, dtype=jnp.uint8).reshape(8, 1)
    bits = (data[..., :, None, :] >> shifts) & jnp.uint8(1)
    shape = (*data.shape[:-2], data.shape[-2] * 8, data.shape[-1])
    return bits.astype(jnp.int8).reshape(shape)


def pack_bits(bits: jax.Array) -> jax.Array:
    """[..., 8m, L] int{8,32} bits -> [..., m, L] uint8, LSB-first."""
    shape = (*bits.shape[:-2], bits.shape[-2] // 8, 8, bits.shape[-1])
    b = bits.reshape(shape).astype(jnp.uint8)
    weights = jnp.asarray([1 << s for s in _BIT_SHIFTS], dtype=jnp.uint8)
    return jnp.einsum("...bl,b->...l", b, weights)


def apply_bitmatrix(bmat: jax.Array, data: jax.Array) -> jax.Array:
    """GF(2^8) matrix application via GF(2) matmul.

    bmat: [8m, 8k] int8 (from gf8.expand_to_bits); data: [..., k, L] uint8.
    Returns [..., m, L] uint8.
    """
    bits = unpack_bits(data)  # [..., 8k, L]
    acc = jnp.einsum(
        "pk,...kl->...pl", bmat, bits, preferred_element_type=jnp.int32
    )
    return pack_bits(acc & 1)


@functools.lru_cache(maxsize=128)
def _parity_bitmatrix(d: int, p: int) -> np.ndarray:
    m = gf8.expand_to_bits(gf8.parity_matrix(d, p)).astype(np.int8)
    m.setflags(write=False)
    return m


@functools.lru_cache(maxsize=512)
def _decode_bitmatrix(d: int, p: int, present: tuple[int, ...], wanted: tuple[int, ...]) -> np.ndarray:
    rec = gf8.decode_matrix(d, p, list(present))  # [n, d]
    m = gf8.expand_to_bits(rec[list(wanted), :]).astype(np.int8)
    m.setflags(write=False)
    return m


def matrix_operand(mat: np.ndarray) -> np.ndarray:
    """A GF(2^8) matrix [m, k] as `matrix_apply_jit`'s bit-matrix operand."""
    return gf8.expand_to_bits(mat).astype(np.int8)


def encode(data: jax.Array, d: int, p: int) -> jax.Array:
    """data [..., d, L] uint8 -> parity [..., p, L] uint8."""
    if data.shape[-2] != d:
        raise ValueError(f"data shard axis {data.shape[-2]} != d={d}")
    return apply_bitmatrix(jnp.asarray(_parity_bitmatrix(d, p)), data)


def reconstruct(
    survivors: jax.Array,
    present: tuple[int, ...],
    wanted: tuple[int, ...],
    d: int,
    p: int,
) -> jax.Array:
    """Rebuild shards `wanted` from the first d surviving shards.

    survivors: [..., d, L] uint8 — rows are shards sorted(present)[:d].
    present/wanted are static (baked into the compiled matrix), matching how
    the reference inverts the matrix once per shard-loss pattern.
    """
    bmat = _decode_bitmatrix(d, p, tuple(sorted(present)[:d]), tuple(wanted))
    return apply_bitmatrix(jnp.asarray(bmat), survivors)


@functools.partial(jax.jit, static_argnums=(1, 2))
def encode_jit(data: jax.Array, d: int, p: int) -> jax.Array:
    return encode(data, d, p)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def reconstruct_jit(survivors, present, wanted, d, p):
    return reconstruct(survivors, present, wanted, d, p)


@jax.jit
def matrix_apply_jit(bmat: jax.Array, data: jax.Array) -> jax.Array:
    """`apply_bitmatrix` with the matrix as an operand (`matrix_operand`):
    one program a shape, for repairs that are a matrix plain RS does not
    name (ops/piggyback.py)."""
    return apply_bitmatrix(bmat, data)
