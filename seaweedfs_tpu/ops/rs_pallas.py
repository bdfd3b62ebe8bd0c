"""Pallas TPU kernel for GF(2^8) Reed-Solomon encode / reconstruct.

The XLA einsum path (ops/rs_jax.py) expresses the GF(2) bit-matmul as
unpack -> einsum -> pack and trusts the compiler to fuse; measured on a v5e
it sustains ~40 GB/s. This kernel pins the whole pipeline in VMEM per tile
and reformulates the two elementwise stages so they vectorize:

* **Plane-major bitcast unpack.** `pltpu.bitcast` reinterprets groups of 4
  sublanes (rows) as one int32 row, so `(x32 >> s) & 0x01010101` extracts
  bit s of FOUR bytes per lane-op. Eight shift/mask passes produce the bit
  planes at ~1/6 the VPU cost of per-element int32 unpacking. The planes
  concatenate plane-major (row s*dp + r = bit s of data row r), and the
  encode matrix's columns are permuted once on the host to match.
* **MXU bit-matmul.** int8 x int8 -> int32 dot of the permuted bit-matrix
  [8m, 8*dp] with the bit planes [8*dp, T]; sums <= 8d < 2^31 so `& 1`
  recovers the GF(2) product exactly.
* **Pack via a second tiny dot.** Recombining 8 parity-bit rows into bytes
  is itself a matmul with a constant [m, 8m] weight matrix (1 << s at
  column 8j+s) — cheaper on the MXU than a cross-sublane shift/sum on the
  VPU (measured: 0.5 ms vs 1.0 ms per 160 MB).

HBM sees the input bytes once and the output bytes once: (d+m)/d bytes per
data byte. Measured end to end (chained-marginal, 160 MB batches, RS 10+4):
~118 GB/s vs ~40 GB/s for the einsum path on the same harness — ~3x.

Replaces: klauspost/reedsolomon's AVX2 galMulSlicesAvx2 loops invoked from
reference weed/storage/erasure_coding/ec_encoder.go:183 (`enc.Encode`) and
weed/storage/store_ec.go:402 (`ReconstructData`).

Availability: the compiled path needs a TPU. `available()` asks the device
gate (ops/device.py), which raises when the backend the process was told to
use does not come up; JaxCoder runs this kernel on a TPU and the einsum
formulation only in a process told JAX_PLATFORMS=cpu, where tests also run
this kernel in interpreter mode so its logic is covered everywhere.

Compiled on a v5e (JAX 0.9.0) at the shapes the daemons dispatch — encode
and rebuild at [32, d, 1 MiB] for RS(14,2) and RS(10,4), degraded-read
intervals at [1, d, C] — tile 32768 fits the default 16 MiB scoped VMEM at
both geometries. The lane axis is padded to whole tiles inside `_apply`: a
whole-C block of arbitrary length (the old fallback for a C no 128-multiple
divides) took 9 s to compile at C=300001 and ran out of VMEM at C=777777.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import device, gf8

DEFAULT_TILE = 1 << 15  # lane-dim tile; best measured on v5e (sweep 2K-32K)
_LANE = 128


def available() -> bool:
    """True on a TPU; a backend that cannot come up raises in the gate."""
    return device.info().platform == "tpu"


def _plane_major(mat: np.ndarray) -> np.ndarray:
    """A GF(2^8) matrix [m, k] as the kernel's bit-matrix [8m, 8*kp]:
    byte-major bit column r*8 + s goes to plane-major column s*kp + r
    (kp = k rounded up to 4 for the sublane bitcast; the columns of the
    padding rows stay zero)."""
    bm = gf8.expand_to_bits(mat).astype(np.int8)
    k = mat.shape[1]
    kp = (k + 3) // 4 * 4
    out = np.zeros((bm.shape[0], 8 * kp), dtype=np.int8)
    for r in range(k):
        for s in range(8):
            out[:, s * kp + r] = bm[:, r * 8 + s]
    out.setflags(write=False)
    return out


@functools.lru_cache(maxsize=512)
def _plane_major_bitmatrix(key: tuple) -> np.ndarray:
    """The bit-matrix of key = (kind, d, p, present, wanted): the parity
    rows, or the decode rows of `wanted` over the survivors `present`."""
    kind, d, p, present, wanted = key
    if kind == "enc":
        return _plane_major(gf8.parity_matrix(d, p))
    return _plane_major(gf8.decode_matrix(d, p, list(present))[list(wanted), :])


def matrix_operand(mat: np.ndarray) -> np.ndarray:
    """Any GF(2^8) matrix [m, k] as `matrix_apply_jit`'s operand."""
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    return _matrix_operand(mat.shape, mat.tobytes())


@functools.lru_cache(maxsize=64)
def _matrix_operand(shape: tuple, raw: bytes) -> np.ndarray:
    return _plane_major(np.frombuffer(raw, dtype=np.uint8).reshape(shape))


@functools.lru_cache(maxsize=64)
def _pack_matrix(m: int) -> np.ndarray:
    """[m, 8m] int8 weights recombining LSB-first bit rows into bytes.

    1 << 7 wraps to -128 in int8; the final uint8 cast of the int32
    accumulator makes the sign irrelevant (mod-256 arithmetic).
    """
    pm = np.zeros((m, 8 * m), dtype=np.int16)
    for j in range(m):
        for s in range(8):
            pm[j, 8 * j + s] = 1 << s
    out = pm.astype(np.int8)
    out.setflags(write=False)
    return out


def _make_kernel(d: int, dp: int, tile: int):
    def kernel(bmat_ref, packm_ref, seed_ref, data_ref, out_ref):
        data = data_ref[0] ^ seed_ref[0].astype(jnp.uint8)
        if dp != d:
            data = jnp.concatenate(
                [data, jnp.zeros((dp - d, tile), jnp.uint8)], axis=0)
        x32 = pltpu.bitcast(data, jnp.int32)              # [dp/4, T]
        planes = [
            pltpu.bitcast(((x32 >> s) & 0x01010101).astype(jnp.int32),
                          jnp.uint8)
            for s in range(8)
        ]
        bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)  # [8dp, T]
        acc = lax.dot_general(bmat_ref[:], bits, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
        pb = (acc & 1).astype(jnp.int8)                   # [8m, T] 0/1
        packed = lax.dot_general(packm_ref[:], pb, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.int32)
        out_ref[0] = packed.astype(jnp.uint8)
    return kernel


def _apply(bmat, data: jax.Array, seed: jax.Array, tile: int,
           interpret: bool) -> jax.Array:
    """The kernel under the plane-major bit-matrix `bmat`: a numpy array
    is baked into the program as a constant (one program per matrix and
    shape), a traced array rides as an operand."""
    b, d, c = data.shape
    m = bmat.shape[0] // 8
    packm = _pack_matrix(m)
    dp = (d + 3) // 4 * 4
    # every block is whole lanes: C splits into the fewest equal tiles
    # no longer than `tile`, each rounded up to the lane multiple, and
    # the padding (< 128 columns per tile; zero columns encode to zero)
    # is sliced off. 1 MiB slabs divide evenly and copy nothing.
    steps = -(-c // tile)
    tile = -(-c // (steps * _LANE)) * _LANE
    cp = steps * tile
    if cp != c:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, cp - c)))
    out = pl.pallas_call(
        _make_kernel(d, dp, tile),
        grid=(b, cp // tile),
        in_specs=[
            pl.BlockSpec(bmat.shape, lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(packm.shape, lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, d, tile), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, m, tile), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, m, cp), jnp.uint8),
        interpret=interpret,
    )(jnp.asarray(bmat), jnp.asarray(packm), seed, data)
    return out if cp == c else out[..., :c]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def encode_jit(data: jax.Array, d: int, p: int, tile: int = DEFAULT_TILE,
               interpret: bool = False) -> jax.Array:
    """data [B, d, C] uint8 -> parity [B, p, C] uint8 (Pallas kernel)."""
    return _apply(_plane_major_bitmatrix(("enc", d, p, (), ())), data,
                  jnp.zeros(1, jnp.int32), tile, interpret)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def reconstruct_jit(survivors: jax.Array, present: tuple, wanted: tuple,
                    d: int, p: int, tile: int = DEFAULT_TILE,
                    interpret: bool = False) -> jax.Array:
    """survivors [B, d, C] (rows = sorted(present)[:d]) -> [B, |wanted|, C]."""
    key = ("rec", d, p, tuple(sorted(present)[:d]), tuple(wanted))
    return _apply(_plane_major_bitmatrix(key), survivors,
                  jnp.zeros(1, jnp.int32), tile, interpret)


@functools.partial(jax.jit, static_argnums=(2, 3))
def matrix_apply_jit(rows: jax.Array, bmat: jax.Array,
                     tile: int = DEFAULT_TILE,
                     interpret: bool = False) -> jax.Array:
    """rows [B, k, C] under the matrix `bmat` (`matrix_operand` of an
    [m, k] GF(2^8) matrix) -> [B, m, C]: the same kernel with the matrix
    as an OPERAND, so one program serves every matrix of a shape (a
    codec's repair matrices, ops/piggyback.py); the plain-RS entries
    above keep their matrices baked in and their programs."""
    return _apply(bmat, rows, jnp.zeros(1, jnp.int32), tile, interpret)
