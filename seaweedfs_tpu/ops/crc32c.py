"""CRC32-Castagnoli: host oracle + batched device scrub kernel.

The reference verifies a CRC32C per needle on every read and during scrub
(reference: weed/storage/needle/crc.go:13 ``crc32.MakeTable(crc32.Castagnoli)``,
weed/storage/volume_checking.go:91 ``verifyNeedleIntegrity``). The stdlib Go
implementation is SSE4.2 hardware CRC; our host fallback is a table loop (the
C++ sidecar in seaweedfs_tpu/native provides the hardware version), and the
*batched* path — millions of needles scrubbed at once, BASELINE config 4 —
runs on TPU using the fact that CRC is GF(2)-affine in the message bits:

    state' = A @ state  ^  D @ byte_bits      (per byte, over GF(2))

so K bytes fold into one [32, 32] state matrix S_K = A^K and one [32, 8K]
injection matrix C_K. Nothing in that needs an order: with a zero initial
state the raw state of a row of T chunks is  XOR_t S_K^(T-1-t) C_K bits(chunk_t),
so the device program (device_crc_states) takes every chunk of every row
as one batch row of a single int8 product against C_K (its rows regrouped
by bit plane on the host, so the device unpacks no interleaved bits) and
then folds the T states of a row in log2(T) halvings with S_K, S_2K, S_4K,
... — no loop, no step that waits for another. Variable needle lengths are
handled by LEFT-padding with zeros: with a zero initial state, leading zero
bytes leave the state unchanged, and the true init (0xFFFFFFFF) is restored
afterwards with the length-dependent affine correction
crc_raw(m, I) = crc_raw(pad||m, 0) ^ A^len @ I, computed on host from
precomputed A^(2^j) powers (a batched 32-bit matvec).
"""

from __future__ import annotations

import functools

import numpy as np

CASTAGNOLI = 0x82F63B78  # reversed (LSB-first) representation
_INIT = 0xFFFFFFFF


@functools.lru_cache(maxsize=1)
def _table() -> np.ndarray:
    t = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ (CASTAGNOLI if (c & 1) else 0)
        t[i] = c
    return t


_native_update = None  # lazily resolved: False = unavailable, else C fn


def _soft_crc32c(data: bytes | np.ndarray, value: int = 0) -> int:
    t = _table()
    s = value ^ _INIT
    buf = bytes(data) if isinstance(data, (bytes, bytearray, memoryview)) else np.asarray(data, dtype=np.uint8).tobytes()
    for b in buf:
        s = (s >> 8) ^ int(t[(s ^ b) & 0xFF])
    return s ^ _INIT


def crc32c(data: bytes | np.ndarray, value: int = 0) -> int:
    """Standard CRC32C (init/final xor 0xFFFFFFFF); `value` chains calls.

    Dispatches to the C++ sidecar's SSE4.2 hardware loop when it loads
    (~1000x the table loop — this sits on every needle read and write),
    with the pure-Python table loop as the fallback oracle.
    """
    global _native_update
    if _native_update is None:
        try:
            from . import native
            lib = native.load()
            _native_update = lib.crc32c_update if lib is not None else False
        except Exception:  # pragma: no cover - toolchain-less env
            _native_update = False
    if _native_update is False:
        return _soft_crc32c(data, value)
    if isinstance(data, np.ndarray):
        arr = np.ascontiguousarray(data, dtype=np.uint8)
        return _native_update(value ^ _INIT, arr.ctypes.data, arr.size) ^ _INIT
    buf = data if isinstance(data, bytes) else bytes(data)
    return _native_update(value ^ _INIT, buf, len(buf)) ^ _INIT


# ---------------------------------------------------------------------------
# GF(2)-linear formulation. Bit convention: state bit i = (crc >> i) & 1,
# message bits LSB-first per byte — identical to ops/rs_jax.unpack_bits.
# ---------------------------------------------------------------------------

def _byte_step_matrices() -> tuple[np.ndarray, np.ndarray]:
    """A [32,32]: state map per byte; D [32,8]: byte-bit injection."""
    t = _table()
    a = np.zeros((32, 32), dtype=np.uint8)
    for i in range(32):
        s = 1 << i
        out = (s >> 8) ^ (int(t[s & 0xFF]))
        for j in range(32):
            a[j, i] = (out >> j) & 1
    d = np.zeros((32, 8), dtype=np.uint8)
    for i in range(8):
        out = int(t[1 << i])
        for j in range(32):
            d[j, i] = (out >> j) & 1
    return a, d


@functools.lru_cache(maxsize=1)
def _a_d() -> tuple[np.ndarray, np.ndarray]:
    return _byte_step_matrices()


def _m2mul(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    return (x.astype(np.int32) @ y.astype(np.int32) & 1).astype(np.uint8)


@functools.lru_cache(maxsize=64)
def chunk_matrices(k: int) -> tuple[np.ndarray, np.ndarray]:
    """(S_K [32,32], C_K [32,8K]) folding K message bytes into the state.

    state_after = S_K @ state ^ C_K @ bits(chunk), chunk byte 0 first,
    C_K columns [8*i : 8*i+8] belong to byte i (LSB-first).
    """
    a, d = _a_d()
    s = np.eye(32, dtype=np.uint8)
    cols = []
    # byte i passes through A another (k-1-i) times after injection
    powers = [np.eye(32, dtype=np.uint8)]
    for _ in range(k):
        powers.append(_m2mul(a, powers[-1]))
    for i in range(k):
        cols.append(_m2mul(powers[k - 1 - i], d))
    c = np.concatenate(cols, axis=1) if cols else np.zeros((32, 0), np.uint8)
    return powers[k], c


@functools.lru_cache(maxsize=1)
def _a_pow2() -> list[np.ndarray]:
    """A^(2^j) for j in 0..47 as uint32 column bitmasks for fast host matvec."""
    a, _ = _a_d()
    mats = []
    cur = a
    for _ in range(48):
        # column c as uint32 bitmask
        mask = np.zeros(32, dtype=np.uint32)
        for c in range(32):
            mask[c] = int.from_bytes(np.packbits(cur[:, c], bitorder="little").tobytes(), "little")
        mats.append(mask)
        cur = _m2mul(cur, cur)
    return mats


def _matvec_u32(colmask: np.ndarray, vec: np.ndarray) -> np.ndarray:
    """Apply 32x32 GF(2) matrix (uint32 column masks) to batched uint32 vecs."""
    out = np.zeros_like(vec)
    for c in range(32):
        bit = (vec >> np.uint32(c)) & np.uint32(1)
        out ^= colmask[c] * bit
    return out


def zero_prefix_correction(lengths: np.ndarray) -> np.ndarray:
    """A^len @ INIT for a batch of lengths -> uint32 raw-state corrections.

    crc_raw(msg, init=0xFFFFFFFF) = device_raw(zeropad||msg) ^ correction(len).
    """
    lengths = np.asarray(lengths, dtype=np.int64)
    vec = np.full(lengths.shape, _INIT, dtype=np.uint32)
    mats = _a_pow2()
    for j in range(48):
        bit = (lengths >> j) & 1
        if not bit.any():
            continue
        applied = _matvec_u32(mats[j], vec)
        vec = np.where(bit.astype(bool), applied, vec)
    return vec


def finalize(raw_states: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Combine device raw states (init-0, left-padded) into true CRC32C values."""
    return (np.asarray(raw_states, dtype=np.uint32)
            ^ zero_prefix_correction(lengths)
            ^ np.uint32(_INIT))


# ---------------------------------------------------------------------------
# Device kernel: batched CRC over [B, L] blocks (L % K == 0), LEFT-padded.
# ---------------------------------------------------------------------------

def plane_matrices(k: int) -> np.ndarray:
    """C_K's rows regrouped by bit plane: [8, K, 32] int8, so that the raw
    state of a K-byte chunk x is  sum_j ((x >> j) & 1) @ out[j]  (mod 2) and
    the device never interleaves bits along the lane axis."""
    _, c = chunk_matrices(k)  # [32, 8K], column 8*i + j = byte i, bit j
    return c.T.reshape(k, 8, 32).transpose(1, 0, 2).astype(np.int8)


def fold_matrices(k: int, depth: int) -> list[np.ndarray]:
    """[S_K^T, S_2K^T, ..., S_{K*2^(depth-1)}^T] as [32, 32] int8, by
    repeated squaring: S_2n = S_n @ S_n."""
    s, _ = chunk_matrices(k)
    out = []
    for _ in range(depth):
        out.append(s.T.astype(np.int8))
        s = _m2mul(s, s)
    return out


def device_crc_states(blocks, chunk: int = 512):
    """blocks [B, L] uint8 (L multiple of `chunk`) -> raw states [B] uint32.

    No step depends on another. Every `chunk`-byte piece of every row is a
    batch row of ONE integer product, its eight bit planes against
    plane_matrices(chunk), which gives the piece's own raw state; the
    T = L/chunk states of a row then fold in log2(T) halvings,
    s[t] <- S_{K*T/2} s[t] ^ s[t + T/2]  (T left-padded with zero states to
    a power of two, harmless as left-padding bytes is). int8 in, int32
    accumulate, `& 1`: exact. Intended to be wrapped in jit (and
    shard_mapped over a mesh for the distributed scrub, parallel/pipeline.py).
    """
    import jax
    import jax.numpy as jnp

    b, l = blocks.shape
    assert l % chunk == 0, (l, chunk)
    t = l // chunk
    if t == 0:
        return jnp.zeros((b,), dtype=jnp.uint32)
    # [B, T, K] and not [B*T, K]: merging B into T is a second relayout of
    # the block on a TPU (and ten times the compile). The barrier keeps the
    # one relayout in front of the eight plane extractions; XLA otherwise
    # extracts first and relays eight planes.
    pieces = jax.lax.optimization_barrier(blocks.reshape(b, t, chunk))
    acc = sum(
        jnp.einsum("btk,kj->btj", ((pieces >> j) & 1).astype(jnp.int8),
                   jnp.asarray(m), preferred_element_type=jnp.int32)
        for j, m in enumerate(plane_matrices(chunk)))
    s = (acc & 1).astype(jnp.int8)
    depth = (t - 1).bit_length()
    s = jnp.pad(s, ((0, 0), ((1 << depth) - t, 0), (0, 0)))
    for s_t in reversed(fold_matrices(chunk, depth)):
        half = s.shape[1] // 2
        s = ((jnp.einsum("bti,ij->btj", s[:, :half], jnp.asarray(s_t),
                         preferred_element_type=jnp.int32)
              + s[:, half:]) & 1).astype(jnp.int8)
    weights = jnp.asarray([np.uint32(1 << i) for i in range(32)], dtype=jnp.uint32)
    return jnp.sum(s[:, 0].astype(jnp.uint32) * weights, axis=1)
