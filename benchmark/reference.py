"""The benchmark's plain reference: what a seal, a repair and a scrub must
produce, in table-driven numpy and independent of the code under test.

* GF(2^8) Reed-Solomon over polynomial 0x11D with the systematic
  Vandermonde matrix (`V[r, c] = r^c`, times the inverse of its top
  square) — the construction `ops/gf8.py` copies from klauspost's
  reedsolomon, copied here so the yardstick does not move when the
  program does.
* The stripe layout of `ec/locate.py` / the fork's `ec_encoder.go`: rows
  of `d` large blocks while more than one large row remains, then rows of
  `d` small blocks, the last row zero-padded.
* CRC32C (Castagnoli, reflected 0x82F63B78), bytewise by table.

Checked against `NativeCoder`, `NumpyCoder` and the store's `crc32c` once
in the benchmark's tests; nothing here imports the program.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D
LARGE_BLOCK = 1 << 30
SMALL_BLOCK = 1 << 20


def _tables():
    exp = np.zeros(510, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:] = exp[:255]
    mul = np.zeros((256, 256), dtype=np.uint8)
    nz = np.arange(1, 256)
    mul[np.ix_(nz, nz)] = exp[log[nz][:, None] + log[nz][None, :]]
    return exp, log, mul


_EXP, _LOG, MUL = _tables()


def _pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(_EXP[(int(_LOG[a]) * n) % 255])


def _inv(a: int) -> int:
    return int(_EXP[255 - int(_LOG[a])])


def _matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for k in range(a.shape[1]):
        out ^= MUL[a[:, k][:, None], b[k][None, :]]
    return out


def _mat_inv(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    aug = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for col in range(n):
        pivot = next(r for r in range(col, n) if aug[r, col])
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        aug[col] = MUL[_inv(int(aug[col, col])), aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= MUL[int(aug[r, col]), aug[col]]
    return aug[:, n:].copy()


@functools.lru_cache(maxsize=16)
def encode_matrix(d: int, p: int) -> np.ndarray:
    """Systematic [d+p, d]: identity on top, parity rows below."""
    vand = np.array([[_pow(r, c) for c in range(d)] for r in range(d + p)],
                    dtype=np.uint8)
    return _matmul(vand, _mat_inv(vand[:d]))


def apply(mat: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """`mat` [m, k] over `rows` [k, L] uint8 -> [m, L]."""
    out = np.zeros((mat.shape[0], rows.shape[1]), dtype=np.uint8)
    for j in range(mat.shape[0]):
        for k in range(mat.shape[1]):
            c = int(mat[j, k])
            if c:
                out[j] ^= MUL[c][rows[k]]
    return out


def encode(data: np.ndarray, p: int) -> np.ndarray:
    """Data rows [d, L] -> parity rows [p, L]."""
    d = data.shape[0]
    return apply(encode_matrix(d, p)[d:], data)


def reconstruct(survivors: np.ndarray, present: "list[int]",
                wanted: "list[int]", d: int, p: int) -> np.ndarray:
    """Rows of the first d shards of sorted `present` -> rows `wanted`."""
    use = sorted(present)[:d]
    enc = encode_matrix(d, p)
    rec = _matmul(enc, _mat_inv(enc[use]))
    return apply(rec[list(wanted)], survivors)


# -- stripe layout -----------------------------------------------------------

def large_rows(dat_size: int, d: int, large: int = LARGE_BLOCK) -> int:
    rows = 0
    while dat_size > large * d:
        rows += 1
        dat_size -= large * d
    return rows


def small_rows(dat_size: int, d: int, large: int = LARGE_BLOCK,
               small: int = SMALL_BLOCK) -> int:
    rest = dat_size - large_rows(dat_size, d, large) * large * d
    return -(-rest // (small * d))


def shard_file_size(dat_size: int, d: int, large: int = LARGE_BLOCK,
                    small: int = SMALL_BLOCK) -> int:
    return (large_rows(dat_size, d, large) * large
            + small_rows(dat_size, d, large, small) * small)


def small_row(dat: np.ndarray, row: int, d: int, large: int = LARGE_BLOCK,
              small: int = SMALL_BLOCK) -> "tuple[np.ndarray, int]":
    """Data blocks [d, small] of small row `row` of a `.dat` (zero-padded
    past its end), and the row's offset inside every shard file."""
    nl = large_rows(dat.size, d, large)
    start = nl * large * d + row * small * d
    flat = np.zeros(small * d, dtype=np.uint8)
    chunk = np.asarray(dat[start:start + small * d])
    flat[:chunk.size] = chunk
    return flat.reshape(d, small), nl * large + row * small


# -- CRC32C ------------------------------------------------------------------

def _crc_table() -> "list[int]":
    table = []
    for n in range(256):
        c = n
        for _ in range(8):
            c = (c >> 1) ^ 0x82F63B78 if c & 1 else c >> 1
        table.append(c)
    return table


_CRC = _crc_table()


def crc32c(data: bytes) -> int:
    c = 0xFFFFFFFF
    for b in data:
        c = _CRC[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF
