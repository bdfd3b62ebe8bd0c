"""The plain reference's Hitchhiker-XOR part (Rashmi et al., SIGCOMM 2014,
arXiv:1412.3022, the XOR variant on two substripes), over the tables and
matrices of `reference.py` and independent of the code under test.

A shard's byte range is two substripes, a = the first half and b = the
second. Substripe a is a plain RS(d, p) codeword. Substripe b is one too,
except that parities 1 .. p-1 carry a piggyback:

    pb_g = P_g(b)  xor  (xor of a_i for i in S_g)        g = 1 .. p-1

with the data shards dealt round-robin into the p-1 groups S_1 .. S_{p-1}
(shard i goes to group i mod (p-1)). A lost data shard f of group g comes
back the paper's two-step way from (d + |S_g|) half-shards:

  1. b_f by plain RS decoding from the b-halves of the other d-1 data
     shards and of parity 0;
  2. a_f = pb_g xor P_g(b) xor (xor of a_i for i in S_g, i != f), with
     P_g(b) computed from the now whole b substripe.

Checked against `PiggybackCoder` once in the benchmark's tests; nothing
here imports the program.
"""

from __future__ import annotations

import numpy as np

from . import reference


def groups(d: int, p: int) -> "list[list[int]]":
    """S_1 .. S_{p-1}: groups[g - 1] backs parity g."""
    return [[i for i in range(d) if i % (p - 1) == g] for g in range(p - 1)]


def encode(data: np.ndarray, p: int) -> np.ndarray:
    """Data rows [d, L] (L even: a = [:L/2], b = [L/2:]) -> the p parity
    rows [p, L], those after the first with their piggyback."""
    d, length = data.shape
    assert length % 2 == 0, "two substripes need an even length"
    half = length // 2
    parity = reference.encode(data, p)
    for g, members in enumerate(groups(d, p), start=1):
        for i in members:
            parity[g, half:] ^= data[i, :half]
    return parity


def reads(f: int, d: int, p: int) -> "list[tuple[int, str]]":
    """What the repair of data shard f reads: (shard, 'a' | 'b') halves,
    d + |S_g| of them."""
    g = f % (p - 1) + 1
    others = [i for i in range(d) if i != f]
    return ([(i, "b") for i in others] + [(d, "b"), (d + g, "b")]
            + [(i, "a") for i in groups(d, p)[g - 1] if i != f])


def repair(halves: "dict[tuple[int, str], np.ndarray]", f: int, d: int,
           p: int) -> np.ndarray:
    """Data shard f, a then b, from the half-shards `reads` names."""
    g = f % (p - 1) + 1
    others = [i for i in range(d) if i != f]
    # step 1: b_f, plain RS over the other data shards and parity 0
    present = others + [d]
    b_f = reference.reconstruct(
        np.stack([halves[(s, "b")] for s in present]), present, [f], d, p)[0]
    # step 2: P_g(b) from the whole b substripe, then peel a_f off pb_g
    b = np.empty((d, b_f.size), dtype=np.uint8)
    for i in others:
        b[i] = halves[(i, "b")]
    b[f] = b_f
    a_f = halves[(d + g, "b")] ^ reference.encode(b, p)[g]
    for i in groups(d, p)[g - 1]:
        if i != f:
            a_f = a_f ^ halves[(i, "a")]
    return np.concatenate([a_f, b_f])


def read_bytes(f: int, d: int, p: int, shard_size: int) -> int:
    """Survivor bytes the repair of data shard f reads, by the plan."""
    return len(reads(f, d, p)) * (shard_size // 2)


# -- a sealed volume ---------------------------------------------------------

def _data_files(dat: np.ndarray, d: int, lo: int, hi: int, small: int,
                ) -> np.ndarray:
    """Bytes [lo, hi) of the d data shard FILES of a volume whose rows
    are all small-block rows: file byte y is byte y % small of the
    block of row y // small."""
    out = np.empty((d, hi - lo), dtype=np.uint8)
    for row in range(lo // small, -(-hi // small)):
        blocks, off = reference.small_row(dat, row, d, small=small)
        a, b = max(lo, off), min(hi, off + small)
        out[:, a - lo:b - lo] = blocks[:, a - off:b - off]
    return out


def sealed_row(dat: np.ndarray, row: int, d: int, p: int,
               large: int = reference.LARGE_BLOCK,
               small: int = reference.SMALL_BLOCK,
               ) -> "tuple[np.ndarray, int]":
    """The blocks [d + p, small] that the d + p shard files of a sealed
    volume hold for small row `row`, and the row's offset in every file.
    The two substripes are the halves of a whole shard FILE, so what of
    the row's parity blocks lies in the files' second half carries the
    group's data bytes from half a file earlier."""
    assert reference.large_rows(dat.size, d, large) == 0, \
        "written for volumes of small-block rows only"
    blocks, off = reference.small_row(dat, row, d, large, small)
    parity = reference.encode(blocks, p)
    half = reference.shard_file_size(dat.size, d, large, small) // 2
    lo = max(off, half)
    if lo < off + small:
        a = _data_files(dat, d, lo - half, off + small - half, small)
        for g, members in enumerate(groups(d, p), start=1):
            for i in members:
                parity[g, lo - off:] ^= a[i]
    return np.concatenate([blocks, parity]), off
