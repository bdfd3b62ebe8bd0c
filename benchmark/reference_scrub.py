"""The plain reference of a scrub of one volume: the `.dat`'s records
walked one by one with their own header parse, liveness from the `.idx`
log replayed, each live needle's CRC32C recomputed bytewise
(`reference.crc32c`). Nothing here imports the program.

The v3 needle record: header (cookie u32, id u64, size u32), then `size`
body bytes (data length u32, data, flags and the rest), the CRC32C of the
data (u32), the append time (u64), zero padding to 8 bytes. The `.idx` is
a log of (id u64, offset / 8 u32, size u32), a deletion's size
0xFFFFFFFF; the `.dat` starts with an 8-byte superblock."""

from __future__ import annotations

import struct

from benchmark.reference import crc32c

_TOMBSTONE = 0xFFFFFFFF


def _record_bytes(size: int) -> int:
    raw = 16 + (0 if size == _TOMBSTONE else size) + 4 + 8
    return raw + (-raw) % 8


def scrub_reference(dat_path: str, idx_path: str):
    """(needles scanned, sorted corrupt ids, torn) of a scrub of the
    volume: every live needle's data against its stored CRC; a live
    needle whose data length overruns its body is corrupt; `torn` if the
    record chain ends before the file does."""
    live = {}
    with open(idx_path, "rb") as f:
        idx = f.read()
    for at in range(0, len(idx) - len(idx) % 16, 16):
        key, off, size = struct.unpack_from("<QII", idx, at)
        if size == _TOMBSTONE:
            live.pop(key, None)
        else:
            live[key] = off * 8
    with open(dat_path, "rb") as f:
        dat = f.read()
    assert dat[0] == 3, f"superblock version {dat[0]}, the reference reads v3"
    end, pos = len(dat), 8
    scanned, corrupt = 0, []
    while pos + 16 <= end:
        _cookie, key, size = struct.unpack_from("<IQI", dat, pos)
        if pos + _record_bytes(size) > end:
            break
        if size != _TOMBSTONE and live.get(key) == pos:
            scanned += 1
            (dlen,) = struct.unpack_from("<I", dat, pos + 16)
            (stored,) = struct.unpack_from("<I", dat, pos + 16 + size)
            if dlen + 4 > size or crc32c(
                    dat[pos + 20:pos + 20 + dlen]) != stored:
                corrupt.append(key)
        pos += _record_bytes(size)
    return scanned, sorted(corrupt), pos < end
