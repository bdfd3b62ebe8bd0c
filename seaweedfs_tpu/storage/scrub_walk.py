"""The scrub's record walk and pack, outside the volume server's
interpreter.

`iter_needles` walks a `.dat`'s record chain and yields each live
needle's data and stored CRC; `iter_blocks` groups them by length bucket
and packs them LEFT-zero-padded into fixed `[B, L]` blocks, in the order
a sweep dispatches them. Both are plain functions over a file and the
needle map's live arrays, with no `Volume`: the host loop of
`storage/scrub.py` runs `iter_needles` in-process, and the device path
runs `iter_blocks` in a walker process of its own,

    python -S -m seaweedfs_tpu.storage.scrub_walk <fd> <dat>

which the sweep starts for one volume (`scrub._Walker`). The walker
imports the standard library and `storage/types.py` only (never numpy,
never jax: the chip belongs to the server), so it starts in about a tenth
of a second. `<fd>` is an inherited memory file of `slots` block slots
that both processes map; -1 where the sweep could not make one (a file
size limit under a slot set), and then each block follows its message
over stdout. `<dat>` is the volume's `.dat`, opened by the sweep under
the volume's lock beside its snapshot of the live needles, so that a
vacuum that swaps the file afterwards does not reach the walk.
Over stdin the sweep sends one JSON line (`end`, `dispatch_bytes`,
`slots`, `slot_bytes`, `live`), then the `live` needle ids and their
byte offsets as u64s, then one byte a slot as it frees one.
Over stdout the walker sends one JSON line a message:

    {"block": k, "rows": B, "L": L, "n": n}   slot k holds a packed block:
        B x L bytes, then the ids (u64), lengths (u64) and stored CRCs
        (u32) of its first n rows, each array `meta_rows(...)` long, in
        the host's byte order; streamed, the n x L bytes of its first n
        rows and the three arrays' n entries follow the line
    {"rotted": id}         a live needle whose length field is rotted
    {"end": true, "error": "...", "walk_s": .., "pack_s": .., "busy_s": ..}

`walk_s` and `pack_s` are the walker's own seconds, `busy_s` its seconds
from the request to the end less its waits for a free slot. A walker
that exits without `end` has died mid-volume.
"""

from __future__ import annotations

import json
import mmap
import os
import struct
import sys
import time
from array import array

from . import types as t
from .super_block import SUPER_BLOCK_SIZE

CHUNK = 512  # the CRC program's chunk: the least row length of a block
_U32 = struct.Struct("<I")
# the record walk reads through one buffer of this many bytes
_READ_BUFFER = 1 << 20


def bucket(n: int) -> int:
    """The power of two at or above `n`, at least one chunk."""
    out = CHUNK
    while out < n:
        out *= 2
    return out


def block_shape(length: int, dispatch_bytes: int) -> "tuple[int, int]":
    """(B, L) of the fixed block a needle of `length` rides in: B x L =
    `dispatch_bytes`, or one row for a needle longer than that."""
    pad_l = bucket(length)
    return max(1, dispatch_bytes // pad_l), pad_l


def meta_rows(dispatch_bytes: int) -> int:
    """The most rows a block of `dispatch_bytes` has."""
    return max(1, dispatch_bytes // CHUNK)


def iter_needles(f, end: int, where):
    """Walk the record chain of the open `.dat` `f` up to `end`, yielding
    ("needle", id, data, stored_crc) for each LIVE needle and ("rotted",
    id) for a live one whose length field is itself rotted, then
    ("error", message) if the walk ended before `end` (header rot
    desyncing the chain, or a torn write: the silent failure the scrub
    exists to catch). `where(id)` is the byte offset of the needle's live
    record, or None; garbage records (overwritten or tombstoned versions,
    pre-vacuum) and tombstones are skipped: rot in unreachable data must
    not alarm."""
    last_end = SUPER_BLOCK_SIZE
    for pos, nid, nsize in t.iter_records(f, SUPER_BLOCK_SIZE, end):
        last_end = pos + t.record_size_from_header(nsize)
        if t.is_tombstone(nsize) or where(nid) != pos:
            continue
        f.seek(pos + t.NEEDLE_HEADER_SIZE)
        body = f.read(nsize + 4)
        (dlen,) = _U32.unpack_from(body, 0)
        if dlen + 4 > nsize:
            yield "rotted", nid
            continue
        yield ("needle", nid, body[4:4 + dlen],
               _U32.unpack_from(body, nsize)[0])
    if last_end < end:
        yield "error", (f"record walk torn at offset {last_end}: "
                        f"{end - last_end} trailing bytes unscanned "
                        f"(header rot or torn write)")


def _pack(buf, shape: "tuple[int, int]", datas: "list[bytes]") -> None:
    """Needles LEFT-zero-padded into the first rows of the [B, L] block
    `buf` (row-major bytes); rows past them are left as they are."""
    pad_l = shape[1]
    mv = memoryview(buf)
    zeros = memoryview(bytes(pad_l - min(map(len, datas), default=pad_l)))
    for i, d in enumerate(datas):
        row = i * pad_l
        at = row + pad_l - len(d)
        mv[row:at] = zeros[:at - row]
        mv[at:row + pad_l] = d


def iter_blocks(dat, end: int, live_keys, live_offsets,
                dispatch_bytes: int, block=bytearray, times=None):
    """The device path's walk: every live needle of the `.dat` `dat` (a
    path, or an open descriptor, which it closes) up to `end`, packed into
    blocks. `live_keys` and
    `live_offsets` are the needle map's live ids and their records' byte
    offsets. Yields, in dispatch order,

        ("block", (B, L), buf, ids, lengths, stored_crcs)

    when a bucket's last row fills, and the partial blocks at the end in
    the order of their first needle; ("rotted", id) as the walk meets a
    rotted length field; last, ("error", message) where the walk tore.
    `block(nbytes)` gives the buffer a block is packed into (a fresh
    bytearray, or a free slot of the walker). `times`, a dict, gets the
    seconds spent walking (`walk`) and packing (`pack`); neither holds
    the time spent in `block` or suspended at a yield."""
    where = dict(zip(map(int, live_keys), map(int, live_offsets))).get
    times = {} if times is None else times
    times.setdefault("walk", 0.0)
    times.setdefault("pack", 0.0)
    pending: "dict[tuple[int, int], tuple[list, list, list]]" = {}
    error = None

    def packed(shape, ids, datas, stored):
        buf = block(shape[0] * shape[1])
        t0 = time.perf_counter()
        _pack(buf, shape, datas)
        times["pack"] += time.perf_counter() - t0
        return "block", shape, buf, ids, [len(d) for d in datas], stored

    with open(dat, "rb", buffering=_READ_BUFFER) as f:
        t0 = time.perf_counter()
        for ev in iter_needles(f, end, where):
            if ev[0] == "error":
                error = ev
                continue
            if ev[0] == "rotted":
                times["walk"] += time.perf_counter() - t0
                yield ev
                t0 = time.perf_counter()
                continue
            _, nid, data, crc = ev
            shape = block_shape(len(data), dispatch_bytes)
            ids, datas, stored = pending.setdefault(shape, ([], [], []))
            ids.append(nid)
            datas.append(data)
            stored.append(crc)
            if len(ids) == shape[0]:
                times["walk"] += time.perf_counter() - t0
                yield packed(shape, *pending.pop(shape))
                t0 = time.perf_counter()
        times["walk"] += time.perf_counter() - t0
    for shape, batch in pending.items():
        yield packed(shape, *batch)
    if error is not None:
        yield error


def _read_exactly(n: int) -> bytes:
    out = bytearray()
    while len(out) < n:
        got = os.read(0, n - len(out))
        if not got:
            raise EOFError("the sweep closed the walker's stdin")
        out += got
    return bytes(out)


def _write_all(data) -> None:
    data = memoryview(data)
    while data:
        data = data[os.write(1, data):]


def main(argv: "list[str]") -> int:
    """The walker process: one volume, then exit."""
    fd, dat = int(argv[0]), int(argv[1])
    line = bytearray()
    while not line.endswith(b"\n"):
        line += _read_exactly(1)
    req = json.loads(line)
    n = int(req["live"])
    keys, offsets = array("Q"), array("Q")
    keys.frombytes(_read_exactly(8 * n))
    offsets.frombytes(_read_exactly(8 * n))
    dispatch = int(req["dispatch_bytes"])
    slot_bytes, rows = int(req["slot_bytes"]), meta_rows(dispatch)
    stride = slot_stride(slot_bytes, dispatch)
    if fd >= 0:  # the slots are shared
        mm = mmap.mmap(fd, stride * int(req["slots"]))
        os.close(fd)
    else:  # the slots are this process's, and each block is streamed
        mm = bytearray(stride * int(req["slots"]))
    free = list(range(int(req["slots"])))
    slot = -1  # the slot the block being packed went into
    waited = 0.0
    t_start = time.perf_counter()

    def send(msg: dict) -> None:
        _write_all((json.dumps(msg) + "\n").encode())

    def block(nbytes: int):
        nonlocal slot, waited
        if nbytes > slot_bytes:
            raise ValueError(f"a block of {nbytes} B does not fit a slot "
                             f"of {slot_bytes} B")
        if not free:  # every slot is with the sweep: wait for one back
            t0 = time.perf_counter()
            free.extend(_read_exactly(1))
            waited += time.perf_counter() - t0
        slot = free.pop(0)
        return memoryview(mm)[slot * stride:slot * stride + nbytes]

    times: dict = {}
    error = ""
    for ev in iter_blocks(dat, int(req["end"]), keys, offsets,
                          dispatch, block, times):
        if ev[0] == "block":
            _, (b, pad_l), _buf, ids, lengths, stored = ev
            at = slot * stride
            parts = [(at, len(ids) * pad_l)]
            at += slot_bytes
            for arr in (array("Q", ids), array("Q", lengths),
                        array("I", stored)):
                raw = arr.tobytes()
                mm[at:at + len(raw)] = raw
                parts.append((at, len(raw)))
                at += arr.itemsize * rows
            send({"block": slot, "rows": b, "L": pad_l, "n": len(ids)})
            if fd < 0:
                for at, size in parts:
                    _write_all(memoryview(mm)[at:at + size])
        elif ev[0] == "rotted":
            send({"rotted": ev[1]})
        else:
            error = ev[1]
    send({"end": True, "error": error, "walk_s": times["walk"],
          "pack_s": times["pack"],
          "busy_s": time.perf_counter() - t_start - waited})
    return 0


def slot_stride(slot_bytes: int, dispatch_bytes: int) -> int:
    """Bytes from one slot to the next: the block, its three row arrays,
    rounded up to a page."""
    raw = slot_bytes + 20 * meta_rows(dispatch_bytes)
    return -(-raw // mmap.PAGESIZE) * mmap.PAGESIZE


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
