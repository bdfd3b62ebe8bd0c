"""Operational CRC scrub: stream a volume's needles through the batched
device CRC kernel (ops/crc32c.device_crc_states) on a TPU, or the host
CRC loop in a host-coder process, and report corrupt needles.

BASELINE config 4 is "1B-needle scrub, device-batched"; round 4 proved
the kernel rate in the bench only. This module is the *operations* wiring
behind it: the VolumeScrub RPC (volume server), the `volume.scrub` shell
command, the `-scrub` modes of fs.verify / volume.check.disk, and the
admin cron all call scrub_volume(). Reference analogue:
shell/command_volume_fsck.go:81 (volume.fsck walks needles; it never got
hardware CRC — this exceeds it).

Which path runs follows the process's device gate (ops/device.py):
`device="auto"` uses the JAX backend only if this process already
resolved one (a `-coder numpy|native` server never imports jax), `"on"`
demands a TPU, and `mode` says "device" only for CRCs a TPU computed.

Batching: needles are grouped by length bucket (the power of two at or
above the data length, at least one 512-byte chunk) and LEFT-zero-padded
into fixed [B, L] blocks with B * L = _DISPATCH_BYTES, so one dispatch is
bounded by bytes whatever the size mix and a sweep compiles one program
per bucket. Every bucket's block is the same 16,384 chunks to the device
program: one product over all of them, then a fold whose depth alone
follows L. The raw device states are corrected for the zero prefix with
crc32c.finalize(lengths).

A block is on the chip while the next is walked and packed: the sweep
owns one device thread for the length of a volume, hands it each packed
block and compares the results in dispatch order as they come back, with
at most _IN_FLIGHT blocks handed over and not yet compared. The sweep's
thread waits only at that bound and at the volume's end. The host loop
starts no thread.

Where the time goes is kept per volume in a tracing.StageAccount. On the
sweep's thread, exclusive, so that they partition `elapsed_s`: `walk`
(the record walk and body reads between two dispatches), `pack`
(zero-fill and copy into the [B, L] block), `wait` (blocked on the device
thread: `device_s` of the result) and `compare` (finalize + the CRC
compare). On the device thread, beside them: `device` (the jitted call
through np.asarray: H2D, the loop-free CRC program, D2H), `device_busy_s`
of the result; 1 - device_s / device_busy_s is the share of the device
stage that host work hid. In a process with jax loaded each is a
`swtpu/scrub.<stage>` annotation in a live profiler trace; `scrub.device`
carries `needed` (needle bytes in the block), `dispatched` (B x L) and
`L`. The host loop has the `walk` alone.
"""

from __future__ import annotations

import contextvars
import functools
import struct
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass, field

import numpy as np

from ..ops import crc32c as crcmod
from ..ops import device as devgate
from ..utils.log import logger
from . import types as t
from .needle import record_size_from_header
from .volume import Volume

log = logger("scrub")

_CHUNK = 512
# padded bytes in one device dispatch; each length bucket L runs at the
# fixed shape [_DISPATCH_BYTES // L, L] (one row for longer needles)
_DISPATCH_BYTES = 8 << 20
# blocks handed to the device thread and not yet compared while the next is
# walked and packed: 24 MiB of blocks alive at most
_IN_FLIGHT = 2


@dataclass
class ScrubResult:
    volume_id: int
    scanned: int = 0
    corrupt: list[int] = field(default_factory=list)  # needle ids
    bytes_checked: int = 0
    elapsed_s: float = 0.0
    mode: str = "cpu"
    error: str = ""  # volume-level trouble (torn walk, tiered skip, ...)
    # exclusive stage seconds of the sweep's thread (they partition
    # elapsed_s; device_s is its wait for the device thread), the device
    # thread's own seconds inside the jitted call through np.asarray
    # beside them, device blocks dispatched and their padded bytes
    # (>= bytes_checked)
    walk_s: float = 0.0
    pack_s: float = 0.0
    device_s: float = 0.0
    compare_s: float = 0.0
    device_busy_s: float = 0.0
    blocks: int = 0
    bytes_dispatched: int = 0

    @property
    def needles_per_s(self) -> float:
        return self.scanned / self.elapsed_s if self.elapsed_s else 0.0


@functools.lru_cache(maxsize=1)
def _crc_jit():
    """The jitted batched CRC; one compiled program per block shape."""
    import jax

    return jax.jit(lambda x: crcmod.device_crc_states(x, chunk=_CHUNK))


def _bucket(n: int) -> int:
    out = _CHUNK
    while out < n:
        out *= 2
    return out


def _block_shape(length: int) -> "tuple[int, int]":
    """(B, L) of the fixed device block a needle of `length` rides in."""
    pad_l = _bucket(length)
    return max(1, _DISPATCH_BYTES // pad_l), pad_l


def _iter_needles(v: Volume, res: ScrubResult):
    """Yield (needle_id, data, stored_crc) for every LIVE needle, walking
    the .dat through volume.iter_records (the single source of truth for
    the on-disk record walk) on a private read-only handle — no lock
    contention with writers. Garbage records (overwritten/tombstoned,
    pre-vacuum) are skipped: rot in unreachable data must not alarm.
    A walk that ends before the append offset (header rot desyncing the
    record chain) is reported in res.error — the silent failure mode the
    tool exists to catch."""
    from .volume import iter_records
    from .super_block import SUPER_BLOCK_SIZE
    with v._lock:
        v._dat.flush()  # the private read handle must see buffered appends
        end = v._append_offset
    last_end = SUPER_BLOCK_SIZE
    with open(v.dat_path, "rb") as f:
        for pos, nid, nsize in iter_records(f, SUPER_BLOCK_SIZE, end):
            last_end = pos + record_size_from_header(nsize)
            if t.is_tombstone(nsize):
                continue
            nv = v.nm.get(nid)
            if nv is None or nv.offset != pos:
                continue  # garbage: overwritten or tombstoned version
            f.seek(pos + t.NEEDLE_HEADER_SIZE)
            body = f.read(nsize + 4)
            (dlen,) = struct.unpack_from("<I", body, 0)
            if dlen + 4 > nsize:
                # live record whose length field is itself rotted
                res.corrupt.append(nid)
                res.scanned += 1
                continue
            yield (nid, bytes(body[4:4 + dlen]),
                   struct.unpack_from("<I", body, nsize)[0])
    if last_end < end:
        res.error = (f"record walk torn at offset {last_end}: "
                     f"{end - last_end} trailing bytes unscanned "
                     f"(header rot or torn write)")


def _pack(shape: "tuple[int, int]",
          datas: "list[bytes]") -> "tuple[np.ndarray, np.ndarray]":
    """Up to B needles LEFT-zero-padded into one fixed [B, L] block, and
    their lengths."""
    rows, pad_l = shape
    blocks = np.zeros((rows, pad_l), dtype=np.uint8)
    lengths = np.zeros(rows, dtype=np.int64)
    for i, d in enumerate(datas):
        lengths[i] = len(d)
        if d:
            blocks[i, pad_l - len(d):] = np.frombuffer(d, np.uint8)
    return blocks, lengths


def _device_stage(acct, blocks: np.ndarray, needed: int) -> np.ndarray:
    """The raw CRC states of one block: the `device` stage, whole on the
    thread that calls it, so its annotation encloses the program's run."""
    with acct.stage("device", needed=needed, dispatched=blocks.size,
                    L=blocks.shape[1]):
        return np.asarray(_crc_jit()(blocks))


def scrub_volume(v: Volume, device: str = "auto") -> ScrubResult:
    """Verify every live needle's stored CRC against its data bytes.

    device: 'auto' (this process's resolved backend: the JAX kernel if
    the device gate brought one up, else the host loop), 'on' (a TPU or
    an error), 'off' (host loop). Tiered volumes (remote .dat) are
    skipped — a scrub must not pull the whole volume back over the
    network; their integrity story is the backend's checksums plus
    verify-before-delete at upload time.
    """
    res = ScrubResult(volume_id=v.id)
    if v.remote_spec is not None:
        res.mode = "skipped-tiered"
        return res
    backend = None if device == "off" else devgate.current()
    if device == "on" and (backend is None or backend.platform != "tpu"):
        raise devgate.DeviceError(
            "volume.scrub -device on needs a TPU; this process runs on "
            f"platform={backend.platform if backend else None!r}")
    if backend is not None:
        # "device" is a TPU's word; the same kernel on the CPU backend a
        # process was told to use says so
        res.mode = "device" if backend.platform == "tpu" \
            else f"xla-{backend.platform}"
    from ..tracing import StageAccount
    acct = StageAccount("scrub", ("walk", "pack", "wait", "device", "compare"))
    t0 = time.monotonic()
    pending: "dict[tuple[int, int], tuple[list, list, list]]" = {}
    # the sweep's own device thread, none in the host loop, and the blocks
    # it holds, oldest first
    device_thread = None if backend is None else ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="scrub-device")
    flying: deque = deque()

    def collect(leave: int) -> None:
        """Compare, in dispatch order, the blocks whose states are back,
        waiting for the oldest while more than `leave` are in flight."""
        while flying and (len(flying) > leave or flying[0][0].done()):
            fut, ids, lengths, stored = flying.popleft()
            if not fut.done():
                with acct.stage("wait"):
                    wait((fut,))
            raw = fut.result()  # or what the device stage raised
            with acct.stage("compare"):
                got = crcmod.finalize(raw.astype(np.uint32),
                                      lengths)[:len(ids)]
                bad = np.nonzero(got != np.array(stored, dtype=np.uint32))[0]
                res.corrupt.extend(ids[int(i)] for i in bad)

    def dispatch(shape, ids, datas, stored) -> None:
        with acct.stage("pack"):
            blocks, lengths = _pack(shape, datas)
        # under a copy of this thread's context, as every executor hop
        flying.append((device_thread.submit(
            contextvars.copy_context().run, _device_stage, acct, blocks,
            int(lengths.sum())), ids, lengths, stored))
        res.blocks += 1
        res.bytes_dispatched += shape[0] * shape[1]
        collect(_IN_FLIGHT)

    needles = _iter_needles(v, res)

    def next_block():
        """Walk on to the next full [B, L] block (None: the walk is over),
        checking on the host right here where no backend is up."""
        for nid, data, crc in needles:
            res.scanned += 1
            res.bytes_checked += len(data)
            if backend is None:
                if crcmod.crc32c(data) != crc:
                    res.corrupt.append(nid)
                continue
            shape = _block_shape(len(data))
            ids, datas, stored = pending.setdefault(shape, ([], [], []))
            ids.append(nid)
            datas.append(data)
            stored.append(crc)
            if len(ids) == shape[0]:
                return (shape, *pending.pop(shape))
        return None

    try:
        while True:
            with acct.stage("walk"):  # from one dispatch to the next
                block = next_block()
            if block is None:
                break
            dispatch(*block)
        for shape, batch in pending.items():
            dispatch(shape, *batch)
        collect(0)
    finally:
        if device_thread is not None:
            # joined on every way out: no thread outlives the sweep
            device_thread.shutdown(wait=True, cancel_futures=True)
    res.elapsed_s = time.monotonic() - t0
    (res.walk_s, res.pack_s, res.device_s, res.compare_s,
     res.device_busy_s) = (acct.seconds(k) for k in (
         "walk", "pack", "wait", "compare", "device"))
    if res.corrupt:
        log.warning("scrub volume %d: %d/%d needles corrupt: %s",
                    v.id, len(res.corrupt), res.scanned,
                    [f"{n:x}" for n in res.corrupt[:10]])
    if res.error:
        log.warning("scrub volume %d: %s", v.id, res.error)
    return res
