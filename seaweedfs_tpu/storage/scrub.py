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

The device path walks and packs outside this interpreter: each volume's
sweep starts a walker process (`storage/scrub_walk.py`, spawned fresh with
`python -S -m`, the standard library alone: no numpy, no jax) that walks
the `.dat` and packs each `[B, L]` block into one of _IN_FLIGHT + 1 slots
of a memory file the two share (where none can be made, as under a file
size limit smaller than the slots, it streams each block down its stdout
into this process's own slots), and lives as long as the volume's sweep.
What only this process can do stays here: the sweep's own device thread
(the chip is this process's), `finalize` and the CRC compare. The sweep's
thread takes each block the walker sends, hands it to the device thread,
compares the results in dispatch order as they come back and gives the
slot back, with at most _IN_FLIGHT blocks handed over and not yet
compared. The host loop (`device="off"`, or no backend) walks in-process
and starts no thread and no process.

Where the time goes is kept per volume in a tracing.StageAccount. On the
device path the sweep's thread has three exclusive stages that partition
`elapsed_s` by construction, `recv` enclosing the other two: `wait`
(blocked on the device thread: `device_s` of the result), `compare`
(finalize + the CRC compare) and `recv`, the rest: the walker's start, the
waits for its next message, the hand-overs to the device thread and the
joins at the end. On the device thread, beside them: `device` (the jitted
call through np.asarray: H2D, the loop-free CRC program, D2H),
`device_busy_s` of the result; 1 - device_s / device_busy_s is the share
of the device stage that host work hid. The walker's own seconds come
back with its last message, beside this process's: `walk_s` (the record walk and
body reads), `pack_s` (zero-fill and copy into a slot) and
`walker_busy_s` (its whole run less its waits for a free slot); `spawn_s`
is from its start to its first message. In a process with jax loaded each
stage of this process is a `swtpu/scrub.<stage>` annotation in a live
profiler trace; `scrub.device` carries `needed` (needle bytes in the
block), `dispatched` (B x L) and `L`. The host loop has the `walk` alone.
"""

from __future__ import annotations

import contextvars
import functools
import json
import mmap
import os
import subprocess
import sys
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass, field

import numpy as np

from ..ops import crc32c as crcmod
from ..ops import device as devgate
from ..utils.log import logger
from . import scrub_walk
from . import types as t
from .volume import Volume

log = logger("scrub")

_CHUNK = scrub_walk.CHUNK
# padded bytes in one device dispatch; each length bucket L runs at the
# fixed shape [_DISPATCH_BYTES // L, L] (one row for longer needles)
_DISPATCH_BYTES = 8 << 20
# blocks handed to the device thread and not yet compared while the
# walker packs the next: with it, _IN_FLIGHT + 1 slots of blocks alive
_IN_FLIGHT = 2
# the walker process's command after the interpreter, and the directory
# that holds the package, for its import path (-S: no site-packages)
_WALKER_CMD = ("-S", "-m", "seaweedfs_tpu.storage.scrub_walk")
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# glibc's allocator in the walker keeps what it frees: a fresh process
# that reads a volume through short-lived body buffers would otherwise
# fault in new pages for each of them, which costs as much as the read
_WALKER_MALLOC = {"MALLOC_MMAP_THRESHOLD_": str(32 << 20),
                  "MALLOC_TRIM_THRESHOLD_": str(1 << 31)}


@dataclass
class ScrubResult:
    volume_id: int
    scanned: int = 0
    corrupt: list[int] = field(default_factory=list)  # needle ids
    bytes_checked: int = 0
    elapsed_s: float = 0.0
    mode: str = "cpu"
    error: str = ""  # volume-level trouble (torn walk, tiered skip, ...)
    # the sweep's thread: on the device path recv_s + device_s (its wait
    # for the device thread) + compare_s add up to elapsed_s, in the host
    # loop walk_s is elapsed_s; beside them the device thread's own seconds
    # inside the jitted call through np.asarray, and the walker process's
    # own walk_s, pack_s and walker_busy_s; spawn_s from the walker's start
    # to its first message; device blocks dispatched and their padded
    # bytes (>= bytes_checked)
    walk_s: float = 0.0
    pack_s: float = 0.0
    device_s: float = 0.0
    compare_s: float = 0.0
    device_busy_s: float = 0.0
    recv_s: float = 0.0
    spawn_s: float = 0.0
    walker_busy_s: float = 0.0
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


def _block_shape(length: int) -> "tuple[int, int]":
    """(B, L) of the fixed device block a needle of `length` rides in."""
    return scrub_walk.block_shape(length, _DISPATCH_BYTES)


def _iter_needles(v: Volume):
    """The host loop's walk (scrub_walk.iter_needles) over the volume's
    .dat on a private read-only handle, no lock contention with writers,
    liveness from the needle map as it stands."""
    with v._lock:
        v._dat.flush()  # the private read handle must see buffered appends
        end = v._append_offset

    def where(nid: int):
        nv = v.nm.get(nid)
        return None if nv is None else nv.offset
    with open(v.dat_path, "rb") as f:
        yield from scrub_walk.iter_needles(f, end, where)


def _shared_slots(size: int):
    """(fd, mapping) of a memory file of `size` bytes for the walker to
    inherit; (-1, a bytearray) where none can be made (no memfd, or a file
    size limit under `size`), and then the walker streams its blocks."""
    try:
        fd = os.memfd_create("swtpu-scrub")
    except (AttributeError, OSError):
        return -1, bytearray(size)
    try:
        os.ftruncate(fd, size)
        return fd, mmap.mmap(fd, size)
    except OSError:
        os.close(fd)
        return -1, bytearray(size)


class _Walker:
    """One volume's walker process and the block slots it packs into, as
    the sweep's thread sees them (the protocol: storage/scrub_walk.py)."""

    def __init__(self, v: Volume):
        # what the walk needs, taken as the host loop takes it: the .dat
        # flushed, the append offset, and the needle map's live entries;
        # and the .dat opened beside them, under the same lock, for the
        # walker to inherit: a vacuum that swaps the file afterwards does
        # not reach the walk
        with v._lock:
            v._dat.flush()
            end = v._append_offset
            keys, offsets, sizes = v.nm.map.items_arrays()
            dat = os.open(v.dat_path, os.O_RDONLY)
        self.slot_bytes = max(_DISPATCH_BYTES, scrub_walk.bucket(
            int(sizes.max()) if sizes.size else 0))
        self.rows = scrub_walk.meta_rows(_DISPATCH_BYTES)
        self.stride = scrub_walk.slot_stride(self.slot_bytes,
                                             _DISPATCH_BYTES)
        slots = _IN_FLIGHT + 1
        fd, self.mm = _shared_slots(self.stride * slots)
        try:
            path = os.environ.get("PYTHONPATH")
            self.proc = subprocess.Popen(
                [sys.executable, *_WALKER_CMD, str(fd), str(dat)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                pass_fds=(fd, dat) if fd >= 0 else (dat,),
                env={**os.environ, **_WALKER_MALLOC, "PYTHONPATH":
                     f"{_ROOT}{os.pathsep}{path}" if path else _ROOT})
        finally:
            os.close(dat)
            if fd >= 0:
                os.close(fd)
        self.streamed = fd < 0
        self.ended = False
        self.send(json.dumps({
            "end": end, "dispatch_bytes": _DISPATCH_BYTES,
            "slots": slots, "slot_bytes": self.slot_bytes,
            "live": int(keys.size)}).encode() + b"\n"
            + keys.astype(np.uint64).tobytes()
            + (offsets.astype(np.uint64) * t.NEEDLE_PADDING).tobytes())

    def send(self, data: bytes) -> None:
        """To the walker; one that has died shows it as the end of its
        messages, so a broken pipe is not raised here."""
        try:
            self.proc.stdin.write(data)
            self.proc.stdin.flush()
        except BrokenPipeError:
            pass

    def messages(self):
        """The walker's messages, up to its `end` or its death; a streamed
        block's rows and arrays are read into its slot first."""
        for line in self.proc.stdout:
            msg = json.loads(line)
            if "block" in msg and self.streamed and not self._read_in(msg):
                return  # the walker died inside the block
            yield msg
            if msg.get("end"):
                self.ended = True
                return

    def _read_in(self, msg: dict) -> bool:
        at, n = msg["block"] * self.stride, msg["n"]
        meta = at + self.slot_bytes
        for off, size in ((at, n * msg["L"]), (meta, 8 * n),
                          (meta + 8 * self.rows, 8 * n),
                          (meta + 16 * self.rows, 4 * n)):
            if self.proc.stdout.readinto(
                    memoryview(self.mm)[off:off + size]) != size:
                return False
        return True

    def block(self, msg: dict):
        """(the [B, L] block in its slot, ids, lengths, stored CRCs)."""
        rows, pad_l, n = msg["rows"], msg["L"], msg["n"]
        at = msg["block"] * self.stride
        blocks = np.frombuffer(self.mm, np.uint8, rows * pad_l,
                               at).reshape(rows, pad_l)
        at += self.slot_bytes
        ids = np.frombuffer(self.mm, np.uint64, n, at).copy()
        lengths = np.zeros(rows, dtype=np.int64)
        lengths[:n] = np.frombuffer(self.mm, np.uint64, n, at + 8 * self.rows)
        stored = np.frombuffer(self.mm, np.uint32, n,
                               at + 16 * self.rows).copy()
        return blocks, ids, lengths, stored

    def close(self) -> int:
        """Join the walker, killed first unless it has sent its end; its
        exit code."""
        if not self.ended:
            self.proc.kill()
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except BrokenPipeError:
                pass
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:  # an end sent, and no exit
            self.proc.kill()
            rc = self.proc.wait()
        if not self.streamed:
            try:
                self.mm.close()
            except BufferError:  # a block view still alive: freed with it
                pass
        return rc


def _device_stage(acct, blocks: np.ndarray, needed: int) -> np.ndarray:
    """The raw CRC states of one block: the `device` stage, whole on the
    thread that calls it, so its annotation encloses the program's run."""
    with acct.stage("device", needed=needed, dispatched=blocks.size,
                    L=blocks.shape[1]):
        return np.asarray(_crc_jit()(blocks))


def _host_loop(v: Volume, res: ScrubResult, acct) -> None:
    """Every live needle's CRC on the host, in this thread: `walk`."""
    with acct.stage("walk"):
        for ev in _iter_needles(v):
            if ev[0] == "needle":
                _, nid, data, crc = ev
                res.scanned += 1
                res.bytes_checked += len(data)
                if crcmod.crc32c(data) != crc:
                    res.corrupt.append(nid)
            elif ev[0] == "rotted":
                res.scanned += 1
                res.corrupt.append(ev[1])
            else:
                res.error = ev[1]


def _device_sweep(v: Volume, res: ScrubResult, acct) -> None:
    """The walker's blocks through the sweep's own device thread, compared
    in dispatch order; no thread and no process outlives it."""
    device_thread = ThreadPoolExecutor(max_workers=1,
                                       thread_name_prefix="scrub-device")
    flying: deque = deque()  # (future, slot, ids, lengths, stored)
    walker = None
    last: dict = {}

    def collect(leave: int) -> None:
        """Compare, in dispatch order, the blocks whose states are back,
        waiting for the oldest while more than `leave` are in flight, and
        give their slots back to the walker."""
        while flying and (len(flying) > leave or flying[0][0].done()):
            fut, slot, ids, lengths, stored = flying.popleft()
            if not fut.done():
                with acct.stage("wait"):
                    wait((fut,))
            raw = fut.result()  # or what the device stage raised
            with acct.stage("compare"):
                got = crcmod.finalize(raw.astype(np.uint32),
                                      lengths)[:len(ids)]
                res.corrupt.extend(ids[got != stored].tolist())
            walker.send(bytes([slot]))

    with acct.stage("recv"):
        t0 = time.perf_counter()
        try:
            walker = _Walker(v)
            for msg in walker.messages():
                if not res.spawn_s:
                    res.spawn_s = time.perf_counter() - t0
                if "block" in msg:
                    blocks, ids, lengths, stored = walker.block(msg)
                    res.scanned += len(ids)
                    res.bytes_checked += int(lengths.sum())
                    res.blocks += 1
                    res.bytes_dispatched += blocks.size
                    # under a copy of this thread's context, as every
                    # executor hop
                    flying.append((device_thread.submit(
                        contextvars.copy_context().run, _device_stage, acct,
                        blocks, int(lengths.sum())), msg["block"], ids,
                        lengths, stored))
                    del blocks
                    collect(_IN_FLIGHT)
                elif "rotted" in msg:
                    res.scanned += 1
                    res.corrupt.append(msg["rotted"])
                else:
                    last = msg
            collect(0)
        finally:
            # joined on every way out: no thread or process outlives it
            flying.clear()
            device_thread.shutdown(wait=True, cancel_futures=True)
            rc = walker.close() if walker is not None else None
    if last:
        res.error = last["error"]
        res.walk_s, res.pack_s = last["walk_s"], last["pack_s"]
        res.walker_busy_s = last["busy_s"]
    else:
        res.error = (f"scrub walker exited {rc} before the volume's end: "
                     f"{res.scanned} needles scanned, the rest unscanned")


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
    from ..tracing import StageAccount
    if backend is None:
        acct = StageAccount("scrub", ("walk",))
        _host_loop(v, res, acct)
        res.elapsed_s = res.walk_s = acct.seconds("walk")  # the whole loop
    else:
        # "device" is a TPU's word; the same kernel on the CPU backend a
        # process was told to use says so
        res.mode = "device" if backend.platform == "tpu" \
            else f"xla-{backend.platform}"
        acct = StageAccount("scrub", ("recv", "wait", "device", "compare"))
        _device_sweep(v, res, acct)
        (res.recv_s, res.device_s, res.compare_s, res.device_busy_s) = (
            acct.seconds(k) for k in ("recv", "wait", "compare", "device"))
        # the enclosing stage's wall, whole: its three parts add up to it
        res.elapsed_s = res.recv_s + res.device_s + res.compare_s
    if res.corrupt:
        log.warning("scrub volume %d: %d/%d needles corrupt: %s",
                    v.id, len(res.corrupt), res.scanned,
                    [f"{n:x}" for n in res.corrupt[:10]])
    if res.error:
        log.warning("scrub volume %d: %s", v.id, res.error)
    return res
