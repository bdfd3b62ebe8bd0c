"""Streaming multi-volume EC encode: disk -> host views -> device -> shards.

Reference hot loop: weed/storage/erasure_coding/ec_encoder.go:198-233
(`encodeDatFile`) reads 14 x 256 KB striped buffers per row and calls the CPU
encoder once per slab (:166-196 `encodeDataOneBatch`), one volume at a time.

This module replaces that with a TPU-shaped pipeline whose three stages —
fill, compute, write — genuinely overlap:

* **Vectorized stripe views.** A .dat's large region is *already* a
  [rows, d, large_block] tensor laid out contiguously on disk; numpy reshapes
  of the memmap expose every slab as a strided view. Each input byte is read
  from disk ONCE: the fill pass builds the [B, d, C] parity batch with one
  strided copy per run and the data-shard bytes are written straight out of
  the source mapping (sync coders) or that same host batch (device coders).
* **Fixed-shape device batches.** Parity is computed over [B, d, C] uint8
  slabs (C = 1 MB, B = 32 by default -> 320 MB of data per device call at
  d=10) so XLA compiles exactly one program.
* **Writeback plane.** Completed data/parity runs are handed to a
  `WriterPool` — one io thread per target shard-file group, bounded work
  queues, `os.pwrite` of batch-contiguous runs — so shard writeback overlaps
  fill and compute instead of serializing behind them (a round-4 host run:
  9.75 s of coder under 43.66 s of serial writes). A writer failure (ENOSPC, bad disk)
  poisons the pool: the job fails cleanly, threads join, partial shard files
  are removed.
* **Writer-gated double buffering.** `ErasureCoder.encode` on the JAX path
  is an async dispatch; the pipeline keeps `depth` batches in flight and
  only blocks when fetching parity bytes for batch N while N+1..N+depth
  transfer and compute. Host staging buffers rotate through a pool sized
  depth+2, and recycling a buffer additionally waits until the writer pool
  has drained every data run still reading it — drain order alone is not
  enough once writes happen off-thread.
* **Cross-volume batching.** `encode_volumes` feeds slabs from many volumes
  through one shared batch stream; a batch may span the tail of volume k and
  the head of volume k+1, so the device never sees a partial batch until the
  very end of the whole job (reference encodes volumes serially,
  command_ec_encode.go:113-126). Volumes are opened lazily as they enter the
  fill window; a volume's source mapping is closed (mmap released, views
  dropped) as soon as its last run has been computed AND written, so a
  100-volume job does not accumulate address space.
* **Multi-device sharding.** Handing a `parallel.pipeline.MeshCoder` in as
  the coder shards each [B, d, C] batch along the batch axis over a
  ('data', 'shard') mesh (NamedSharding device_put, shard_map compute), so
  one encode stream scales across chips.

Shard-file writes are batch-contiguous: a run's k slabs land at consecutive
offsets of each shard file, so a run is ONE queue item per shard that the
writer flushes with k contiguous `os.pwrite`s (or a single one when the
source bytes are themselves contiguous).
"""

from __future__ import annotations

import math
import os
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ..ops.coder import ErasureCoder
from ..utils import fsutil
from ..utils.env import env_int
from ..utils.log import logger
from . import files
from .locate import EcGeometry

log = logger("ec.stream")

DEFAULT_CHUNK = 1 << 20   # device slab length (= reference small block)
DEFAULT_BATCH = 32        # slabs per device call
DEFAULT_DEPTH = 2         # batches in flight beyond the one being drained


def _default_writers() -> int:
    return env_int("SWTPU_EC_WRITERS", max(2, min(8, os.cpu_count() or 1)))


def _default_writer_queue() -> int:
    # per-writer item bound; items reference (not copy) up to batch*chunk
    # bytes each, so this also bounds parity arrays kept alive
    return env_int("SWTPU_EC_WRITER_QUEUE", 8)


def fit_chunk(geo: EcGeometry, chunk: int) -> int:
    """Largest slab length <= chunk that divides both block sizes.

    Any valid slab length divides g = gcd(large_block, small_block), so the
    answer is the largest divisor of g that is <= chunk — found by an
    O(sqrt(g)) divisor walk instead of decrementing until something divides
    (which was O(chunk) when g is odd and chunk even, say).
    """
    g = math.gcd(geo.large_block, geo.small_block)
    if chunk >= g:
        return g
    chunk = max(1, chunk)
    best = 1
    i = 1
    while i * i <= g:
        if g % i == 0:
            if best < i <= chunk:
                best = i
            j = g // i
            if best < j <= chunk:
                best = j
        i += 1
    return best


def _populated_view(path: str) -> "tuple[np.ndarray, object]":
    """Read-only uint8 view of a file, page tables pre-populated.

    First-touch minor faults cost ~7 us/page on virtualized hosts (nested
    EPT walks), capping a cold np.memmap read at well under 1 GB/s;
    MAP_POPULATE establishes all PTEs in one syscall (~20 GB/s) so the
    pipeline's strided reads run at memory bandwidth.

    Returns (array, mmap); the caller owns the mapping and must close it
    once every derived view is dropped (see _VolumePlan._release_source) —
    waiting for GC leaks address space and page tables across a long job.
    """
    import mmap as _mmap
    size = os.path.getsize(path)
    if size == 0:
        return np.empty(0, dtype=np.uint8), None
    f = open(path, "rb")
    try:
        flags = _mmap.MAP_SHARED | getattr(_mmap, "MAP_POPULATE", 0)
        m = _mmap.mmap(f.fileno(), size, flags=flags, prot=_mmap.PROT_READ)
    finally:
        f.close()
    return np.frombuffer(m, dtype=np.uint8), m


def _pwrite_full(fd: int, mv, off: int) -> None:
    n = os.pwrite(fd, mv, off)
    while n < len(mv):  # partial writes are legal, if rare, on regular files
        mv = memoryview(mv)[n:]
        off += n
        n = os.pwrite(fd, mv, off)


def _write_run(fd: int, off: int, arr: np.ndarray) -> None:
    """Write one batch-contiguous run: arr is 1-D (contiguous source) or
    [k, chunk] whose k rows land at consecutive chunk offsets of fd."""
    if arr.ndim == 1:
        _pwrite_full(fd, arr.data, off)
        return
    if arr.flags.c_contiguous:
        _pwrite_full(fd, arr.reshape(-1).data, off)
        return
    step = arr.shape[-1]
    for r in range(arr.shape[0]):
        _pwrite_full(fd, arr[r].data, off + r * step)


class WriterPool:
    """The writeback plane: one io thread per target shard-file group.

    Work is routed group = shard_id % writers, so every write to a given
    shard file is issued by the same thread (one writer per target
    disk/shard-file group, like the per-disk flushers in a real store).
    Queues are bounded: `submit` blocks when the pipeline outruns the
    disks, which is the backpressure that keeps memory flat.

    A writer that fails (ENOSPC, EIO) records the first exception and keeps
    draining its queue without writing — completion callbacks still run so
    buffer gating can never hang — and the error surfaces on the next
    `submit()`/`drain()` on the submitting thread.
    """

    def __init__(self, acct, writers: "int | None" = None,
                 queue_depth: "int | None" = None):
        # the pipeline's StageAccount: the submitting thread's seconds
        # lost to backpressure are booked as stage `write_block` as they
        # happen, out of whichever stage (fill, drain) it was in
        self._acct = acct
        self.writers = max(1, int(writers if writers is not None
                                  else _default_writers()))
        depth = max(1, int(queue_depth if queue_depth is not None
                           else _default_writer_queue()))
        self._queues = [queue.Queue(maxsize=depth)
                        for _ in range(self.writers)]
        self._busy = [0.0] * self.writers
        self._err: "BaseException | None" = None
        self._err_lock = threading.Lock()
        self._closed = False
        self._threads = [
            threading.Thread(target=self._run, args=(i,), daemon=True,
                             name=f"swtpu-ec-writer-{i}")
            for i in range(self.writers)]
        for t in self._threads:
            t.start()

    # -- writer side --------------------------------------------------------
    def _run(self, i: int) -> None:
        from ..stats import EC_WRITER_QUEUE_DEPTH
        q = self._queues[i]
        while True:
            item = q.get()
            if item is None:
                q.task_done()
                return
            fd, off, arr, on_done = item
            item = None
            if self._err is None:
                t0 = time.perf_counter()
                try:
                    _write_run(fd, off, arr)
                    self._busy[i] += time.perf_counter() - t0
                except BaseException as e:  # noqa: BLE001 — surfaced via submit/drain
                    with self._err_lock:
                        if self._err is None:
                            self._err = e
            # drop the data reference BEFORE signalling completion: on_done
            # may recycle the buffer / close the source mmap this run reads
            arr = None
            if on_done is not None:
                try:
                    on_done()
                except Exception:  # noqa: BLE001 — a callback must not kill the writer
                    log.warning("ec writer completion callback failed",
                                exc_info=True)
            EC_WRITER_QUEUE_DEPTH.add(amount=-1)
            q.task_done()

    # -- submitting side ----------------------------------------------------
    def submit(self, shard_id: int, fd: int, off: int, arr: np.ndarray,
               on_done=None) -> None:
        """Queue one batch-contiguous run for shard_id's writer thread."""
        if self._err is not None:
            raise self._err
        from ..stats import EC_WRITER_QUEUE_DEPTH
        q = self._queues[shard_id % self.writers]
        item = (fd, off, arr, on_done)
        t0 = time.perf_counter()
        # delta, not an absolute set: concurrent encodes each run their own
        # pool but share the gauge, and absolutes would clobber each other.
        # Counted BEFORE the put so the writer's post-dequeue decrement can
        # never race the gauge below zero under a concurrent scrape.
        EC_WRITER_QUEUE_DEPTH.add(amount=1)
        while True:
            try:
                q.put(item, timeout=0.2)
                break
            except queue.Full:
                if self._err is not None:
                    EC_WRITER_QUEUE_DEPTH.add(amount=-1)  # never enqueued
                    raise self._err from None
        self._acct.add("write_block", time.perf_counter() - t0)

    def drain(self) -> None:
        """Barrier: wait for every queued run, then re-raise any failure."""
        t0 = time.perf_counter()
        for q in self._queues:
            q.join()
        self._acct.add("write_block", time.perf_counter() - t0)
        if self._err is not None:
            raise self._err

    def poison(self, exc: "BaseException | None" = None) -> None:
        """Abort: queued-but-unwritten runs are skipped (callbacks still run)."""
        with self._err_lock:
            if self._err is None:
                self._err = exc or RuntimeError("ec writer pool aborted")

    def close(self) -> None:
        # no gauge reset here: every dequeued item already decremented it,
        # and zeroing would erase a concurrent pool's live contribution
        if self._closed:
            return
        self._closed = True
        for q in self._queues:
            q.put(None)
        for t in self._threads:
            t.join()

    # -- introspection ------------------------------------------------------
    def queued(self) -> int:
        return sum(q.qsize() for q in self._queues)

    @property
    def busy_s(self) -> float:
        """Aggregate seconds writer threads spent inside pwrite."""
        return sum(self._busy)

    @property
    def error(self) -> "BaseException | None":
        return self._err


class AsyncPipe:
    """Depth-bounded async dispatch with a writer-gated host-buffer pool.

    Shared by encode_volumes and encoder.rebuild_shards. `depth` batches may
    be in flight beyond the one being drained; the pool holds depth+2
    buffers so a buffer is never refilled while its device transfer may
    still be reading it (a batch's input is provably consumed by the time
    its output is fetched, and batch N's buffer is only reused at
    N + depth + 2 > N + depth, by which point N has been drained).

    With a writer pool in the picture drain order alone is not enough: data
    runs submitted to writers keep READING the fill buffer after its batch
    drained. Callers `retain(buf)` per outstanding run and the writer's
    completion callback `release(buf)`s it; `next_buffer` blocks until the
    slot's hold count is zero. That blocking is booked as `write_block` —
    it shows up as writer backpressure in the pipeline stats.

    Who owns the buffers: the pipe makes its own `depth + 2`, zeroed, and
    they live as long as it does (the seal's feed). A caller that keeps
    buffers longer than one pipe (the rebuild: ec/buffers.py) passes its
    own `buffers`, `depth + 2` arrays of `shape` holding whatever they
    hold; the pipe cycles through them and never keeps them: they are the
    caller's again once `flush` has returned.
    """

    def __init__(self, shape: tuple, acct, depth: int = DEFAULT_DEPTH,
                 buffers: "list[np.ndarray] | None" = None):
        # the operation's StageAccount: each blocking fetch is one stage
        # `drain` (annotated with the batch number), a wait for writers
        # still reading a buffer is booked as `write_block`
        self._acct = acct
        self._drained = 0
        self.depth = depth
        if buffers is None:
            buffers = [np.zeros(shape, dtype=np.uint8)
                       for _ in range(depth + 2)]
        if len(buffers) != depth + 2 or \
                any(b.shape != tuple(shape) for b in buffers):
            raise ValueError(f"AsyncPipe needs {depth + 2} buffers of "
                             f"{tuple(shape)}")
        self.pool = buffers
        self.pending: deque = deque()
        self._slot = 0
        self._holds = [0] * len(self.pool)
        self._ids = {id(b): i for i, b in enumerate(self.pool)}
        self._cv = threading.Condition()

    def next_buffer(self) -> np.ndarray:
        i = self._slot
        self._slot = (self._slot + 1) % len(self.pool)
        t0 = time.perf_counter()
        with self._cv:
            held = bool(self._holds[i])
            while self._holds[i]:
                self._cv.wait()
        if held:
            self._acct.add("write_block", time.perf_counter() - t0)
        return self.pool[i]

    def retain(self, buf: np.ndarray) -> None:
        with self._cv:
            self._holds[self._ids[id(buf)]] += 1

    def release(self, buf: np.ndarray) -> None:
        with self._cv:
            self._holds[self._ids[id(buf)]] -= 1
            self._cv.notify_all()

    def submit(self, fut, ctx, drain_fn) -> None:
        """Queue (future, ctx); drain the oldest once over depth."""
        self.pending.append((fut, ctx, drain_fn))
        if len(self.pending) > self.depth:
            self.drain_one()

    def drain_one(self) -> None:
        fut, ctx, drain_fn = self.pending.popleft()
        # FIFO: the n-th fetch is the n-th dispatch's result
        with self._acct.stage("drain", batch=self._drained):
            out = np.asarray(fut)  # np.asarray blocks on the device
        self._drained += 1
        drain_fn(out, ctx)

    def flush(self) -> None:
        while self.pending:
            self.drain_one()


@dataclass
class _Run:
    """k consecutive slabs of one volume occupying batch rows [b0, b0+k)."""
    plan: "_VolumePlan"
    shard_off: int              # where slab 0's parity lands in each shard file
    b0: int
    k: int


@dataclass
class _VolumePlan:
    """Slab enumeration state for one volume's .dat."""
    dat_path: str
    out_base: str
    idx_path: str | None
    geo: EcGeometry
    chunk: int
    dat_size: int = 0
    shard_size: int = 0
    fds: list[int] = field(default_factory=list)
    inflight_runs: int = 0
    finished: bool = False
    # (view4d [rows, d, nch, C], shard_base, rows, nch) per region
    regions: list[tuple[np.ndarray, int, int, int]] = field(default_factory=list)
    # overlay codec (ops/piggyback.py, ops/product_matrix.py) to seal
    # with: slabs are encoded as plain RS by the inner coder (device
    # batching untouched) and finish() applies the codec's overlay —
    # piggyback XOR-folds, msr rewrites the parities — before the .vif
    # seal
    overlay: "object | None" = None
    # iteration cursor: (region_idx, row, chunk)
    _pos: tuple[int, int, int] = (0, 0, 0)
    # source mapping ownership + outstanding writer-pool runs
    _arr: "np.ndarray | None" = None
    _mm: object = None
    _pending_writes: int = 0
    _cv: threading.Condition = field(default_factory=threading.Condition)

    def open(self, open_fds: bool = True) -> None:
        geo, chunk = self.geo, self.chunk
        self.dat_size = os.path.getsize(self.dat_path)
        self.shard_size = geo.shard_file_size(self.dat_size)
        paths = [self.out_base + files.shard_ext(i) for i in range(geo.n)]
        for p in paths:
            with open(p, "wb") as f:
                if self.shard_size:
                    f.truncate(self.shard_size)
        if self.dat_size == 0:
            return
        if open_fds:
            # append as we go: a mid-list EMFILE must leave the already-
            # opened fds visible to _close_fds/abort, not leak them
            for p in paths:
                self.fds.append(os.open(p, os.O_WRONLY))
        mm, raw = _populated_view(self.dat_path)
        self._arr, self._mm = mm, raw

        nl = geo.large_rows(self.dat_size)
        lb, sb, d = geo.large_block, geo.small_block, geo.d
        large_bytes = nl * d * lb
        regions = []
        if nl:
            nch = lb // chunk
            v = np.asarray(mm[:large_bytes]).reshape(nl, d, nch, chunk)
            regions.append((v, 0, nl, nch))
        rest = self.dat_size - large_bytes
        ns = geo.small_rows(self.dat_size)
        if ns:
            nchs = sb // chunk
            full = rest // (d * sb)
            if full:
                v = np.asarray(
                    mm[large_bytes:large_bytes + full * d * sb]
                ).reshape(full, d, nchs, chunk)
                regions.append((v, nl * lb, full, nchs))
            tail = rest - full * d * sb
            if tail:
                pad = np.zeros((1, d, nchs, chunk), dtype=np.uint8)
                flat = pad.reshape(-1)
                flat[:tail] = mm[large_bytes + full * d * sb:]
                regions.append((pad, nl * lb + full * sb, 1, nchs))
        self.regions = regions

    def fill(self, buf: np.ndarray, b0: int) -> tuple[int, int | None]:
        """Fill buf[b0:] with the next slabs; return (rows_filled, shard_off).

        shard_off is where the first filled slab lands in each shard file
        (None if this volume is exhausted). Slabs within one call are
        guaranteed contiguous in the shard files.
        """
        ri, row, ch = self._pos
        if ri >= len(self.regions):
            return 0, None
        view, base, rows, nch = self.regions[ri]
        space = buf.shape[0] - b0
        # contiguous slabs remaining in the current row
        k = min(space, nch - ch)
        buf[b0:b0 + k] = view[row, :, ch:ch + k].transpose(1, 0, 2)
        shard_off = base + (row * nch + ch) * self.chunk
        ch += k
        if ch == nch:
            row, ch = row + 1, 0
            if row == rows:
                ri, row = ri + 1, 0
        self._pos = (ri, row, ch)
        return k, shard_off

    def exhausted(self) -> bool:
        return self._pos[0] >= len(self.regions)

    # -- writer-pool accounting ---------------------------------------------
    def note_write(self) -> None:
        with self._cv:
            self._pending_writes += 1

    def write_done(self) -> None:
        with self._cv:
            self._pending_writes -= 1
            self._cv.notify_all()

    def writes_done(self) -> bool:
        with self._cv:
            return self._pending_writes == 0

    # -- teardown ------------------------------------------------------------
    def _release_source(self) -> None:
        """Drop every view of the source mapping and close it NOW.

        The regions (and the frombuffer array under them) hold buffer
        exports on the mmap; once they are gone the close succeeds and the
        address space + page tables are returned immediately instead of at
        some future GC. A stray export (caller still holding a view) makes
        close raise BufferError — fall back to GC-close for that mapping
        rather than failing the job.
        """
        self.regions = []
        self._arr = None
        mm, self._mm = self._mm, None
        if mm is not None:
            try:
                mm.close()
            except BufferError:
                log.debug("ec source mmap for %s still exported; "
                          "deferring close to GC", self.dat_path)

    def _close_fds(self) -> None:
        fds, self.fds = self.fds, []
        for fd in fds:
            try:
                os.close(fd)
            except OSError:
                log.debug("closing shard fd for %s failed", self.out_base,
                          exc_info=True)

    def finish(self) -> None:
        """All runs computed AND written: seal the volume's outputs.

        Shard bytes must be durable BEFORE the .vif seals the volume
        (the pre-writeback encoder msync'd every output mapping here): a
        power loss must never leave a valid-looking .vif over shards
        still in page cache, because a "successfully" converted volume's
        .dat may already be gone.
        """
        for fd in self.fds:
            os.fsync(fd)
        self._close_fds()
        self._release_source()
        geo = self.geo
        codec = "rs"
        if self.overlay is not None:
            # overlay BEFORE the .vif seal: a crash mid-overlay leaves
            # unsealed (hence rebuildable-from-.dat) outputs, never a
            # valid-looking .vif over half-sealed parities
            from .repair import apply_codec_overlay
            apply_codec_overlay(self.out_base, self.overlay,
                                self.shard_size)
            codec = self.overlay.codec
            # the overlay rewrote parity bytes AFTER the writer-pool
            # fsyncs above — re-pin them before the seal claims them
            for i in range(geo.d, geo.n):
                fsutil.fsync_path(self.out_base + files.shard_ext(i))
        if self.idx_path and os.path.exists(self.idx_path):
            files.write_ecx_from_idx(self.idx_path, self.out_base + ".ecx")
            # the .ecx must be durable BEFORE the .vif seals the volume
            # for the same reason as the shard fsyncs: a sealed .vif
            # over a torn .ecx serves no needle at all
            fsutil.fsync_path(self.out_base + ".ecx")
        files.write_vif(self.out_base + ".vif", version=3,
                        dat_size=self.dat_size, d=geo.d, p=geo.p,
                        large_block=geo.large_block,
                        small_block=geo.small_block, codec=codec)
        self.finished = True

    def abort(self) -> None:
        """Failure path: close everything and remove partial outputs."""
        self._close_fds()
        self._release_source()
        for i in range(self.geo.n):
            _unlink_quiet(self.out_base + files.shard_ext(i))
        _unlink_quiet(self.out_base + ".ecx")
        _unlink_quiet(self.out_base + ".vif")


def _unlink_quiet(path: str) -> None:
    try:
        os.unlink(path)
    except FileNotFoundError:
        pass
    except OSError:
        log.warning("could not remove partial EC output %s", path,
                    exc_info=True)


def _reap(finishing: deque, acct, pool: "WriterPool | None" = None,
          force: bool = False) -> None:
    """Finish (in submit order) every plan whose writeback has completed,
    each as one stage `finish` of the pipeline's account.

    A poisoned pool's writers SKIP queued runs but still fire their
    completion callbacks (so buffer gating can't hang), which makes
    writes_done() true for a volume whose bytes never hit disk — sealing
    it would leave a valid-looking .vif over holed shards and _abort
    would then keep it as "completed". The error check must come AFTER
    the writes_done() observation: _err is set before any run is
    skipped, so writes_done() == True with _err still None proves every
    one of the volume's runs was genuinely written.
    """
    while finishing and (force or finishing[0].writes_done()):
        if not force and pool is not None and pool.error is not None:
            return  # job is failing; _abort removes the partial outputs
        with acct.stage("finish"):
            finishing.popleft().finish()


def encode_volumes(jobs: "list[tuple[str, str, str | None]]", geo: EcGeometry,
                   coder: ErasureCoder, chunk: int = DEFAULT_CHUNK,
                   batch: int = DEFAULT_BATCH, depth: int = DEFAULT_DEPTH,
                   stats: "dict | None" = None,
                   null_sink: bool = False,
                   writers: "int | None" = None,
                   ) -> "dict[str, list[str]]":
    """Encode many volumes through one shared device stream.

    jobs: (dat_path, out_base, idx_path | None) per volume.
    Returns {dat_path: [shard paths]}. `chunk` is clamped to the largest
    value that divides both block sizes (fit_chunk). Pass a dict as `stats`
    to receive pipeline timings (wall_s, fill_s, write_s, write_block_s,
    finish_s, bytes, ...): the stage sums of one tracing.StageAccount,
    whose stages `swtpu/ec.fill|dispatch|drain|finish` also land in a
    live JAX profiler trace. `writers` sizes the writeback plane (default
    SWTPU_EC_WRITERS).

    Reference equivalent: the per-volume VolumeEcShardsGenerate RPC body
    (volume_grpc_erasure_coding.go:39 -> WriteEcFiles ec_encoder.go:57), but
    batched across volumes so the device always sees full [B, d, C] slabs.

    Synchronous host coders (native AVX2, numpy) skip the batch assembly
    entirely: they have no fixed-shape compile constraint, so each volume
    region feeds the coder zero-copy [k, d, chunk] views of the populated
    source mapping; completed data/parity runs are queued to the writer
    pool so shard writeback overlaps the next batch's compute.

    On failure (a writer hitting ENOSPC, a coder error) the pool is
    poisoned and joined, and every not-yet-finished volume's partial
    outputs (.ec*, .ecx, .vif) are removed before the error re-raises.
    """
    assert coder.d == geo.d and coder.p == geo.p
    chunk = fit_chunk(geo, chunk)
    # overlay codecs (piggyback, msr) encode their slabs as plain RS
    # through the inner backend (so the device pipeline below is
    # codec-agnostic) and seal the real parities at finish()
    # (_VolumePlan.finish -> repair.apply_codec_overlay)
    from .repair import OVERLAYS
    pb = coder if coder.codec in OVERLAYS else None
    slab_coder = coder.inner if pb is not None else coder
    if null_sink and slab_coder.async_dispatch:
        raise ValueError("null_sink is a sync-coder measurement mode")
    if stats is None:
        stats = {}
    from .. import tracing
    total = sum(os.path.getsize(j[0]) for j in jobs
                if os.path.exists(j[0]))
    with tracing.start_span(
            "ec.encode", component="ec",
            attrs={"volumes": len(jobs), "bytes": total,
                   "coder": type(coder).__name__, "codec": coder.codec,
                   "geometry": f"{geo.d}+{geo.p}"}) as sp:
        acct = tracing.StageAccount("ec", _STAGES)
        t0 = time.perf_counter()
        if not slab_coder.async_dispatch:
            res = _encode_volumes_sync(jobs, geo, slab_coder, chunk, batch,
                                       stats, acct, null_sink=null_sink,
                                       writers=writers, pb=pb)
        else:
            res = _encode_volumes_async(jobs, geo, slab_coder, chunk, batch,
                                        depth, stats, acct, writers=writers,
                                        pb=pb)
        stats.update(wall_s=time.perf_counter() - t0, bytes=total,
                     fill_s=acct.seconds("fill"),
                     write_block_s=acct.seconds("write_block"),
                     finish_s=acct.seconds("finish"))
        _publish_pipeline_stats(stats, acct, sp)
        return res


# the pipeline's stages (exclusive: they partition wall_s). What is left
# of the wall is opening the plans (truncate n shard files, MAP_POPULATE
# the .dat), queueing runs to the writers and joining their threads.
_STAGES = ("fill", "dispatch", "drain", "write_block", "finish")


def _publish_pipeline_stats(stats: dict, acct, span) -> None:
    """Feed the per-call stage breakdown into the stage histogram (with the
    active trace exemplar-linked automatically) and onto the ec.encode span
    so /debug/traces shows where an encode spent its wall time."""
    from ..stats import EC_PIPELINE_SECONDS
    wall = stats.get("wall_s", 0.0)
    for stage in ("fill", "dispatch", "drain"):
        EC_PIPELINE_SECONDS.observe(stage, value=acct.seconds(stage))
    EC_PIPELINE_SECONDS.observe("write", value=stats.get("write_s", 0.0))
    acct.publish(span)
    span.set_attr("write_s", round(stats.get("write_s", 0.0), 4))
    span.set_attr("wall_s", round(wall, 4))
    span.set_attr("writers", stats.get("writers", 0))
    if wall > 0:
        # fraction of writer busy time hidden behind fill/compute: 1 means
        # writes were free (fully overlapped), 0 means fully additive
        overlap = 1.0 - min(1.0, stats.get("write_block_s", 0.0) / wall)
        stats["write_overlap"] = round(overlap, 4)
        span.set_attr("write_overlap", stats["write_overlap"])
    if "batches" in stats:
        span.set_attr("batches", stats["batches"])


def _encode_volumes_sync(jobs, geo: EcGeometry, coder: ErasureCoder,
                         chunk: int, batch: int, stats: dict, acct,
                         null_sink: bool = False,
                         writers: "int | None" = None,
                         pb=None,
                         ) -> "dict[str, list[str]]":
    """Zero-copy streaming encode for synchronous host coders.

    Per region with one chunk per row (every small-block region — the
    dominant layout), the coder input is a [k, d, chunk] VIEW of the
    populated source mapping: no batch buffer, no stripe copy. Data-shard
    runs are views of the source mapping and parity runs are views of the
    coder's fresh output — both queued to the writer pool, which pwrites
    them while the main thread computes the next batch; only strided
    multi-chunk (large-block) coder inputs and padded tails stage through
    a scratch buffer.
    """
    from ..stats import EC_ENCODE_BYTES

    d, p = geo.d, geo.p
    out: dict[str, list[str]] = {}
    scratch = None
    pool = None if null_sink else WriterPool(acct, writers)
    finishing: deque = deque()
    created: list[_VolumePlan] = []
    try:
        for dat_path, out_base, idx_path in jobs:
            plan = _VolumePlan(dat_path, out_base, idx_path, geo, chunk,
                               overlay=pb)
            created.append(plan)
            out[dat_path] = [out_base + files.shard_ext(i)
                             for i in range(geo.n)]
            plan.open(open_fds=not null_sink)
            if plan.dat_size == 0:
                with acct.stage("finish"):
                    plan.finish()
                continue
            for view, base, rows, nch in plan.regions:
                contiguous = nch == 1 and view.base is not None
                r0 = 0
                while r0 < rows * nch:
                    row, ch = divmod(r0, nch)
                    if contiguous:
                        k = min(batch, rows - r0)
                        inp = view[r0:r0 + k].reshape(k, d, chunk)
                    else:
                        # strided slabs (large-block region) or padded tail
                        if scratch is None:
                            scratch = np.zeros((batch, d, chunk),
                                               dtype=np.uint8)
                        k = min(batch, nch - ch)
                        with acct.stage("fill"):
                            scratch[:k] = view[row, :, ch:ch + k].transpose(
                                1, 0, 2)
                        inp = scratch[:k]
                    with acct.stage("dispatch"):  # a host coder computes here
                        parity = np.asarray(coder.encode(inp))
                    if not null_sink:
                        shard_off = base + r0 * chunk
                        # data runs come straight off the source mapping
                        # (scratch is recycled next batch; the view is not)
                        for i in range(d):
                            arr = (inp[:, i, :] if contiguous
                                   else view[row, i, ch:ch + k].reshape(-1))
                            plan.note_write()
                            # WriterPool is an io plane, not an executor:
                            # writer threads never read the trace context
                            pool.submit(i, plan.fds[i], shard_off, arr,  # swtpu-lint: disable=executor-no-context
                                        plan.write_done)
                        for j in range(p):
                            plan.note_write()
                            pool.submit(d + j, plan.fds[d + j], shard_off,  # swtpu-lint: disable=executor-no-context
                                        parity[:, j, :], plan.write_done)
                    r0 += k
            EC_ENCODE_BYTES.inc(type(coder).__name__, amount=plan.dat_size)
            if not plan.finished:
                finishing.append(plan)
            # seal volumes whose writeback drained
            _reap(finishing, acct, pool)
        if pool is not None:
            pool.drain()
        _reap(finishing, acct, force=True)
    except BaseException:
        _abort(pool, created)
        raise
    finally:
        if pool is not None:
            pool.close()
    stats.update(mode="sync", coder_s=acct.seconds("dispatch"),
                 write_s=pool.busy_s if pool else 0.0,
                 writers=pool.writers if pool else 0)
    return out


def _abort(pool: "WriterPool | None", created: "list[_VolumePlan]") -> None:
    """Shared failure path: stop the writeback plane (queued runs are
    skipped, callbacks still fire, threads join) and remove every
    unfinished volume's partial outputs. Completed volumes are kept —
    their shards are whole and verified by construction."""
    if pool is not None:
        pool.poison()
        pool.close()
    for plan in created:
        if not plan.finished:
            plan.abort()


def _encode_volumes_async(jobs, geo: EcGeometry, coder: ErasureCoder,
                          chunk: int, batch: int, depth: int,
                          stats: dict, acct,
                          writers: "int | None" = None,
                          pb=None,
                          ) -> "dict[str, list[str]]":

    from ..stats import EC_ENCODE_BYTES
    out: dict[str, list[str]] = {}
    todo = deque()
    for dat_path, out_base, idx_path in jobs:
        todo.append(_VolumePlan(dat_path, out_base, idx_path, geo, chunk,
                                overlay=pb))
        out[dat_path] = [out_base + files.shard_ext(i) for i in range(geo.n)]

    d, p = geo.d, geo.p
    pool = WriterPool(acct, writers)
    pipe = AsyncPipe((batch, d, chunk), acct, depth)
    finishing: deque = deque()
    created: list[_VolumePlan] = []

    def drain(parity: np.ndarray, runs: "list[_Run]") -> None:
        # parity is a fresh host array; the queued run slices keep it alive
        # until the writers have flushed them
        for run in runs:
            plan = run.plan
            for j in range(p):
                plan.note_write()
                pool.submit(d + j, plan.fds[d + j], run.shard_off,
                            parity[run.b0:run.b0 + run.k, j],
                            plan.write_done)
            plan.inflight_runs -= 1
            if plan.exhausted() and plan.inflight_runs == 0:
                finishing.append(plan)

    active: deque = deque()  # opened plans still producing slabs

    def pump() -> bool:
        """Open lazily until a plan with slabs is at the front; False if done.

        Exhausted plans leave `active` here; their finish() runs once their
        last parity batch has drained AND the writer pool has flushed their
        runs (_reap on the main thread).
        """
        while not active or active[0].exhausted():
            if active and active[0].exhausted():
                active.popleft()
                continue
            if not todo:
                return False
            plan = todo.popleft()
            created.append(plan)
            plan.open()
            if plan.dat_size == 0:
                with acct.stage("finish"):
                    plan.finish()
                continue
            active.append(plan)
        return True

    def _data_done(plan: _VolumePlan, buf: np.ndarray):
        def done():
            pipe.release(buf)
            plan.write_done()
        return done

    first_dispatch_s = 0.0
    batches = 0

    def fill(buf: np.ndarray) -> "tuple[int, list[_Run]]":
        b0, runs = 0, []
        while b0 < batch and pump():
            plan = active[0]
            k, shard_off = plan.fill(buf, b0)
            if k:
                run = _Run(plan, shard_off, b0, k)
                plan.inflight_runs += 1
                runs.append(run)
                # data shards go to the writer pool straight out of the
                # host batch (one disk read per input byte; reference
                # re-reads per shard); each run holds the buffer until
                # its writer flushes it
                done = _data_done(plan, buf)
                for i in range(d):
                    pipe.retain(buf)
                    plan.note_write()
                    pool.submit(i, plan.fds[i], shard_off,  # swtpu-lint: disable=executor-no-context
                                buf[b0:b0 + k, i], done)
                b0 += k
        return b0, runs

    try:
        while pump():
            buf = pipe.next_buffer()  # waits for writers still reading it
            with acct.stage("fill", batch=batches):
                b0, runs = fill(buf)
                if b0 and b0 < batch:
                    buf[b0:] = 0  # final partial batch: stable jit shape
            if b0 == 0:
                break
            EC_ENCODE_BYTES.inc(type(coder).__name__, amount=buf.nbytes)
            # H2D + launch; the batch number pairs a trace's program run
            # with its dispatch and (AsyncPipe.drain_one) its drain
            with acct.stage("dispatch", batch=batches):
                fut = coder.encode(buf)
            if not batches:  # the first dispatch traces and compiles
                first_dispatch_s = acct.seconds("dispatch")
            pipe.submit(fut, runs, drain)
            batches += 1
            _reap(finishing, acct, pool)
        pipe.flush()
        pool.drain()
        _reap(finishing, acct, force=True)
    except BaseException:
        _abort(pool, created)
        raise
    finally:
        pool.close()
    stats.update(mode="async", batches=batches,
                 batch_bytes=batch * geo.d * chunk,
                 dispatch_s=acct.seconds("dispatch"),
                 first_dispatch_s=first_dispatch_s,
                 drain_block_s=acct.seconds("drain"),
                 write_s=pool.busy_s, writers=pool.writers)
    if getattr(coder, "batch_bytes_by_device", None):
        stats["batch_bytes_by_device"] = coder.batch_bytes_by_device
    return out
