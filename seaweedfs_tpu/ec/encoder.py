"""EC encode / rebuild / decode pipelines over volume files.

Reference: weed/storage/erasure_coding/ec_encoder.go:57 (`WriteEcFiles`),
:61 (`RebuildEcFiles`), ec_decoder.go:154 (`WriteDatFile`). The reference's
hot loop feeds 256 KB slabs through the CPU encoder one row at a time
(encodeDataOneBatch :166-196); here slabs from many rows (and, at the Store
level, many volumes) are batched into a single [B, d, C] uint8 tensor per
device call, with fixed shapes so XLA compiles once. Data shards are pure
strided copies (no compute); only parity rides the coder.

The whole .dat byte stream is striped, super block included, exactly like the
reference — decode reproduces the original file bit-for-bit.
"""

from __future__ import annotations

import contextvars
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

import numpy as np

from ..ops.coder import ErasureCoder
from . import buffers, files
from .locate import EcGeometry

DEFAULT_CHUNK = 1 << 20   # device slab length per stripe row
DEFAULT_BATCH = 32        # slabs per device call


def encode_volume(dat_path: str, out_base: str, geo: EcGeometry,
                  coder: ErasureCoder, idx_path: str | None = None,
                  chunk: int = DEFAULT_CHUNK, batch: int = DEFAULT_BATCH,
                  stats: "dict | None" = None,
                  writers: "int | None" = None,
                  ) -> list[str]:
    """Produce .ec00..ec{n-1} (+ .ecx if idx_path given). Returns shard paths.

    Reference flow: VolumeEcShardsGenerate (volume_grpc_erasure_coding.go:39)
    -> WriteEcFiles + WriteSortedFileFromIdx. Single-volume wrapper over the
    streaming multi-volume pipeline (ec/stream.py); `stats` receives the
    fill/dispatch/drain/write stage breakdown and `writers` sizes the
    writeback plane.
    """
    from . import stream
    res = stream.encode_volumes([(dat_path, out_base, idx_path)], geo, coder,
                                chunk=chunk, batch=batch, stats=stats,
                                writers=writers)
    return res[dat_path]


def find_shards(base: str, n: int) -> dict[int, str]:
    return {i: base + files.shard_ext(i)
            for i in range(n) if os.path.exists(base + files.shard_ext(i))}


def rebuild_shards(base: str, geo: EcGeometry, coder: ErasureCoder,
                   wanted: Sequence[int] | None = None,
                   chunk: int = DEFAULT_CHUNK, batch: int = DEFAULT_BATCH,
                   shard_reader=None,
                   remote_shards: Sequence[int] | None = None,
                   stats: "dict | None" = None,
                   fragment_reader=None,
                   fold_planner=None,
                   ) -> list[int]:
    """Recreate missing shard files from >= d survivors.

    Reference: RebuildEcFiles ec_encoder.go:61 / rebuildEcFiles :237-291.
    Survivors may live elsewhere: `shard_reader(sid, offset, length)`
    (ec/volume.py contract -> VolumeEcShardRead) serves the ids listed in
    `remote_shards` by RANGE, so a repair-efficient codec's plan fetches
    byte ranges off the network instead of d full shards (a reader that
    also has `readinto`, ec/repair.py, lands them straight in the batched
    rebuild's rows);
    `fragment_reader(sid, ranges)` additionally lets a survivor holder
    gather scattered ranges server-side and ship ONE computed fragment
    (the MSR codec's beta-fragments ride this). `fold_planner(coder, f)
    -> [(sids, fetch)]` (geo plane) lets the caller group far-DC
    survivors behind relay holders that fold their plane rows into one
    partial before crossing the expensive link — only consulted on the
    single-loss msr fast path. Every survivor byte consumed lands in
    SeaweedFS_repair_bytes_read_total{codec} and in `stats`
    (bytes_read / bytes_written / codec / path). Returns the shard ids
    rebuilt (always materialized locally under `base`).
    """
    from .. import tracing
    present_local = find_shards(base, geo.n)
    # a shard the caller explicitly wants rebuilt is never a survivor,
    # even if a stale holder list still claims a remote copy
    remote = [s for s in (remote_shards or ())
              if s not in present_local and shard_reader is not None
              and (wanted is None or s not in set(wanted))]
    present = set(present_local) | set(remote)
    missing = sorted(set(wanted) if wanted is not None
                     else set(range(geo.n)) - present)
    missing = [m for m in missing if m not in present_local]
    if not missing:
        return []
    if len(present) < geo.d:
        raise RuntimeError(
            f"cannot rebuild: only {len(present)} shards present, need {geo.d}")
    shard_size = _shard_size(base, geo, present_local)
    with tracing.start_span(
            "ec.rebuild", component="ec",
            attrs={"base": os.path.basename(base), "missing": missing,
                   "present": len(present), "remote": len(remote),
                   "coder": type(coder).__name__,
                   "codec": coder.codec}) as sp:
        from . import repair
        counter = repair.RepairCounter(coder.codec)
        acct = tracing.StageAccount("rebuild", _REBUILD_STAGES)
        readers, loaders, frag_readers, close = repair.make_readers(
            base, present_local, shard_reader, remote, counter,
            fragment_reader=fragment_reader)
        try:
            path, warm = _dispatch_rebuild(
                base, geo, coder, tuple(sorted(present)), missing, readers,
                loaders, frag_readers, shard_size, chunk, batch, counter,
                acct, fold_planner=fold_planner,
                local_sids=frozenset(present_local))
        finally:
            close()
        sp.set_attr("bytes_read", counter.bytes_read)
        sp.set_attr("bytes_written", counter.bytes_written)
        sp.set_attr("path", path)
        sp.set_attr("warm_batches", warm)
        acct.publish(sp)
        if stats is not None:
            stats.update(bytes_read=counter.bytes_read,
                         bytes_written=counter.bytes_written,
                         codec=coder.codec, path=path,
                         shard_size=shard_size,
                         batches=acct.count("dispatch"), warm_batches=warm,
                         **acct.fields())
        return missing


# a rebuild's stages (tracing.StageAccount, annotated `swtpu/rebuild.*`):
# `read` loads survivor ranges (local preads or remote ranged fetches)
# into the batch, `dispatch` is the coder's matrix apply (H2D + launch; a
# host coder computes here), `drain` blocks on the result (device + D2H),
# `write` stores into the rebuilt shard files and flushes them. Plain RS
# and every codec repair that is one matrix (`repair_linear`: piggyback's
# single data shard) run these four in `_rebuild_batched`. The codecs'
# other executors (ec/repair.py: piggyback's `general`, msr's `ranged` and
# `general`) are one coarser stage `codec` (decode + pwrite) with their
# survivor reads taken out as `read`.
_REBUILD_STAGES = ("read", "dispatch", "drain", "write")
# a batch's survivor ranges load side by side: `read` stays the stage's
# wall on the rebuild's thread, and the loads' own seconds are summed
# beside it, all of them and by kind of survivor (never stages: they
# overlap, so they partition nothing). read_busy_s / read_s is the
# overlap achieved.
_READ_BUSY = ("read_busy", "read_local_busy", "read_remote_busy")


def _shard_size(base: str, geo: EcGeometry,
                present_local: dict[int, str]) -> int:
    if present_local:
        return os.path.getsize(next(iter(present_local.values())))
    info = files.read_vif(base + ".vif")
    dat_size = info.get("dat_size")
    if dat_size is None:
        raise RuntimeError(f"cannot size shards of {base}: no local "
                           "survivor and no .vif")
    return geo.shard_file_size(dat_size)


def _dispatch_rebuild(base: str, geo: EcGeometry, coder: ErasureCoder,
                      present: tuple, missing: list[int], readers: dict,
                      loaders: dict, frag_readers: dict, shard_size: int,
                      chunk: int, batch: int, counter, acct,
                      fold_planner=None,
                      local_sids: frozenset = frozenset()) -> "tuple[str, int]":
    """Pick the cheapest reconstruction the codec supports. A repair that
    is one matrix over the ranges it reads (plain RS over d survivors; a
    codec's `repair_linear`) is a row source and a matrix for
    `_rebuild_batched`, which takes the survivors' `loaders`; the rest
    resolve through the repair.REBUILDERS registry and take `readers`,
    so a new codec plugs in its executors without touching this
    dispatch. Returns the path taken ("ranged" | "general" | "full" |
    "ranged-folded") and the batches staged in warm buffers, for
    stats/traces."""
    from . import repair
    ranged, general = repair.REBUILDERS.get(coder.codec, (None, None))
    plan = coder.repair_plan(present, tuple(missing), shard_size)
    linear = (coder.repair_linear(tuple(missing), shard_size)
              if plan is not None else None)
    if linear is not None:
        matrix, targets, engine = linear
        (extent,) = {ln for _, _, ln in plan}
        warm = _rebuild_batched(
            base, type(coder).__name__,
            [(sid, off) for sid, off, _ in plan], extent, targets,
            lambda arr: engine.apply_matrix(matrix, arr), loaders,
            shard_size, chunk, batch, counter, acct, local_sids)
        return "ranged", warm
    if (plan is not None and ranged is not None) or general is not None:
        readers = {s: acct.timed("read", r) for s, r in readers.items()}
        frag_readers = {s: acct.timed("read", r)
                        for s, r in frag_readers.items()}
    if plan is not None and ranged is not None:
        folds = ()
        if fold_planner is not None and coder.codec == "msr":
            # a survivor on THIS disk never folds: local preads beat any
            # relay hop, and a stale holder list must not reroute them
            folds = tuple(x for x in (fold_planner(coder, missing[0]) or ())
                          if not set(x[0]) & local_sids)
        with acct.stage("codec"):
            if folds:
                ranged(base, coder, missing[0], readers, frag_readers,
                       shard_size, counter, folds=folds)
                return "ranged-folded", 0
            ranged(base, coder, missing[0], readers, frag_readers,
                   shard_size, counter)
            return "ranged", 0
    if general is not None:
        with acct.stage("codec"):
            general(base, coder, present, missing, readers, frag_readers,
                    shard_size, counter)
        return "general", 0
    # plain RS: positional reconstruct over the first d survivors, whole
    use = tuple(sorted(present)[:geo.d])
    wanted = tuple(missing)
    warm = _rebuild_batched(
        base, type(coder).__name__, [(sid, 0) for sid in use], shard_size,
        [(m, 0) for m in missing],
        lambda arr: coder.reconstruct(arr, use, wanted), loaders,
        shard_size, chunk, batch, counter, acct, local_sids)
    return "full", warm


def _rebuild_batched(base: str, coder_name: str, sources: list, extent: int,
                     targets: list, apply, loaders: dict, shard_size: int,
                     chunk: int, batch: int, counter, acct,
                     local_sids: frozenset) -> int:
    """One matrix over [batch, len(sources), chunk] slabs (device-batched
    like encode). Row r of a slab is `extent` bytes of survivor
    sources[r] = (shard_id, offset); `apply(slab)` gives one row per
    target = (shard_id, offset), `extent` bytes of a rebuilt shard file
    from that offset on. Plain RS walks d whole survivors into the lost
    shards; a codec's ranged repair walks the ranges of its plan. A
    batch's rows load side by side, one task each: the batch waits for
    its slowest survivor, not for their sum.

    Whose the buffers are: this rebuild's for as long as it runs, leased
    from `buffers.REBUILD`, which keeps them for the next rebuild (of any
    shape they have the bytes for) and drops them when none has come for
    `buffers.IDLE_DROP_S`. They arrive DIRTY, and the read path allocates
    nothing a batch: each loader puts its survivor's bytes straight into
    its rows (`loaders[sid](offset, length, rows)`, ec/repair.py). The
    last batch is dispatched whole and never padded from a survivor: the
    tail of its last slab is zeroed here, and the slabs past it hold what
    the buffer held (an earlier batch's or an earlier rebuild's rows),
    are computed and dropped — every column is on its own, so nothing of
    them reaches a shard file. Returns how many batches went through
    memory an earlier rebuild had already written (`warm_batches`)."""
    for name in _READ_BUSY:  # the fields exist though a kind has no load
        acct.add(name, 0.0, n=0)
    outs = {}
    with acct.stage("write"):
        for m in {m for m, _ in targets}:
            p = base + files.shard_ext(m)
            with open(p, "wb") as f:
                f.truncate(shard_size)
            outs[m] = np.memmap(p, dtype=np.uint8, mode="r+",
                                shape=(shard_size,))

    from ..stats import EC_REBUILD_BYTES
    from .stream import DEFAULT_DEPTH, AsyncPipe

    def drain(rebuilt: np.ndarray, ctx) -> None:
        off, span, nb = ctx
        with acct.stage("write"):
            for k, (m, at) in enumerate(targets):
                outs[m][at + off:at + off + span] = \
                    rebuilt[:nb, k].reshape(-1)[:span]
        counter.wrote(span * len(targets))

    def load(arr: np.ndarray, r: int, sid: int, off: int, span: int,
             nb: int) -> None:
        """One survivor's range into its rows of the batch (disjoint from
        every other task's), and zeros behind a range that ends inside
        its last row."""
        t0 = time.perf_counter()
        rows = arr[:nb, r]
        loaders[sid](off, span, rows)
        if span < nb * chunk:
            rows[nb - 1, span - (nb - 1) * chunk:] = 0
        # booked from the worker: no stage is open on this thread, so
        # nothing is taken out of the caller's `read`
        busy = time.perf_counter() - t0
        acct.add("read_busy", busy)
        acct.add("read_local_busy" if sid in local_sids
                 else "read_remote_busy", busy)

    shape = (batch, len(sources), chunk)
    slab = len(sources) * chunk
    warm = 0
    pool = ThreadPoolExecutor(max_workers=len(sources),
                              thread_name_prefix="ec-rebuild-read")
    try:
        with buffers.REBUILD.lease(shape, DEFAULT_DEPTH + 2) as held:
            was_warm = list(held.touched)
            pipe = AsyncPipe(shape, acct, buffers=held.views(shape))
            for n, off in enumerate(range(0, extent, chunk * batch)):
                span = min(chunk * batch, extent - off)
                nb = (span + chunk - 1) // chunk
                arr = pipe.next_buffer()
                slot = n % len(was_warm)  # the pipe hands them out in turn
                warm += int(nb * slab <= was_warm[slot])
                held.touched[slot] = max(held.touched[slot], nb * slab)
                with acct.stage("read", batch=n):
                    # each task under a copy of this thread's context: the
                    # QoS class rides a remote read to its holder, and the
                    # fetch spans keep `ec.rebuild` as their parent
                    loads = [pool.submit(contextvars.copy_context().run,
                                         load, arr, r, sid, at + off, span,
                                         nb)
                             for r, (sid, at) in enumerate(sources)]
                    for task in loads:
                        task.result()
                EC_REBUILD_BYTES.inc(coder_name, amount=arr.nbytes)
                with acct.stage("dispatch", batch=n):
                    fut = apply(arr)
                pipe.submit(fut, (off, span, nb), drain)
            # every batch drained: nothing reads the buffers any more
            pipe.flush()
    finally:
        # waits: after an error too every load has ended before the
        # caller closes the survivors' fds (and the set, never handed
        # back then, goes with the last load that wrote into it)
        pool.shutdown()
    with acct.stage("write"):
        for o in outs.values():
            o.flush()
    return warm


def decode_volume(base: str, dat_out: str, geo: EcGeometry,
                  coder: ErasureCoder, dat_size: int | None = None) -> None:
    """Concatenate data shards row-interleaved back into a .dat
    (reference ec_decoder.go:154 WriteDatFile). Rebuilds missing data shards
    first if any."""
    present = find_shards(base, geo.n)
    missing_data = [i for i in range(geo.d) if i not in present]
    if missing_data:
        rebuild_shards(base, geo, coder, wanted=missing_data)
        present = find_shards(base, geo.n)
    if dat_size is None:
        info = files.read_vif(base + ".vif")
        dat_size = info.get("dat_size")
        if dat_size is None:
            dat_size = files.max_ecx_extent(base + ".ecx")
    if dat_size == 0:
        open(dat_out, "wb").close()
        return
    shards = [np.memmap(present[i], dtype=np.uint8, mode="r") for i in range(geo.d)]
    with open(dat_out, "wb") as f:
        f.truncate(dat_size)
    out = np.memmap(dat_out, dtype=np.uint8, mode="r+", shape=(dat_size,))
    # vectorized region copies (mirror of stream._VolumePlan region views)
    d, lb, sb = geo.d, geo.large_block, geo.small_block
    nl = geo.large_rows(dat_size)
    large_bytes = nl * d * lb
    if nl:
        view = out[:large_bytes].reshape(nl, d, lb)
        for i in range(d):
            view[:, i, :] = np.asarray(shards[i][:nl * lb]).reshape(nl, lb)
    rest = dat_size - large_bytes
    full = rest // (d * sb)
    if full:
        view = out[large_bytes:large_bytes + full * d * sb].reshape(full, d, sb)
        for i in range(d):
            view[:, i, :] = np.asarray(
                shards[i][nl * lb:nl * lb + full * sb]).reshape(full, sb)
    tail_start = large_bytes + full * d * sb
    pos = tail_start
    shard_base = nl * lb + full * sb
    for i in range(d):
        if pos >= dat_size:
            break
        ln = min(sb, dat_size - pos)
        out[pos:pos + ln] = shards[i][shard_base:shard_base + ln]
        pos += ln
    out.flush()
