"""Repair-traffic plumbing: byte-counted shard readers, codec overlay
seals, ranged/codec-aware rebuild paths, and degraded-interval
reconstruction for piggybacked and MSR volumes.

This module is the file-and-wire half of ops/piggyback.py and
ops/product_matrix.py: the coders own the GF math and the repair *plan*
(which byte ranges — or computed fragments — of which survivors), this
module executes plans against local shard files and remote fetches
(`shard_reader` -> ranged VolumeEcShardRead; `fragment_reader` -> its
ranged-COMPUTE mode, one wire fragment per survivor per window), counts
every survivor byte into `SeaweedFS_repair_bytes_read_total` /
`_written_total`, and streams in bounded windows so a 30 GB stripe
never needs d shards of RAM.

A plan whose repair is ONE matrix over the ranges it reads (piggyback's
single data shard: `PiggybackCoder.repair_linear`) has no executor here:
ec/encoder.py runs it as a row source and a matrix under the rebuild's
own loaders, pipe and stages, through this module's readers and counter.
What is left here runs window by window under the stage `codec`:
piggyback's `general` (several shards lost, or a parity), msr's `ranged`
and `general`.
"""

from __future__ import annotations

import os
import threading
from typing import Callable

import numpy as np

from ..ops.piggyback import PiggybackCoder
from ..utils.log import logger
from . import files

log = logger("ec.repair")

# streaming window for the windowed repair paths: big enough to amortize
# per-call fetch overhead, small enough to keep d in-flight rows bounded
REPAIR_WINDOW = 4 << 20

# shard_reader(shard_id, offset, length) -> bytes (ec/volume.py contract)
ShardReader = Callable[[int, int, int], bytes]

# A shard_reader may also have the landing form, as a file has `readinto`
# beside `read`: shard_reader.readinto(shard_id, offset, length, rows) ->
# bytes landed, where `rows` is a writable [n, width] uint8 array whose
# rows are each contiguous (one survivor's rows of a rebuild's batch) and
# the range goes to rows.reshape(-1)[:length] as `land` puts it there. A
# rebuild then takes a remote survivor's bytes off the wire straight into
# its batch; a reader without it is asked for `bytes` and those are copied.

# fragment_reader(shard_id, [(offset, length), ...]) -> bytes: the
# ranged-compute shard read — the holder gathers the scattered ranges
# server-side and ships ONE packed fragment (VolumeEcShardRead with
# fragment_offsets/fragment_lengths)
FragmentReader = Callable[[int, list], bytes]


class RepairCounter:
    """bytes_read / bytes_written accounting for one repair, mirrored to
    the codec-labelled repair counters as it accumulates. `read` is
    called from every survivor's loader at once (ec/encoder.py)."""

    def __init__(self, codec: str):
        self.codec = codec or "rs"
        self.bytes_read = 0
        self.bytes_written = 0
        self._lock = threading.Lock()

    def read(self, n: int) -> None:
        with self._lock:
            self.bytes_read += n
        try:
            from ..stats import REPAIR_BYTES_READ
            REPAIR_BYTES_READ.inc(self.codec, amount=n)
        except Exception:  # noqa: BLE001  # swtpu-lint: disable=silent-except (metrics must never break repair)
            pass

    def wrote(self, n: int) -> None:
        self.bytes_written += n
        try:
            from ..stats import REPAIR_BYTES_WRITTEN
            REPAIR_BYTES_WRITTEN.inc(self.codec, amount=n)
        except Exception:  # noqa: BLE001  # swtpu-lint: disable=silent-except (metrics must never break repair)
            pass


def land(rows: np.ndarray, pos: int, data, limit: int) -> int:
    """Copy `data` to byte `pos` of the range that fills `rows` ([n,
    width], each row contiguous) row after row, whatever `data`'s length
    is to a row's; bytes from `limit` on are dropped. Returns the position
    after `data`, dropped bytes counted too, so a stream that is too long
    shows as one."""
    width = rows.shape[1]
    src = np.frombuffer(data, dtype=np.uint8)
    end = pos + len(src)
    src = src[:max(0, limit - pos)]
    while len(src):
        k, at = divmod(pos, width)
        n = min(width - at, len(src))
        rows[k, at:at + n] = src[:n]
        src = src[n:]
        pos += n
    return end


def make_readers(base: str, present_local: "dict[int, str]",
                 shard_reader: "ShardReader | None",
                 remote_sids, counter: RepairCounter,
                 fragment_reader: "FragmentReader | None" = None,
                 ) -> "tuple[dict[int, Callable[[int, int], np.ndarray]], dict[int, Callable], dict[int, Callable], Callable[[], None]]":
    """(readers, loaders, frag_readers, close): per-shard `read(offset,
    length) -> uint8 array`, `load(offset, length, rows)` and
    `frag(ranges) -> concatenated uint8 array` over local files and
    remote fetches, every byte counted once. Fragment reads of local
    shards are gathered preads; remote ones go through the holder's
    ranged-compute mode when the caller wires `fragment_reader`, else
    degrade to one ranged fetch per run.

    `read` returns memory of its own (the degraded read, the codecs'
    windowed executors). `load` is the batched rebuild's: the range lands
    in `rows`, the caller's [n, width] view of one survivor's rows of a
    batch, as `land` lays it out, and nothing else is allocated: a local
    survivor by one `preadv` over the rows, a remote one through the
    reader's `readinto` where it has one. The rows stay the caller's;
    a load that fails may have written any part of them."""
    fds: dict[int, int] = {}

    def local(sid: int):
        def read(off: int, ln: int) -> np.ndarray:
            buf = os.pread(fds[sid], ln, off)
            if len(buf) != ln:
                raise OSError(f"short read of shard {sid} at {off}")
            counter.read(ln)
            return np.frombuffer(buf, dtype=np.uint8)
        return read

    def local_load(sid: int):
        def load(off: int, ln: int, rows: np.ndarray) -> None:
            full, rest = divmod(ln, rows.shape[1])
            into = [rows[k] for k in range(full)]
            if rest:
                into.append(rows[full, :rest])
            if os.preadv(fds[sid], into, off) != ln:
                raise OSError(f"short read of shard {sid} at {off}")
            counter.read(ln)
        return load

    def local_frag(sid: int):
        def frag(ranges) -> np.ndarray:
            out = np.empty(sum(ln for _, ln in ranges), dtype=np.uint8)
            pos = 0
            for off, ln in ranges:
                buf = os.pread(fds[sid], ln, off)
                if len(buf) != ln:
                    raise OSError(f"short read of shard {sid} at {off}")
                out[pos:pos + ln] = np.frombuffer(buf, dtype=np.uint8)
                pos += ln
            counter.read(len(out))
            return out
        return frag

    def remote(sid: int):
        def read(off: int, ln: int) -> np.ndarray:
            buf = shard_reader(sid, off, ln)
            if len(buf) != ln:
                raise OSError(f"short remote read of shard {sid} at {off}")
            counter.read(ln)
            return np.frombuffer(buf, dtype=np.uint8)
        return read

    def remote_load(sid: int):
        readinto = getattr(shard_reader, "readinto", None)

        def load(off: int, ln: int, rows: np.ndarray) -> None:
            if readinto is not None:
                got = readinto(sid, off, ln, rows)
            else:
                got = land(rows, 0, shard_reader(sid, off, ln), ln)
            if got != ln:
                raise OSError(f"short remote read of shard {sid} at {off}")
            counter.read(ln)
        return load

    def remote_frag(sid: int):
        def frag(ranges) -> np.ndarray:
            want = sum(ln for _, ln in ranges)
            if fragment_reader is not None:
                buf = fragment_reader(sid, list(ranges))
                if len(buf) != want:
                    raise OSError(f"short fragment from shard {sid}: "
                                  f"{len(buf)} != {want}")
                counter.read(want)
                return np.frombuffer(buf, dtype=np.uint8)
            out = np.empty(want, dtype=np.uint8)
            pos = 0
            for off, ln in ranges:
                buf = shard_reader(sid, off, ln)
                if len(buf) != ln:
                    raise OSError(f"short remote read of shard {sid}")
                out[pos:pos + ln] = np.frombuffer(buf, dtype=np.uint8)
                pos += ln
            counter.read(want)
            return out
        return frag

    readers: dict[int, Callable] = {}
    loaders: dict[int, Callable] = {}
    frag_readers: dict[int, Callable] = {}
    for sid, path in present_local.items():
        fds[sid] = os.open(path, os.O_RDONLY)
        readers[sid] = local(sid)
        loaders[sid] = local_load(sid)
        frag_readers[sid] = local_frag(sid)
    for sid in remote_sids or ():
        if sid not in readers and shard_reader is not None:
            readers[sid] = remote(sid)
            loaders[sid] = remote_load(sid)
            frag_readers[sid] = remote_frag(sid)

    def close() -> None:
        for fd in fds.values():
            try:
                os.close(fd)
            except OSError:
                log.debug("closing survivor fd under %s failed", base,
                          exc_info=True)
    return readers, loaders, frag_readers, close


def _open_outputs(base: str, missing, shard_size: int) -> "dict[int, int]":
    outs = {}
    for m in missing:
        p = base + files.shard_ext(m)
        with open(p, "wb") as f:
            f.truncate(shard_size)
        outs[m] = os.open(p, os.O_RDWR)
    return outs


def _pwrite(fd: int, arr: np.ndarray, off: int) -> None:
    mv = memoryview(np.ascontiguousarray(arr)).cast("B")
    n = os.pwrite(fd, mv, off)
    while n < len(mv):
        mv = mv[n:]
        off += n
        n = os.pwrite(fd, mv, off)


# ---------------------------------------------------------------------------
# General piggyback rebuild (multi-loss, parity loss): two streamed passes.
# ---------------------------------------------------------------------------

def rebuild_piggyback_general(base: str, pb: PiggybackCoder,
                              present, missing, readers: dict,
                              shard_size: int, counter: RepairCounter,
                              window: int = REPAIR_WINDOW) -> None:
    """Pass A rebuilds the a-halves (substripe a is plain RS over ALL
    shards, piggybacked parities included); pass B purifies surviving
    piggybacked parities with the now-complete a substripe, decodes the
    b-halves, and re-applies the piggyback to rebuilt parities."""
    d = pb.d
    half = shard_size // 2
    used = tuple(sorted(present))[:d]
    missing = tuple(sorted(missing))
    outs = _open_outputs(base, missing, shard_size)

    def out_read(m: int, off: int, ln: int) -> np.ndarray:
        buf = os.pread(outs[m], ln, off)
        return np.frombuffer(buf, dtype=np.uint8)

    try:
        for w in range(0, half, window):  # pass A: a substripe
            wl = min(window, half - w)
            a_rows = np.stack([readers[s](w, wl) for s in used])
            rec = np.asarray(pb.inner.reconstruct(a_rows, used, missing),
                             dtype=np.uint8)
            for wi, m in enumerate(missing):
                _pwrite(outs[m], rec[wi], w)
                counter.wrote(wl)
        # which piggyback groups pass B must materialize: one per
        # surviving piggybacked parity (to purify) or rebuilt one
        need_g = sorted({s - d for s in used if s > d}
                        | {m - d for m in missing if m > d})
        # a group member may be missing WITHOUT being rebuilt here (the
        # caller wanted only a parity): its a-half exists nowhere on
        # disk, so decode it per-window from the survivors' a substripe
        aux = tuple(sorted({i for g in need_g for i in pb.groups[g - 1]
                            if i not in readers and i not in outs}))
        for w in range(0, half, window):  # pass B: b substripe
            wl = min(window, half - w)
            b_rows = np.stack([readers[s](half + w, wl) for s in used])
            aux_a = {}
            if aux:
                a_rows = np.stack([readers[s](w, wl) for s in used])
                rec_a = np.asarray(pb.inner.reconstruct(a_rows, used, aux),
                                   dtype=np.uint8)
                aux_a = {i: rec_a[ai] for ai, i in enumerate(aux)}
            xg = {}
            for g in need_g:
                x = np.zeros(wl, dtype=np.uint8)
                for i in pb.groups[g - 1]:
                    if i in aux_a:
                        x = x ^ aux_a[i]
                    elif i in readers:
                        x = x ^ readers[i](w, wl)
                    else:
                        x = x ^ out_read(i, w, wl)
                xg[g] = x
            for idx, s in enumerate(used):
                if s > d:
                    b_rows[idx] ^= xg[s - d]
            rec = np.asarray(pb.inner.reconstruct(b_rows, used, missing),
                             dtype=np.uint8)
            for wi, m in enumerate(missing):
                row = rec[wi]
                if m > d:
                    row = row ^ xg[m - d]
                _pwrite(outs[m], row, half + w)
                counter.wrote(wl)
    finally:
        for fd in outs.values():
            os.fsync(fd)
            os.close(fd)


# ---------------------------------------------------------------------------
# Encode-side overlay: plain-RS shard files -> piggybacked parity files.
# ---------------------------------------------------------------------------

def apply_piggyback_overlay(out_base: str, pb: PiggybackCoder,
                            shard_size: int,
                            window: int = REPAIR_WINDOW) -> None:
    """Fold the piggyback XORs into freshly written plain-RS parity
    files (ec/stream.py encodes slabs with the inner coder — device
    batching untouched — then seals through this overlay): for each
    piggybacked parity g, parity_file[half:] ^= XOR of the group's data
    files[:half]. Runs while the encode's page cache is hot."""
    if shard_size == 0:
        return
    if shard_size % 2:
        raise ValueError(f"piggyback needs an even shard size, got "
                         f"{shard_size} (block sizes must be even)")
    half = shard_size // 2
    d = pb.d
    for g, grp in enumerate(pb.groups, start=1):
        if not grp:
            continue
        data_fds = [os.open(out_base + files.shard_ext(i), os.O_RDONLY)
                    for i in grp]
        pfd = os.open(out_base + files.shard_ext(d + g), os.O_RDWR)
        try:
            for w in range(0, half, window):
                wl = min(window, half - w)
                x = np.frombuffer(os.pread(pfd, wl, half + w),
                                  dtype=np.uint8).copy()
                for fd in data_fds:
                    x ^= np.frombuffer(os.pread(fd, wl, w), dtype=np.uint8)
                _pwrite(pfd, x, half + w)
            os.fsync(pfd)
        finally:
            os.close(pfd)
            for fd in data_fds:
                os.close(fd)


# ---------------------------------------------------------------------------
# MSR (product-matrix) repair: β-sized computed fragments from every
# survivor for single loss; streamed coupled decode for multi-loss.
# ---------------------------------------------------------------------------

def _msr_window(pm, shard_size: int, window: int) -> int:
    """Inner-offset window width: the decode working set is
    nbar * alpha * width, so dividing `window` by alpha caps it near
    nbar * window (~64 MB at the default 4 MB window) while each
    helper's in-flight fragment stays <= window / q."""
    s = shard_size // pm.alpha
    return max(1, min(s, window // pm.alpha))


def rebuild_msr_single(base: str, pm, f: int, readers: dict,
                       frag_readers: dict, shard_size: int,
                       counter: RepairCounter,
                       window: int = REPAIR_WINDOW, folds=()) -> None:
    """Rebuild any single lost shard — data OR parity — from computed
    fragments of ALL n-1 survivors: each ships only its repair-plane
    sub-symbols ((n-1)/p shard-equivalents total, the MSR cut-set
    bound), one fragment RPC per survivor per window.

    `folds` (geo plane) is a list of (sids, fetch) relay groups: the
    sids are far-side survivors whose plane rows a single relay holder
    gathers and folds through the stacked per-helper repair matrix
    (geo/repair_fold.py) — `fetch(ranges)` returns the group's ONE
    folded partial of alpha rows per window. Folded survivors skip the
    per-survivor fetch; their contribution XORs into the near-side
    decode, which is byte-identical to the flat path because
    `repair_decode` is GF-linear in the helpers' plane symbols."""
    g = pm.grid
    planes = g.repair_planes(f)
    s = shard_size // pm.alpha
    wl = _msr_window(pm, shard_size, window)
    folded_sids = {sid for sids, _fetch in folds for sid in sids}
    outs = _open_outputs(base, [f], shard_size)
    try:
        for u in range(0, s, wl):
            w = min(wl, s - u)
            ranges = [(int(z) * s + u, w) for z in planes]
            c = np.zeros((g.nbar, g.alpha, w), dtype=np.uint8)
            for sid in range(pm.n):
                if sid == f or sid in folded_sids:
                    continue
                frag = frag_readers[sid](ranges)
                c[sid, planes] = frag.reshape(len(planes), w)
            row = pm.repair_decode(c, f)
            for _sids, fetch in folds:
                part = fetch(ranges)
                counter.read(part.size)
                row = row ^ part.reshape(pm.alpha, w)
            for z in range(pm.alpha):
                _pwrite(outs[f], row[z], z * s + u)
            counter.wrote(pm.alpha * w)
    finally:
        for fd in outs.values():
            os.fsync(fd)
            os.close(fd)


def rebuild_msr_general(base: str, pm, present, missing, readers: dict,
                        frag_readers: dict, shard_size: int,
                        counter: RepairCounter,
                        window: int = REPAIR_WINDOW) -> None:
    """Multi-loss (or missing-helper) rebuild: stream the coupled
    layered decode over d full survivors, reading EACH SURVIVOR EXACTLY
    ONCE across all losses — never once per lost shard."""
    g = pm.grid
    s = shard_size // pm.alpha
    missing = tuple(sorted(missing))
    # prefer local survivors: make_readers inserts local fds before
    # remote fetchers, so frag_readers' iteration order is the byte-
    # cheapest d-subset
    avail = set(present)
    order = [sid for sid in frag_readers if sid in avail]
    used = tuple(sorted(order[: pm.d]))
    if len(used) < pm.d:
        raise RuntimeError(f"msr rebuild needs {pm.d} survivors, "
                           f"have {len(used)}")
    all_layers = np.arange(pm.alpha)
    wl = _msr_window(pm, shard_size, window)
    outs = _open_outputs(base, missing, shard_size)
    try:
        for u in range(0, s, wl):
            w = min(wl, s - u)
            c = np.zeros((g.nbar, g.alpha, w), dtype=np.uint8)
            for sid in used:
                ranges = [(int(z) * s + u, w) for z in all_layers]
                c[sid] = frag_readers[sid](ranges).reshape(pm.alpha, w)
            pm.decode_coupled(c, used)
            for m in missing:
                for z in range(pm.alpha):
                    _pwrite(outs[m], c[m, z], z * s + u)
                counter.wrote(pm.alpha * w)
    finally:
        for fd in outs.values():
            os.fsync(fd)
            os.close(fd)


def apply_msr_overlay(out_base: str, pm, shard_size: int,
                      window: int = REPAIR_WINDOW) -> None:
    """Encode-side seal: rewrite the parity files with the MSR coupled
    parities computed from the data shard files (ec/stream.py's device
    pipeline encodes plain-RS slabs — codec-agnostic — and this overlay
    replaces the parity bytes before the .vif seals the codec)."""
    if shard_size == 0:
        return
    if shard_size % pm.alpha:
        raise ValueError(
            f"msr needs shard files divisible by alpha={pm.alpha}, got "
            f"{shard_size}: use a power-of-two p or a small_block "
            "divisible by alpha")
    g = pm.grid
    s = shard_size // pm.alpha
    wl = _msr_window(pm, shard_size, window)
    data_fds = [os.open(out_base + files.shard_ext(i), os.O_RDONLY)
                for i in range(pm.d)]
    par_fds = [os.open(out_base + files.shard_ext(pm.d + j), os.O_RDWR)
               for j in range(pm.p)]
    try:
        for u in range(0, s, wl):
            w = min(wl, s - u)
            sub = np.empty((pm.d, pm.alpha, w), dtype=np.uint8)
            for i, fd in enumerate(data_fds):
                for z in range(pm.alpha):
                    buf = os.pread(fd, w, z * s + u)
                    if len(buf) != w:
                        raise OSError(f"short read sealing {out_base}")
                    sub[i, z] = np.frombuffer(buf, dtype=np.uint8)
            par = pm.encode_subsymbols(sub)
            for j, fd in enumerate(par_fds):
                for z in range(pm.alpha):
                    _pwrite(fd, par[j, z], z * s + u)
        for fd in par_fds:
            os.fsync(fd)
    finally:
        for fd in data_fds + par_fds:
            os.close(fd)


def apply_codec_overlay(out_base: str, coder, shard_size: int,
                        window: int = REPAIR_WINDOW) -> None:
    """Seal-time overlay dispatch for codecs whose parity differs from
    the plain-RS slabs the streaming pipeline writes."""
    fn = OVERLAYS.get(coder.codec)
    if fn is None:
        raise ValueError(f"codec {coder.codec!r} has no overlay seal")
    fn(out_base, coder, shard_size, window)


# ---------------------------------------------------------------------------
# Degraded reads: reconstruct one interval of a lost data shard when the
# gathered survivors include piggybacked parities.
# ---------------------------------------------------------------------------

def reconstruct_interval(pb: PiggybackCoder, gathered: "dict[int, np.ndarray]",
                         f: int, offset: int, length: int, shard_size: int,
                         fetch_pair, fetch_map=None) -> bytes:
    """gathered: >= d survivors' bytes for [offset, offset+length) of
    their shard files. Survivors from {0..d} (data + the unpiggybacked
    parity) are positionally plain RS everywhere, and *every* shard is
    positionally plain in the a-half — only b-half spans decoded through
    a piggybacked parity need its piggyback stripped, which takes the
    paired a-range: `fetch_pair(sid, off, ln) -> bytes` supplies it.
    `fetch_map(fetch_pair, [(sid, off, ln), ...]) -> [bytes, ...]` lets
    the caller fan the d paired fetches out concurrently (the degraded
    p99 pays one RTT per shard otherwise); default is sequential."""
    half = shard_size // 2
    used = tuple(sorted(gathered))[: pb.d]
    rows = np.stack([np.frombuffer(gathered[s], dtype=np.uint8)
                     for s in used])
    out = np.empty(length, dtype=np.uint8)
    a_len = max(0, min(length, half - offset))
    if a_len:  # a-half span: all shards positionally plain
        rec = np.asarray(pb.inner.reconstruct(rows[:, :a_len], used, (f,)),
                         dtype=np.uint8)
        out[:a_len] = rec[0]
    if a_len < length:  # b-half span
        b_rows = rows[:, a_len:].copy()
        pair_off = offset + a_len - half
        pair_len = length - a_len
        piggy_gs = sorted({s - pb.d for s in used if s > pb.d})
        if piggy_gs:
            reqs = [(s, pair_off, pair_len) for s in used]
            if fetch_map is None:
                rows_b = [fetch_pair(*r) for r in reqs]
            else:
                rows_b = fetch_map(fetch_pair, reqs)
            pair = np.stack([np.frombuffer(r, dtype=np.uint8)
                             for r in rows_b])
            a_data = np.asarray(pb.inner.reconstruct(
                pair, used, tuple(range(pb.d))), dtype=np.uint8)
            for idx, s in enumerate(used):
                if s > pb.d:
                    b_rows[idx] ^= pb._xor_group(a_data, pb.groups[s - pb.d - 1])
        rec = np.asarray(pb.inner.reconstruct(b_rows, used, (f,)),
                         dtype=np.uint8)
        out[a_len:] = rec[0]
    return out.tobytes()


# ---------------------------------------------------------------------------
# Codec dispatch: how encoder.rebuild_shards executes each codec's
# cheapest path where that is not one matrix (`coder.repair_linear`,
# which ec/encoder.py batches itself). Uniform signatures:
#   ranged(base, coder, f, readers, frag_readers, shard_size, counter)
#   general(base, coder, present, missing, readers, frag_readers,
#           shard_size, counter)
# A codec registered here never falls through to the positional plain-RS
# rebuild (which would decode its parities as if they were RS).
# ---------------------------------------------------------------------------

def _pb_general(base, coder, present, missing, readers, frag_readers,
                shard_size, counter):
    rebuild_piggyback_general(base, coder, present, missing, readers,
                              shard_size, counter)


REBUILDERS = {
    "piggyback": (None, _pb_general),  # its ranged repair is linear
    "msr": (rebuild_msr_single, rebuild_msr_general),
}

OVERLAYS = {
    "piggyback": apply_piggyback_overlay,
    "msr": apply_msr_overlay,
}
