"""Seeded volumes, written with the store's own `Volume` writer before a
daemon mounts them.

Every payload is a slice of one random pool made from the seed, so any
needle can be regenerated for comparison without keeping it. A volume is
written by one worker process; workers share nothing but the seed.
Record timestamps are fixed, so the same seed gives the same `.dat` bytes.
"""

from __future__ import annotations

import math
import os
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass
from multiprocessing import get_context

import numpy as np

POOL_BYTES = 16 << 20
APPEND_AT_NS = 1_700_000_000_000_000_000  # fixed: same seed, same bytes
_CHUNK = 64 << 20  # raw record bytes handed to the writer at once


def pool(seed: int, max_needle: int) -> bytes:
    rng = np.random.default_rng([seed, 0])
    return rng.integers(0, 256, POOL_BYTES + max_needle,
                        dtype=np.uint8).tobytes()


@dataclass
class VolumeManifest:
    """What was written into one volume: needle i has key `keys[i]`,
    cookie `cookies[i]` and payload `pool[offs[i]:offs[i]+sizes[i]]`."""
    collection: str
    vid: int
    keys: np.ndarray
    cookies: np.ndarray
    offs: np.ndarray
    sizes: np.ndarray
    dat_bytes: int

    @property
    def payload_bytes(self) -> int:
        return int(self.sizes.sum())

    def fid(self, i: int, vid: "int | None" = None) -> str:
        v = self.vid if vid is None else vid
        return f"{v},{int(self.keys[i]):x}{int(self.cookies[i]):08x}"


def plan_sizes(rng: np.random.Generator, spec: dict) -> np.ndarray:
    """Payload sizes of one volume. `spec` is the configuration's
    `needles` group: fixed `size` x `count`, or log-uniform between
    `min` and `max` ("many small, a few huge") until `fill_bytes`."""
    if "size" in spec:
        return np.full(int(spec["count"]), int(spec["size"]), dtype=np.int64)
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    # draw in blocks; cut where the running sum first reaches fill_bytes
    sizes = np.empty(0, dtype=np.int64)
    while sizes.sum() < spec["fill_bytes"]:
        more = np.exp(rng.uniform(lo, hi, 4096)).astype(np.int64)
        sizes = np.concatenate([sizes, more])
    cut = int(np.searchsorted(np.cumsum(sizes), spec["fill_bytes"])) + 1
    return sizes[:cut]


def write_volume(directory: str, collection: str, vid: int, seed: int,
                 spec: dict, first_key: int = 1) -> VolumeManifest:
    """One full volume `<collection>_<vid>.dat/.idx` under `directory`."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    max_needle = int(spec.get("max", spec.get("size", 0)))
    data = pool(seed, max_needle)
    rng = np.random.default_rng([seed, 1, vid])
    sizes = plan_sizes(rng, spec)
    n = len(sizes)
    offs = rng.integers(0, POOL_BYTES, n, dtype=np.int64)
    cookies = rng.integers(1, 1 << 32, n, dtype=np.int64)
    keys = np.arange(first_key, first_key + n, dtype=np.int64)
    v = Volume(directory, collection, vid)
    try:
        recs, held = [], 0
        for i in range(n):
            o, s = int(offs[i]), int(sizes[i])
            rec = Needle(int(keys[i]), int(cookies[i]),
                         data[o:o + s]).to_bytes(now_ns=APPEND_AT_NS)
            recs.append(rec)
            held += len(rec)
            if held >= _CHUNK:
                v.append_records(b"".join(recs))
                recs, held = [], 0
        if recs:
            v.append_records(b"".join(recs))
    finally:
        v.close()
    dat = Volume.path_for(directory, collection, vid) + ".dat"
    return VolumeManifest(collection, vid, keys, cookies, offs, sizes,
                          os.path.getsize(dat))


def write_volumes(directory: str, collection: str, vids: "list[int]",
                  seed: int, spec: dict) -> "list[VolumeManifest]":
    """All of `vids`, one worker process per volume (spawned: the parent
    has threads). Keys are distinct across the volumes."""
    per = _keys_per_volume(spec)
    jobs = [(directory, collection, vid, seed, spec, 1 + k * per)
            for k, vid in enumerate(vids)]
    if len(jobs) == 1:
        return [write_volume(*jobs[0])]
    with ProcessPoolExecutor(max_workers=min(len(jobs), os.cpu_count() or 1),
                             mp_context=get_context("spawn")) as ex:
        futures = [ex.submit(write_volume, *job) for job in jobs]
        return [f.result() for f in futures]


def _keys_per_volume(spec: dict) -> int:
    if "count" in spec:
        return int(spec["count"])
    return int(spec["fill_bytes"] // spec["min"]) + 1


def corrupt(dat_path: str, idx_path: str, manifest: VolumeManifest,
            picks: "list[int]") -> None:
    """Flip two payload bytes of each picked needle, on disk."""
    from seaweedfs_tpu.storage import types as st
    from seaweedfs_tpu.storage.needle_map import walk_idx_file

    where = {k: off for k, off, _ in walk_idx_file(idx_path)}
    with open(dat_path, "r+b") as f:
        for i in picks:
            at = (st.stored_to_offset(where[int(manifest.keys[i])])
                  + st.NEEDLE_HEADER_SIZE + 4 + int(manifest.sizes[i]) // 2)
            f.seek(at)
            orig = f.read(2)
            f.seek(at)
            f.write(bytes(b ^ 0xFF for b in orig))
