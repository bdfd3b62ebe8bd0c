"""What the EC traffic kinds share: placing staged volumes on A, finding
shard files, and comparing a sealed volume with the plain reference."""

from __future__ import annotations

import hashlib
import os

import numpy as np

from seaweedfs_tpu.client import http_util

from . import reference
from .cluster import Cluster, check
from .data import VolumeManifest


def base(directory: str, collection: str, vid: int) -> str:
    """A volume's file stem, as the store lays it out."""
    return os.path.join(directory, f"{collection}_{vid}")


def shard_ext(sid: int) -> str:
    return f".ec{sid:02d}"


def place(cl: Cluster, stage: str, m: VolumeManifest, vid: int) -> None:
    """Hard-link a staged volume into A's directory as volume `vid` and
    mount it; the staged files stay for the next round."""
    for ext in (".dat", ".idx"):
        os.link(base(stage, m.collection, m.vid) + ext,
                base(cl.a_dir, m.collection, vid) + ext)
    cl.mount("A", m.collection, vid)


def shard_path(cl: Cluster, collection: str, vid: int, sid: int,
               ) -> "tuple[str, str] | None":
    """(holder, path) of a shard file, None if no server holds it."""
    for holder, d in (("A", cl.a_dir), ("B", cl.b_dir)):
        p = base(d, collection, vid) + shard_ext(sid)
        if os.path.exists(p):
            return holder, p
    return None


def shards_on(cl: Cluster, which: str, collection: str, vid: int,
              n: int) -> "list[int]":
    d = cl.a_dir if which == "A" else cl.b_dir
    return [s for s in range(n)
            if os.path.exists(base(d, collection, vid) + shard_ext(s))]


def seal(cl: Cluster, collection: str, d: int, p: int, vids: "list[int]",
         by_id: bool = False) -> dict:
    """One `lock; ec.encode; unlock` as an operator's cron runs it, timed
    (`Cluster.timed_shell`)."""
    what = (f"-volumeId {vids[0]}" if by_id
            else f"-collection {collection}")
    op = cl.timed_shell(f"lock; ec.encode {what} -ecShards {d},{p}; unlock")
    check(op["rc"] == 0 and f"ec encoded {len(vids)} volumes" in op["out"],
          f"ec.encode of {vids} exited {op['rc']}:\n{op['out'][-2000:]}")
    return op


def unseal(cl: Cluster, collection: str, vid: int, n: int) -> None:
    """Remove an EC volume's shards everywhere (what `ec.volume.delete`
    does, without a shell start)."""
    for which in ("A", "B"):
        sids = shards_on(cl, which, collection, vid, n)
        if sids:
            cl.drop_shards(which, collection, vid, sids)


def check_sealed(cl: Cluster, stage: str, m: VolumeManifest, vid: int,
                 d: int, p: int, rng: np.random.Generator, pool: bytes,
                 rows: int, gets: int) -> "str | None":
    """A sealed volume against the reference: every shard file's size,
    `rows` stripe rows (first and last included) recomputed and compared
    at every shard, `gets` needles read from the EC volume through A.
    Returns what is wrong, or None."""
    dat = np.memmap(base(stage, m.collection, m.vid) + ".dat",
                    dtype=np.uint8, mode="r")
    want_size = reference.shard_file_size(dat.size, d)
    paths = []
    for sid in range(d + p):
        found = shard_path(cl, m.collection, vid, sid)
        if found is None:
            return f"volume {vid}: shard {sid} missing"
        if os.path.getsize(found[1]) != want_size:
            return (f"volume {vid}: shard {sid} holds "
                    f"{os.path.getsize(found[1])} bytes, not {want_size}")
        paths.append(found[1])
    if os.path.exists(base(cl.a_dir, m.collection, vid) + ".dat"):
        return f"volume {vid}: source .dat still on A after the seal"
    n_rows = reference.small_rows(dat.size, d)
    picks = {0, n_rows - 1}
    picks.update(int(r) for r in rng.integers(0, n_rows, max(0, rows - 2)))
    for row in sorted(picks):
        blocks, off = reference.small_row(dat, row, d)
        want = np.concatenate([blocks, reference.encode(blocks, p)])
        for sid, path in enumerate(paths):
            with open(path, "rb") as f:
                f.seek(off)
                got = f.read(reference.SMALL_BLOCK)
            if got != want[sid].tobytes():
                return f"volume {vid}: shard {sid} differs in row {row}"
    for i in rng.integers(0, len(m.keys), gets):
        i = int(i)
        r = http_util.get(f"http://{cl.a_url}/{m.fid(i, vid)}")
        o, s = int(m.offs[i]), int(m.sizes[i])
        if not r.ok or r.content != pool[o:o + s]:
            return (f"volume {vid}: GET {m.fid(i, vid)} from the EC volume: "
                    f"HTTP {r.status}, {len(r.content)} bytes")
    return None


def sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            buf = f.read(8 << 20)
            if not buf:
                return h.hexdigest()
            h.update(buf)
