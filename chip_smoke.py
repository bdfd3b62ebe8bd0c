#!/usr/bin/env python3
"""chip_smoke.py — does the store's device path start and answer right on
the chip, through the daemons a user runs?

Drives load -> scrub -> ec.encode -> degraded read -> ec.rebuild across
separate processes started with ``python -m seaweedfs_tpu``:

* a master (``-volumeSizeLimitMB`` from --size; ``-maintenanceScripts ""``:
  the smoke is the operator here — the cron's own first sweep, a minute or
  two after start, would ``ec.encode`` every full volume at the master's
  default geometry and hold the admin lock while the verbs below run),
* volume server **A**, which owns the chip (``-coder auto``,
  ``JAX_PLATFORMS=tpu`` so JAX itself refuses a missing chip; with
  ``--chips 4`` it runs ``-coder mesh``),
* volume server **B**, which owns none (``-coder native``,
  ``JAX_PLATFORMS=cpu``) and holds half of every stripe.

This parent never imports jax: it learns what A runs on from A's
``GET /status``. Every byte that comes back is compared with the plain host
reference — needles against the seeded generator, every EC shard against
``ec.encoder.encode_volume`` with ``NativeCoder`` (itself checked against
``NumpyCoder`` on sampled stripes) over a copy of the ``.dat`` kept before
encoding. Exit code 0 and a last stdout line
``{"ok": true, "device": {...}}`` only if every phase passed.

    python chip_smoke.py                     # the chip, full size
    python chip_smoke.py --chips 4           # one process driving four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu --size tiny   # CPU debug

Where the machine caps file sizes (RLIMIT_FSIZE: the driver's chip machine
stops files at 1 GiB, which a volume filled to a 1 GiB limit overruns by its
last needle) or is short of disk, the volume limit comes down and the
volume count goes up to keep the data size; the run prints ``CUT:`` and the
size it ran at. Block geometry and device batch are never cut.

``--allow-cpu`` relaxes the platform assertions only (A runs ``-coder jax``
on the CPU backend, scrub may say ``xla-cpu``); the driver never passes it.
"""

from __future__ import annotations

import argparse
import errno
import filecmp
import json
import math
import os
import re
import resource
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from seaweedfs_tpu.client import http_util, operation
from seaweedfs_tpu.client.master_client import FidLeaseAllocator, MasterClient
from seaweedfs_tpu.ec import encoder as ec_encoder
from seaweedfs_tpu.ec import files as ec_files
from seaweedfs_tpu.ec.locate import EcGeometry, locate
from seaweedfs_tpu.ops.coder import NumpyCoder
from seaweedfs_tpu.ops.native import NativeCoder
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.shell.commands import CommandEnv
from seaweedfs_tpu.storage import types as st
from seaweedfs_tpu.storage.needle_map import walk_idx_file
from seaweedfs_tpu.storage.volume import rebuild_idx_from_dat
from seaweedfs_tpu.utils.rpc import VOLUME_SERVICE, Stub

REPO = os.path.dirname(os.path.abspath(__file__))

# data scale per --size; the block geometry (1 GiB / 1 MiB) and the
# [32, d, 1 MiB] device batch are the store's defaults at every size
SIZES = {
    "full": {"limit_mb": 1024, "volumes": 2, "max_needle": 4 << 20},
    "small": {"limit_mb": 256, "volumes": 2, "max_needle": 4 << 20},
    "tiny": {"limit_mb": 8, "volumes": 2, "max_needle": 256 << 10},
}
MIN_NEEDLE = 1 << 10
# collection -> (d, p, shards lost per encoded volume before ec.rebuild)
COLLECTIONS = {"c14": (14, 2, (1, 0)), "c10": (10, 4, (1, 4))}
RACK_A, RACK_B = "chip", "host"


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Failed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def max_file_bytes(directory: str, want: int) -> int:
    """The largest file, up to `want` bytes, that this process and the
    daemons it starts may write under `directory`. RLIMIT_FSIZE is raised
    to its hard limit first; what remains (the hard limit, the file
    system's own cap) is found by writing one byte at the far end of a
    sparse file. Python ignores SIGXFSZ, so a refused write is EFBIG."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
    fd, path = tempfile.mkstemp(prefix="chip_smoke_probe_", dir=directory)
    try:
        def writable(n: int) -> bool:
            try:
                os.pwrite(fd, b"\0", n - 1)
            except OSError as e:
                if e.errno != errno.EFBIG:
                    raise
                return False
            finally:
                os.ftruncate(fd, 0)
            return True
        if writable(want):
            return want
        lo, hi = 0, want
        while hi - lo > 4096:
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if writable(mid) else (lo, mid)
        return lo
    finally:
        os.close(fd)
        os.remove(path)


def plan_size(name: str, directory: str) -> dict:
    """SIZES[name], cut to what this machine lets us write. A volume's .dat
    ends up to one needle past the limit, plus its record headers; where
    files may not grow that far the volume limit comes down and more
    volumes per collection make up the data (A takes 16). Disk in use
    peaks near 2.8x the data loaded (.dat copies for the reference plus
    both sets of shards); where the disk is short of 3.5x, the extra
    volumes go first."""
    size = dict(SIZES[name])
    limit = size["limit_mb"] << 20
    slack = size["max_needle"] + limit // 64
    cap = max_file_bytes(directory, limit + slack)
    cuts = []
    if cap < limit + slack:
        size["limit_mb"] = (cap - size["max_needle"]) * 64 // 65 >> 20
        check(size["limit_mb"] >= 1, f"files under {directory} may hold "
              f"{cap} bytes: no room for a volume")
        size["volumes"] = min(8, -(-limit * size["volumes"]
                                   // (size["limit_mb"] << 20)))
        cuts.append(f"files here may hold {cap} bytes: volumes of "
                    f"{size['limit_mb']} MiB instead of {limit >> 20}")
    free = shutil.disk_usage(directory).free

    def need() -> int:
        return (size["limit_mb"] << 20) * size["volumes"] \
            * len(COLLECTIONS) * 7 // 2
    if free < need():
        cuts.append(f"{free >> 20} MiB of disk free")
        while size["volumes"] > SIZES[name]["volumes"] and free < need():
            size["volumes"] -= 1
    check(free >= need(), f"{directory} has {free >> 20} MiB free, --size "
          f"{name} needs {need() >> 20} MiB")
    if cuts:
        size["cut"] = (f"{'; '.join(cuts)}: {size['volumes']} volumes of "
                       f"{size['limit_mb']} MiB per collection")
    return size


def vol_base(directory: str, collection: str, vid: int) -> str:
    """A volume's file stem, as DiskLocation.base_name lays it out."""
    return os.path.join(directory, f"{collection}_{vid}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------

class Cluster:
    """The three daemons, their logs, and the handles the phases need."""

    def __init__(self, root: str, args):
        self.root = root
        self.args = args
        self.procs: "dict[str, subprocess.Popen]" = {}
        self.logs: "dict[str, str]" = {}
        self.m_port, self.m_http = free_port(), free_port()
        self.a_port, self.a_grpc = free_port(), free_port()
        self.b_port, self.b_grpc = free_port(), free_port()
        self.a_dir = os.path.join(root, "A")
        self.b_dir = os.path.join(root, "B")
        for d in (self.a_dir, self.b_dir, os.path.join(root, "logs")):
            os.makedirs(d)
        self.master = f"127.0.0.1:{self.m_port}"
        self.a_url = f"127.0.0.1:{self.a_port}"
        self.b_url = f"127.0.0.1:{self.b_port}"
        self.mc: "MasterClient | None" = None

    def spawn(self, name: str, argv: "list[str]", env: dict) -> None:
        log_path = os.path.join(self.root, "logs", f"{name}.log")
        self.logs[name] = log_path
        with open(log_path, "wb") as log:
            self.procs[name] = subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu", *argv],
                cwd=REPO, env={**os.environ, **env},
                stdout=log, stderr=subprocess.STDOUT)

    def start(self) -> None:
        a = self.args
        size = a.plan
        self.spawn("master", ["master", "-port", str(self.m_port),
                              "-httpPort", str(self.m_http),
                              "-volumeSizeLimitMB", str(size["limit_mb"]),
                              "-maintenanceScripts", ""],
                   {"JAX_PLATFORMS": "cpu"})
        if a.allow_cpu:
            a_coder, a_platform = "jax", "cpu"
        else:
            a_coder = "mesh" if a.chips > 1 else "auto"
            a_platform = "tpu"
        self.spawn("A", ["volume", "-port", str(self.a_port),
                         "-grpcPort", str(self.a_grpc),
                         "-mserver", self.master, "-dir", self.a_dir,
                         "-max", "16", "-rack", RACK_A, "-coder", a_coder],
                   {"JAX_PLATFORMS": a_platform})
        self.spawn("B", ["volume", "-port", str(self.b_port),
                         "-grpcPort", str(self.b_grpc),
                         "-mserver", self.master, "-dir", self.b_dir,
                         "-max", "16", "-rack", RACK_B, "-coder", "native"],
                   {"JAX_PLATFORMS": "cpu"})
        # A imports jax and opens the chip before it listens
        self.wait(lambda: self.status(self.a_url) and self.status(self.b_url),
                  180, "volume servers answering /status")
        self.mc = MasterClient(self.master).start()

    def alive(self) -> None:
        for name, p in self.procs.items():
            rc = p.poll()
            if rc is not None:
                raise Failed(f"{name} exited with code {rc}:\n"
                             + self.log_tail(name))

    def log_tail(self, name: str, lines: int = 40) -> str:
        try:
            with open(self.logs[name], errors="replace") as f:
                return "".join(f.readlines()[-lines:])
        except OSError as e:
            return f"(no log: {e})"

    def wait(self, cond, timeout: float, what: str, interval: float = 0.3):
        deadline = time.monotonic() + timeout
        while True:
            self.alive()
            got = cond()
            if got:
                return got
            if time.monotonic() > deadline:
                raise Failed(f"timed out after {timeout:.0f}s waiting for "
                             f"{what}")
            time.sleep(interval)

    def status(self, url: str) -> "dict | None":
        # plain urllib: polling a server that is still starting must not
        # trip the client library's per-peer circuit breaker
        try:
            with urllib.request.urlopen(f"http://{url}/status",
                                        timeout=5) as r:
                return json.load(r)
        except OSError:  # not listening yet: poll again
            return None

    def events(self, url: str, etype: str) -> "list[dict]":
        r = http_util.get(f"http://{url}/debug/events",
                          params={"type": etype, "limit": 5000})
        check(r.ok, f"/debug/events on {url}: HTTP {r.status}")
        return r.json()["events"]

    def metric(self, url: str, name: str) -> float:
        r = http_util.get(f"http://{url}/metrics")
        check(r.ok, f"/metrics on {url}: HTTP {r.status}")
        total = 0.0
        for line in r.content.decode().splitlines():
            if line.startswith(name) and not line.startswith("#"):
                total += float(line.rsplit(" ", 1)[1])
        return total

    def shell(self, script: str, timeout: float = 3600,
              ) -> "tuple[int, str]":
        """Run shell verbs the way an operator's cron does: `shell -c`."""
        self.alive()
        r = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell",
             "-master", self.master, "-c", script],
            cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=timeout)
        return r.returncode, r.stdout + r.stderr

    def stub(self, grpc_port: int) -> Stub:
        return Stub(f"127.0.0.1:{grpc_port}", VOLUME_SERVICE)

    def stop(self) -> None:
        if self.mc is not None:
            self.mc.stop()
        for p in self.procs.values():
            if p.poll() is None:
                p.terminate()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)


# ---------------------------------------------------------------------------
# seeded data
# ---------------------------------------------------------------------------

class Needles:
    """Seeded payloads: needle i is a slice of one random pool, so any
    needle can be regenerated for comparison without keeping it."""

    def __init__(self, seed: int, max_needle: int):
        rng = np.random.default_rng(seed)
        self.pool = rng.integers(0, 256, (16 << 20) + max_needle,
                                 dtype=np.uint8).tobytes()
        self.rng = rng
        self.max_needle = max_needle
        self.plan: "list[tuple[int, int]]" = []  # (pool offset, size)
        self.fids: "list[str]" = []

    def next_payload(self) -> bytes:
        """Log-uniform sizes: many small needles, few large."""
        size = int(math.exp(self.rng.uniform(math.log(MIN_NEEDLE),
                                             math.log(self.max_needle))))
        off = int(self.rng.integers(0, 16 << 20))
        self.plan.append((off, size))
        return self.pool[off:off + size]

    def payload(self, i: int) -> bytes:
        off, size = self.plan[i]
        return self.pool[off:off + size]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(cl: Cluster, args) -> dict:
    sa, sb = cl.status(cl.a_url), cl.status(cl.b_url)
    say(f"A /status: coder={sa['coder']} platform={sa['platform']} "
        f"device_kind={sa['device_kind']} devices={sa['devices']}")
    say(f"B /status: coder={sb['coder']} platform={sb['platform']} "
        f"jax_loaded={sb['jax_loaded']}")
    if not args.allow_cpu:
        check(sa["platform"] == "tpu",
              f"A runs on platform={sa['platform']!r}, not tpu")
        check(sa["devices"] == args.chips,
              f"A sees {sa['devices']} devices, --chips {args.chips}")
        check(sa["coder"] == ("mesh" if args.chips > 1 else "jax"),
              f"A resolved coder {sa['coder']!r}")
    check(sb["coder"] in ("native", "numpy") and sb["platform"] is None
          and not sb["jax_loaded"],
          f"B must stay off JAX, /status says {sb}")
    return {"platform": sa["platform"], "kind": sa["device_kind"],
            "count": sa["devices"]}


def pinned_volume(cl: Cluster, collection: str, used: "set[int]") -> int:
    """A fresh writable volume of `collection` on A: the rack preference
    rides the plain HTTP assign and decides where growth lands; once the
    previous volume reports full the master grows the next one."""
    def fresh():
        r = http_util.get(f"http://127.0.0.1:{cl.m_http}/dir/assign",
                          params={"collection": collection, "rack": RACK_A})
        if not r.ok:
            return None
        vid = int(r.json()["fid"].split(",")[0])
        return vid if vid not in used else None
    return cl.wait(fresh, 120, f"a new writable {collection} volume")


def volume_sizes(cl: Cluster) -> "dict[int, tuple[str, int]]":
    """vid -> (server url, size) as the master's topology reports it."""
    env = CommandEnv(cl.master, mc=cl.mc)
    return {v.id: (srv["id"], v.size)
            for srv in env.collect_volume_servers()
            for disk in srv["disks"].values() for v in disk.volume_infos}


def phase_load(cl: Cluster, args, needles: Needles) -> dict:
    """Fill `volumes` volumes per collection to the size limit, on A:
    even volumes through leased /bulk frames, odd ones through
    assign -> PUT per needle."""
    size = args.plan
    limit = size["limit_mb"] << 20
    vols: "dict[str, list[int]]" = {}
    total = 0
    for collection in COLLECTIONS:
        vols[collection] = []
        for k in range(size["volumes"]):
            vid = pinned_volume(cl, collection,
                                {v for vs in vols.values() for v in vs})
            vols[collection].append(vid)
            alloc = FidLeaseAllocator(cl.mc, collection=collection)
            filled = 0
            while filled < limit:
                cl.alive()
                if k % 2 == 0:
                    batch, nbytes = [], 0
                    while nbytes < (32 << 20) and filled + nbytes < limit:
                        batch.append(needles.next_payload())
                        nbytes += len(batch[-1])
                    fids = [r.fid for r in operation.submit_batch(
                        cl.mc, batch, allocator=alloc)]
                else:
                    data = needles.next_payload()
                    nbytes = len(data)
                    fids = [operation.submit(cl.mc, data,
                                             collection=collection).fid]
                for fid in fids:
                    check(int(fid.split(",")[0]) == vid,
                          f"needle {fid} landed outside volume {vid}")
                needles.fids.extend(fids)
                filled += nbytes
            total += filled
            say(f"  {collection} volume {vid}: {filled >> 20} MiB via "
                f"{'/bulk frames' if k % 2 == 0 else 'assign->PUT'}")
    # ec.encode selects by the size the master knows: wait for heartbeats
    want = [v for vs in vols.values() for v in vs]

    def all_full():
        sizes = volume_sizes(cl)
        return all(sizes.get(v, ("", 0))[1] >= limit for v in want)
    cl.wait(all_full, 60, "the master to see every volume full")
    for vid, (node, _) in volume_sizes(cl).items():
        check(node == cl.a_url, f"volume {vid} is on {node}, not on A")
    say(f"  loaded {len(needles.fids)} needles, {total / 2**30:.2f} GiB, "
        f"sizes {MIN_NEEDLE} B..{size['max_needle']} B log-uniform")
    return {"vols": vols, "bytes": total}


def phase_copy(cl: Cluster, loaded: dict, ref_dir: str) -> None:
    """The reference's input: a copy of every .dat taken before the
    device touches it, and an .idx rebuilt from that copy alone."""
    for collection, vids in loaded["vols"].items():
        for vid in vids:
            ref = vol_base(ref_dir, collection, vid)
            shutil.copyfile(vol_base(cl.a_dir, collection, vid) + ".dat",
                            ref + ".dat")
            rebuild_idx_from_dat(ref + ".dat", ref + ".idx")


_SCRUB_LINE = re.compile(
    r"volume (\d+): (\d+) needles .* in ([0-9.]+)s \[([^\]]+)\]"
    r"(?:.*CORRUPT: \[([^\]]*)\])?")


def run_scrub(cl: Cluster, args) -> "tuple[int, dict]":
    """volume.scrub over every server; returns (rc, {vid: line fields})."""
    flag = "auto" if args.allow_cpu else "on"
    rc, out = cl.shell(f"volume.scrub -device {flag}", timeout=900)
    found = {}
    for line in out.splitlines():
        m = _SCRUB_LINE.search(line)
        if m:
            found[int(m.group(1))] = {
                "needles": int(m.group(2)), "s": float(m.group(3)),
                "mode": m.group(4),
                "corrupt": sorted(int(x.strip(" '"), 16) for x in
                                  (m.group(5) or "").split(",") if x.strip())}
        check("ERROR:" not in line and "scrub failed" not in line,
              f"scrub reported trouble: {line}")
    return rc, found


def phase_scrub(cl: Cluster, args, needles: Needles, loaded: dict,
                ref_dir: str) -> dict:
    want_mode = ("device", "xla-cpu") if args.allow_cpu else ("device",)
    vols = [v for vs in loaded["vols"].values() for v in vs]
    rc, clean = run_scrub(cl, args)
    check(rc == 0, f"clean scrub exited {rc}")
    check(sorted(clean) == sorted(vols), f"scrubbed {sorted(clean)}, "
          f"loaded {sorted(vols)}")
    for vid, r in clean.items():
        check(r["mode"] in want_mode, f"volume {vid} scrubbed in mode "
              f"[{r['mode']}], wanted {want_mode}")
        check(not r["corrupt"], f"clean volume {vid} reports {r['corrupt']}")
    check(sum(r["needles"] for r in clean.values()) == len(needles.fids),
          "scrub did not scan every needle loaded")
    # rot one needle on disk: that id, and only that one, is reported
    victim = int(needles.rng.integers(0, len(needles.fids)))
    while needles.plan[victim][1] < 64:
        victim = (victim + 1) % len(needles.fids)
    vid, key, _cookie = st.parse_file_id(needles.fids[victim])
    collection = next(c for c, vs in loaded["vols"].items() if vid in vs)
    base = vol_base(cl.a_dir, collection, vid)
    stored = [off for k, off, _ in walk_idx_file(
        vol_base(ref_dir, collection, vid) + ".idx") if k == key]
    check(stored, f"needle {key:x} not in the reference index")
    at = (st.stored_to_offset(stored[-1]) + st.NEEDLE_HEADER_SIZE + 4
          + needles.plan[victim][1] // 2)
    with open(base + ".dat", "r+b") as f:
        f.seek(at)
        orig = f.read(2)
        f.seek(at)
        f.write(bytes(b ^ 0xFF for b in orig))
    try:
        rc, rotten = run_scrub(cl, args)
    finally:
        with open(base + ".dat", "r+b") as f:
            f.seek(at)
            f.write(orig)
    reported = {(v, k) for v, r in rotten.items() for k in r["corrupt"]}
    check(reported == {(vid, key)},
          f"flipped needle {key:x} of volume {vid}, scrub reported "
          f"{sorted(reported)}")
    check(rc != 0, "scrub over a rotten needle exited 0")
    say(f"  {len(needles.fids)} needles clean in mode "
        f"[{clean[vols[0]]['mode']}]; flipped needle {key:x} of volume "
        f"{vid} caught, and only it")
    times = [clean[v]["s"] for v in vols]
    return {"first_volume_s": times[0],
            "later_volume_s": sorted(times[1:])[len(times[1:]) // 2]}


def shard_path(cl: Cluster, collection: str, vid: int, sid: int,
               ) -> "tuple[str, str] | None":
    """(holder, path) of a shard file, None if no server holds it."""
    for holder, d in (("A", cl.a_dir), ("B", cl.b_dir)):
        p = vol_base(d, collection, vid) + ec_files.shard_ext(sid)
        if os.path.exists(p):
            return holder, p
    return None


def phase_encode(cl: Cluster, args, loaded: dict) -> dict:
    out = {}
    for collection, (d, p, _) in COLLECTIONS.items():
        vids = loaded["vols"][collection]
        t0 = time.monotonic()
        rc, text = cl.shell(f"lock; ec.encode -collection {collection} "
                            f"-ecShards {d},{p}; unlock")
        wall = time.monotonic() - t0
        check(rc == 0, f"ec.encode {collection} exited {rc}:\n{text}")
        check(f"ec encoded {len(vids)} volumes" in text,
              f"ec.encode {collection} did not encode {len(vids)} "
              f"volumes:\n{text}")
        ev = [e["attrs"] for e in cl.events(cl.a_url, "ec.encode.finish")
              if sorted(e["attrs"].get("vids", [])) == sorted(vids)]
        check(len(ev) == 1, f"A journaled {len(ev)} ec.encode.finish "
              f"events for volumes {vids}")
        ev = ev[0]
        min_batches = 1 if args.size == "tiny" else 2
        check(ev["ok"] and ev["mode"] == "async"
              and ev["batches"] >= min_batches,
              f"ec.encode.finish on A: {ev}")
        for vid in vids:
            holders = [shard_path(cl, collection, vid, s)
                       for s in range(d + p)]
            check(all(holders), f"volume {vid}: shard files missing "
                  f"{[s for s, h in enumerate(holders) if not h]}")
            for holder_dir in {os.path.dirname(h[1]) for h in holders}:
                for ext in (".ecx", ".vif"):
                    path = vol_base(holder_dir, collection, vid) + ext
                    check(os.path.exists(path), f"missing {path}")
            check(not os.path.exists(
                vol_base(cl.a_dir, collection, vid) + ".dat"),
                f"source volume {vid} still on A after ec.encode")
        later = ((ev["dispatch_s"] - ev["first_dispatch_s"])
                 / max(1, ev["batches"] - 1))
        say(f"  {collection} RS({d},{p}): {len(vids)} volumes, "
            f"{ev['batches']} batches of [32,{d},1MiB] in mode="
            f"{ev['mode']}, verb {wall:.1f}s, pipeline {ev['wall_s']}s "
            f"(fill {ev['fill_s']} drain {ev['drain_block_s']} "
            f"write-block {ev['write_block_s']}); first dispatch "
            f"{ev['first_dispatch_s']}s, later {later:.3f}s")
        if "batch_bytes_by_device" in ev:
            say(f"  one input batch on the mesh, bytes per device: "
                f"{ev['batch_bytes_by_device']}")
        out[collection] = {"first_dispatch_s": ev["first_dispatch_s"],
                           "later_dispatch_s": round(later, 3),
                           "pipeline_s": ev["wall_s"],
                           "batch_bytes_by_device":
                               ev.get("batch_bytes_by_device")}
    return out


def phase_reference(cl: Cluster, args, loaded: dict, ref_dir: str,
                    seed: int) -> None:
    """Every shard of every volume, byte for byte, against the host
    encode of the .dat copy: NativeCoder, no JAX in this process."""
    rng = np.random.default_rng(seed + 1)
    for collection, (d, p, _) in COLLECTIONS.items():
        geo = EcGeometry(d, p)
        native, oracle = NativeCoder(d, p), NumpyCoder(d, p)
        for vid in loaded["vols"][collection]:
            ref = vol_base(ref_dir, collection, vid)
            dat = np.memmap(ref + ".dat", dtype=np.uint8, mode="r")
            for _ in range(4):  # the reference's own check, on samples
                width = 1 << 14
                at = int(rng.integers(0, dat.size - d * width))
                stripe = np.array(dat[at:at + d * width]).reshape(d, width)
                check(np.array_equal(native.encode(stripe),
                                     oracle.encode(stripe)),
                      "NativeCoder disagrees with NumpyCoder")
            del dat
            ec_encoder.encode_volume(ref + ".dat", ref, geo, native,
                                     idx_path=ref + ".idx")
            for sid in range(geo.n):
                _holder, path = shard_path(cl, collection, vid, sid)
                check(filecmp.cmp(path, ref + ec_files.shard_ext(sid),
                                  shallow=False),
                      f"{path} differs from the host reference")
            for holder_dir in (cl.a_dir, cl.b_dir):
                ecx = vol_base(holder_dir, collection, vid) + ".ecx"
                if os.path.exists(ecx):
                    check(filecmp.cmp(ecx, ref + ".ecx", shallow=False),
                          f"{ecx} differs from the host reference")
            os.remove(ref + ".dat")
        say(f"  {collection}: {len(loaded['vols'][collection])} volumes x "
            f"{d + p} shards byte-identical to the host encode")


def read_sample(cl: Cluster, needles: Needles, picks: "list[int]") -> None:
    for i in picks:
        got = operation.read(cl.mc, needles.fids[i])
        check(got == needles.payload(i),
              f"needle {needles.fids[i]} read back different bytes")


def needle_shards(geo: EcGeometry, dat_size: int, stored: int,
                  size: int) -> "set[int]":
    """Shard ids a needle's record touches."""
    return {iv.shard_and_offset(geo)[0] for iv in locate(
        geo, dat_size, st.stored_to_offset(stored),
        st.actual_record_size(size))}


def phase_degrade(cl: Cluster, args, needles: Needles, loaded: dict,
                  ref_dir: str, skip: "set[int]") -> dict:
    """Remove shards from B (so A keeps the most and hosts the rebuild),
    then GET needles whose bytes sat on a removed data shard, from A."""
    lost: "dict[int, tuple[str, list[int]]]" = {}
    first, later = [], []
    b_stub = cl.stub(cl.b_grpc)
    for collection, (d, p, losses) in COLLECTIONS.items():
        geo = EcGeometry(d, p)
        for vid, n_lost in zip(loaded["vols"][collection], losses):
            if not n_lost:
                continue
            on_b = [s for s in range(geo.n)
                    if shard_path(cl, collection, vid, s)[0] == "B"]
            sids = ([s for s in on_b if s < d]
                    + [s for s in on_b if s >= d])[:n_lost]
            check(len(sids) == n_lost and sids[0] < d,
                  f"volume {vid}: B holds {on_b}, cannot lose {n_lost}")
            b_stub.call("VolumeEcShardsUnmount",
                        vpb.VolumeEcShardsUnmountRequest(
                            volume_id=vid, shard_ids=sids),
                        vpb.VolumeEcShardsUnmountResponse)
            b_stub.call("VolumeEcShardsDelete",
                        vpb.VolumeEcShardsDeleteRequest(
                            volume_id=vid, collection=collection,
                            shard_ids=sids),
                        vpb.VolumeEcShardsDeleteResponse)
            check(all(shard_path(cl, collection, vid, s) is None
                      for s in sids), f"shards {sids} of {vid} not removed")
            lost[vid] = (collection, sids)
            # needles with bytes on a lost data shard, small to large
            ref = vol_base(ref_dir, collection, vid)
            dat_size = ec_files.read_vif(
                vol_base(cl.a_dir, collection, vid) + ".vif")["dat_size"]
            where = {k: (off, sz) for k, off, sz in
                     walk_idx_file(ref + ".idx")}
            hit = []
            for i, fid in enumerate(needles.fids):
                v, key, _ = st.parse_file_id(fid)
                if v == vid and i not in skip and needle_shards(
                        geo, dat_size, *where[key]) & set(sids):
                    hit.append(i)
            hit.sort(key=lambda i: needles.plan[i][1])
            check(len(hit) >= 3, f"volume {vid}: only {len(hit)} needles "
                  f"touch lost shards {sids}")
            picks = [hit[j * (len(hit) - 1) // 4] for j in range(5)]
            before = cl.metric(cl.a_url, "SeaweedFS_degraded_ec_reads_total")
            for n, i in enumerate(dict.fromkeys(picks)):
                t0 = time.monotonic()
                r = http_util.get(f"http://{cl.a_url}/{needles.fids[i]}")
                (first if n == 0 else later).append(time.monotonic() - t0)
                check(r.ok and r.content == needles.payload(i),
                      f"degraded GET {needles.fids[i]} from A: HTTP "
                      f"{r.status}, {len(r.content)} bytes")
            rebuilt = cl.metric(
                cl.a_url, "SeaweedFS_degraded_ec_reads_total") - before
            check(rebuilt >= len(set(picks)),
                  f"volume {vid}: {len(set(picks))} degraded GETs but A "
                  f"reconstructed {rebuilt:.0f} intervals")
            say(f"  {collection} volume {vid}: lost shards {sids}; "
                f"{len(set(picks))} degraded GETs of "
                f"{[needles.plan[i][1] for i in dict.fromkeys(picks)]} B "
                f"byte-identical, {rebuilt:.0f} intervals reconstructed "
                f"on A")
    return {"lost": lost, "first_get_s": round(max(first), 3),
            "later_get_s": round(sorted(later)[len(later) // 2], 3)}


def phase_rebuild(cl: Cluster, args, degraded: dict, ref_dir: str) -> None:
    rc, text = cl.shell("lock; ec.rebuild; unlock")
    check(rc == 0, f"ec.rebuild exited {rc}:\n{text}")
    finished = {e["attrs"]["vid"]: e["attrs"]
                for e in cl.events(cl.a_url, "ec.rebuild.finish")}
    for vid, (collection, sids) in degraded["lost"].items():
        ev = finished.get(vid)
        check(ev and ev["ok"] and ev["node"] == cl.a_url
              and sorted(ev["rebuilt_shard_ids"]) == sorted(sids),
              f"volume {vid}: A's ec.rebuild.finish says {ev}, lost {sids}")
        for sid in sids:
            path = vol_base(cl.a_dir, collection, vid) \
                + ec_files.shard_ext(sid)
            check(os.path.exists(path) and filecmp.cmp(
                path, vol_base(ref_dir, collection, vid)
                + ec_files.shard_ext(sid), shallow=False),
                f"rebuilt {path} differs from the host reference")
        say(f"  {collection} volume {vid}: shards {sids} rebuilt on A in "
            f"{ev['duration_ms'] / 1e3:.1f}s, byte-identical")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--size", choices=sorted(SIZES), default="full")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="debug on the CPU backend: relaxes the platform "
                         "assertions, nothing else")
    ap.add_argument("--dir", default=REPO,
                    help="where the data directory is made (and removed)")
    args = ap.parse_args()
    try:
        size = args.plan = plan_size(args.size, args.dir)
    except (Failed, OSError) as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    if "cut" in size:
        say(f"CUT: {size['cut']}")
    root = tempfile.mkdtemp(prefix="chip_smoke_", dir=args.dir)
    ref_dir = os.path.join(root, "ref")
    os.makedirs(ref_dir)
    cl = Cluster(root, args)
    walls: "dict[str, float]" = {}
    t_all = time.monotonic()

    def timed(name, fn, *a):
        say(f"== {name}")
        t0 = time.monotonic()
        res = fn(*a)
        cl.alive()
        walls[name] = round(time.monotonic() - t0, 1)
        return res

    try:
        timed("start", cl.start)
        device = timed("device", phase_device, cl, args)
        needles = Needles(args.seed, size["max_needle"])
        loaded = timed("load", phase_load, cl, args, needles)
        timed("copy", phase_copy, cl, loaded, ref_dir)
        scrub = timed("scrub", phase_scrub, cl, args, needles, loaded,
                      ref_dir)
        enc = timed("encode", phase_encode, cl, args, loaded)
        timed("reference", phase_reference, cl, args, loaded, ref_dir,
              args.seed)
        sample = [int(i) for i in needles.rng.choice(
            len(needles.fids), min(128, len(needles.fids)), replace=False)]
        timed("read", read_sample, cl, needles, sample)
        deg = timed("degraded-read", phase_degrade, cl, args, needles,
                    loaded, ref_dir, set(sample))
        timed("rebuild", phase_rebuild, cl, args, deg, ref_dir)
        timed("read-again", read_sample, cl, needles, sample[:32])
    except Exception as e:  # noqa: BLE001 — any failed phase fails the run
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        for name in cl.procs:
            print(f"--- last lines of {name}.log ---\n{cl.log_tail(name)}",
                  file=sys.stderr)
        return 1
    finally:
        cl.stop()
        shutil.rmtree(root, ignore_errors=True)
    say(f"size={args.size}: {loaded['bytes'] / 2**30:.2f} GiB in "
        f"{sum(len(v) for v in loaded['vols'].values())} volumes of "
        f"{size['limit_mb']} MiB, {len(needles.fids)} needles, seed "
        f"{args.seed}" + (f" (CUT: {size['cut']})" if "cut" in size else ""))
    say("wall seconds by phase: " + json.dumps(walls)
        + f" total {time.monotonic() - t_all:.1f}")
    say("first vs later (compile shows in the first): " + json.dumps({
        "scrub_volume_s": [scrub["first_volume_s"], scrub["later_volume_s"]],
        **{f"encode_{c}_dispatch_s": [v["first_dispatch_s"],
                                      v["later_dispatch_s"]]
           for c, v in enc.items()},
        "degraded_get_s": [deg["first_get_s"], deg["later_get_s"]]}))
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
