"""Traffic kind `degraded_get`: readers GET needles of EC volumes two of
whose shards are lost, open loop.

Set-up writes the configuration's `ec_volumes` full volumes from the seed,
seals them at `-ecShards d,p` with one `ec.encode` and checks the seal
(as `repair_ring` does). Then `holders_down` of B's data shards of each
volume, drawn from the seed, are lost: a seeded sample of the intervals
that fall on them is hashed, the shards are unmounted and deleted on B,
and the set-up waits until the master no longer lists them. Every GET
that reads such an interval now has A rebuild it from d survivors: its
own shards by `preadv`, the rest fetched from B.

The window offers Poisson arrivals at the fixed `rate_rps`, all GETs to
A over `connections` keep-alive connections (`open_loop_serve`'s pool and
schedule), keys Zipf(`zipf_constant`) over every needle of the volumes
with the ranks shuffled from the seed. A GET is timed from the moment it
was due; one not answered `deadline_s` after that counts as failed, and
the window's tail is waited for `drain_s` at most, so that a program that
compiles on every degraded GET ends its run instead of hanging.

`correct` needs every GET's bytes equal to the seeded payload, A's
`SeaweedFS_degraded_ec_reads_total` to have moved by at least the lost
intervals the completed GETs read (from the stripe layout), and, for the
hashed sample, the plain reference's reconstruct of the survivors, read
from the shard files, to equal the bytes the lost shard held.
"""

from __future__ import annotations

import asyncio
import hashlib
import re
import sys

import numpy as np

from seaweedfs_tpu.ec.locate import EcGeometry, locate
from seaweedfs_tpu.pb import master_pb2 as mpb
from seaweedfs_tpu.storage.needle_map import walk_idx_file
from seaweedfs_tpu.storage.types import (record_size_from_header,
                                         stored_to_offset)
from seaweedfs_tpu.utils.rpc import MASTER_SERVICE, Stub

from benchmark import data, ecutil, reference, stats
from benchmark.cluster import check
from benchmark.kinds import scrub_sweep
from benchmark.kinds.open_loop_serve import Pool, schedule
from benchmark.layer_metrics._shared import prom_delta

DEGRADED = "SeaweedFS_degraded_ec_reads_total"
RECONSTRUCT = "SeaweedFS_volumeServer_ec_reconstruct_seconds"
POOL_WAIT = "SeaweedFS_pool_queue_wait_seconds"
PROBE_VID = 90  # a small plain volume on A, scrubbed by the probe only


def counter(text: str, name: str) -> float:
    """Sum of the samples of `name` in a /metrics text, labelled or not."""
    return sum(float(v) for v in re.findall(
        rf"^{re.escape(name)}(?:{{[^}}]*}})? (\S+)$", text, re.M))


def generate(run) -> None:
    """The volumes, and every needle's intervals in the stripe layout:
    `(shard, offset in the shard file, length)`."""
    cfg, s = run.config, run.samples
    vids = list(range(1, int(cfg["ec_volumes"]) + 1))
    s["pool"] = data.pool(run.seed, int(cfg["needles"]["max"]))
    s["group"] = data.write_volumes(run.stage, cfg["collection"], vids,
                                    run.seed, cfg["needles"])
    geo = EcGeometry(int(cfg["DataShardsCount"]),
                     int(cfg["ParityShardsCount"]),
                     int(cfg["ErasureCodingLargeBlockSize"]),
                     int(cfg["ErasureCodingSmallBlockSize"]))
    s["needles"] = []  # (manifest, index, fid path, [(sid, off, len)])
    for m in s["group"]:
        at = {k: (stored_to_offset(off), size) for k, off, size in
              walk_idx_file(ecutil.base(run.stage, m.collection, m.vid)
                            + ".idx")}
        for i, key in enumerate(m.keys):
            off, size = at[int(key)]
            ivs = [(*iv.shard_and_offset(geo), iv.size) for iv in
                   locate(geo, m.dat_bytes, off, record_size_from_header(
                       size))]
            s["needles"].append((m, i, m.fid(i).encode(), ivs))
    s["probe"] = data.write_volume(
        run.stage, "probe", PROBE_VID, run.seed,
        {**cfg["needles"], "fill_bytes": int(run.traffic["probe_fill_bytes"])})


def _lookup(run, vid: int) -> "set[int]":
    """The shards of `vid` the master lists a holder for."""
    resp = Stub(run.cluster.master, MASTER_SERVICE).call(
        "LookupEcVolume", mpb.LookupEcVolumeRequest(volume_id=vid),
        mpb.LookupEcVolumeResponse)
    return {e.shard_id for e in resp.shard_id_locations if e.locations}


class Reads:
    """One window of open-loop GETs and its bookkeeping."""

    def __init__(self, run, due: np.ndarray, which: np.ndarray,
                 connections: int = 0):
        tr, s = run.traffic, run.samples
        self.due, self.which = due, which
        n = len(due)
        self.sent = np.full(n, np.nan)   # seconds from the window's start
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, dtype=bool)
        self.needles, self.pool_bytes = s["needles"], s["pool"]
        self.deadline = float(tr["deadline_s"])
        self.drain = float(tr["drain_s"])
        self.a = Pool("127.0.0.1", run.cluster.a_port,
                      connections or int(tr["connections"]))
        self.left = n
        self.finished: "asyncio.Event | None" = None
        self.t0 = 0.0
        self.loop = None

    def _finish(self, i: int, good: bool) -> None:
        if not np.isnan(self.done[i]):
            return
        self.done[i] = self.loop.time() - self.t0
        self.ok[i] = good and self.done[i] - self.due[i] <= self.deadline
        self.left -= 1
        if not self.left:
            self.finished.set()

    def _get(self, i: int) -> None:
        m, k, path, _ = self.needles[int(self.which[i])]
        o, size = int(m.offs[k]), int(m.sizes[k])
        want = self.pool_bytes[o:o + size]
        self.a.submit(b"GET /" + path + b" HTTP/1.1\r\nHost: a\r\n\r\n",
                      lambda status, body: self._finish(
                          i, status == 200 and body == want))

    async def offer(self) -> None:
        self.loop = asyncio.get_running_loop()
        self.finished = asyncio.Event()
        await self.a.open()
        conns = list(self.a.idle)
        self.t0 = self.loop.time()
        i, n = 0, len(self.due)
        while i < n:
            now = self.loop.time() - self.t0
            while i < n and self.due[i] <= now:
                self.sent[i] = now
                self._get(i)
                i += 1
            if i < n:
                await asyncio.sleep(max(0.0, self.due[i] - (
                    self.loop.time() - self.t0)))
        # one in flight at the window's end finishes and counts; one
        # still unanswered `drain_s` later has failed
        try:
            if self.left:
                await asyncio.wait_for(self.finished.wait(), self.drain)
        except asyncio.TimeoutError:
            pass
        finally:
            for conn in conns:  # busy ones too: their GETs have failed
                conn.transport.close()


def _plan(run, seconds: float, rate: float) -> "tuple[np.ndarray, ...]":
    """Due times (Poisson) and the needle each GET reads: Zipf over all
    needles, rank r drawn with weight 1 / r^s, ranks shuffled."""
    n = len(run.samples["needles"])
    due = schedule(run.rng, rate, seconds, 1.0, n)["due"]
    weights = 1.0 / np.arange(1, n + 1) ** float(
        run.traffic["zipf_constant"])
    cdf = np.cumsum(weights) / weights.sum()
    ranks = np.minimum(np.searchsorted(cdf, run.rng.random(len(due))),
                       n - 1)
    return due, run.samples["rank_to_needle"][ranks]


def _offer(run, seconds: float, rate: float) -> Reads:
    reads = Reads(run, *_plan(run, seconds, rate))
    asyncio.run(reads.offer())
    return reads


def _lost_intervals(run, j: int) -> "list[tuple[int, int, int]]":
    m, _, _, ivs = run.samples["needles"][j]
    lost = run.samples["lost"][m.vid]
    return [iv for iv in ivs if iv[0] in lost]


def _shard_file(run, vid: int, sid: int) -> str:
    found = ecutil.shard_path(run.cluster, run.config["collection"], vid,
                              sid)
    check(found is not None, f"volume {vid}: no holder has shard {sid}")
    return found[1]


def _read(path: str, off: int, length: int) -> bytes:
    with open(path, "rb") as f:
        f.seek(off)
        return f.read(length)


def install(run) -> None:
    cfg, tr, cl, s = run.config, run.traffic, run.cluster, run.samples
    d, p = cfg["DataShardsCount"], cfg["ParityShardsCount"]
    coll = cfg["collection"]
    for m in s["group"] + [s["probe"]]:
        ecutil.place(cl, run.stage, m, m.vid)
    rc, text = cl.shell(f"lock; ec.encode -collection {coll} -fullPercent 0 "
                        f"-ecShards {d},{p}; unlock")
    check(rc == 0 and f"ec encoded {len(s['group'])} volumes" in text,
          f"set-up ec.encode exited {rc}:\n{text[-2000:]}")
    for m in s["group"]:
        wrong = ecutil.check_sealed(cl, run.stage, m, m.vid, d, p, run.rng,
                                    s["pool"], int(tr["check_rows"]),
                                    int(tr["check_gets"]))
        check(wrong is None, f"set-up seal: {wrong}")
    # the holders down: B's data shards of each volume, drawn
    s["lost"] = {}
    for m in s["group"]:
        on_b = [sid for sid in ecutil.shards_on(cl, "B", coll, m.vid, d + p)
                if sid < d]
        check(len(on_b) >= int(cfg["holders_down"]),
              f"volume {m.vid}: B holds data shards {on_b}")
        s["lost"][m.vid] = sorted(int(x) for x in run.rng.choice(
            on_b, int(cfg["holders_down"]), replace=False))
    degraded = [j for j in range(len(s["needles"]))
                if _lost_intervals(run, j)]
    check(degraded, "no needle reads a lost shard")
    # the sample the reference rebuilds after the window, hashed now
    picks = run.rng.choice(len(degraded), min(len(degraded),
                                              int(tr["check_intervals"])),
                           replace=False)
    s["hashed"] = []
    for j in (degraded[int(x)] for x in picks):
        m = s["needles"][j][0]
        sid, off, length = _lost_intervals(run, j)[0]
        s["hashed"].append((m.vid, sid, off, length, hashlib.sha256(
            _read(_shard_file(run, m.vid, sid), off, length)).hexdigest()))
    for m in s["group"]:
        cl.drop_shards("B", coll, m.vid, s["lost"][m.vid])
    cl.wait(lambda: all(not _lookup(run, m.vid) & set(s["lost"][m.vid])
                        for m in s["group"]), 60,
            "the master to stop listing the lost shards")
    print("[degraded_get] lost " + "; ".join(
        f"{vid}: {sids}" for vid, sids in s["lost"].items()) + f"; "
        f"{len(degraded)} of {len(s['needles'])} needles read one",
        file=sys.stderr, flush=True)
    s["rank_to_needle"] = run.rng.permutation(len(s["needles"]))
    # warm: one degraded GET for each power-of-two class of a lost
    # interval's length, one at a time, so that whatever a program builds
    # per length class is built here (the first of each volume also
    # refreshes A's shard locations); then a second of the mix at a tenth
    # of the rate
    by_class = {}
    for j in degraded:
        for _, _, length in _lost_intervals(run, j):
            by_class.setdefault((length - 1).bit_length(), j)
    for j in sorted(set(by_class.values())):
        warm = Reads(run, np.zeros(1), np.array([j]), connections=1)
        warm.deadline = warm.drain = float(tr["warm_deadline_s"])
        asyncio.run(warm.offer())
        check(warm.ok.all(), f"the warm-up GET of {warm.needles[j][2]} "
              f"failed")
    load = _offer(run, 1.0, float(tr["rate_rps"]) / 10)
    check(load.ok.all(), "warm-up requests failed")


def _p99(xs: np.ndarray) -> str:
    return f"{stats.percentile(xs.tolist(), 99):.2f}" if len(xs) else "-"


def run(run) -> dict:
    tr, s = run.traffic, run.samples
    with run.phase("requests"):
        reads = _offer(run, run.seconds, float(tr["rate_rps"]))
    lat = np.array(stats.due_latencies_ms(reads.due, reads.done, reads.ok))
    worst = run.seconds * 1e3  # stands for "over any limit" in the line
    lat = np.where(np.isfinite(lat), lat, worst)
    s["late_ms"] = (reads.sent - reads.due) * 1e3
    s["get_ms"] = lat
    s["completed"] = int(reads.ok.sum())
    lost = np.array([len(_lost_intervals(run, int(j))) for j in reads.which])
    s["lost_read"] = int(lost[reads.ok].sum())
    hit = lost > 0
    big = np.array([m.sizes[k] > 1 << 20 for m, k, _, _ in
                    (s["needles"][int(j)] for j in reads.which)], dtype=bool)
    failed = int((~reads.ok).sum())
    get_ms = lat.tolist()
    p50, p95, p99 = (stats.percentile(get_ms, q) for q in (50, 95, 99))
    p99_1s = stats.sliced_percentile(reads.due.tolist(), get_ms, 99)
    print(f"[degraded_get] offered {tr['rate_rps']}/s for "
          f"{run.seconds:.0f}s: {len(lat)} GETs ({int(hit.sum())} read "
          f"a lost shard), last done at {np.nanmax(reads.done):.2f}s; p50 "
          f"{p50:.2f} p95 {p95:.2f} p99 {p99:.2f} (a second's p99, median "
          f"{p99_1s:.2f}) ms, generator late p99 "
          f"{stats.percentile(s['late_ms'].tolist(), 99):.2f} ms; p99 of "
          f"the GETs that read a lost shard {_p99(lat[hit])}, of the others "
          f"{_p99(lat[~hit])} ms; {int(big.sum())} GETs of needles over "
          f"1 MiB ({int((big & hit).sum())} of them degraded)",
          file=sys.stderr, flush=True)
    # the Zipf head the seed drew: what a second's slowest GET reads
    head = []
    for j in s["rank_to_needle"][:5]:
        m, k, _, _ = s["needles"][int(j)]
        at = reads.which == j
        head.append(f"{int(m.sizes[k]) / 1024:.0f} KiB, "
                    f"{len(_lost_intervals(run, int(j)))} lost, "
                    f"{100 * at.mean():.1f}% of GETs, p50 "
                    + (f"{stats.percentile(lat[at].tolist(), 50):.2f} ms"
                       if at.any() else "-"))
    print("[degraded_get] ranks 1-5: " + "; ".join(head), file=sys.stderr,
          flush=True)
    if failed:
        print(f"[degraded_get] {failed} of {len(lat)} GETs failed, were late "
              f"by over {tr['deadline_s']} s or returned wrong bytes",
              file=sys.stderr, flush=True)
    return {"attempted": len(lat), "failed": failed,
            "metrics": {"get_p50_ms": p50, "get_p95_ms": p95,
                        "get_p99_ms": p99, "get_p99_1s_ms": p99_1s}}


def probe(run) -> None:
    """Traced runs only, after the window: where the decodes ran on the
    host, the trace may hold no device operation, and a traced run must
    hold one; one `volume.scrub -device on` of the small plain volume
    puts the CRC program on the device."""
    op = scrub_sweep.sweep(run, PROBE_VID)
    check(op["rc"] == 0 and not op["reported"],
          f"the probe scrub reported {sorted(op['reported'])}")
    check(run.rehearsal or all(v["mode"] == "device"
                               for v in op["volumes"].values()),
          f"the probe scrub did not run on the device: {op['volumes']}")


def _account(run) -> None:
    """One line on where the window's reconstructs spent their time, from
    A's /metrics: intervals rebuilt a 100 completed GETs, the gather's
    share of gather + decode, the mean decode, where the decodes ran, and
    the mean wait for a thread of the `ec_read` pool every gather's
    fetches run on. Absent families (a program without the account) read
    as nothing."""
    def mean_us(name, **labels):
        count = prom_delta(run, name + "_count", **labels)
        return 1e6 * prom_delta(run, name + "_sum", **labels) / count \
            if count else None

    gather = prom_delta(run, RECONSTRUCT + "_sum", stage="gather")
    decode = prom_delta(run, RECONSTRUCT + "_sum", stage="decode")
    where = {w: prom_delta(run, RECONSTRUCT + "_count", stage="decode",
                           where=w) for w in ("host", "device")}
    done = run.samples["completed"]
    print(f"[degraded_get] account: "
          f"{100 * run.samples['reconstructed'] / max(done, 1):.2f} "
          f"intervals rebuilt a 100 GETs; gather share "
          + (f"{100 * gather / (gather + decode):.2f}%"
             if gather + decode else "-")
          + f"; decode {mean_us(RECONSTRUCT, stage='decode') or 0:.1f} us "
          f"(host {where['host']:.0f}, device {where['device']:.0f}); "
          f"gather {mean_us(RECONSTRUCT, stage='gather') or 0:.1f} us; "
          f"ec_read pool wait {mean_us(POOL_WAIT, pool='ec_read') or 0:.1f} "
          f"us", file=sys.stderr, flush=True)


def verify(run) -> bool:
    """The journal of lost intervals, and the reference's rebuild of the
    hashed sample from the survivors on disk."""
    cfg, s = run.config, run.samples
    d, p = cfg["DataShardsCount"], cfg["ParityShardsCount"]
    moved = s["reconstructed"] = (counter(run.metrics1, DEGRADED)
                                  - counter(run.metrics0, DEGRADED))
    _account(run)
    if moved < s["lost_read"]:
        print(f"[degraded_get] {DEGRADED} moved by {moved:.0f}, the "
              f"completed GETs read {s['lost_read']} lost intervals",
              file=sys.stderr, flush=True)
        return False
    for vid, sid, off, length, digest in s["hashed"]:
        present = [x for x in range(d + p) if x not in s["lost"][vid]][:d]
        survivors = np.stack([np.frombuffer(_read(
            _shard_file(run, vid, x), off, length), dtype=np.uint8)
            for x in present])
        got = reference.reconstruct(survivors, present, [sid], d, p)[0]
        if hashlib.sha256(got.tobytes()).hexdigest() != digest:
            print(f"[degraded_get] volume {vid} shard {sid} at {off} "
                  f"(+{length}): the reference's rebuild differs from the "
                  f"lost bytes", file=sys.stderr, flush=True)
            return False
    return True
