"""Traffic kind `open_loop_serve`: independent users reading and writing
small files on A, open loop; a scrub loop may run behind.

Set-up writes the configuration's `file_volumes` volumes of `needles`
(count x size) from the seed and mounts them on A. The window offers
Poisson arrivals at the fixed `rate_rps`: a share `get_share` of GETs,
uniform over the loaded files, the rest assign -> PUT of new files of the
same size. Every request is timed from the moment it was DUE, so a stall
charges everything queued behind it; how late the generator itself ran is
kept apart. All load comes from this one process: one event loop, a fixed
pool of keep-alive connections, no thread per request. With
`background_scrub` true one thread meanwhile repeats `volume.scrub -device
on` over all of A's volumes, back to back, which is what drives the device
in such a cell. With it false nothing else runs in the window, and the
device is idle: a traced run then scrubs one volume after the window
(`probe`), because the driver refuses a trace without a device operation.

Every GET's bytes are compared with the seeded payload; every
acknowledged PUT is read back after the window.
"""

from __future__ import annotations

import asyncio
import collections
import json
import sys
import threading

import numpy as np

from seaweedfs_tpu.client import http_util

from benchmark import data, ecutil, stats
from benchmark.cluster import RACK_A, check
from benchmark.kinds import scrub_sweep


class Conn(asyncio.Protocol):
    """One keep-alive HTTP/1.1 connection: one request in flight, the
    response handed to a callback as (status, body)."""

    def __init__(self, pool: "Pool"):
        self.pool = pool
        self.buf = bytearray()
        self.callback = None
        self.need = -1  # body bytes wanted once the head is parsed
        self.status = 0
        self.transport = None

    def connection_made(self, transport) -> None:
        self.transport = transport

    def send(self, request: bytes, callback) -> None:
        self.callback = callback
        self.transport.write(request)

    def data_received(self, chunk: bytes) -> None:
        self.buf += chunk
        if self.need < 0:
            end = self.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            head = bytes(self.buf[:end]).lower()
            self.status = int(head[9:12])
            at = head.find(b"content-length:")
            self.need = int(head[at + 15:].split(b"\r\n", 1)[0]) \
                if at >= 0 else 0
            del self.buf[:end + 4]
        if len(self.buf) >= self.need:
            body = bytes(self.buf[:self.need])
            del self.buf[:self.need]
            self.need = -1
            callback, self.callback = self.callback, None
            callback(self.status, body)
            self.pool.release(self)

    def connection_lost(self, exc) -> None:
        if self.callback is not None:  # died with a request in flight
            callback, self.callback = self.callback, None
            callback(0, b"")
        self.pool.lost(self)


class Pool:
    """Idle connections to one server, and the requests waiting for one."""

    def __init__(self, host: str, port: int, size: int):
        self.host, self.port, self.size = host, port, size
        self.idle: "collections.deque[Conn]" = collections.deque()
        self.waiting: collections.deque = collections.deque()

    async def open(self) -> None:
        loop = asyncio.get_running_loop()
        for _ in range(self.size):
            _, conn = await loop.create_connection(
                lambda: Conn(self), self.host, self.port)
            self.idle.append(conn)

    def submit(self, request: bytes, callback) -> None:
        if self.idle:
            self.idle.popleft().send(request, callback)
        else:
            self.waiting.append((request, callback))

    def release(self, conn: Conn) -> None:
        if self.waiting:
            conn.send(*self.waiting.popleft())
        else:
            self.idle.append(conn)

    def lost(self, conn: Conn) -> None:
        if conn in self.idle:
            self.idle.remove(conn)

    def close(self) -> None:
        for conn in self.idle:
            conn.transport.close()


def schedule(rng: np.random.Generator, rate: float, seconds: float,
             get_share: float, files: int) -> dict:
    """The window's arrivals, all from the seed: due times (Poisson),
    which are GETs, and the file each GET reads."""
    n = max(1, int(rate * seconds))
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    due = due[due < seconds]
    n = len(due)
    return {"due": due, "is_get": rng.random(n) < get_share,
            "file": rng.integers(0, files, n)}


class Load:
    """One window of open-loop load and its bookkeeping."""

    def __init__(self, run, plan: dict):
        cl, cfg, s = run.cluster, run.config, run.samples
        self.plan = plan
        n = len(plan["due"])
        self.sent = np.full(n, np.nan)   # seconds from the window's start
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, dtype=bool)
        self.puts: "list[tuple[str, int]]" = []  # acknowledged (fid, off)
        self.pool_bytes = s["pool"]
        self.size = int(cfg["needles"]["size"])
        self.files = s["files"]  # (fid path bytes, payload offset)
        self.put_offs = run.rng.integers(0, data.POOL_BYTES, n)
        self.a = Pool("127.0.0.1", cl.a_port, int(run.traffic["connections"]))
        self.m = Pool("127.0.0.1", cl.m_http, 8)
        self.assign = (f"GET /dir/assign?collection={cfg['collection']}"
                       f"&rack={RACK_A} HTTP/1.1\r\nHost: m\r\n\r\n").encode()
        self.left = n
        self.finished: "asyncio.Event | None" = None
        self.t0 = 0.0
        self.loop = None

    def _finish(self, i: int, good: bool) -> None:
        self.done[i] = self.loop.time() - self.t0
        self.ok[i] = good
        self.left -= 1
        if not self.left:
            self.finished.set()

    def _get(self, i: int) -> None:
        path, off = self.files[int(self.plan["file"][i])]
        want = self.pool_bytes[off:off + self.size]
        self.a.submit(b"GET /" + path + b" HTTP/1.1\r\nHost: a\r\n\r\n",
                      lambda status, body: self._finish(
                          i, status == 200 and body == want))

    def _put(self, i: int) -> None:
        off = int(self.put_offs[i])
        payload = self.pool_bytes[off:off + self.size]

        def assigned(status: int, body: bytes) -> None:
            try:
                fid = json.loads(body)["fid"] if status == 200 else ""
            except (ValueError, KeyError):
                fid = ""
            if not fid:
                self._finish(i, False)
                return

            def stored(status: int, _body: bytes) -> None:
                if status in (200, 201):
                    self.puts.append((fid, off))
                self._finish(i, status in (200, 201))
            self.a.submit(
                (f"POST /{fid} HTTP/1.1\r\nHost: a\r\nContent-Type: "
                 f"application/octet-stream\r\nContent-Length: "
                 f"{len(payload)}\r\n\r\n").encode() + payload, stored)
        self.m.submit(self.assign, assigned)

    async def offer(self) -> None:
        self.loop = asyncio.get_running_loop()
        self.finished = asyncio.Event()
        await self.a.open()
        await self.m.open()
        due, is_get = self.plan["due"], self.plan["is_get"]
        self.t0 = self.loop.time()
        i, n = 0, len(due)
        while i < n:
            now = self.loop.time() - self.t0
            while i < n and due[i] <= now:
                self.sent[i] = now
                (self._get if is_get[i] else self._put)(i)
                i += 1
            if i < n:
                await asyncio.sleep(max(0.0, due[i] - (self.loop.time()
                                                       - self.t0)))
        # one in flight at the window's end finishes and counts
        try:
            await asyncio.wait_for(self.finished.wait(), 60)
        finally:
            self.a.close()
            self.m.close()


def generate(run) -> None:
    cfg, s = run.config, run.samples
    vids = list(range(1, int(cfg["file_volumes"]) + 1))
    s["pool"] = data.pool(run.seed, int(cfg["needles"]["size"]))
    s["group"] = data.write_volumes(run.stage, cfg["collection"], vids,
                                    run.seed, cfg["needles"])
    s["files"] = [(m.fid(i).encode(), int(m.offs[i]))
                  for m in s["group"] for i in range(len(m.keys))]
    check(len(s["files"]) == int(cfg["files"]),
          f"{len(s['files'])} files written, the configuration says "
          f"{cfg['files']}")


def _offer(run, seconds: float, rate: float) -> Load:
    tr = run.traffic
    plan = schedule(run.rng, rate, seconds, float(tr["get_share"]),
                    len(run.samples["files"]))
    load = Load(run, plan)
    asyncio.run(load.offer())
    return load


def install(run) -> None:
    """Warm: a second of the same mix at a tenth of the rate (opens
    volumes for writing, fills the client's pools) and, where a scrub
    loop will run, one sweep (loads the file size's one CRC program)."""
    for m in run.samples["group"]:
        ecutil.place(run.cluster, run.stage, m, m.vid)
    load = _offer(run, 1.0, float(run.traffic["rate_rps"]) / 10)
    check(load.ok.all(), "warm-up requests failed")
    if run.traffic["background_scrub"]:
        scrub_sweep.sweep(run)


def _scrub_loop(run, stop: threading.Event, errors: list) -> None:
    try:
        while not stop.is_set():
            op = scrub_sweep.sweep(run)
            if op["reported"] or op["rc"] != 0:
                errors.append(f"background scrub reported {op['reported']}")
            run.op_done({**op, "label": "scrub", "needles": sum(
                v["needles"] for v in op["volumes"].values())})
    except Exception as e:  # noqa: BLE001 — the main thread reports it
        errors.append(f"{type(e).__name__}: {e}")


def run(run) -> dict:
    tr, s = run.traffic, run.samples
    stop, errors = threading.Event(), []
    bg = None
    if tr["background_scrub"]:
        bg = threading.Thread(target=_scrub_loop, args=(run, stop, errors),
                              name="bg-scrub")
        bg.start()
    try:
        with run.phase("requests"):
            load = _offer(run, run.seconds, float(tr["rate_rps"]))
    finally:
        stop.set()
        if bg is not None:
            bg.join(timeout=300)
    check(not errors, "; ".join(errors))
    plan = load.plan
    lat = np.array(stats.due_latencies_ms(plan["due"], load.done, load.ok))
    worst = run.seconds * 1e3  # stands for "over any limit" in the line
    lat = np.where(np.isfinite(lat), lat, worst)
    gets = plan["is_get"]
    s["load"] = load
    s["late_ms"] = (load.sent - plan["due"]) * 1e3
    s["put_ms"] = lat[~gets]
    s["get_ms"] = lat[gets]
    failed = int((~load.ok).sum())
    get_ms = s["get_ms"].tolist()
    p50, p95, p99 = (stats.percentile(get_ms, q) for q in (50, 95, 99))
    p99_1s = stats.sliced_percentile(plan["due"][gets].tolist(), get_ms, 99)
    print(f"[open_loop_serve] offered {tr['rate_rps']}/s for "
          f"{run.seconds:.0f}s: {len(lat)} requests, last done at "
          f"{np.nanmax(load.done):.2f}s; GET p50 {p50:.2f} p95 {p95:.2f} "
          f"p99 {p99:.2f} (a second's p99, median {p99_1s:.2f}) ms, PUT p99 "
          f"{stats.percentile(lat[~gets].tolist(), 99):.2f} ms, generator "
          f"late p99 {stats.percentile(s['late_ms'].tolist(), 99):.2f} ms",
          file=sys.stderr, flush=True)
    if failed:
        print(f"[open_loop_serve] {failed} of {len(lat)} requests failed or "
              f"returned wrong bytes", file=sys.stderr, flush=True)
    # the harness reports those BENCHMARK.json lists end to end for the
    # cell; the per-layer readers take the rest from `samples`
    return {"attempted": len(lat), "failed": failed,
            "metrics": {"get_p50_ms": p50, "get_p95_ms": p95,
                        "get_p99_ms": p99, "get_p99_1s_ms": p99_1s}}


def probe(run) -> None:
    """Traced runs only, after the window: where no scrub loop ran, one
    `volume.scrub -device on` of the first volume, so that the trace
    holds a device operation."""
    if run.traffic["background_scrub"]:
        return
    op = scrub_sweep.sweep(run, run.samples["group"][0].vid)
    check(op["rc"] == 0 and not op["reported"],
          f"the probe scrub reported {sorted(op['reported'])}")
    check(run.rehearsal or all(v["mode"] == "device"
                               for v in op["volumes"].values()),
          f"the probe scrub did not run on the device: {op['volumes']}")


def verify(run) -> bool:
    """Every acknowledged PUT, read back byte for byte."""
    load = run.samples["load"]
    for fid, off in load.puts:
        r = http_util.get(f"http://{run.cluster.a_url}/{fid}")
        if not r.ok or r.content != load.pool_bytes[off:off + load.size]:
            print(f"[open_loop_serve] PUT {fid} read back HTTP {r.status}, "
                  f"{len(r.content)} bytes", file=sys.stderr, flush=True)
            return False
    return True
