"""The daemons a cell runs against, as separate `python -m seaweedfs_tpu`
processes (copied from chip_smoke.py's `Cluster`, which stays the
program's own smoke): a master with no maintenance cron, volume server
**A** which owns the chip (`-coder auto`, `JAX_PLATFORMS=tpu`, started
through `launch_a.py`) and volume server **B** which owns none (`-coder
native`). The parent never imports jax: it learns the device from A's
`GET /status`.
"""

from __future__ import annotations

import json
import os
import random
import socket
import subprocess
import sys
import time
import urllib.request

from seaweedfs_tpu.client import http_util
from seaweedfs_tpu.client.master_client import MasterClient
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.utils.rpc import VOLUME_SERVICE, Stub

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RACK_A, RACK_B = "chip", "host"


class Failed(Exception):
    """The run cannot produce a result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def compile_cache_dir() -> str:
    """Where A's persistent compile cache lives: as `ops/device.py` has
    it — `JAX_COMPILATION_CACHE_DIR` if set, else `<checkout>/.jax_cache`."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))


def free_port() -> int:
    """A port nothing listens on, below the range outgoing connections
    take theirs from: a daemon binds seconds after this call (A imports
    jax first), and meanwhile only another listener could take it."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            top = int(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        top = 32768
    while True:
        port = random.randrange(min(20000, top - 1000), top)
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
            return port


class Cluster:
    def __init__(self, root: str, limit_mb: int, rehearsal: bool = False):
        self.root = root
        self.limit_mb = limit_mb
        self.rehearsal = rehearsal
        self.procs: "dict[str, subprocess.Popen]" = {}
        self.logs: "dict[str, str]" = {}
        self.m_port, self.m_http = free_port(), free_port()
        self.a_port, self.a_grpc = free_port(), free_port()
        self.b_port, self.b_grpc = free_port(), free_port()
        self.a_dir = os.path.join(root, "A")
        self.b_dir = os.path.join(root, "B")
        self.ctl_dir = os.path.join(root, "ctl")
        for d in (self.a_dir, self.b_dir, self.ctl_dir,
                  os.path.join(root, "logs")):
            os.makedirs(d)
        self.master = f"127.0.0.1:{self.m_port}"
        self.a_url = f"127.0.0.1:{self.a_port}"
        self.b_url = f"127.0.0.1:{self.b_port}"
        self.mc: "MasterClient | None" = None
        self._sent = 0

    # -- processes ---------------------------------------------------------
    def _spawn(self, name: str, argv: "list[str]", env: dict,
               stdin=subprocess.DEVNULL) -> None:
        log_path = os.path.join(self.root, "logs", f"{name}.log")
        self.logs[name] = log_path
        with open(log_path, "wb") as log:
            self.procs[name] = subprocess.Popen(
                [sys.executable, "-m", *argv], cwd=CHECKOUT,
                env={**os.environ, **env}, stdin=stdin, stdout=log,
                stderr=subprocess.STDOUT)

    def spawn(self) -> None:
        """Start the three daemons; `wait_ready` waits for them."""
        self._spawn("master", ["seaweedfs_tpu", "master",
                               "-port", str(self.m_port),
                               "-httpPort", str(self.m_http),
                               "-volumeSizeLimitMB", str(self.limit_mb),
                               "-maintenanceScripts", ""],
                    {"JAX_PLATFORMS": "cpu"})
        # the rehearsal (tests only) computes on the CPU it is told to use
        # (and on the host coder: the CPU einsum at the real batch shape
        # takes seconds and gigabytes a call)
        a_coder, a_platform = (("native", "cpu") if self.rehearsal
                               else ("auto", "tpu"))
        self._spawn("A", ["benchmark.launch_a", self.ctl_dir, "volume",
                          "-port", str(self.a_port),
                          "-grpcPort", str(self.a_grpc),
                          "-mserver", self.master, "-dir", self.a_dir,
                          "-max", "64", "-rack", RACK_A, "-coder", a_coder],
                    {"JAX_PLATFORMS": a_platform,
                     # every compile is written to the cache, also where
                     # the cache directory comes from the environment
                     "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"},
                    stdin=subprocess.PIPE)
        self._spawn("B", ["seaweedfs_tpu", "volume",
                          "-port", str(self.b_port),
                          "-grpcPort", str(self.b_grpc),
                          "-mserver", self.master, "-dir", self.b_dir,
                          "-max", "64", "-rack", RACK_B, "-coder", "native"],
                    {"JAX_PLATFORMS": "cpu"})

    def wait_ready(self) -> None:
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

    def wait(self, cond, timeout: float, what: str, interval: float = 0.1):
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

    def stop(self) -> None:
        if self.mc is not None:
            self.mc.stop()
            self.mc = None
        for p in self.procs.values():
            if p.poll() is None:
                p.terminate()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)
            if p.stdin is not None:
                p.stdin.close()

    # -- what the daemons say ------------------------------------------------
    def status(self, url: str) -> "dict | None":
        # plain urllib: polling a server that is still starting must not
        # trip the client library's per-peer circuit breaker
        try:
            with urllib.request.urlopen(f"http://{url}/status",
                                        timeout=5) as r:
                return json.load(r)
        except OSError:  # not listening yet: poll again
            return None

    def device(self, chips: int) -> dict:
        """What A computes on, from its /status; no chip, no result."""
        sa, sb = self.status(self.a_url), self.status(self.b_url)
        check(sb["coder"] in ("native", "numpy") and not sb["jax_loaded"],
              f"B must stay off JAX, /status says {sb}")
        if not self.rehearsal:
            check(sa["platform"] == "tpu" and sa["coder"] == "jax",
                  f"A runs coder={sa['coder']!r} on platform="
                  f"{sa['platform']!r}, not on a TPU")
            check(sa["devices"] >= chips,
                  f"A sees {sa['devices']} devices, the cell asks for "
                  f"{chips}")
        return {"platform": sa["platform"], "kind": sa["device_kind"],
                "count": sa["devices"]}

    def events(self, url: str, since: int = 0) -> "list[dict]":
        r = http_util.get(f"http://{url}/debug/events",
                          params={"limit": 5000, "since": since})
        check(r.ok, f"/debug/events on {url}: HTTP {r.status}")
        return r.json()["events"]

    def last_seq(self, url: str) -> int:
        r = http_util.get(f"http://{url}/debug/events", params={"limit": 1})
        check(r.ok, f"/debug/events on {url}: HTTP {r.status}")
        return r.json()["last_seq"]

    def metrics_text(self, url: str) -> str:
        r = http_util.get(f"http://{url}/metrics")
        check(r.ok, f"/metrics on {url}: HTTP {r.status}")
        return r.content.decode()

    # -- what an operator does -------------------------------------------------
    def shell(self, script: str, timeout: float = 600) -> "tuple[int, str]":
        """Run shell verbs the way an operator's cron does: `shell -c`."""
        self.alive()
        r = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell",
             "-master", self.master, "-c", script],
            cwd=CHECKOUT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=timeout)
        return r.returncode, r.stdout + r.stderr

    def timed_shell(self, script: str) -> dict:
        """One verb as a timed operation: its start and end on
        time.time(), its wall on the monotonic clock, exit code, output."""
        t0, m0 = time.time(), time.monotonic()
        rc, out = self.shell(script)
        wall = time.monotonic() - m0
        return {"t0": t0, "t1": time.time(), "wall_s": wall, "rc": rc,
                "out": out}

    def stub(self, which: str) -> Stub:
        port = self.a_grpc if which == "A" else self.b_grpc
        return Stub(f"127.0.0.1:{port}", VOLUME_SERVICE)

    def mount(self, which: str, collection: str, vid: int) -> None:
        self.stub(which).call(
            "VolumeMount",
            vpb.VolumeMountRequest(volume_id=vid, collection=collection),
            vpb.VolumeMountResponse)

    def drop_shards(self, which: str, collection: str, vid: int,
                    sids: "list[int]") -> None:
        """Lose shards of an EC volume on one server: unmount, delete."""
        stub = self.stub(which)
        stub.call("VolumeEcShardsUnmount",
                  vpb.VolumeEcShardsUnmountRequest(volume_id=vid,
                                                   shard_ids=sids),
                  vpb.VolumeEcShardsUnmountResponse)
        stub.call("VolumeEcShardsDelete",
                  vpb.VolumeEcShardsDeleteRequest(
                      volume_id=vid, collection=collection, shard_ids=sids),
                  vpb.VolumeEcShardsDeleteResponse)

    # -- the launcher's control pipe ---------------------------------------------
    def control(self, command: str, timeout: float = 120) -> dict:
        """One command to A's launcher (launch_a.py); its reply."""
        a = self.procs["A"]
        a.stdin.write((command + "\n").encode())
        a.stdin.flush()
        reply = os.path.join(self.ctl_dir, f"{self._sent}.json")
        self._sent += 1
        self.wait(lambda: os.path.exists(reply), timeout,
                  f"A's launcher to answer {command.split()[0]!r}", 0.05)
        with open(reply) as f:
            got = json.load(f)
        check("error" not in got, f"launcher: {command}: {got.get('error')}")
        return got
