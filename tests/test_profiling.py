"""Continuous profiling & flight-recorder plane (profiling/).

Unit layer: the sampler's folded stacks match a thread running a known
call chain; the aggregate stays bounded (and count-exact) under stack
churn; the loop-lag probe detects a deliberately blocked event loop;
MonitoredPool books queue depth/wait; the flight ring bounds, filters,
sorts and trace-correlates; /debug/profile query validation (malformed
seconds -> 400, NaN rejected, SWTPU_PROFILE_MAX_SECONDS clamp).

Cluster layer: the four daemons' shared gate — a volume server behind a
non-matching IP whitelist answers 401 on /debug/profile AND
/debug/flight (the route shipped unguarded before this plane); and a
1-master/2-volume mini-cluster where /cluster/telemetry?profile=1
merges per-node summaries with counts summing exactly, rendered by the
cluster.profile shell verb.
"""

from __future__ import annotations

import asyncio
import io
import json
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest
from conftest import wait_cluster_up, wait_until

from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.profiling import (FlightRecorder, LoopLagMonitor,
                                     MonitoredPool, classify_thread,
                                     debug_flight_payload,
                                     handle_profile_query)
from seaweedfs_tpu.profiling.sampler import ContinuousSampler
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.store import Store


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------

class TestClassify:
    @pytest.mark.parametrize("name,cls", [
        ("vs-read-8080_3", "read_pool"),
        ("ec-degraded-read_0", "read_pool"),
        ("swtpu-ec-writer-1", "writer_pool"),
        ("chunk-upload-2", "writer_pool"),
        ("grpc-worker_5", "grpc"),
        ("raft-rpc-0", "raft"),
        ("vs-http-8080", "event_loop"),
        ("master-http", "event_loop"),
        ("Thread-7", "other"),
        ("", "other"),
    ])
    def test_name_rules(self, name, cls):
        assert classify_thread(name) == cls


def _burn_leaf(stop):
    # distinctive leaf that never blocks: must classify as on_cpu
    while not stop.is_set():
        sum(range(50))


def _burn_mid(stop):
    _burn_leaf(stop)


def _burn_outer(stop):
    _burn_mid(stop)


class TestSampler:
    def test_folded_stack_matches_known_call_chain(self):
        stop = threading.Event()
        busy = threading.Thread(target=_burn_outer, args=(stop,),
                                name="vs-read-sampled", daemon=True)
        parked = threading.Thread(target=stop.wait, args=(30,),
                                  name="swtpu-ec-writer-parked", daemon=True)
        s = ContinuousSampler(hz=200, max_stacks=500)
        busy.start()
        parked.start()
        s.start()
        try:
            wait_until(lambda: s.summary()["samples"] >= 50, timeout=10,
                       msg="sampler collected 50 thread-samples")
        finally:
            s.stop()
            stop.set()
            busy.join(timeout=5)
            parked.join(timeout=5)
        text = s.collapsed()
        lines = [ln for ln in text.splitlines() if not ln.startswith("#")]
        assert lines
        # every line is `class;state;frames... count`
        for ln in lines:
            stack, _, cnt = ln.rpartition(" ")
            assert cnt.isdigit()
            cls, state = stack.split(";", 2)[:2]
            assert cls in ("event_loop", "read_pool", "writer_pool",
                           "grpc", "raft", "other")
            assert state in ("on_cpu", "waiting")
        # the burner: read_pool class, on_cpu state, root-to-leaf order
        burner = [ln for ln in lines
                  if ln.startswith("read_pool;on_cpu;")
                  and "test_profiling.py:_burn_leaf" in ln]
        assert burner, text
        stack = burner[0].rpartition(" ")[0]
        outer = stack.index("test_profiling.py:_burn_outer")
        mid = stack.index("test_profiling.py:_burn_mid")
        leaf = stack.index("test_profiling.py:_burn_leaf")
        assert outer < mid < leaf, "folded stacks must read root-to-leaf"
        # the parked thread: writer_pool class, waiting state (its leaf
        # frame is threading.py's Event.wait wrapper)
        assert any(ln.startswith("writer_pool;waiting;")
                   and "threading.py:wait" in ln for ln in lines), text

    def test_bounded_aggregate_under_stack_churn(self, monkeypatch):
        # 100 distinct real frames (exec'd one-off functions), fed
        # through _sample_once with sys._current_frames patched: the
        # aggregate must stay bounded while total counts stay exact
        frames = []
        ns: dict = {"sys": sys}
        for i in range(100):
            exec(f"def churn_fn_{i}():\n    return sys._getframe()", ns)
            frames.append(ns[f"churn_fn_{i}"]())
        s = ContinuousSampler(hz=0, max_stacks=8)
        for i, fr in enumerate(frames):
            monkeypatch.setattr(
                "seaweedfs_tpu.profiling.sampler.sys._current_frames",
                lambda fr=fr, i=i: {10_000_000 + i: fr})
            s._sample_once()
        summ = s.summary()
        assert summ["samples"] == 100
        assert sum(it["count"] for it in summ["stacks"]) == 100
        # 8 distinct stacks + at most a couple of ~other buckets
        assert len(s._agg) <= 10
        assert any(k.endswith(";~other") for k in s._agg)
        # per-class totals survived the collapse
        assert sum(c["on_cpu"] + c["waiting"]
                   for c in summ["classes"].values()) == 100
        # summary(top=N) rolls the tail the same way
        top3 = s.summary(top=3)
        assert sum(it["count"] for it in top3["stacks"]) == 100
        assert len(top3["stacks"]) <= 3 + len(summ["classes"]) * 2


# ---------------------------------------------------------------------------
# loop lag + monitored pools
# ---------------------------------------------------------------------------

class TestLagMonitors:
    def test_loop_lag_probe_detects_blocked_loop(self):
        from seaweedfs_tpu.stats import EVENT_LOOP_LAG

        class Recording(LoopLagMonitor):
            """Keeps each probe's lag as the loop thread books it: with
            a probe every 20 ms, the one right after the block is
            overwritten before a polling thread can read it."""

            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                self.seen: "list[tuple[int, float]]" = []

            def _tick(self):
                super()._tick()
                self.seen.append((self.probes, self.last_lag_s))

        mon = Recording("lagtest", interval_s=0.02)
        loop = asyncio.new_event_loop()
        t = threading.Thread(target=loop.run_forever, daemon=True)
        t.start()
        try:
            loop.call_soon_threadsafe(mon.attach, loop)
            wait_until(lambda: mon.probes >= 2, timeout=10,
                       msg="probe ticked on an idle loop")
            idle_lag = mon.last_lag_s
            assert idle_lag < 0.25
            before = EVENT_LOOP_LAG.count("lagtest")
            assert before >= 1
            # block the loop thread outright: the next probe fires late
            # by roughly the block length. The count is read on the loop
            # thread as the block starts, so a probe that fires before it
            # does not count as the one after it.
            probes0 = []

            def block():
                probes0.append(mon.probes)
                time.sleep(0.3)
            loop.call_soon_threadsafe(block)

            def after_block() -> "list[float]":
                # `seen` gains the probe once its whole tick, the
                # histogram's observation included, has run
                return [lag for n, lag in list(mon.seen)
                        if probes0 and n == probes0[0] + 1]
            wait_until(after_block, timeout=10,
                       msg="probe fired after the block")
            (last_lag_s,) = after_block()
            assert last_lag_s > 0.15, last_lag_s
            assert EVENT_LOOP_LAG.count("lagtest") > before
        finally:
            loop.call_soon_threadsafe(mon.close)
            loop.call_soon_threadsafe(loop.stop)
            t.join(timeout=5)
            loop.close()

    def test_monitored_pool_books_depth_and_wait(self):
        from seaweedfs_tpu.stats import POOL_QUEUE_DEPTH, POOL_QUEUE_WAIT
        gate = threading.Event()
        pool = MonitoredPool("lagtest_pool", max_workers=1,
                             thread_name_prefix="lagtest-pool")
        wait0 = POOL_QUEUE_WAIT.count("lagtest_pool")
        try:
            # worker 1 parks on the gate; 2 more queue behind it
            futs = [pool.submit(gate.wait, 10) for _ in range(3)]
            wait_until(
                lambda: POOL_QUEUE_WAIT.count("lagtest_pool") == wait0 + 1,
                timeout=10, msg="first task picked up")
            # two tasks still queued, depth gauge says so
            assert POOL_QUEUE_DEPTH.value("lagtest_pool") == 2.0
            gate.set()
            for f in futs:
                assert f.result(timeout=10) is True
            wait_until(
                lambda: POOL_QUEUE_DEPTH.value("lagtest_pool") == 0.0,
                timeout=10, msg="depth gauge drained to zero")
            assert POOL_QUEUE_WAIT.count("lagtest_pool") == wait0 + 3
        finally:
            gate.set()
            pool.shutdown(wait=True)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_threshold_bounds_filters_and_sort(self):
        fr = FlightRecorder(capacity=4, slow_ms=5.0)
        assert fr.record("volume.get", 0.002) is None  # fast + ok: dropped
        assert fr.record("volume.get", 0.002, status=500) is not None
        for i in range(8):
            fr.record("volume.bulk", 0.010 + i * 0.001,
                      stages={"store": 0.009}, qos_class="ingest")
        assert fr.recorded() == 9
        entries = fr.snapshot()
        assert len(entries) == 4  # ring bound
        # slowest first, every survivor carries its stage timeline
        durs = [e["duration_ms"] for e in entries]
        assert durs == sorted(durs, reverse=True)
        assert all(e["stages_ms"]["store"] == 9.0 for e in entries)
        # filters
        assert fr.snapshot(min_ms=1000) == []
        assert all(e["kind"] == "volume.bulk"
                   for e in fr.snapshot(kind="volume.bulk"))
        assert len(fr.snapshot(limit=2)) == 2

    def test_trace_correlation_runs_both_ways(self):
        from seaweedfs_tpu import tracing
        fr = FlightRecorder(capacity=8, slow_ms=1.0)
        with tracing.start_span("flight-test") as sp:
            entry = fr.record("volume.get", 0.050, path="/1,abc")
            assert entry["trace_id"] == sp.context.trace_id
            assert entry["span_id"] == sp.context.span_id
            # the span learned it was captured
            assert any(ev["name"] == "flight.recorded"
                       and ev["seq"] == entry["seq"]
                       for ev in sp.events)

    @pytest.mark.parametrize("query", [
        {"min_ms": "abc"}, {"min_ms": "nan"}, {"min_ms": "-3"},
        {"limit": "many"},
    ])
    def test_payload_rejects_malformed_filters(self, query):
        code, body = debug_flight_payload(query)
        assert code == 400 and "error" in body

    def test_payload_shape(self):
        code, body = debug_flight_payload({"min_ms": "0", "limit": "5"})
        assert code == 200
        assert set(body) == {"capacity", "slow_ms", "recorded", "entries"}


# ---------------------------------------------------------------------------
# /debug/profile query validation (the shared handler)
# ---------------------------------------------------------------------------

class TestProfileQuery:
    @pytest.mark.parametrize("query", [
        {"seconds": "abc"}, {"seconds": "nan"}, {"seconds": "inf"},
        {"seconds": "0"}, {"seconds": "-2"},
        {"hz": "abc"}, {"hz": "nan"}, {"hz": "-1"},
        {"mode": "bogus"}, {"mode": "summary", "top": "x"},
    ])
    def test_malformed_queries_are_400(self, query):
        code, ctype, body = handle_profile_query(query)
        assert code == 400, (query, body)
        assert "error" in json.loads(body)

    def test_seconds_clamped_by_env_cap(self, monkeypatch):
        # a typo'd seconds=86400 must not pin a thread for a day: the
        # cap turns it into a sub-second capture that finishes here
        monkeypatch.setenv("SWTPU_PROFILE_MAX_SECONDS", "0.2")
        t0 = time.perf_counter()
        code, ctype, body = handle_profile_query({"seconds": "86400"})
        took = time.perf_counter() - t0
        assert code == 200 and ctype.startswith("text/plain")
        assert took < 5.0, f"capture ran {took:.1f}s despite the cap"

    def test_hz_retune_ack_and_continuous_modes(self, monkeypatch):
        s = ContinuousSampler(hz=0, max_stacks=100)
        monkeypatch.setattr("seaweedfs_tpu.profiling.sampler._default", s)
        code, ctype, body = handle_profile_query({"hz": "0"})
        assert code == 200 and json.loads(body) == {"ok": True, "hz": 0.0}
        s._agg["other;on_cpu;x.py:f"] = 3
        s._samples = 3
        code, ctype, body = handle_profile_query({"mode": "continuous"})
        assert code == 200 and "other;on_cpu;x.py:f 3" in body
        code, ctype, body = handle_profile_query({"mode": "summary"})
        assert code == 200
        assert json.loads(body)["samples"] == 3


# ---------------------------------------------------------------------------
# cluster: identical gating + fleet merge
# ---------------------------------------------------------------------------

def _make_server(tmpdir, mport, guard=None):
    from seaweedfs_tpu.server.volume_server import VolumeServer
    geo = EcGeometry(d=4, p=2, large_block=1 << 20, small_block=1 << 14)
    port = free_port()
    store = Store("127.0.0.1", port, f"127.0.0.1:{port}",
                  [DiskLocation(str(tmpdir), max_volume_count=10)],
                  ec_geometry=geo, coder_name="numpy")
    vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                      grpc_port=free_port(), pulse_seconds=0.3,
                      guard=guard)
    vs.start()
    return vs


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


@pytest.mark.parametrize("white_list,want", [
    (["203.0.113.9"], 401), (None, 200)], ids=["whitelisted-out", "open"])
def test_volume_debug_profile_gated_like_master(tmp_path, monkeypatch,
                                                white_list, want):
    """The satellite the tentpole rode in on: /debug/profile shipped
    UNGUARDED on the volume server. With an IP whitelist that excludes
    localhost, profile AND flight must answer 401, and non-GET 405.
    Open, a GET over the threshold lands in the ring with its stage
    timeline and a trace id that /debug/traces on the same node
    resolves."""
    from seaweedfs_tpu.profiling.flight import FLIGHT
    from seaweedfs_tpu.security.guard import Guard
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.types import file_id
    monkeypatch.setattr(FLIGHT, "slow_ms", 0.0)  # every request is "slow"
    vs = _make_server(tmp_path, free_port(),
                      guard=Guard(white_list=white_list) if white_list
                      else None)
    try:
        wait_until(lambda: _probe(f"http://{vs.url}/status") == 200,
                   timeout=10, msg="volume http up")
        for path in ("/debug/profile?mode=summary", "/debug/flight"):
            assert _probe(f"http://{vs.url}{path}") == want, path
        req = urllib.request.Request(
            f"http://{vs.url}/debug/profile", method="POST", data=b"")
        assert _probe_req(req) == 405
        if white_list:
            return
        vs.store.add_volume(7)
        vs.store.write_needle(7, Needle(id=1, cookie=9, data=b"flown"))
        fid = file_id(7, 1, 9)
        assert _get(f"http://{vs.url}/{fid}")[1] == "flown"
        _, body = _get(f"http://{vs.url}/debug/flight?kind=volume.get")
        (ent,) = [e for e in json.loads(body)["entries"]
                  if e["node"] == vs.url and e["path"] == f"/{fid}"]
        assert ent["why"] == "slow" and ent["status"] == 200
        assert {"recv_parse", "queue_wait", "auth_admit", "store",
                "serialize_flush"} <= set(ent["stages_ms"])
        _, body = _get(f"http://{vs.url}/debug/traces"
                       f"?trace_id={ent['trace_id']}")
        assert json.loads(body)["count"] >= 1
    finally:
        vs.stop()


def _probe(url):
    return _probe_req(urllib.request.Request(url))


def _probe_req(req):
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status
    except urllib.error.HTTPError as e:
        return e.code


@pytest.fixture(scope="module")
def profile_cluster(tmp_path_factory):
    from seaweedfs_tpu.master.master_server import MasterServer
    mport, hport = free_port(), free_port()
    master = MasterServer(port=mport, http_port=hport,
                          volume_size_limit_mb=64, pulse_seconds=0.3,
                          ec_parity_shards=2,
                          # explicit trigger only: no background timer
                          telemetry_interval_s=3600)
    master.start()
    dirs = [tmp_path_factory.mktemp(f"pvs{i}") for i in range(2)]
    servers = [_make_server(dirs[i], mport) for i in range(2)]
    wait_cluster_up(master, servers)
    yield master, servers, hport
    for vs in servers:
        vs.stop()
    master.stop()


def test_cluster_profile_merges_with_counts_summing(profile_cluster):
    master, servers, hport = profile_cluster
    from seaweedfs_tpu.profiling import default_sampler
    # the daemons acquired the shared sampler on start(); let it tick
    s = default_sampler()
    assert s is not None and s.running
    wait_until(lambda: s.summary()["samples"] > 0, timeout=15,
               msg="sampler collected samples")

    def fetch():
        _, body = _get(f"http://127.0.0.1:{hport}/cluster/telemetry"
                       "?profile=1&trigger=1")
        return json.loads(body)

    # volume targets come from heartbeat topology; wait for both
    wait_until(lambda: len(fetch().get("profile", {}).get("nodes", {}))
               >= 3, timeout=20, msg="master + 2 volume nodes profiled")
    snap = fetch()
    prof = snap["profile"]
    assert len(prof["nodes"]) >= 3  # master local + 2 scraped volumes
    # the headline invariant: truncation never loses counts — the
    # cluster total IS the sum of the per-node totals, and the merged
    # stacks re-add to it exactly
    assert prof["samples"] == sum(n["samples"]
                                  for n in prof["nodes"].values())
    assert prof["samples"] > 0
    assert sum(it["count"] for it in prof["stacks"]) == prof["samples"]
    assert sum(c["on_cpu"] + c["waiting"]
               for c in prof["classes"].values()) == prof["samples"]
    # without ?profile=1 the snapshot stays lean
    _, body = _get(f"http://127.0.0.1:{hport}/cluster/telemetry")
    assert "profile" not in json.loads(body)

    # the shell verb renders the same payload (421-following fetch)
    from seaweedfs_tpu.shell.commands import CommandEnv, run_command
    from seaweedfs_tpu.shell import telemetry_commands  # noqa: F401
    out = io.StringIO()
    env = CommandEnv(f"127.0.0.1:{master.port}", mc=None, out=out)
    run_command(env, f"cluster.profile -url http://127.0.0.1:{hport} "
                     "-noTrigger")
    text = out.getvalue()
    assert "thread classes" in text
    assert "event_loop" in text
    out.truncate(0)
    out.seek(0)
    run_command(env, f"cluster.profile -url http://127.0.0.1:{hport} "
                     "-noTrigger -raw")
    raw = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    assert raw and all(ln.rpartition(" ")[2].isdigit() for ln in raw)
    assert sum(int(ln.rpartition(" ")[2]) for ln in raw) == prof["samples"]
