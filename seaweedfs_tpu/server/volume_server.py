"""Volume server daemon: HTTP data path + gRPC admin/EC + master heartbeat.

Reference: weed/server/volume_server.go, volume_server_handlers_write.go:18
(PostHandler -> ReplicatedWrite), volume_server_handlers_read.go:44,
volume_grpc_client_to_master.go:50 (heartbeat loop),
volume_grpc_erasure_coding.go (EC RPC set incl. fork CopyByRebuild/Move),
topology/store_replicate.go:25 (synchronous replica fan-out).
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.parse

from ..ec import MAX_SHARD_ID
from ..ec import degraded as ec_degraded
from ..ec import files as ec_files
from ..ec import push as ec_push
from ..ec import repair as ec_repair
from ..ec.encoder import rebuild_shards
from ..ec.locate import EcGeometry
from ..pb import master_pb2 as mpb
from ..pb import volume_server_pb2 as vpb
from ..stats import (EC_RECONSTRUCT_BYTES, EC_RECONSTRUCT_SECONDS,
                     VOLUME_STORE_READ_SECONDS)
from ..storage.needle import Needle
from ..storage.store import Store
from ..storage.types import TTL, parse_file_id
from ..storage.vacuum import commit_compact, compact
from ..telemetry.hot import record as hot_record
from ..tracing.stages import StageAccount
from ..utils import failpoints, fsutil, retry
from ..utils.log import logger
from ..utils.rpc import (MASTER_SERVICE, VOLUME_SERVICE, RpcService, Stub,
                         drop_channel, serve)

log = logger("volume")

# the pause between attempts to reach a master not yet registered with
_HB_FIRST_RETRY_S = 0.1


def _observe_stages(kind: str, t_recv: float, t_parsed: float, t0: float,
                    t_admit, t_done, t_end: float) -> dict:
    """Per-stage timing for the protocol-ceiling teardown (round 5, host
    clock: 93-139 us of protocol per hop): contiguous perf_counter segments
    recv/parse (first wire byte -> request parsed), queue_wait (parsed
    -> handler entry: drain-queue + event-loop queueing, the split that
    de-confounds the old queueing-inflated recv_parse number),
    auth/admit (QoS admission), store (the storage handler itself, jwt
    check included) and serialize/flush (response build + accounting).
    The five sums cover the full wire-to-wire interval, so per-type
    stage totals account for >= 100% of VOLUME_REQUEST_SECONDS.
    t_admit/t_done may be None on shed/error paths (stage collapses to
    zero and the tail lands in serialize_flush). Returns the stage dict
    so the flight recorder can reuse it without re-deriving."""
    from ..stats import VOLUME_STAGE_SECONDS
    a = t_admit if t_admit is not None else t0
    d = t_done if t_done is not None else a
    r = t_recv or t_parsed or t0
    p = t_parsed or r
    stages = {
        "recv_parse": max(0.0, p - r),
        "queue_wait": max(0.0, t0 - p),
        "auth_admit": max(0.0, a - t0),
        "store": max(0.0, d - a),
        "serialize_flush": max(0.0, t_end - d),
    }
    for stage, v in stages.items():
        VOLUME_STAGE_SECONDS.observe(kind, stage, value=v)
    return stages


def _vid_of_path(path: str) -> "str | None":
    head = path.lstrip("/").split(",", 1)[0]
    return head if head.isdigit() else None


def _maintenance_tagged(fn):
    """Tag a gRPC handler's whole execution maintenance-class: these
    RPCs exist ONLY as repair/replication/rebalance machinery, so their
    nested reads (ranged survivor fetches, CopyFile pulls from peers)
    inherit the tag and yield to foreground work wherever they land —
    even when an operator drives them by hand from the shell."""
    import functools

    from .. import qos as qos_mod

    @functools.wraps(fn)
    def wrapped(req, context):
        with qos_mod.tagged(qos_mod.CLASS_MAINTENANCE):
            return fn(req, context)
    return wrapped


def _ec_stage_fields(stats: dict) -> dict:
    """ec.encode.finish event fields from an encode pipeline stats dict:
    the fill/dispatch/drain/write stage split plus the overlap fraction, so
    /debug/events shows WHERE an encode spent its wall time without pulling
    the trace."""
    fields = {}
    for key in ("fill_s", "dispatch_s", "first_dispatch_s", "coder_s",
                "drain_block_s", "write_s", "write_block_s", "finish_s",
                "wall_s", "push_s", "push_wait_s"):
        if key in stats:
            fields[key] = round(stats[key], 3)
    # the shards streamed to other holders as they were written, beside
    # the bytes of all shards placed off this server (ec/stream.py)
    for key in ("write_overlap", "writers", "batches", "mode", "bytes",
                "batch_bytes_by_device", "push_bytes", "remote_bytes"):
        if key in stats:
            fields[key] = stats[key]
    return fields


class VolumeServer:
    def __init__(self, store: Store, master_address: str,
                 ip: str = "127.0.0.1", port: int = 8080,
                 grpc_port: int | None = None,
                 data_center: str = "", rack: str = "",
                 pulse_seconds: float = 2.0, read_mode: str = "proxy",
                 guard=None, metrics_gateway: str = "",
                 metrics_interval_s: int = 15,
                 qos_policy: "dict | str | None" = None):
        self.store = store
        # optional push-gateway loop (reference -metricsPort push config);
        # started in start(), joined in stop() via the PushLoop handle
        self.metrics_gateway = metrics_gateway
        self.metrics_interval_s = metrics_interval_s
        self._metrics_push = None
        # comma-separated master quorum; heartbeats follow leader hints
        # and rotate through the list on failure (reference
        # volume_grpc_client_to_master.go:28 checkWithMaster)
        self.masters = [m for m in master_address.split(",") if m]
        self.master_address = self.masters[0]
        self._master_rr = 0
        self.current_leader = self.masters[0]
        self.ip = ip
        self.port = port
        self.grpc_port = grpc_port or port + 10000
        self.data_center = data_center
        self.rack = rack
        self.pulse_seconds = pulse_seconds
        self.read_mode = read_mode
        # security.Guard: JWT/white-list gate on mutating HTTP requests
        # (reference guard wiring in weed/server/volume_server.go; the write
        # token is the single-fid JWT the master minted on Assign).
        self.guard = guard
        self._stop = threading.Event()
        self._leave = threading.Event()  # volume.server.leave: stop heartbeats
        self._hb_wake = threading.Event()
        # heartbeat flush bookkeeping: state seq bumps on every mutation
        # trigger; the loop records which seq each SENT snapshot covered and
        # advances _hb_acked_seq when the master's 1:1 response arrives, so
        # flush_heartbeat() can wait for "master has processed my change"
        self._hb_cond = threading.Condition()
        self._hb_state_seq = 0
        self._hb_acked_seq = -1
        self._hb_inflight: "list[int]" = []
        self._grpc = None
        self._http_thread = None
        self._hb_thread = None
        self._hb_active_stream = None
        self._http_runner = None
        # EC shard-location cache (tiers, store_ec.go:256-267) + the
        # degraded-read fan-out pool (store_ec.go:367 goroutine fan-out)
        self._ec_loc_cache: dict[int, tuple[dict, float, bool]] = {}
        self._ec_loc_lock = threading.Lock()
        # geo plane: peer gRPC address -> data center, learned from the
        # master's LookupEcVolume answers (Location.data_center). Keyed
        # by address, not volume — a server's DC never changes within a
        # process lifetime, so single whole-value writes under the GIL
        # need no lock and staleness is not a failure mode.
        self._ec_addr_dc: dict[str, str] = {}
        # replica-set cache for the write fan-out (see _lookup_replicas_cached)
        self._replica_cache: dict[int, tuple[float, list[str]]] = {}
        from ..profiling import LoopLagMonitor, MonitoredPool
        self._ec_read_pool = MonitoredPool(
            "ec_read", max_workers=16,
            thread_name_prefix="ec-degraded-read")
        # read-path data plane: the hot-needle cache (segmented LRU,
        # storage/read_cache.py; SWTPU_READ_CACHE_MB=0 disables) and the
        # pool GET/bulk-GET storage reads run on. With the seqlock read
        # protocol (storage/volume.py) these threads read in PARALLEL —
        # no GET ever queues behind a writer's fsync on the volume lock.
        from ..storage import read_cache as read_cache_mod
        from ..utils.env import env_int
        self.read_cache = read_cache_mod.default_cache()
        # lifecycle heat epoch: read counters live in memory, so this
        # server can only attest "quiet for <= uptime" — the planner
        # uses it as the ceiling for volumes with no recorded read
        self._started_mono = time.monotonic()
        self._read_pool = MonitoredPool(
            "read", max_workers=max(1, env_int("SWTPU_READ_THREADS", 8)),
            thread_name_prefix=f"vs-read-{port}")
        # profiling plane: loop-lag probe (installed on the HTTP loop by
        # serve_fast_app's on_loop hook) + the process-shared continuous
        # sampler (acquired in start(), released in stop())
        self._loop_lag = LoopLagMonitor("volume")
        self._sampler = None
        # multi-tenant QoS plane (qos/): tenant = collection, classes
        # interactive (GET) > ingest (PUT/DELETE) > maintenance (tagged
        # repair/rebuild/copy traffic). A dict is a policy document; a
        # string is a policy FILE hot-reloaded on mtime change
        # (-qosPolicy); None/empty = admission disabled (zero-cost
        # pass-through). Live state at /debug/qos, retune via POST.
        from ..qos import QosScheduler
        self.qos = QosScheduler(name=f"volume-{port}")
        if isinstance(qos_policy, str) and qos_policy:
            self.qos.attach_file(qos_policy)
        elif qos_policy:
            self.qos.load(qos_policy)

    @property
    def url(self) -> str:
        return f"{self.ip}:{self.port}"

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        from ..profiling import acquire_sampler
        self._sampler = acquire_sampler()
        key = self.guard.signing_key if self.guard is not None else ""
        if key:
            from ..utils.rpc import set_cluster_key
            set_cluster_key(key)
        self._grpc = serve(f"{self.ip}:{self.grpc_port}",
                           [self._build_service()], auth_key=key)
        self._http_thread = threading.Thread(target=self._run_http, daemon=True,
                                             name=f"vs-http-{self.port}")
        self._http_thread.start()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           daemon=True,
                                           name=f"vs-hb-{self.port}")
        self._hb_thread.start()
        if self.metrics_gateway:
            from ..stats import start_push_loop
            self._metrics_push = start_push_loop(
                self.metrics_gateway, f"volume-{self.url}",
                self.metrics_interval_s)
        log.info("volume server %s up (grpc :%d)", self.url, self.grpc_port)

    def stop(self) -> None:
        if self._stop.is_set():
            return
        self._stop.set()
        self._hb_wake.set()
        # tear the live heartbeat stream so the blocked thread unblocks
        # NOW, then join it — otherwise it outlives the test/daemon and
        # spams "I/O operation on closed file" retrying against a closed
        # store and torn-down logging
        stream = self._hb_active_stream
        if stream is not None:
            try:
                stream.cancel()
            except Exception as e:  # noqa: BLE001
                log.debug("heartbeat stream cancel failed: %s", e)
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2.0)
        if self._metrics_push is not None:
            self._metrics_push.stop()
        if self._grpc:
            self._grpc.stop(grace=0.5)
        self._ec_read_pool.shutdown(wait=False, cancel_futures=True)
        self._read_pool.shutdown(wait=False, cancel_futures=True)
        self._loop_lag.close()
        if self._sampler is not None:
            from ..profiling import release_sampler
            release_sampler()
            self._sampler = None
        self.qos.close()
        if self.read_cache is not None:
            self.read_cache.clear()
        self.store.close()

    # -- heartbeat (reference volume_grpc_client_to_master.go) ---------------
    def _update_gauges(self, hb: dict) -> None:
        """Volume/EC/disk gauges from heartbeat state (reference sets
        VolumeServerDiskSizeGauge from EC heartbeat, store_ec.go:41).
        Label sets seen before but absent now are zeroed, so removed
        volumes/collections don't linger in dashboards."""
        from ..stats import (VOLUME_SERVER_DISK_SIZE_GAUGE,
                             VOLUME_SERVER_EC_SHARD_GAUGE,
                             VOLUME_SERVER_VOLUME_GAUGE)
        per: dict[tuple[str, str], int] = {}
        size: dict[tuple[str, str], int] = {}
        for v in hb["volumes"]:
            key = (v["collection"], v["disk_type"])
            per[key] = per.get(key, 0) + 1
            size[key] = size.get(key, 0) + v["size"]
        ec_per: dict[tuple[str], int] = {}
        for s in hb["ec_shards"]:
            n = bin(s["ec_index_bits"]).count("1")
            key = (s["collection"],)
            ec_per[key] = ec_per.get(key, 0) + n
        for gauge, cur, attr in (
                (VOLUME_SERVER_VOLUME_GAUGE, per, "_g_vol"),
                (VOLUME_SERVER_DISK_SIZE_GAUGE, size, "_g_size"),
                (VOLUME_SERVER_EC_SHARD_GAUGE, ec_per, "_g_ec")):
            prev: set = getattr(self, attr, set())
            for key in prev - set(cur):
                gauge.set(*key, value=0)
            for key, n in cur.items():
                gauge.set(*key, value=n)
            setattr(self, attr, set(cur))

    def _heartbeat_messages(self):
        while not (self._stop.is_set() or self._leave.is_set()):
            try:
                # per-pulse housekeeping (fork store.go:389 reap +
                # ec_volume.go idle-handle close). Reaps are lifecycle
                # transitions (→trash): journaled + metered like every
                # other tier move so the plane's books balance.
                reaped = self.store.delete_expired_ec_volumes()
                if reaped:
                    from ..lifecycle import TIER_TRASH
                    from ..ops import events
                    from ..stats import (LIFECYCLE_BYTES_MOVED,
                                         LIFECYCLE_TRANSITIONS)
                    for rec in reaped:
                        events.emit("lifecycle.transition", kind="reap",
                                    vid=rec["vid"], node=self.url,
                                    collection=rec["collection"],
                                    **{"from": rec["from"],
                                       "to": TIER_TRASH},
                                    bytes_moved=rec["bytes"])
                        LIFECYCLE_TRANSITIONS.inc(rec["from"], TIER_TRASH)
                        LIFECYCLE_BYTES_MOVED.inc(rec["from"], TIER_TRASH,
                                                  amount=rec["bytes"])
                    log.info("reaped expired ec volumes %s",
                             [r["vid"] for r in reaped])
                self.store.close_idle_ec_handles()
            except Exception as e:  # noqa: BLE001
                log.warning("ec housekeeping: %s", e)
            # read the seq BEFORE snapshotting: any mutation that bumped
            # the seq before this point is included in the snapshot, so
            # acking snap_seq proves the master saw those mutations
            snap_seq = self._hb_state_seq
            hb = self.store.collect_heartbeat()
            self._update_gauges(hb)
            msg = mpb.Heartbeat(
                ip=self.ip, port=self.port, grpc_port=self.grpc_port,
                public_url=self.store.public_url,
                max_file_key=hb["max_file_key"],
                data_center=self.data_center, rack=self.rack,
                max_volume_counts=hb["max_volume_counts"],
                has_no_volumes=not hb["volumes"],
                has_no_ec_shards=not hb["ec_shards"],
                ec_data_shards=self.store.ec_geometry.d,
                ec_parity_shards=self.store.ec_geometry.p)
            for v in hb["volumes"]:
                msg.volumes.add(**v)
            for s in hb["ec_shards"]:
                msg.ec_shards.add(**s)
            # failpoint: a raised error tears the heartbeat stream (the
            # master sees the disconnect and unregisters); delay models a
            # stalled node feeding the failure detector
            failpoints.check("volume.heartbeat")
            with self._hb_cond:
                self._hb_inflight.append(snap_seq)
            yield msg
            self._hb_wake.wait(timeout=self.pulse_seconds)
            self._hb_wake.clear()

    def _heartbeat_loop(self) -> None:
        # until a master first answers, a failed stream is retried every
        # _HB_FIRST_RETRY_S: a server started beside its master registers
        # as soon as the master listens, not up to a pulse later; after
        # that, a pulse (at most 2 s) apart
        registered = False
        failures = 0
        while not (self._stop.is_set() or self._leave.is_set()):
            try:
                stub = Stub(self.current_leader, MASTER_SERVICE)
                stream = stub.stream_stream(
                    "SendHeartbeat", self._heartbeat_messages(),
                    mpb.Heartbeat, mpb.HeartbeatResponse)
                # kept for stop(): cancelling unblocks this thread so the
                # join in stop() returns promptly
                self._hb_active_stream = stream
                if self._stop.is_set():
                    stream.cancel()
                    return
                for resp in stream:
                    registered = True
                    # master answers 1:1 AFTER ingesting each heartbeat:
                    # the oldest in-flight snapshot is now master-visible
                    with self._hb_cond:
                        if self._hb_inflight:
                            self._hb_acked_seq = self._hb_inflight.pop(0)
                            self._hb_cond.notify_all()
                    if resp.volume_size_limit:
                        pass  # informational
                    if resp.leader and resp.leader != self.current_leader:
                        log.info("leader moved to %s", resp.leader)
                        self.current_leader = resp.leader
                        break
                    if self._stop.is_set():
                        return
            except Exception as e:  # noqa: BLE001
                if not self._stop.is_set():
                    failures += 1
                    (log.warning if registered or failures == 1
                     else log.debug)("heartbeat to %s failed: %s; retrying",
                                     self.current_leader, e)
                    if not registered:
                        # a fresh channel dials at once; the failed one
                        # would wait out gRPC's reconnect backoff (1 s,
                        # then 1.6 s, ...) before it tried again
                        drop_channel(self.current_leader)
                    if len(self.masters) > 1:
                        self._master_rr = ((self._master_rr + 1)
                                           % len(self.masters))
                        self.current_leader = self.masters[self._master_rr]
                    # interruptible wait: a stop() during the retry pause
                    # must not leave a zombie heartbeat thread behind
                    self._stop.wait(min(self.pulse_seconds, 2.0 if registered
                                        else _HB_FIRST_RETRY_S))
            finally:
                with self._hb_cond:
                    # unacked sends died with the stream; the next stream
                    # re-sends full state, so waiters should not count them
                    self._hb_inflight.clear()
                    self._hb_cond.notify_all()

    def trigger_heartbeat(self) -> None:
        with self._hb_cond:
            self._hb_state_seq += 1
        self._hb_wake.set()

    def flush_heartbeat(self, timeout: float = 3.0) -> bool:
        """Block until the master has ingested a heartbeat reflecting every
        state change made before this call (or timeout). Admin RPCs that
        mutate volume/EC registration call this so topology reads anywhere
        in the cluster see the change once the RPC returns — closing the
        assemble-send-ingest race the old fire-and-forget trigger left.
        The shell's admin verbs plan from ONE topology read on the strength
        of it (shell/ec_commands.py:_ec_volumes), so a timeout is logged:
        the RPC still answers (the change is made, the next pulse carries
        it), but until then the master's view is behind this server."""
        if self._stop.is_set() or self._leave.is_set():
            return False  # no heartbeat loop to ack (leave/decommission)
        with self._hb_cond:
            self._hb_state_seq += 1
            target = self._hb_state_seq
        self._hb_wake.set()
        deadline = time.monotonic() + timeout
        with self._hb_cond:
            while self._hb_acked_seq < target:
                remaining = deadline - time.monotonic()
                if self._stop.is_set() or self._leave.is_set():
                    return False
                if remaining <= 0:
                    log.warning(
                        "master %s did not acknowledge a heartbeat within "
                        "%.1fs of a registration change: topology reads "
                        "may miss it until the next pulse",
                        self.current_leader, timeout)
                    return False
                self._hb_cond.wait(min(remaining, 0.25))
        return True

    # -- HTTP data path (utils/fastweb hand-rolled HTTP/1.1) ----------------
    def _flight_record(self, kind: str, request, status: int,
                       stages: dict, sp, t_wire: float,
                       t_end: float) -> None:
        """Offer a finished request to the flight recorder with the
        at-admit context (loop lag, pool queue depths) a postmortem
        needs to tell 'this request was slow' from 'the node was
        drowning'. Below-threshold requests cost two float compares."""
        from ..profiling import record_flight
        record_flight(
            kind, t_end - t_wire, status=status, path=request.path,
            stages=stages,
            qos_class=str(sp.attrs.get("qos_class", "")),
            cache=sp.attrs.get("cache"),
            loop_lag_s=self._loop_lag.last_lag_s,
            queue_depths={"read": self._read_pool.queued(),
                          "ec_read": self._ec_read_pool.queued()},
            node=self.url)

    def _run_http(self) -> None:
        import asyncio

        from ..utils import fastweb
        from ..utils.fastweb import Redirect, json_response

        from ..stats import (VOLUME_REQUEST_COUNTER,
                             VOLUME_REQUEST_SECONDS)

        from .. import tracing

        _kind = {"POST": "post", "PUT": "put", "GET": "get",
                 "HEAD": "head", "DELETE": "delete"}

        async def handle(request: fastweb.Request):
            kind = _kind.get(request.method, "other")
            t0 = time.perf_counter()
            t_admit = t_done = None
            resp = None
            status = 500
            # server span continues the caller's trace (traceparent
            # header) — a PUT's span parents the replication fan-out and
            # a GET's the EC shard fetches; the latency observation runs
            # INSIDE the span so the histogram captures its exemplar
            with tracing.start_span(
                    f"volume.{kind}", component="volume",
                    child_of=tracing.extract(request.headers),
                    attrs={"fid": request.path.lstrip("/"),
                           "server": self.url}) as sp:
                try:
                    # QoS admission: tenant = the fid's collection,
                    # class from the verb unless the hop is tagged
                    # (maintenance repair reads, class-inheriting
                    # replica hops). Reads post-charge their response
                    # bytes; replica hops charge but never shed.
                    grant, qos_token = None, None
                    if self.qos.enabled:
                        from .. import qos as qos_mod
                        is_read = request.method in ("GET", "HEAD")
                        klass = qos_mod.class_from_headers(
                            request.headers,
                            qos_mod.CLASS_INTERACTIVE if is_read
                            else qos_mod.CLASS_INGEST)
                        try:
                            grant = await self.qos.admit(
                                self._qos_tenant_of_path(request.path),
                                klass,
                                cost=len(request.body or b""),
                                no_shed=request.query.get("type")
                                == "replicate")
                        except qos_mod.QosShed as e:
                            status = 503
                            sp.set_attr("qos", "shed")
                            return self._qos_shed_response(e)
                        sp.set_attr("qos_class", klass)
                        # the handler (and its replication fan-out)
                        # inherits the admitted class
                        qos_token = qos_mod.set_class(klass)
                    t_admit = time.perf_counter()
                    try:
                        if request.method in ("POST", "PUT"):
                            resp = await self._handle_write(request)
                        elif request.method in ("GET", "HEAD"):
                            resp = await self._handle_read(request)
                        elif request.method == "DELETE":
                            resp = await self._handle_delete(request)
                        else:
                            resp = json_response(
                                {"error": "method not allowed"}, status=405)
                    except KeyError as e:
                        resp = json_response({"error": str(e)}, status=404)
                    except PermissionError as e:
                        resp = json_response({"error": str(e)}, status=403)
                    except Redirect as e:
                        status = e.status
                        sp.status = "redirect"  # control flow, not a fault
                        raise
                    except Exception as e:  # noqa: BLE001
                        log.error("http error: %s", e)
                        resp = json_response({"error": str(e)}, status=500)
                    t_done = time.perf_counter()
                    status = resp.status
                    if grant is not None and request.method in \
                            ("GET", "HEAD") and resp.body:
                        grant.charge(len(resp.body))
                    return resp
                finally:
                    if qos_token is not None:
                        from .. import qos as qos_mod
                        qos_mod.reset_class(qos_token)
                    if grant is not None:
                        grant.release()
                    sp.set_attr("status", status)
                    if status >= 500:
                        sp.set_error(f"HTTP {status}")
                    t_end = time.perf_counter()
                    VOLUME_REQUEST_COUNTER.inc(kind, str(status))
                    VOLUME_REQUEST_SECONDS.observe(kind, value=t_end - t0)
                    stages = _observe_stages(kind, request.t_recv,
                                             request.t_parsed, t0,
                                             t_admit, t_done, t_end)
                    self._flight_record(f"volume.{kind}", request, status,
                                        stages, sp,
                                        request.t_recv or t0, t_end)
                    # heavy hitters: bytes moved = payload in + body out
                    hot_record(
                        volume=_vid_of_path(request.path),
                        tenant=self._qos_tenant_of_path(request.path),
                        method=kind,
                        nbytes=len(request.body or b"")
                        + (len(resp.body) if resp is not None and resp.body
                           else 0))

        def status(request):
            return json_response({"version": "swtpu", **self.store.status()})

        def metrics(request):
            from ..stats import scrape_payload
            body, ctype = scrape_payload(request.headers.get("Accept", ""))
            return fastweb.Response(body.encode(), content_type=ctype)

        def debug_traces(request):
            return json_response(tracing.debug_traces_payload(request.query))

        def debug_events(request):
            from ..ops import events
            return json_response(events.debug_events_payload(request.query))

        def debug_locks(request):
            from ..utils import locktrack
            return json_response(
                locktrack.debug_locks_payload(request.query))

        def debug_qos(request):
            """GET dumps live scheduler state (buckets, queues, per-
            tenant counters); POST with a JSON policy document hot-
            reloads it (the operator retune path the S3 breaker's
            config reload established); GET ?reload=1 re-reads the
            attached -qosPolicy file immediately. On a guarded cluster
            the MUTATING forms demand write admission (whitelist/basic
            auth/any valid cluster jwt) — a throttled tenant must not
            be able to switch its own throttle off."""
            if (request.method == "POST" or request.query.get("reload")) \
                    and self.guard is not None:
                ok, why = self.guard.check_write(request.remote or "",
                                                 request.query,
                                                 request.headers)
                if not ok:
                    return json_response({"error": why}, status=401)
            if request.method == "POST":
                try:
                    doc = json.loads(request.body or b"{}")
                    self.qos.load(doc)
                except (ValueError, TypeError) as e:
                    return json_response({"error": str(e)}, status=400)
                return json_response({"ok": True,
                                      "enabled": self.qos.enabled})
            if request.query.get("reload"):
                self.qos._reload_file(initial=True)
            return json_response(self.qos.debug_payload())

        def debug_lifecycle(request):
            """GET dumps this server's per-volume heat + tier state —
            the planner's input: read counters and last-read/last-write
            ages from the storage layer (the read-cache hit path feeds
            them too), per-EC-volume local vs offloaded shards, remote
            read counts and DestroyTime. POST stamps a DestroyTime onto
            a local EC volume's .vif ({"volume": N, "destroy_time": T}
            — the lifecycle executor's TTL verb after a policy encode);
            guarded like /debug/qos: a tenant must not be able to
            schedule its own data's reaping."""
            if request.method == "POST":
                if self.guard is not None:
                    ok, why = self.guard.check_write(request.remote or "",
                                                     request.query,
                                                     request.headers)
                    if not ok:
                        return json_response({"error": why}, status=401)
                try:
                    doc = json.loads(request.body or b"{}")
                    vid = int(doc["volume"])
                    at = float(doc["destroy_time"])
                except (KeyError, TypeError, ValueError) as e:
                    return json_response({"error": str(e)}, status=400)
                if not self._set_destroy_time(vid, at):
                    return json_response(
                        {"error": f"no ec volume {vid}"}, status=404)
                return json_response({"ok": True, "volume": vid,
                                      "destroy_time": at})
            return json_response(self._lifecycle_payload())

        def _operator_gate(request):
            """Same gate policy as the master's guarded() debug routes:
            stacks/flight entries leak fids, paths and peer addresses,
            so the IP whitelist applies (this route shipped unguarded
            while master/S3 gated theirs — all four daemons now gate
            identically). Returns an error response, or None."""
            if request.method != "GET":
                return json_response({"error": "method not allowed"},
                                     status=405)
            if self.guard is not None:
                ok, why = self.guard.check_ip(request.remote or "")
                if not ok:
                    return json_response({"error": why}, status=401)
            return None

        async def debug_profile(request):
            import contextvars

            from .. import profiling as prof
            denied = _operator_gate(request)
            if denied is not None:
                return denied
            # shared contract (profiling.handle_profile_query): seconds
            # validation/clamp, continuous/summary modes, hz retune;
            # offloaded — a capture blocks for `seconds`
            loop = asyncio.get_running_loop()
            ctx = contextvars.copy_context()  # keep the trace span
            code, ctype, body = await loop.run_in_executor(
                None, ctx.run, prof.handle_profile_query, request.query)
            return fastweb.Response(body.encode(), status=code,
                                    content_type=ctype)

        def debug_flight(request):
            from .. import profiling as prof
            denied = _operator_gate(request)
            if denied is not None:
                return denied
            code, payload = prof.debug_flight_payload(request.query)
            return json_response(payload, status=code)

        def debug_jax_profiler(request):
            from ..utils import profiling
            port = int(request.query.get("port", "9999"))
            return fastweb.text_response(profiling.start_jax_profiler(port))

        def debug_failpoints(request):
            """GET: list armed failpoints; ?name=X&spec=Y arms/updates one
            at runtime (operator-driven chaos drills). A bare ?name=X
            without spec is a read — it must not disarm mid-drill."""
            name = request.query.get("name")
            spec = request.query.get("spec")
            if name and spec is not None:
                try:
                    failpoints.configure(name, spec)
                except ValueError as e:
                    return fastweb.text_response(f"bad spec: {e}",
                                                 status=400)
            return json_response({"armed": failpoints.active(),
                                  "fired": failpoints.fired_counts()})

        def status_ui(request):
            # human status UI (reference weed/server/volume_server_ui)
            from ..utils.ui import render_page
            st = self.store.status()
            rows = []
            ec_rows = []
            for loc in self.store.locations:
                with loc.lock:  # allocate/mount mutate these dicts
                    vols = sorted(loc.volumes.items())
                    ecs = sorted(loc.ec_volumes.items())
                for vid, v in vols:
                    rows.append([vid, v.collection or "-", loc.disk_type,
                                 f"{v.content_size >> 20} MB",
                                 v.file_count, v.deleted_count,
                                 "ro" if v.read_only else "rw"])
                for vid, ev in ecs:
                    ec_rows.append([vid, ev.collection or "-",
                                    sorted(ev.shards)])
            page = render_page(
                f"swtpu volume server {self.url}",
                {"Master": ", ".join(self.masters),
                 "Volumes": st["volumes"], "EC volumes": len(ec_rows),
                 "Rack": self.rack or "-",
                 "Data center": self.data_center or "-"},
                [("Volumes", ["id", "collection", "disk", "size", "files",
                              "deleted", "mode"], rows),
                 ("EC volumes", ["id", "collection", "shards"], ec_rows)])
            return fastweb.html_response(page)

        async def handle_bulk(request: fastweb.Request):
            # same envelope as the default data-path handler, with its
            # own request kind so dashboards separate bulk frames from
            # per-needle PUTs; the span is the bulk.put root the
            # replication fan-out children hang under
            t0 = time.perf_counter()
            t_admit = t_done = None
            resp = None
            status = 500
            with tracing.start_span(
                    "bulk.put", component="volume",
                    child_of=tracing.extract(request.headers),
                    attrs={"server": self.url,
                           "bytes": len(request.body or b"")}) as sp:
                try:
                    grant, qos_token = None, None
                    if self.qos.enabled:
                        from .. import qos as qos_mod
                        klass = qos_mod.class_from_headers(
                            request.headers, qos_mod.CLASS_INGEST)
                        try:
                            grant = await self.qos.admit(
                                self._qos_tenant_of_query(request.query),
                                klass,
                                cost=len(request.body or b""),
                                no_shed=request.query.get("type")
                                == "replicate")
                        except qos_mod.QosShed as e:
                            status = 503
                            sp.set_attr("qos", "shed")
                            return self._qos_shed_response(e)
                        sp.set_attr("qos_class", klass)
                        qos_token = qos_mod.set_class(klass)
                    t_admit = time.perf_counter()
                    try:
                        resp = await self._handle_bulk(request, sp)
                    except KeyError as e:
                        resp = json_response({"error": str(e)}, status=404)
                    except PermissionError as e:
                        resp = json_response({"error": str(e)}, status=403)
                    except Exception as e:  # noqa: BLE001
                        log.error("bulk http error: %s", e)
                        resp = json_response({"error": str(e)}, status=500)
                    t_done = time.perf_counter()
                    status = resp.status
                    return resp
                finally:
                    if qos_token is not None:
                        from .. import qos as qos_mod
                        qos_mod.reset_class(qos_token)
                    if grant is not None:
                        grant.release()
                    sp.set_attr("status", status)
                    if status >= 500:
                        sp.set_error(f"HTTP {status}")
                    t_end = time.perf_counter()
                    VOLUME_REQUEST_COUNTER.inc("bulk", str(status))
                    VOLUME_REQUEST_SECONDS.observe("bulk", value=t_end - t0)
                    stages = _observe_stages("bulk", request.t_recv,
                                             request.t_parsed, t0,
                                             t_admit, t_done, t_end)
                    self._flight_record("volume.bulk", request, status,
                                        stages, sp,
                                        request.t_recv or t0, t_end)
                    hot_record(
                        volume=request.query.get("vid") or None,
                        tenant=self._qos_tenant_of_query(request.query),
                        method="bulk",
                        nbytes=len(request.body or b""))

        async def handle_bulk_read(request: fastweb.Request):
            # bulk.read mirrors bulk.put: its own request kind on the
            # dashboards, one span the per-needle resolution hangs under
            t0 = time.perf_counter()
            t_admit = t_done = None
            resp = None
            status = 500
            with tracing.start_span(
                    "bulk.read", component="volume",
                    child_of=tracing.extract(request.headers),
                    attrs={"server": self.url,
                           "bytes": len(request.body or b"")}) as sp:
                try:
                    grant, qos_token = None, None
                    if self.qos.enabled:
                        from .. import qos as qos_mod
                        klass = qos_mod.class_from_headers(
                            request.headers, qos_mod.CLASS_INTERACTIVE)
                        try:
                            grant = await self.qos.admit(
                                self._qos_tenant_of_query(request.query),
                                klass)
                        except qos_mod.QosShed as e:
                            status = 503
                            sp.set_attr("qos", "shed")
                            return self._qos_shed_response(e)
                        sp.set_attr("qos_class", klass)
                        qos_token = qos_mod.set_class(klass)
                    t_admit = time.perf_counter()
                    try:
                        resp = await self._handle_bulk_read(request, sp)
                    except KeyError as e:
                        resp = json_response({"error": str(e)}, status=404)
                    except PermissionError as e:
                        resp = json_response({"error": str(e)}, status=403)
                    except Exception as e:  # noqa: BLE001
                        log.error("bulk-read http error: %s", e)
                        resp = json_response({"error": str(e)}, status=500)
                    t_done = time.perf_counter()
                    status = resp.status
                    if grant is not None and resp.body:
                        # the assembled frame is the byte cost of a bulk
                        # read — charged once known
                        grant.charge(len(resp.body))
                    return resp
                finally:
                    if qos_token is not None:
                        from .. import qos as qos_mod
                        qos_mod.reset_class(qos_token)
                    if grant is not None:
                        grant.release()
                    sp.set_attr("status", status)
                    if status >= 500:
                        sp.set_error(f"HTTP {status}")
                    t_end = time.perf_counter()
                    VOLUME_REQUEST_COUNTER.inc("bulk-read", str(status))
                    VOLUME_REQUEST_SECONDS.observe("bulk-read",
                                                   value=t_end - t0)
                    stages = _observe_stages("bulk-read", request.t_recv,
                                             request.t_parsed, t0,
                                             t_admit, t_done, t_end)
                    self._flight_record("volume.bulk-read", request,
                                        status, stages, sp,
                                        request.t_recv or t0, t_end)
                    hot_record(
                        volume=request.query.get("vid") or None,
                        tenant=self._qos_tenant_of_query(request.query),
                        method="bulk-read",
                        nbytes=(len(resp.body) if resp is not None
                                and resp.body else 0))

        app = fastweb.FastApp()
        app.route("/status", status)
        app.route("/ui", status_ui)
        app.route("/bulk", handle_bulk)
        app.route("/bulk-read", handle_bulk_read)
        app.route("/metrics", metrics)
        # pprof-style triggers (reference -debug.port net/http/pprof)
        app.route("/debug/profile", debug_profile)
        app.route("/debug/flight", debug_flight)
        app.route("/debug/jax-profiler", debug_jax_profiler)
        app.route("/debug/failpoints", debug_failpoints)
        app.route("/debug/traces", debug_traces)
        app.route("/debug/events", debug_events)
        app.route("/debug/locks", debug_locks)
        app.route("/debug/qos", debug_qos)
        app.route("/debug/lifecycle", debug_lifecycle)
        app.default(handle)
        fastweb.serve_fast_app(app, self.ip, self.port, self._stop,
                               client_max_size=256 << 20, logger=log,
                               on_loop=self._loop_lag.attach)

    # -- lifecycle heat report ----------------------------------------------
    def _set_destroy_time(self, vid: int, at: float) -> bool:
        """Stamp DestroyTime into a local EC volume's .vif + live
        object (one seam for the gRPC verb and the debug POST).
        False = no such EC volume here."""
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            return False
        from ..ec import files as ec_files
        ec_files.update_vif(ev.base + ".vif", {"destroy_time": at})
        ev.destroy_time = at
        return True

    def _lifecycle_payload(self) -> dict:
        """The planner's per-server input (served at /debug/lifecycle):
        heat AGES, never absolute clocks — monotonic read clocks and
        wall-clock needle timestamps both reduce to seconds-ago here so
        the planner compares apples across processes."""
        access = self.store.access_snapshot()
        now_wall = time.time()  # swtpu-lint: disable=wallclock-duration (needle timestamps are persisted wall-clock)
        now_mono = time.monotonic()
        vols: dict = {}
        ecs: dict = {}
        for loc in self.store.locations:
            for vid, v in list(loc.volumes.items()):
                a = access.get(vid, {})
                if v.last_append_at_ns:
                    write_age = max(0.0,
                                    now_wall - v.last_append_at_ns / 1e9)
                else:  # loaded sealed: the .dat mtime is the last write
                    try:
                        write_age = max(0.0, now_wall - os.path.getmtime(
                            v.dat_path))
                    except OSError:
                        write_age = None
                vols[str(vid)] = {
                    "collection": v.collection,
                    "size": v.content_size,
                    "read_only": v.read_only,
                    "tiered": v.remote_spec is not None,
                    "last_write_age_s": (round(write_age, 3)
                                         if write_age is not None
                                         else None),
                    "reads": a.get("reads", 0),
                    "last_read_age_s": a.get("last_read_age_s"),
                }
            for vid, ev in list(loc.ec_volumes.items()):
                a = access.get(vid, {})
                # read_age_s() extends the quiet period across restarts
                # via the .vif last-read stamp; the store's counter can
                # only SHORTEN it (a more recent read)
                ages = [ev.read_age_s()]
                if a.get("last_read_age_s") is not None:
                    ages.append(a["last_read_age_s"])
                remote = ev.remote_shard_ids()
                ecs[str(vid)] = {
                    "collection": ev.collection,
                    "local_shards": sorted(set(ev.shards) - set(remote)),
                    "remote_shards": remote,
                    "remote_spec": (ev.remote_spec or {}).get("spec", ""),
                    "remote_reads": ev.remote_reads(),
                    "reads": ev.reads,
                    "last_read_age_s": round(min(ages), 3),
                    "destroy_time": ev.destroy_time,
                    "shard_size": ev.shard_size,
                    "dat_size": ev.dat_size,
                }
        return {"server": self.url,
                "uptime_s": round(now_mono - self._started_mono, 3),
                "volumes": vols, "ec_volumes": ecs}

    # -- QoS helpers ---------------------------------------------------------
    def _qos_tenant(self, vid: int) -> str:
        """Tenant identity at the volume tier: the vid's collection
        ('default' for the unnamed collection and unknown vids)."""
        v = self.store.find_volume(vid)
        if v is None:
            ev = self.store.find_ec_volume(vid)
            return (ev.collection or "default") if ev is not None \
                else "default"
        return v.collection or "default"

    def _qos_tenant_of_path(self, path: str) -> str:
        try:
            vid = int(path.lstrip("/").split(",", 1)[0])
        except ValueError:
            return "default"
        return self._qos_tenant(vid)

    def _qos_tenant_of_query(self, query: dict) -> str:
        try:
            vid = int(query.get("vid", ""))
        except ValueError:
            return "default"
        return self._qos_tenant(vid)

    @staticmethod
    def _qos_shed_response(e):
        """503 + Retry-After, the volume-tier mirror of S3's SlowDown:
        the client (or SDK) backs off for the bucket's ETA."""
        from ..utils.fastweb import Response
        return Response(
            json.dumps({"error": str(e), "qos": "shed",
                        "retryAfterSeconds": e.retry_after_header}).encode(),
            status=503, content_type="application/json",
            headers={"Retry-After": e.retry_after_header})

    def _read_body(self, request):
        ct = request.headers.get("Content-Type") or ""
        name = mime = b""
        gzipped = False
        if ct.startswith("multipart/"):
            from ..utils.fastweb import parse_multipart_single
            data, filename, ptype, part_headers = parse_multipart_single(
                request.body, ct)
            name = filename.encode()
            if ptype and not ptype.startswith("multipart/"):
                mime = ptype.encode()
            gzipped = part_headers.get("Content-Encoding") == "gzip"
            return data, name, mime, gzipped
        data = request.body
        if ct and ct != "application/octet-stream":
            mime = ct.encode()
        gzipped = request.headers.get("Content-Encoding") == "gzip"
        name = (request.query.get("name") or "").encode()  # replicate fan-out
        return data, name, mime, gzipped

    async def _handle_write(self, request):
        from ..utils.fastweb import json_response

        fid = request.path.lstrip("/")
        if self.guard is not None:
            ok, why = self.guard.check_write(request.remote or "",
                                             request.query,
                                             request.headers, fid)
            if not ok:
                return json_response({"error": why}, status=401)
        vid, key, cookie = parse_file_id(fid)
        is_replicate = request.query.get("type") == "replicate"
        ttl = TTL.parse(request.query.get("ttl"))
        # ?fsync=true (reference UploadOption.Fsync, fed by a filer path
        # rule's fsync flag): this ack stands on a real fsync
        fsync = request.query.get("fsync") in ("true", "1")

        # body parse + needle serialization + the store write run
        # OFF-LOOP in one executor hop (contextvars carried): a multi-MB
        # chunk PUT is milliseconds of memcpy/crc (plus an fsync wait
        # when durable), and the filer's windowed upload fan-out sends
        # several at once — on-loop they serialized behind each other
        # and every other request
        def parse_and_write():
            data, name, mime, gzipped = self._read_body(request)
            n = Needle(id=key, cookie=cookie, data=data, name=name,
                       mime=mime, is_gzipped=gzipped, ttl=ttl)
            self.store.write_needle(vid, n, sync=fsync)
            return data, name, mime, gzipped, n

        import asyncio
        import contextvars
        ctx = contextvars.copy_context()
        loop = asyncio.get_running_loop()
        data, name, mime, gzipped, n = await loop.run_in_executor(
            None, ctx.run, parse_and_write)
        if not is_replicate:
            await self._replicate(fid, data, name, mime, gzipped,
                                  fsync=fsync)
        return json_response({"name": name.decode(errors="replace"),
                              "size": len(data),
                              "eTag": f"{n.checksum:x}"}, status=201)

    async def _replicate(self, fid: str, data: bytes, name: bytes,
                         mime: bytes, gzipped: bool,
                         fsync: bool = False) -> None:
        """Synchronous fan-out to replica peers (store_replicate.go:25),
        preserving the needle attributes (name/mime/gzip flag) and the
        durability mode (a ?fsync=true write is fsync'd on EVERY
        replica, or the ack overstates what a crash can keep)."""
        vid = int(fid.split(",")[0])
        # single-copy volumes need no peer lookup at all: the superblock
        # carries the xyz placement, and '000' means this write is final
        # (reference checks ReplicaPlacement.GetCopyCount() == 1 the same way)
        v = self.store.find_volume(vid)
        if v is not None and v.super_block.replica_placement.copy_count == 1:
            return
        peers = [u for u in self._lookup_replicas_cached(vid) if u != self.url]
        if not peers:
            return
        from .. import tracing

        headers = {"Content-Type": mime.decode() or "application/octet-stream"}
        if gzipped:
            headers["Content-Encoding"] = "gzip"

        async def send_one(sess, peer):
            url = f"http://{peer}/{fid}?type=replicate"
            if fsync:
                url += "&fsync=true"
            if name:
                url += "&" + urllib.parse.urlencode(
                    {"name": name.decode(errors="replace")})
            url += self._peer_jwt_param(fid)
            from .. import qos as qos_mod
            async with sess.post(
                    url, data=data,
                    headers=qos_mod.inject(tracing.inject(headers))) as r:
                return r.status

        await self._fan_out_to_peers(
            peers,
            lambda peer: {"peer": peer, "fid": fid, "bytes": len(data)},
            "replicate", send_one)

    async def _fan_out_to_peers(self, peers, span_attrs, desc,
                                send_one) -> None:
        """Shared synchronous replica fan-out envelope (reference
        store_replicate.go:25): EVERY peer must land or the write fails,
        so a transiently-flaky peer gets the retry envelope (jittered
        backoff, per-attempt timeout, one overall deadline bounding the
        whole fan-out) before we give up. Breakers record outcomes for
        observability but never skip a peer here — durability beats
        latency on the replica hop. A 3xx/4xx is a deterministic
        rejection (auth/config mismatch): the peer is alive and the
        identical retry can't succeed, so no breaker charge, no backoff,
        the write fails now. `send_one(sess, peer) -> status` performs
        one attempt; `span_attrs(peer)` labels the per-peer span."""
        import asyncio

        import aiohttp

        from .. import tracing

        pol = retry.WRITE_POLICY
        timeout = aiohttp.ClientTimeout(total=pol.attempt_timeout)
        deadline = time.monotonic() + pol.deadline
        async with aiohttp.ClientSession(auto_decompress=False,
                                         timeout=timeout) as sess:
            for peer in peers:
                br = retry.breaker(peer)
                last_err: Exception | None = None
                # one child span per replica hop: a slow or retried write
                # shows WHICH peer cost it directly in the trace
                with tracing.start_span(
                        "volume.replicate", component="volume",
                        attrs=span_attrs(peer)) as sp:
                    for attempt in range(1, pol.max_attempts + 1):
                        try:
                            # failpoint: a dead replica peer without
                            # killing a real process — drives write-path
                            # failure handling
                            failpoints.check("replicate.peer")
                            status = await send_one(sess, peer)
                            if 300 <= status < 500:
                                last_err = OSError(f"{desc} to {peer}: "
                                                   f"HTTP {status}")
                                break
                            if status >= 500:
                                raise OSError(f"{desc} to {peer}: "
                                              f"HTTP {status}")
                            br.record_success()
                            retry.BUDGET.deposit()
                            last_err = None
                            break
                        except Exception as e:  # noqa: BLE001
                            br.record_failure()
                            last_err = e
                            delay = pol.backoff(attempt)
                            if (attempt >= pol.max_attempts
                                    or time.monotonic() + delay > deadline
                                    or not retry.BUDGET.withdraw()):
                                break
                            try:
                                from ..stats import RETRY_ATTEMPTS
                                RETRY_ATTEMPTS.inc("replicate.peer")
                            except Exception:  # noqa: BLE001  # swtpu-lint: disable=silent-except (metrics must never break IO)
                                pass
                            sp.add_event("retry", op="replicate.peer",
                                         attempt=attempt,
                                         breaker=br.state,
                                         delay_ms=round(delay * 1e3, 2),
                                         error=str(e)[:200])
                            await asyncio.sleep(delay)
                    if last_err is not None:
                        sp.set_error(last_err)
                if last_err is not None:
                    raise OSError(f"{desc} to {peer} failed after "
                                  f"retries: {last_err}")

    def _peer_jwt_param(self, fid: str) -> str:
        """Replica fan-out re-mints a write token with the shared signing key
        (reference store_replicate.go forwards the request's jwt; peers share
        the key, so minting locally is equivalent and survives expiry)."""
        if self.guard is None or not self.guard.signing_key:
            return ""
        from ..security import gen_jwt_for_volume_server
        tok = gen_jwt_for_volume_server(self.guard.signing_key,
                                        self.guard.expires_after_sec, fid)
        return "&jwt=" + urllib.parse.quote(tok)

    # -- bulk ingest data plane (batched control plane, ISSUE 7) -----------
    async def _handle_bulk(self, request, sp):
        """One framed bulk-PUT: N needles land under a single volume-lock
        acquisition with one batched needle-map update and ONE fsync
        (storage/volume.py write_needles), the range JWT is validated
        once for the whole frame, and replicas receive the frame in one
        fan-out hop instead of N. This is where the per-needle ~115 us
        of PUT protocol amortizes to ~115/N us."""
        from ..utils.fastweb import json_response

        if request.method not in ("POST", "PUT"):
            return json_response({"error": "method not allowed"}, status=405)
        # chaos arm: the volume server dying mid-bulk-PUT — nothing
        # written, no ack; the client must re-lease and burn the fids
        failpoints.check("volume.bulk.put")
        from ..storage import bulk as bulk_frame

        # frame parse + per-needle crc32c is real CPU at 8 MB frames —
        # run it off-loop like the write below, or concurrent bulk
        # clients head-of-line-block every read on this server
        import asyncio
        import contextvars
        loop = asyncio.get_running_loop()
        ctx = contextvars.copy_context()
        try:
            vid, entries = await loop.run_in_executor(
                None, ctx.run, bulk_frame.unpack_frame,
                request.body or b"")
        except bulk_frame.FrameError as e:
            return json_response({"error": str(e)}, status=400)
        q_vid = request.query.get("vid", "")
        try:
            if q_vid and int(q_vid) != vid:
                return json_response(
                    {"error": f"query vid {q_vid} != frame vid {vid}"},
                    status=400)
        except ValueError:
            return json_response({"error": f"bad vid {q_vid!r}"},
                                 status=400)
        cookies = {e.cookie for e in entries}
        if len(cookies) != 1:
            # a lease shares ONE cookie across its range; mixed cookies
            # means a stitched frame — reject before the auth check
            return json_response({"error": "mixed cookies in frame"},
                                 status=400)
        keys = [e.key for e in entries]
        cookie = entries[0].cookie
        sp.set_attr("vid", vid)
        sp.set_attr("needles", len(entries))
        if self.guard is not None:
            # ONE token validation covers the whole frame (range JWT)
            ok, why = self.guard.check_bulk(request.remote or "",
                                            request.query, request.headers,
                                            vid, keys, cookie)
            if not ok:
                return json_response({"error": why}, status=401)
        ttl_str = request.query.get("ttl") or ""
        ttl = TTL.parse(ttl_str)
        is_replicate = request.query.get("type") == "replicate"

        # needle construction + the batched append + frame fsync run
        # off-loop in ONE executor hop (contextvars carried so the
        # storage failpoints/trace stay under this span)
        def build_and_write():
            needles = [Needle(id=e.key, cookie=e.cookie,
                              data=bytes(e.data),
                              is_gzipped=bool(e.flags & 0x01), ttl=ttl)
                       for e in entries]
            return self.store.write_needles_bulk(vid, needles)

        await loop.run_in_executor(None, ctx.run, build_and_write)
        if not is_replicate:
            await self._replicate_bulk(vid, request.body, keys, cookie,
                                       ttl_str)
        # chaos arm: ack lost AFTER the frame is durable everywhere —
        # the client burns the fids; the needles stay readable orphans
        failpoints.check("volume.bulk.ack")
        from ..stats import BULK_PUT_NEEDLES
        BULK_PUT_NEEDLES.observe(value=len(entries))
        from ..ops import events
        events.emit("bulk.put", vid=vid, needles=len(entries),
                    bytes=len(request.body), node=self.url,
                    replicate=is_replicate)
        return json_response(
            {"count": len(entries),
             "eTags": [f"{e.crc:x}" for e in entries]}, status=201)

    async def _replicate_bulk(self, vid: int, body: bytes,
                              keys: "list[int]", cookie: int,
                              ttl_str: str = "") -> None:
        """Synchronous replica fan-out of a WHOLE bulk frame: one hop
        per peer instead of one per needle, under the same retry
        envelope + all-replicas-or-fail semantics as _replicate."""
        v = self.store.find_volume(vid)
        if v is not None and v.super_block.replica_placement.copy_count == 1:
            return
        peers = [u for u in self._lookup_replicas_cached(vid)
                 if u != self.url]
        if not peers:
            return
        from .. import tracing

        url_tail = f"&type=replicate{self._peer_range_jwt_param(vid, keys, cookie)}"
        if ttl_str:
            # replicas must store the SAME ttl or the copies diverge
            # in expiry semantics
            url_tail += "&ttl=" + urllib.parse.quote(ttl_str)

        async def send_one(sess, peer):
            from .. import qos as qos_mod
            async with sess.put(f"http://{peer}/bulk?vid={vid}{url_tail}",
                                data=body,
                                headers=qos_mod.inject(
                                    tracing.inject({}))) as r:
                return r.status

        await self._fan_out_to_peers(
            peers,
            lambda peer: {"peer": peer, "vid": vid,
                          "bulk_needles": len(keys), "bytes": len(body)},
            "bulk replicate", send_one)

    def _peer_range_jwt_param(self, vid: int, keys: "list[int]",
                              cookie: int) -> str:
        """Range token for the bulk replica hop, minted locally with the
        shared signing key over the frame's [min, max] key span."""
        if self.guard is None or not self.guard.signing_key:
            return ""
        from ..security import gen_jwt_for_fid_range
        lo = min(keys)
        tok = gen_jwt_for_fid_range(
            self.guard.signing_key,
            max(30, self.guard.expires_after_sec),
            vid, lo, max(keys) - lo + 1, cookie)
        return "&jwt=" + urllib.parse.quote(tok)

    # -- bulk read data plane (read-side mirror of /bulk, ISSUE 9) ----------
    async def _handle_bulk_read(self, request, sp):
        """One framed bulk GET: the client names a vid + (key, cookie)
        list ("SWBR"), the server resolves the whole batch in one index
        pass over the lock-free read path and streams every found
        needle back in a single length-prefixed frame ("SWBG") with a
        per-needle status for misses/deleted — the read-side mirror of
        the /bulk ingest plane, amortizing the per-GET HTTP protocol
        N-fold. Hot needles come out of the read cache without touching
        the volume file at all."""
        from ..utils.fastweb import Response, json_response

        if request.method not in ("POST", "PUT"):
            return json_response({"error": "method not allowed"}, status=405)
        # chaos arm: the volume server dying mid-bulk-read — the client
        # fails over to a replica holder
        failpoints.check("volume.bulk.read")
        from ..storage import bulk as bulk_frame
        try:
            vid, pairs = bulk_frame.unpack_read_request(request.body or b"")
        except bulk_frame.FrameError as e:
            return json_response({"error": str(e)}, status=400)
        q_vid = request.query.get("vid", "")
        try:
            if q_vid and int(q_vid) != vid:
                return json_response(
                    {"error": f"query vid {q_vid} != frame vid {vid}"},
                    status=400)
        except ValueError:
            return json_response({"error": f"bad vid {q_vid!r}"},
                                 status=400)
        sp.set_attr("vid", vid)
        sp.set_attr("needles", len(pairs))
        if self.guard is not None:
            # read tokens are per-fid: the frame is admitted only if the
            # caller is whitelisted or its token covers EVERY fid in the
            # frame — the exact scoping the per-needle GET enforces, so
            # /bulk-read can never widen one fid's token into a
            # read-everything pass (check_read short-circuits before any
            # decode when read security is off)
            from ..storage.types import file_id as _file_id
            for key, cookie in pairs:
                ok, why = self.guard.check_read(
                    request.remote or "", request.query, request.headers,
                    _file_id(vid, key, cookie))
                if not ok:
                    return json_response({"error": why}, status=401)
        if (self.store.find_volume(vid) is None
                and self.store.find_ec_volume(vid) is None):
            # no proxy hop for frames: the client fans out by vid and
            # fails over to replica holders itself
            return json_response({"error": f"volume {vid} not local"},
                                 status=404)
        import asyncio
        import contextvars
        loop = asyncio.get_running_loop()
        ctx = contextvars.copy_context()
        body, hits = await loop.run_in_executor(
            self._read_pool, ctx.run, self._bulk_read_frame, vid, pairs)
        sp.set_attr("cache_hits", hits)
        from ..stats import BULK_READ_NEEDLES
        BULK_READ_NEEDLES.observe(value=len(pairs))
        return Response(body, content_type="application/octet-stream")

    def _bulk_read_frame(self, vid: int,
                         pairs: "list[tuple[int, int]]",
                         ) -> "tuple[bytes, int]":
        """Resolve one bulk-read frame (runs on the read pool): cache
        hits first, then ONE batched storage pass for the misses, cache
        fills on the way out. A per-frame byte budget
        (SWTPU_BULK_READ_FRAME_BYTES, 32 MB) bounds what one frame can
        materialize — found needles past it come back READ_OVERFLOW
        unread and the client re-fetches them per-needle, so a frame of
        large objects can't OOM the server across read-pool threads.
        Returns (response_frame, cache_hits)."""
        from ..storage import bulk as bulk_frame
        from ..storage.needle import FLAG_GZIP
        from ..utils.env import env_int

        budget = env_int("SWTPU_BULK_READ_FRAME_BYTES", 32 << 20)
        cache = (self.read_cache
                 if self.store.find_volume(vid) is not None else None)
        results: "list[tuple[int, int, int, int, bytes] | None]" = \
            [None] * len(pairs)
        misses: "list[int]" = []
        hits = 0
        used = 0
        epoch = cache.epoch(vid) if cache is not None else None
        for i, (key, cookie) in enumerate(pairs):
            n = cache.get(vid, key, cookie) if cache is not None else None
            if n is not None:
                # hits consume the frame budget too: the response join
                # is the allocation the budget bounds, and a frame
                # naming hot keys (or one key repeatedly) must not
                # assemble more than the cap
                if used >= budget:
                    results[i] = (key, cookie, bulk_frame.READ_OVERFLOW,
                                  0, b"")
                    continue
                hits += 1
                used += len(n.data)
                results[i] = (key, cookie, bulk_frame.READ_OK,
                              FLAG_GZIP if n.is_gzipped else 0, n.data)
            else:
                misses.append(i)
        if hits:
            # cache hits never reach the store: feed the lifecycle heat
            # counters (misses are counted inside read_needles_bulk)
            self.store.note_read(vid, n=hits)
        if misses:
            got = self.store.read_needles_bulk(
                vid, [pairs[i] for i in misses],
                shard_reader=self._make_shard_reader(vid),
                byte_budget=max(0, budget - used))
            for i, (st, n) in zip(misses, got):
                key, cookie = pairs[i]
                if st == bulk_frame.READ_OK:
                    results[i] = (key, cookie, st,
                                  FLAG_GZIP if n.is_gzipped else 0, n.data)
                    if cache is not None:
                        cache.put(vid, key, n, epoch=epoch)
                else:
                    results[i] = (key, cookie, st, 0, b"")
        return bulk_frame.pack_read_response(vid, results), hits

    def _lookup_replicas_cached(self, vid: int) -> list[str]:
        """Replica sets move only on evacuate/rebalance; a short-TTL cache
        keeps the per-write master round-trip off the hot path."""
        now = time.monotonic()
        hit = self._replica_cache.get(vid)
        if hit is not None and now - hit[0] < 5.0:
            return hit[1]
        urls = self._lookup_replicas(vid)
        self._replica_cache[vid] = (now, urls)
        return urls

    def _lookup_replicas(self, vid: int) -> list[str]:
        try:
            stub = Stub(self.current_leader, MASTER_SERVICE)
            resp = stub.call("LookupVolume",
                             mpb.LookupVolumeRequest(volume_or_file_ids=[str(vid)]),
                             mpb.LookupVolumeResponse, timeout=5)
            for e in resp.volume_id_locations:
                return [loc.url for loc in e.locations]
        except Exception as e:  # noqa: BLE001
            log.warning("replica lookup vid=%d failed: %s", vid, e)
        return []

    @staticmethod
    def _parse_range(value: "str | None"):
        """One single-range `bytes=` spec, or None for absent / invalid /
        multi-range (those serve the full body, per RFC 7233's allowance
        to ignore unsupported Range headers). Returns ("suffix", n) |
        ("from", start) | ("range", start, last)."""
        if not value or not value.startswith("bytes="):
            return None
        spec = value[len("bytes="):].strip()
        if "," in spec:
            return None
        first, sep, last = spec.partition("-")
        if not sep:
            return None
        first, last = first.strip(), last.strip()
        try:
            if not first:
                n = int(last)
                return ("suffix", n) if n > 0 else None
            start = int(first)
            if start < 0:
                return None
            if not last:
                return ("from", start)
            stop = int(last)
            return ("range", start, stop) if stop >= start else None
        except ValueError:
            return None

    @staticmethod
    def _resolve_range(spec, size: int) -> "tuple[int, int] | None":
        """[start, stop) byte window of `spec` over a `size`-byte body,
        or None when unsatisfiable (RFC 7233: start past the end)."""
        if spec[0] == "suffix":
            if size == 0:
                return None
            return max(0, size - spec[1]), size
        start = spec[1]
        if start >= size:
            return None
        if spec[0] == "from":
            return start, size
        return start, min(spec[2] + 1, size)

    async def _handle_read(self, request):
        import asyncio
        import contextvars

        from .. import tracing
        from ..utils.fastweb import Response, json_response

        fid = request.path.lstrip("/")
        if self.guard is not None:
            ok, why = self.guard.check_read(request.remote or "",
                                            request.query,
                                            request.headers, fid)
            if not ok:
                return json_response({"error": why}, status=401)
        vid, key, cookie = parse_file_id(fid)
        # hot-needle cache sits in front of the storage read for LOCAL
        # plain volumes only: EC/degraded and proxied reads stream
        # uncached (their bytes still flow through the identical
        # serve/Range logic below, so the response is path-invariant)
        cache = self.read_cache
        cacheable = (cache is not None
                     and self.store.find_volume(vid) is not None)
        n = None
        epoch = None
        if cacheable:
            n = cache.get(vid, key, cookie)
            if n is not None:
                # cache hits never reach the store: feed the lifecycle
                # heat counters here or hot volumes would read as cold
                self.store.note_read(vid)
            sp = tracing.current_span()
            if sp is not None:
                sp.set_attr("cache", "hit" if n is not None else "miss")
        try:
            if n is None:
                if cacheable:
                    # epoch BEFORE the storage read: a mutation landing
                    # in between invalidates this fill (read_cache.put)
                    epoch = cache.epoch(vid)
                loop = asyncio.get_running_loop()
                ctx = contextvars.copy_context()
                # storage read off-loop on the parallel read pool: the
                # seqlock read path never touches the volume lock, so
                # concurrent GETs proceed while a writer fsyncs
                n = await loop.run_in_executor(
                    self._read_pool, ctx.run, self._store_read,
                    vid, key, cookie, request.method.lower())
                if epoch is not None:
                    cache.put(vid, key, n, epoch=epoch)
        except KeyError:
            if (self.store.find_volume(vid) is not None
                    or self.store.find_ec_volume(vid) is not None):
                # the VOLUME is local, so this server is an authoritative
                # replica: a missing/deleted needle is a definitive 404.
                # Proxying here would ping-pong between replicas that
                # each re-proxy — a livelock on read-after-delete (write
                # fan-out fails the whole write on any replica failure,
                # so replicas can't silently diverge on live needles).
                raise
            if request.query.get("proxied"):
                raise  # one forwarding hop max: never proxy a proxy
            # volume not local: proxy or redirect by master lookup (ReadMode)
            return await self._read_remote(request, fid, vid)
        except OSError as e:
            # degraded EC read that couldn't gather d shards from HERE —
            # another holder may reach a different shard subset, so fail
            # over unless this request is already a forwarded hop. When
            # no failover exists (local read mode, sole holder, already
            # proxied) answer 503, NOT 404: the object is recoverable,
            # and a 404 would read as "deleted" to clients and filers.
            if (self.store.find_ec_volume(vid) is not None
                    and not request.query.get("proxied")
                    and self.read_mode != "local"
                    and [u for u in self._lookup_replicas(vid)
                         if u != self.url]):
                return await self._read_remote(request, fid, vid)
            return json_response({"error": str(e)}, status=503)
        body = n.data
        headers = {}
        if n.name:
            headers["Content-Disposition"] = f'inline; filename="{n.name.decode(errors="replace")}"'
        # on-the-fly image ops need uncompressed bytes (reference
        # conditionallyResizeImages, volume_server_handlers_read.go:321);
        # a resize request therefore forces decompression of gzip needles.
        name = n.name.decode(errors="replace") if n.name else ""
        ext = os.path.splitext(name)[1].lower()
        w = h = 0
        mode, do_resize = "", False
        if ext:
            from ..images import should_resize
            w, h, mode, do_resize = should_resize(ext, request.query)
        # Range semantics are computed on the FINAL identity bytes this
        # handler assembled, after the gzip/resize decisions — so the
        # answer is byte-identical whether the needle came from the
        # cache, a lock-free volume pread, or a degraded EC reconstruct.
        # A ranged read of a gzip needle serves identity (sliced
        # compressed bytes would be useless to a client).
        rng_spec = None if do_resize else self._parse_range(
            request.headers.get("Range"))
        gzip_ok = "gzip" in (request.headers.get("Accept-Encoding") or "")
        if n.is_gzipped and (do_resize or rng_spec is not None
                             or not gzip_ok):
            import gzip as _gz
            body = _gz.decompress(body)
        elif n.is_gzipped:
            headers["Content-Encoding"] = "gzip"
        if do_resize:
            from ..images import fix_jpeg_orientation, resized
            if ext in (".jpg", ".jpeg"):
                # bake EXIF rotation only when we re-encode anyway — the
                # plain read path serves stored bytes untouched
                body = fix_jpeg_orientation(body)
            body = resized(ext, body, w, h, mode)
        status = 200
        if rng_spec is not None:
            window = self._resolve_range(rng_spec, len(body))
            if window is None:
                return Response(
                    b"", status=416,
                    headers={"Content-Range": f"bytes */{len(body)}"},
                    content_type="application/octet-stream")
            start, stop = window
            headers["Content-Range"] = \
                f"bytes {start}-{stop - 1}/{len(body)}"
            body = body[start:stop]
            status = 206
        return Response(body, status=status, headers=headers or None,
                        content_type=(n.mime.decode() if n.mime else
                                      "application/octet-stream"))

    def _store_read(self, vid: int, key: int, cookie: "int | None",
                    kind: str = "get"):
        """Blocking storage read (runs on the read pool), timed there:
        with the pool's queue wait it splits the request's `store` stage
        into queue, read and the way back onto the loop. Every
        microsecond of Python here is one the event loop waits for the
        GIL: no exemplar lookup, few buckets."""
        t0 = time.perf_counter()
        try:
            return self.store.read_needle(
                vid, key, cookie=cookie,
                shard_reader=self._make_shard_reader(vid))
        finally:
            VOLUME_STORE_READ_SECONDS.observe(
                kind, value=time.perf_counter() - t0, trace_id="")

    async def _read_remote(self, request, fid: str, vid: int):
        from ..utils.fastweb import Redirect, Response, json_response

        if self.read_mode == "local":
            return json_response({"error": f"volume {vid} not local"},
                                 status=404)
        # known-dead holders go last on the proxy/redirect hop too
        peers = retry.order_by_breaker(
            [u for u in self._lookup_replicas(vid) if u != self.url])
        if not peers:
            return json_response({"error": f"volume {vid} not found"},
                                 status=404)
        # preserve the caller's query (jwt, resize params, …) on
        # proxy/redirect, marking the hop so the receiver never forwards
        # again (bounds the proxy chain at one hop — no ping-pong)
        qs = request.query_string
        qs = (f"{qs}&" if qs else "") + "proxied=1"
        suffix = f"?{qs}"
        if self.read_mode == "redirect":
            raise Redirect(f"http://{peers[0]}/{fid}{suffix}", status=301)
        import aiohttp

        timeout = aiohttp.ClientTimeout(
            total=retry.READ_POLICY.attempt_timeout)
        from .. import tracing
        # the Range header must survive the proxy hop (and its
        # Content-Range/-Encoding must survive the way back) or ranged
        # reads would silently widen to full bodies on proxied volumes
        fwd = {}
        for h in ("Range", "Accept-Encoding"):
            val = request.headers.get(h)
            if val:
                fwd[h] = val
        # skip aiohttp's default Accept-Encoding — only the CLIENT's own
        # header may reach the origin, or a gzip-stored needle comes back
        # compressed to a caller that never advertised gzip (with
        # auto_decompress off, nobody would decompress it)
        async with aiohttp.ClientSession(
                timeout=timeout, auto_decompress=False,
                skip_auto_headers=("Accept-Encoding",)) as sess:
            last_err: Exception | None = None
            for peer in peers:
                br = retry.breaker(peer)
                try:
                    async with sess.get(f"http://{peer}/{fid}{suffix}",
                                        headers=tracing.inject(fwd)) as r:
                        body = await r.read()
                        br.record_success()
                        back = {}
                        for h in ("Content-Range", "Content-Encoding",
                                  "Content-Disposition"):
                            if h in r.headers:
                                back[h] = r.headers[h]
                        return Response(
                            body, status=r.status, headers=back or None,
                            content_type=(r.content_type
                                          or "application/octet-stream"))
                except Exception as e:  # noqa: BLE001
                    br.record_failure()
                    last_err = e
            return json_response(
                {"error": f"proxy read vid {vid} failed: {last_err}"},
                status=502)

    async def _handle_delete(self, request):
        from ..utils.fastweb import json_response

        fid = request.path.lstrip("/")
        if self.guard is not None:
            ok, why = self.guard.check_write(request.remote or "",
                                             request.query,
                                             request.headers, fid)
            if not ok:
                return json_response({"error": why}, status=401)
        vid, key, _ = parse_file_id(fid)
        is_replicate = request.query.get("type") == "replicate"
        v = self.store.find_volume(vid)
        if v is not None:
            ok = self.store.delete_needle(vid, key)
        else:
            ev = self.store.find_ec_volume(vid)
            if ev is None:
                raise KeyError(f"volume {vid} not local")
            ok = ev.delete_needle(key)
        # fan out even when the needle wasn't found locally (reference
        # ReplicatedDelete): a replica that missed an earlier delete's
        # best-effort fan-out still holds the needle, and re-deleting
        # through any holder must converge the set, not just this copy
        if not is_replicate:
            peers = [u for u in self._lookup_replicas(vid) if u != self.url]
            if peers:
                import aiohttp

                timeout = aiohttp.ClientTimeout(
                    total=retry.WRITE_POLICY.attempt_timeout)
                async with aiohttp.ClientSession(timeout=timeout) as sess:
                    for peer in peers:
                        try:
                            # failpoint: a replica missing the delete
                            # fan-out (the tombstone heals on the next
                            # write/vacuum) — per-peer best effort, the
                            # local delete already succeeded
                            failpoints.check("replicate.delete.peer")
                            await sess.delete(
                                f"http://{peer}/{fid}?type=replicate"
                                + self._peer_jwt_param(fid))
                        except Exception as e:  # noqa: BLE001
                            log.warning("delete fan-out to %s: %s", peer, e)
        return json_response({"size": 1 if ok else 0}, status=202)

    # -- EC shard reader: remote fetch + degraded reconstruct ---------------
    def _fetch_remote_shard(self, vid: int, sid: int, offset: int,
                            length: int, holders: "list[str]",
                            include_open: bool = False,
                            into=None) -> "bytes | int | None":
        """The range as `bytes`, or, with `into` (the [n, width] rows of
        ec/repair.py's `readinto` contract), landed there message by
        message and returned as the count of bytes the stream carried.
        None: no holder served it."""
        # one span per shard fetch: a degraded read's trace shows every
        # attempted shard as a child, INCLUDING the failed/missing ones
        # (status=error with the per-holder failures as events)
        from .. import tracing
        with tracing.start_span(
                "ec.shard.fetch", component="volume",
                attrs={"vid": vid, "shard": sid, "offset": offset,
                       "length": length, "holders": len(holders)}) as sp:
            data = self._fetch_remote_shard_inner(vid, sid, offset, length,
                                                  holders, include_open, sp,
                                                  into)
            if data is None:
                sp.set_error("no holder served shard"
                             if holders else "shard has no holders")
            return data

    def _fetch_remote_shard_inner(self, vid: int, sid: int, offset: int,
                                  length: int, holders: "list[str]",
                                  include_open: bool,
                                  sp, into=None) -> "bytes | int | None":
        try:
            # failpoint: shard fetch failure -> the caller's degraded
            # reconstruct-from-d-others path, without destroying a shard
            failpoints.check("ec.shard.read")
        except failpoints.FailpointError as e:
            log.warning("ec shard %d.%d read failpoint: %s", vid, sid, e)
            sp.add_event("failpoint", error=str(e)[:200])
            return None
        # circuit-open holders are SKIPPED entirely (returning None sends
        # the caller down the reconstruct path — that's the graceful
        # degradation: a known-dead shard peer must not cost a connect
        # timeout per read). `include_open=True` is the reconstruct
        # path's last resort when the healthy shards alone can't reach d.
        ordered = retry.order_by_breaker(holders)
        if not include_open:
            allowed = []
            for addr in ordered:
                br = retry.breaker(addr)
                if br.would_allow():
                    allowed.append(addr)
                else:
                    sp.add_event("breaker_open", peer=addr,
                                 state=br.state)
            ordered = allowed
        for addr in ordered:
            br = retry.breaker(addr)
            try:
                stub = Stub(addr, VOLUME_SERVICE)
                stream = stub.call_stream(
                    "VolumeEcShardRead",
                    vpb.VolumeEcShardReadRequest(
                        volume_id=vid, shard_id=sid,
                        offset=offset, size=length),
                    vpb.VolumeEcShardReadResponse)
                if into is None:
                    data = b"".join([r.data for r in stream])
                else:
                    # each message goes to its place in the caller's rows
                    # as it arrives: no list of parts, no joined copy. A
                    # holder that failed part way is overwritten from the
                    # range's start by the next one
                    got = 0
                    for r in stream:
                        got = ec_repair.land(into, got, r.data, length)
                br.record_success()
                sp.set_attr("holder", addr)
                # corrupt site: bit-flips on the shard wire — the needle
                # CRC downstream must catch what reconstruction produces
                if into is None:
                    return failpoints.corrupt("ec.shard.read.data", data)
                if failpoints.armed("ec.shard.read.data"):
                    n = min(got, length)
                    ec_repair.land(into, 0, failpoints.corrupt(
                        "ec.shard.read.data",
                        into.reshape(-1)[:n].tobytes()), n)
                return got
            except Exception as e:  # noqa: BLE001
                br.record_failure()
                sp.add_event("holder_failed", peer=addr,
                             error=str(e)[:200])
                log.warning("remote shard %d.%d read from %s: %s",
                            vid, sid, addr, e)
        return None

    def _make_shard_reader(self, vid: int):
        from .. import tracing

        def reader(shard_id: int, offset: int, length: int) -> bytes:
            locs = self._lookup_ec_shards(vid)
            data = self._fetch_remote_shard(vid, shard_id, offset, length,
                                            locs.get(shard_id, []))
            if data is None and locs.get(shard_id):
                # holders listed but unreachable: locations may be stale
                # (11 s tier, store_ec.go:263) — refresh once and retry
                fresh = self._lookup_ec_shards(vid, failed=True)
                if fresh.get(shard_id, []) != locs.get(shard_id, []):
                    tracing.add_event("stale_locations_refreshed", vid=vid,
                                      shard=shard_id)
                    data = self._fetch_remote_shard(
                        vid, shard_id, offset, length,
                        fresh.get(shard_id, []))
                locs = fresh
            if data is not None:
                return data
            # degraded read: reconstruct this interval from >= d other
            # shards fetched CONCURRENTLY (store_ec.go:357-400 fans out
            # one goroutine per shard; sequential fetches would stack one
            # RTT per shard onto the degraded p99)
            with tracing.start_span(
                    "ec.reconstruct", component="volume",
                    attrs={"vid": vid, "shard": shard_id, "offset": offset,
                           "length": length}) as sp:
                return _reconstruct(shard_id, offset, length, locs, sp)

        def _reconstruct(shard_id: int, offset: int, length: int,
                         locs: dict, sp) -> bytes:
            ev = self.store.find_ec_volume(vid)
            if ev is None:
                raise KeyError(f"shard {shard_id} unreachable")
            geo = ev.geo
            if ev.codec == "msr":
                # the coupled code is not positional: degraded reads
                # fetch the interval plan's layer slices (repair planes
                # when all n-1 helpers answer, a closure-restricted
                # general decode otherwise)
                return _reconstruct_msr(ev, shard_id, offset, length,
                                        locs, sp)
            piggybacked = ev.codec == "piggyback"
            coder = self.store.coder(geo.d, geo.p, codec=ev.codec)
            inner = coder.inner if piggybacked else coder
            where = ec_degraded.where(inner)
            sp.set_attr("where", where)
            acct = StageAccount("degraded", ("gather", "decode"))
            with acct.stage("gather"):
                gathered: dict[int, bytes] = {}
                remote_sids = []
                for sid in range(geo.n):
                    if sid == shard_id:
                        continue
                    local = ev.shards.get(sid)
                    if local is not None and len(gathered) < geo.d:
                        gathered[sid] = local.read_at(offset, length)
                    elif local is None:
                        remote_sids.append(sid)
                sp.set_attr("local_shards", len(gathered))
                # piggybacked volumes: shards 0..d (data + the
                # unpiggybacked parity) decode positionally anywhere, so
                # fetch those first and touch piggybacked parities only
                # when the plain set cannot reach d (they need a paired
                # a-range fetch to strip)
                if piggybacked:
                    waves = [[s for s in remote_sids if s <= geo.d],
                             [s for s in remote_sids if s > geo.d]]
                else:
                    waves = [remote_sids]
                for wave in waves:
                    if len(gathered) >= geo.d or not wave:
                        continue
                    import concurrent.futures as cf
                    import contextvars
                    # copy_context per submit: the pool threads' fetch
                    # spans must land under THIS reconstruct span, not as
                    # orphan roots (ThreadPoolExecutor does not propagate
                    # contextvars)
                    futs = {}
                    for sid in wave:
                        ctx = contextvars.copy_context()
                        futs[self._ec_read_pool.submit(
                            ctx.run, self._fetch_remote_shard, vid, sid,
                            offset, length, locs.get(sid, []))] = sid
                    for fut in cf.as_completed(futs):
                        data = fut.result()
                        if data is not None:
                            gathered[futs[fut]] = data
                        if len(gathered) >= geo.d:
                            for f in futs:  # stop burning pool workers on
                                f.cancel()  # fetches nobody will use
                            break
                if len(gathered) < geo.d:
                    # healthy shards alone can't reach d: as a last
                    # resort probe the circuit-open holders too — an open
                    # breaker should cost latency, never turn a
                    # recoverable read into an error
                    for sid in remote_sids:
                        if sid in gathered or len(gathered) >= geo.d:
                            continue
                        data = self._fetch_remote_shard(
                            vid, sid, offset, length, locs.get(sid, []),
                            include_open=True)
                        if data is not None:
                            gathered[sid] = data
            sp.set_attr("gathered", len(gathered))
            sp.set_attr("needed", geo.d)
            if len(gathered) < geo.d:
                # availability failure, NOT a lookup miss: OSError so the
                # read handler fails over to another holder instead of
                # reporting a recoverable object as 404/deleted
                raise OSError(
                    f"cannot reconstruct shard {shard_id}: only "
                    f"{len(gathered)} shards reachable")
            present = tuple(sorted(gathered))[:geo.d]
            from ..stats import DEGRADED_EC_READS
            if piggybacked and any(s > geo.d for s in present):
                # a piggybacked parity is load-bearing: strip its
                # piggyback with the paired a-range (ec/repair.py)
                from ..ec import repair as ec_repair

                def fetch_pair(sid: int, off: int, ln: int) -> bytes:
                    local = ev.shards.get(sid)
                    if local is not None:
                        return local.read_at(off, ln)
                    return self._fetch_range_or_raise(vid, sid, off, ln,
                                                      locs.get(sid, []))
                def fetch_map(fn, reqs):
                    # same fan-out discipline as the gather waves above:
                    # one serial RTT per paired range would stack onto
                    # the degraded p99 (copy_context keeps fetch spans
                    # under this reconstruct span)
                    import contextvars
                    futs = [self._ec_read_pool.submit(
                        contextvars.copy_context().run, fn, *r)
                        for r in reqs]
                    return [f.result() for f in futs]
                sp.set_attr("piggyback_strip", True)
                out_b = ec_repair.reconstruct_interval(
                    coder, {s: gathered[s] for s in present}, shard_id,
                    offset, length, ev.shard_size, fetch_pair,
                    fetch_map=fetch_map)
                DEGRADED_EC_READS.inc()
                return out_b
            with acct.stage("decode", needed=length * (geo.d + 1),
                            cols=length, where=where):
                out = ec_degraded.decode_interval(
                    inner, [gathered[s] for s in present], present,
                    (shard_id,))
            for stage in ("gather", "decode"):
                EC_RECONSTRUCT_SECONDS.observe(stage, where,
                                               value=acct.seconds(stage))
            EC_RECONSTRUCT_BYTES.inc(where, amount=length)
            DEGRADED_EC_READS.inc()
            return out[0].tobytes()

        def _fetch_plan(ev, plan, locs) -> "dict[int, bytes | None]":
            """Gather one IntervalPlan's per-survivor fragments — local
            shards by pread, remote by ranged-compute fetch — fanned out
            on the EC read pool. None entries mark unreachable helpers."""
            import concurrent.futures as cf
            import contextvars

            def one(sid: int) -> "bytes | None":
                ranges = plan.byte_ranges(sid)
                local = ev.shards.get(sid)
                try:
                    if local is not None:
                        return b"".join(local.read_at(o, ln)
                                        for o, ln in ranges)
                    return self._fetch_fragment_or_raise(
                        vid, sid, ranges, locs.get(sid, []))
                except Exception as e:  # noqa: BLE001
                    log.warning("msr fragment %d.%d: %s", vid, sid, e)
                    return None

            futs = {self._ec_read_pool.submit(
                contextvars.copy_context().run, one, sid): sid
                for sid in plan.fetch}
            return {futs[f]: f.result() for f in cf.as_completed(futs)}

        def _reconstruct_msr(ev, shard_id: int, offset: int, length: int,
                             locs: dict, sp) -> bytes:
            from ..stats import DEGRADED_EC_READS
            geo = ev.geo
            coder = self.store.coder(geo.d, geo.p, codec="msr")
            sub = ev.shard_size // coder.alpha
            ragged = sub and (offset % sub or (offset + length) % sub)
            sp.set_attr("msr", True)
            if ragged and offset // sub != (offset + length - 1) // sub:
                # a span crossing sub-symbol boundaries would widen the
                # shared inner window to the full sub-symbol width.
                # Split into at most THREE pieces — partial head,
                # layer-aligned middle (one combined plan: every interior
                # byte is wanted, so the full-width window wastes
                # nothing), partial tail — so a ragged edge fetches only
                # its exact inner span without serializing one
                # plan+fan-out round per interior layer.
                end = offset + length
                cuts = [offset]
                head_end = -(-offset // sub) * sub   # round up
                mid_end = (end // sub) * sub         # round down
                if offset < head_end:
                    cuts.append(head_end)
                if head_end < mid_end:
                    cuts.append(mid_end)
                if cuts[-1] != end:
                    cuts.append(end)
                pieces = [_msr_piece(ev, coder, shard_id, a, b - a,
                                     locs, sp)
                          for a, b in zip(cuts, cuts[1:])]
                sp.set_attr("msr_mode", "+".join(m for _, m, _ in pieces))
                sp.set_attr("msr_fetch_bytes",
                            sum(fb for _, _, fb in pieces))
            else:
                pieces = [_msr_piece(ev, coder, shard_id, offset, length,
                                     locs, sp)]
                sp.set_attr("msr_mode", pieces[0][1])
                sp.set_attr("msr_fetch_bytes", pieces[0][2])
            DEGRADED_EC_READS.inc()  # one logical degraded read
            return b"".join(buf for buf, _, _ in pieces)

        def _msr_piece(ev, coder, shard_id: int, offset: int, length: int,
                       locs: dict, sp) -> "tuple[bytes, str, int]":
            """(bytes, plan mode, fetch bytes) for one boundary-aligned
            (or single-layer) span of the lost shard."""
            geo = ev.geo
            helpers = tuple(s for s in range(geo.n) if s != shard_id)
            plan = coder.interval_plan(helpers, shard_id, offset,
                                       length, ev.shard_size)
            got = _fetch_plan(ev, plan, locs)
            if any(v is None for v in got.values()):
                # a helper is down: closure-restricted decode over d
                # survivors that DID answer (one retry; a second wave of
                # failures means the stripe is genuinely unreadable)
                present = tuple(s for s, v in got.items() if v is not None)
                if len(present) < geo.d:
                    raise OSError(
                        f"cannot reconstruct shard {shard_id}: only "
                        f"{len(present)} msr helpers reachable")
                sp.add_event("msr_repair_degraded",
                             reachable=len(present))
                plan = coder.interval_plan(present, shard_id, offset,
                                           length, ev.shard_size)
                got = _fetch_plan(ev, plan, locs)
                if any(v is None for v in got.values()):
                    raise OSError(
                        f"cannot reconstruct shard {shard_id}: msr "
                        "survivors unreachable")
            return (coder.interval_decode(plan, got), plan.mode,
                    plan.bytes_total())
        return reader

    def _make_repair_reader(self, vid: int, codec: "str | None" = None):
        """(shard_reader, fragment_reader, remote_sids, fold_planner)
        for a rebuild on THIS server: survivors that live elsewhere are
        fetched by RANGE through VolumeEcShardRead — or, for repair-
        efficient codecs whose plans name many scattered ranges (msr
        repair planes), by its ranged-COMPUTE mode, which packs them
        into one wire fragment per survivor per window. `fold_planner`
        (geo plane) additionally groups far-DC msr helpers behind a
        same-DC relay that folds their plane rows into ONE alpha-row
        partial before crossing the expensive link.

        Every off-node fetch books SeaweedFS_repair_bytes_by_link_total
        by the holder's DC vs this server's (the master's answers carry
        DC, not rack, so same-DC hops book as cross_rack).

        The read-path location cache is BYPASSED: its freshest tier is
        still 11 s, and a rebuild planned against a pre-failure holder
        set would count the lost shard among its survivors. Admin
        rebuilds are rare; a master round-trip is the right price."""
        locs = self._lookup_ec_shards_master(vid)
        if locs is None:
            # master unreachable: serve the stale cache entry directly
            # (going through _lookup_ec_shards would re-ask the master we
            # just saw fail — a second full lookup timeout per rebuild)
            with self._ec_loc_lock:
                ent = self._ec_loc_cache.get(vid)
            locs = ent[0] if ent is not None else {}
        else:
            now = time.monotonic()
            with self._ec_loc_lock:
                self._ec_loc_cache[vid] = (locs, now, False)
        me = f"{self.ip}:{self.grpc_port}"
        peers = {sid: [a for a in addrs if a != me]
                 for sid, addrs in locs.items()}
        remote = sorted(sid for sid, addrs in peers.items() if addrs)
        if codec is None:
            ev = self.store.find_ec_volume(vid)
            codec = ev.codec if ev is not None else "rs"

        def _book(link: str, n: int) -> None:
            try:
                from ..stats import REPAIR_BYTES_BY_LINK
                REPAIR_BYTES_BY_LINK.inc(codec, link, amount=n)
            except Exception:  # noqa: BLE001  # swtpu-lint: disable=silent-except (metrics must never break repair)
                pass

        def _link_of(sid: int) -> "str | None":
            # attribution by primary holder: the fallback discipline may
            # serve from a later holder, but the first healthy one is
            # the overwhelmingly common server and the only defensible
            # single answer without per-fetch plumbing
            holders = peers.get(sid)
            if not holders or not self.data_center:
                return None
            dc = self._ec_addr_dc.get(holders[0], "")
            if not dc:
                return None
            return "cross_rack" if dc == self.data_center else "cross_dc"

        def reader(sid: int, offset: int, length: int) -> bytes:
            data = self._fetch_range_or_raise(vid, sid, offset, length,
                                              peers.get(sid, []))
            link = _link_of(sid)
            if link:
                _book(link, len(data))
            return data

        def readinto(sid: int, offset: int, length: int, rows) -> int:
            got = self._fetch_range_or_raise(vid, sid, offset, length,
                                             peers.get(sid, []), into=rows)
            link = _link_of(sid)
            if link:
                _book(link, got)
            return got
        # the landing form of ec/repair.py's shard_reader contract
        reader.readinto = readinto

        def fragment_reader(sid: int, ranges) -> bytes:
            buf = self._fetch_fragment_or_raise(vid, sid, ranges,
                                                peers.get(sid, []))
            link = _link_of(sid)
            if link:
                _book(link, len(buf))
            return buf

        def _fold_fetch(f, sids, srcs, mat, alpha):
            """One relay group's fetch(ranges) -> folded partial of
            alpha rows. sids[0]/srcs[0] is the relay; it gathers the
            rest of the group's plane rows DC-locally (gather_* request
            fields) and applies the stacked combine matrix, so only
            alpha rows cross the thin link instead of |group|*beta."""
            import numpy as np
            relay_sid, relay = sids[0], srcs[0]

            def fetch(ranges) -> "np.ndarray":
                want = alpha * ranges[0][1]
                try:
                    stub = Stub(relay, VOLUME_SERVICE)
                    parts = [r.data for r in stub.call_stream(
                        "VolumeEcShardRead",
                        vpb.VolumeEcShardReadRequest(
                            volume_id=vid, shard_id=relay_sid,
                            fragment_offsets=[o for o, _ in ranges],
                            fragment_lengths=[ln for _, ln in ranges],
                            combine_rows=alpha,
                            combine_matrix=mat.tobytes(),
                            gather_shard_ids=list(sids[1:]),
                            gather_sources=list(srcs[1:])),
                        vpb.VolumeEcShardReadResponse)]
                    buf = b"".join(parts)
                    if len(buf) != want:
                        raise OSError(f"folded partial {len(buf)} bytes "
                                      f"!= {want}")
                    _book("cross_dc", want)
                    return np.frombuffer(buf, dtype=np.uint8)
                except Exception as e:  # noqa: BLE001
                    log.warning("folded fetch vid=%d f=%d relay=%s: %s; "
                                "shipping raw fragments", vid, f, relay, e)
                # relay down or legacy: ship the raw rows (no geo
                # saving) and fold locally — repair still converges
                from ..ops import gf8
                w = ranges[0][1]
                rows = []
                for s in sids:
                    buf = fragment_reader(s, list(ranges))
                    arr = np.frombuffer(buf, dtype=np.uint8)
                    rows.extend(arr.reshape(len(ranges), w))
                return gf8.np_gf_apply(mat, np.stack(rows))
            return fetch

        def fold_planner(coder, f: int):
            """[(sids, fetch)] relay groups for rebuild_msr_single: one
            per far DC holding > q helpers (geo/repair_fold.py). Empty
            when geo folding is off (SWTPU_GEO_FOLD=0), topology is
            unknown, or no far group is big enough to pay for a relay
            hop."""
            if os.environ.get("SWTPU_GEO_FOLD", "1") == "0" or \
                    not self.data_center or coder.codec != "msr":
                return []
            g = coder.grid
            if g.q < 2:
                return []
            from ..geo import repair_fold
            helper_dcs = {}
            for sid, addrs in peers.items():
                if sid == f or not addrs:
                    continue
                dc = self._ec_addr_dc.get(addrs[0], "")
                if dc:
                    helper_dcs[sid] = dc
            folds = []
            for dc, sids in repair_fold.fold_groups(
                    helper_dcs, self.data_center, g.q):
                srcs = []
                for s in sids:
                    cands = [a for a in peers.get(s, ())
                             if self._ec_addr_dc.get(a) == dc]
                    if not cands:
                        break
                    srcs.append(cands[0])
                if len(srcs) != len(sids):
                    continue  # a member lost its in-DC holder
                mat = repair_fold.stacked_matrix(g.d, g.p, f, sids)
                folds.append((sids, _fold_fetch(f, sids, srcs, mat,
                                                g.alpha)))
            return folds
        return reader, fragment_reader, remote, fold_planner

    def _fetch_range_or_raise(self, vid: int, sid: int, offset: int,
                              length: int, holders: "list[str]",
                              into=None) -> "bytes | int":
        """One ranged fetch with the shared fallback discipline: healthy
        holders first, then circuit-open ones as a last resort (latency
        beats failing a repair or a recoverable read), else OSError.
        `into`: as `_fetch_remote_shard`."""
        data = self._fetch_remote_shard(vid, sid, offset, length, holders,
                                        into=into)
        if data is None:
            data = self._fetch_remote_shard(vid, sid, offset, length,
                                            holders, include_open=True,
                                            into=into)
        if data is None:
            raise OSError(f"shard {vid}.{sid} range [{offset}, +{length}) "
                          "unreachable")
        return data

    def _fetch_fragment_or_raise(self, vid: int, sid: int, ranges,
                                 holders: "list[str]") -> bytes:
        """Fetch one computed fragment (scattered ranges packed holder-
        side). A holder predating the ranged-compute fields answers the
        legacy zero-size read with an empty stream — detected and
        degraded to per-range fetches so mixed-version repairs still
        converge."""
        from .. import tracing
        want = sum(ln for _, ln in ranges)
        if want == 0:
            return b""
        ordered = retry.order_by_breaker([a for a in holders
                                          if retry.breaker(a).would_allow()]) \
            or list(holders)
        for addr in ordered:
            try:
                # same fault-injection site as the ranged path; a firing
                # failpoint degrades to per-range fetches (no breaker
                # penalty — the peer did nothing wrong)
                failpoints.check("ec.shard.read")
            except failpoints.FailpointError as e:
                log.warning("ec fragment read failpoint: %s", e)
                break
            br = retry.breaker(addr)
            try:
                stub = Stub(addr, VOLUME_SERVICE)
                parts = [r.data for r in stub.call_stream(
                    "VolumeEcShardRead",
                    vpb.VolumeEcShardReadRequest(
                        volume_id=vid, shard_id=sid,
                        fragment_offsets=[o for o, _ in ranges],
                        fragment_lengths=[ln for _, ln in ranges]),
                    vpb.VolumeEcShardReadResponse)]
                buf = b"".join(parts)
                if len(buf) == want:
                    br.record_success()
                    return failpoints.corrupt("ec.shard.read.data", buf)
                if not buf:
                    tracing.add_event("fragment_unsupported", peer=addr,
                                      vid=vid, shard=sid)
                    break  # legacy holder: per-range fallback below
                raise OSError(f"fragment length {len(buf)} != {want}")
            except Exception as e:  # noqa: BLE001
                br.record_failure()
                log.warning("fragment read %d.%d from %s: %s",
                            vid, sid, addr, e)
        out = bytearray()
        for off, ln in ranges:
            out += self._fetch_range_or_raise(vid, sid, off, ln, holders)
        return bytes(out)

    # shard-location cache staleness tiers (store_ec.go:256-267): complete
    # location sets refresh every 37 min, incomplete every 7 min, and a
    # failed read may force a refresh after 11 s — the master is OFF the
    # EC read hot path.
    _EC_LOC_TTL_COMPLETE = 37 * 60
    _EC_LOC_TTL_INCOMPLETE = 7 * 60
    _EC_LOC_TTL_FAILED = 11

    def _lookup_ec_shards(self, vid: int, failed: bool = False,
                          ) -> dict[int, list[str]]:
        """shard id -> gRPC addresses of holders, via the tiered cache."""
        now = time.monotonic()
        with self._ec_loc_lock:
            ent = self._ec_loc_cache.get(vid)
            if ent is not None:
                locs, fetched, complete = ent
                ttl = (self._EC_LOC_TTL_FAILED if failed else
                       self._EC_LOC_TTL_COMPLETE if complete else
                       self._EC_LOC_TTL_INCOMPLETE)
                if now - fetched < ttl:
                    return locs
        locs = self._lookup_ec_shards_master(vid)
        if locs is not None:
            ev = self.store.find_ec_volume(vid)
            n = ev.geo.n if ev is not None else 0
            complete = n > 0 and all(locs.get(s) for s in range(n))
            with self._ec_loc_lock:
                self._ec_loc_cache[vid] = (locs, now, complete)
            return locs
        # master unreachable: serve stale rather than fail the read, and
        # re-stamp the entry (complete=False) so the next probe waits a full
        # incomplete tier (11 s via failed=True) instead of paying the 5 s
        # lookup timeout on EVERY read for the whole outage
        with self._ec_loc_lock:
            ent = self._ec_loc_cache.get(vid)
            if ent is not None:
                self._ec_loc_cache[vid] = (ent[0], now, False)
        return ent[0] if ent is not None else {}

    def _lookup_ec_shards_master(self, vid: int) -> "dict | None":
        try:
            stub = Stub(self.current_leader, MASTER_SERVICE)
            resp = stub.call("LookupEcVolume",
                             mpb.LookupEcVolumeRequest(volume_id=vid),
                             mpb.LookupEcVolumeResponse, timeout=5)
            locs: dict[int, list[str]] = {}
            for e in resp.shard_id_locations:
                addrs = []
                for l in e.locations:
                    addr = f"{l.url.rsplit(':', 1)[0]}:{l.grpc_port}"
                    addrs.append(addr)
                    if l.data_center:
                        self._ec_addr_dc[addr] = l.data_center
                locs[e.shard_id] = addrs
            return locs
        except Exception as e:  # noqa: BLE001
            log.warning("ec lookup vid=%d: %s", vid, e)
            return None

    # -- gRPC admin service ---------------------------------------------------
    def _build_service(self) -> RpcService:
        svc = RpcService(VOLUME_SERVICE)
        vs = self
        store = self.store
        # a volume's push arriving here (VolumeEcShardsReceive) and a
        # delete of its shards here take the volume's lock: a delete
        # sent after a failed seal waits until the push ended, sealed or
        # undone, so nothing the push wrote outlives it
        receiving: dict = {}

        def receive_lock(vid: int) -> threading.Lock:
            return receiving.setdefault(vid, threading.Lock())

        @svc.unary("AllocateVolume", vpb.AllocateVolumeRequest,
                   vpb.AllocateVolumeResponse)
        def allocate(req, context):
            store.add_volume(req.volume_id, req.collection, req.replication,
                             req.ttl, req.disk_type or None)
            vs.flush_heartbeat()
            return vpb.AllocateVolumeResponse()

        @svc.unary("VolumeDelete", vpb.VolumeDeleteRequest, vpb.VolumeDeleteResponse)
        def vol_delete(req, context):
            store.delete_volume(req.volume_id, req.only_empty)
            vs.flush_heartbeat()
            return vpb.VolumeDeleteResponse()

        @svc.unary("VolumeScrub", vpb.VolumeScrubRequest,
                   vpb.VolumeScrubResponse)
        def volume_scrub(req, context):
            """Stream live needles through the batched CRC kernel
            (storage/scrub.py); device='auto' follows the backend this
            server's -coder resolved to (the JAX kernel on a device
            coder, the host loop otherwise). One failing volume never
            loses the other volumes' results; a time budget + rotating
            cursor lets the admin cron cover large servers across sweeps."""
            from ..ops import events
            from ..storage.scrub import scrub_volume
            if req.volume_id:
                v = store.find_volume(req.volume_id)
                if v is None:
                    context.abort(5, f"volume {req.volume_id} not found")
                vols = [v]
            else:
                vols = []
                for loc in store.locations:
                    with loc.lock:
                        vols.extend(loc.volumes.values())
                vols.sort(key=lambda v: v.id)
                # rotate: start after the last volume a budgeted sweep
                # finished with, so coverage advances sweep over sweep
                cursor = getattr(vs, "_scrub_cursor", 0)
                vols = ([v for v in vols if v.id > cursor]
                        + [v for v in vols if v.id <= cursor])
            resp = vpb.VolumeScrubResponse()
            deadline = (time.monotonic() + req.time_budget_s
                        if req.time_budget_s else None)
            for v in vols:
                try:
                    r = scrub_volume(v, device=req.device or "auto")
                    resp.results.add(volume_id=r.volume_id,
                                     scanned=r.scanned,
                                     corrupt_needle_ids=r.corrupt,
                                     bytes_checked=r.bytes_checked,
                                     elapsed_s=r.elapsed_s, mode=r.mode,
                                     error=r.error)
                    events.emit(
                        "volume.scrub.finish", vid=r.volume_id,
                        node=vs.url, scanned=r.scanned,
                        corrupt=len(r.corrupt),
                        bytes_checked=r.bytes_checked,
                        bytes_dispatched=r.bytes_dispatched,
                        blocks=r.blocks, elapsed_s=round(r.elapsed_s, 4),
                        mode=r.mode, walk_s=round(r.walk_s, 4),
                        pack_s=round(r.pack_s, 4),
                        device_s=round(r.device_s, 4),
                        compare_s=round(r.compare_s, 4),
                        device_busy_s=round(r.device_busy_s, 4),
                        recv_s=round(r.recv_s, 4),
                        spawn_s=round(r.spawn_s, 4),
                        walker_busy_s=round(r.walker_busy_s, 4))
                except Exception as e:  # noqa: BLE001 — isolate per volume
                    resp.results.add(volume_id=v.id, mode="error",
                                     error=str(e))
                if not req.volume_id:
                    vs._scrub_cursor = v.id
                if deadline is not None and time.monotonic() > deadline:
                    break
            return resp

        @svc.unary("VolumeMarkReadonly", vpb.VolumeMarkReadonlyRequest,
                   vpb.VolumeMarkReadonlyResponse)
        def mark_ro(req, context):
            store.mark_readonly(req.volume_id, True)
            vs.flush_heartbeat()
            return vpb.VolumeMarkReadonlyResponse()

        @svc.unary("VolumeMarkWritable", vpb.VolumeMarkWritableRequest,
                   vpb.VolumeMarkWritableResponse)
        def mark_rw(req, context):
            store.mark_readonly(req.volume_id, False)
            vs.flush_heartbeat()
            return vpb.VolumeMarkWritableResponse()

        @svc.unary("VolumeConfigure", vpb.VolumeConfigureRequest,
                   vpb.VolumeConfigureResponse)
        def vol_configure(req, context):
            """Rewrite the super block's replica placement (reference
            volume_grpc_admin.go VolumeConfigure)."""
            from ..storage.types import ReplicaPlacement
            v = store.find_volume(req.volume_id)
            if v is None:
                return vpb.VolumeConfigureResponse(
                    error=f"volume {req.volume_id} not found")
            try:
                rp = ReplicaPlacement.parse(req.replication)
            except Exception as e:  # noqa: BLE001
                return vpb.VolumeConfigureResponse(error=str(e))
            with v._lock:
                v.super_block.replica_placement = rp
                if v.remote_spec is None:
                    v._dat.seek(0)
                    v._dat.write(v.super_block.to_bytes())
                    v._dat.flush()
            vs.flush_heartbeat()
            return vpb.VolumeConfigureResponse()

        @svc.unary("VolumeStatus", vpb.VolumeStatusRequest, vpb.VolumeStatusResponse)
        def vol_status(req, context):
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            return vpb.VolumeStatusResponse(
                is_read_only=v.read_only, volume_size=v.content_size,
                file_count=v.file_count, file_deleted_count=v.deleted_count)

        # vacuum phases (reference volume_grpc_vacuum.go)
        @svc.unary("VolumeMount", vpb.VolumeMountRequest,
                   vpb.VolumeMountResponse)
        def volume_mount(req, context):
            store.mount_volume(req.volume_id, req.collection)
            vs.flush_heartbeat()
            return vpb.VolumeMountResponse()

        @svc.unary("VolumeUnmount", vpb.VolumeUnmountRequest,
                   vpb.VolumeUnmountResponse)
        def volume_unmount(req, context):
            if not store.unmount_volume(req.volume_id):
                context.abort(5, f"volume {req.volume_id} not found")
            vs.flush_heartbeat()
            return vpb.VolumeUnmountResponse()

        @svc.unary("VolumeServerLeave", vpb.VolumeServerLeaveRequest,
                   vpb.VolumeServerLeaveResponse)
        def volume_server_leave(req, context):
            """Stop heartbeating so the master forgets this node; data
            service keeps running for direct reads (reference
            volume_grpc_admin.go VolumeServerLeave)."""
            vs._leave.set()
            vs._hb_wake.set()
            return vpb.VolumeServerLeaveResponse()

        # ---- tail / incremental sync (reference volume_grpc_tail.go,
        # volume_grpc_copy_incremental.go) ----
        @svc.unary("VolumeSyncStatus", vpb.VolumeSyncStatusRequest,
                   vpb.VolumeSyncStatusResponse)
        def volume_sync_status(req, context):
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            v.sync()
            return vpb.VolumeSyncStatusResponse(
                volume_id=v.id, collection=v.collection,
                tail_offset=v._append_offset,
                compact_revision=v.super_block.compaction_revision,
                last_append_at_ns=v.last_append_at_ns)

        @svc.unary_stream("VolumeIncrementalCopy",
                          vpb.VolumeIncrementalCopyRequest,
                          vpb.VolumeIncrementalCopyResponse)
        def volume_incremental_copy(req, context):
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            start = v.offset_by_append_ns(req.since_ns)
            with v._lock:
                end = v._append_offset
            buf = 2 << 20
            for off in range(start, end, buf):
                yield vpb.VolumeIncrementalCopyResponse(
                    file_content=v.read_raw(off, min(buf, end - off)))

        @svc.unary_stream("VolumeTailSender", vpb.VolumeTailSenderRequest,
                          vpb.VolumeTailSenderResponse)
        def volume_tail_sender(req, context):
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            last_ns = req.since_ns
            draining = req.idle_timeout_seconds or 0
            while context.is_active():  # dead client must free the worker
                progressed = False
                for rec, ts, _nsize in v.read_records_since(last_ns):
                    yield vpb.VolumeTailSenderResponse(needle_record=rec,
                                                      append_at_ns=ts)
                    last_ns = max(last_ns, ts)
                    progressed = True
                if req.idle_timeout_seconds == 0:
                    time.sleep(1.0)  # follow forever (while client lives)
                    continue
                if progressed:
                    draining = req.idle_timeout_seconds
                else:
                    draining -= 1
                    if draining <= 0:
                        return
                time.sleep(1.0)

        @svc.unary("VolumeTailReceiver", vpb.VolumeTailReceiverRequest,
                   vpb.VolumeTailReceiverResponse)
        def volume_tail_receiver(req, context):
            """Pull records from a peer's tail into the local volume
            (reference volume_grpc_tail.go:VolumeTailReceiver)."""
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            src = Stub(req.source_volume_server, VOLUME_SERVICE)
            received = 0
            for resp in src.call_stream(
                    "VolumeTailSender",
                    vpb.VolumeTailSenderRequest(
                        volume_id=req.volume_id, since_ns=req.since_ns,
                        idle_timeout_seconds=req.idle_timeout_seconds or 2),
                    vpb.VolumeTailSenderResponse):
                v.append_records(resp.needle_record)
                received += 1
            return vpb.VolumeTailReceiverResponse(received=received)

        @svc.unary("VacuumVolumeCheck", vpb.VacuumVolumeCheckRequest,
                   vpb.VacuumVolumeCheckResponse)
        def vacuum_check(req, context):
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            # tiered volumes never report garbage: compacting one would
            # silently un-tier it and orphan the remote copy
            if v.remote_spec is not None:
                return vpb.VacuumVolumeCheckResponse(garbage_ratio=0.0)
            return vpb.VacuumVolumeCheckResponse(garbage_ratio=v.garbage_ratio())

        @svc.unary("VacuumVolumeCompact", vpb.VacuumVolumeCompactRequest,
                   vpb.VacuumVolumeCompactResponse)
        def vacuum_compact(req, context):
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            if v.remote_spec is not None:
                context.abort(9, f"volume {req.volume_id} is tiered; "
                              "download it before compacting")
            _, reclaimed = compact(v)
            return vpb.VacuumVolumeCompactResponse(processed_bytes=reclaimed)

        @svc.unary("VacuumVolumeCommit", vpb.VacuumVolumeCommitRequest,
                   vpb.VacuumVolumeCommitResponse)
        def vacuum_commit(req, context):
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            newv = commit_compact(v)
            for loc in store.locations:
                if loc.volumes.get(req.volume_id) is v:
                    loc.volumes[req.volume_id] = newv
            vs.flush_heartbeat()
            return vpb.VacuumVolumeCommitResponse(volume_size=newv.content_size)

        @svc.unary("VacuumVolumeCleanup", vpb.VacuumVolumeCleanupRequest,
                   vpb.VacuumVolumeCleanupResponse)
        def vacuum_cleanup(req, context):
            v = store.find_volume(req.volume_id)
            if v is not None:
                base = v.file_name()
                for ext in (".cpd", ".cpx"):
                    if os.path.exists(base + ext):
                        os.remove(base + ext)
            return vpb.VacuumVolumeCleanupResponse()

        @svc.unary("BatchDelete", vpb.BatchDeleteRequest, vpb.BatchDeleteResponse)
        def batch_delete(req, context):
            resp = vpb.BatchDeleteResponse()
            for fid in req.file_ids:
                r = resp.results.add(file_id=fid)
                try:
                    vid, key, cookie = parse_file_id(fid)
                    if store.delete_needle(vid, key):
                        r.status = 202
                    else:
                        r.status, r.error = 404, "not found"
                except Exception as e:  # noqa: BLE001
                    r.status, r.error = 500, str(e)
            return resp

        # ---- EC RPC set ----
        def _ensure_vif(vid: int, collection: str,
                        base: "str | None" = None) -> "str | None":
            """A rebuild decodes with the codec/geometry sealed in the
            .vif — make sure one exists at `base`, pulling the tiny
            sidecar from any peer holder when this server's copy is
            gone (e.g. bases written before source-volume deletes
            learned to spare it)."""
            if base is None:
                ev = store.find_ec_volume(vid)
                if ev is not None:
                    base = ev.base
                else:
                    for loc in store.locations:
                        cand = loc.base_name(collection, vid)
                        if os.path.exists(cand + ".ecx"):
                            base = cand
                            break
            if base is None or os.path.exists(base + ".vif"):
                return base
            me = f"{vs.ip}:{vs.grpc_port}"
            locs = vs._lookup_ec_shards(vid, failed=True)
            for addr in sorted({a for addrs in locs.values()
                                for a in addrs if a != me}):
                try:
                    src = Stub(addr, VOLUME_SERVICE)
                    parts = [r.file_content for r in src.call_stream(
                        "CopyFile",
                        vpb.CopyFileRequest(volume_id=vid,
                                            collection=collection,
                                            ext=".vif", is_ec_volume=True),
                        vpb.CopyFileResponse)]
                except Exception:  # noqa: BLE001 — peer may lack it too
                    continue
                if any(parts):
                    # parse before installing, and install through the
                    # one sanctioned .vif writer: a torn peer copy must
                    # never land as a valid-looking sidecar
                    try:
                        info = json.loads(b"".join(parts))
                    except ValueError:
                        continue  # peer's copy is torn; try the next
                    ec_files.write_vif(base + ".vif", **info)
                    return base
            return base

        @svc.unary("VolumeEcShardsGenerate", vpb.VolumeEcShardsGenerateRequest,
                   vpb.VolumeEcShardsGenerateResponse)
        def ec_generate(req, context):
            from ..ops import events
            events.emit("ec.encode.start", vid=req.volume_id,
                        collection=req.collection, node=vs.url)
            t0 = time.perf_counter()
            stats: dict = {}
            try:
                store.generate_ec_shards(req.volume_id, req.collection,
                                         req.data_shards or None,
                                         req.parity_shards or None,
                                         stats=stats,
                                         codec=req.codec or None)
            except Exception as e:  # noqa: BLE001
                events.emit("ec.encode.finish", severity=events.ERROR,
                            vid=req.volume_id, node=vs.url, ok=False,
                            error=str(e)[:200])
                raise
            events.emit("ec.encode.finish", vid=req.volume_id, node=vs.url,
                        ok=True,
                        duration_ms=round((time.perf_counter() - t0) * 1e3, 1),
                        **_ec_stage_fields(stats))
            return vpb.VolumeEcShardsGenerateResponse()

        @svc.unary("VolumeEcShardsGenerateBatch",
                   vpb.VolumeEcShardsGenerateBatchRequest,
                   vpb.VolumeEcShardsGenerateBatchResponse)
        def ec_generate_batch(req, context):
            from ..ops import events
            t0 = time.perf_counter()
            stats: dict = {}
            targets: dict = {}
            for t in req.targets:
                targets.setdefault(t.volume_id, []).append(
                    (t.address, list(t.shard_ids)))
            try:
                done = store.generate_ec_shards_batch(
                    list(req.volume_ids), req.collection,
                    req.data_shards or None, req.parity_shards or None,
                    stats=stats, codec=req.codec or None, targets=targets)
            except Exception as e:  # noqa: BLE001
                events.emit("ec.encode.finish", severity=events.ERROR,
                            node=vs.url, ok=False,
                            vids=list(req.volume_ids), error=str(e))
                raise
            events.emit("ec.encode.finish", node=vs.url, ok=True,
                        vids=list(done),
                        duration_ms=round((time.perf_counter() - t0) * 1e3, 1),
                        **_ec_stage_fields(stats))
            return vpb.VolumeEcShardsGenerateBatchResponse(
                encoded_volume_ids=done,
                data_shards=req.data_shards or store.ec_geometry.d,
                parity_shards=req.parity_shards or store.ec_geometry.p,
                codec=req.codec or store.ec_codec,
                pushed=[vpb.EcShardTarget(volume_id=vid, address=addr,
                                          shard_ids=sids)
                        for vid, addr, sids in stats.get("pushed", [])])

        @svc.unary("VolumeEcShardsInfo", vpb.VolumeEcShardsInfoRequest,
                   vpb.VolumeEcShardsInfoResponse)
        def ec_info(req, context):
            """Geometry probe from the .vif (TPU extension; the reference
            hardcodes RS(14,2) so it never needs this). local_shard_ids
            reports every shard file ON DISK — mounted or not — which is
            what the repair planner's remount probe needs: a shard
            unmounted by a crashed move while its server stayed up is a
            zero-copy repair (mount it back) instead of a rebuild."""
            from ..ec import files as ec_files

            def on_disk(base):
                return sorted(sid for sid in range(32)
                              if os.path.exists(base
                                                + ec_files.shard_ext(sid)))
            ev = store.find_ec_volume(req.volume_id)
            if ev is not None:
                return vpb.VolumeEcShardsInfoResponse(
                    data_shards=ev.geo.d, parity_shards=ev.geo.p,
                    dat_size=ev.dat_size or 0,
                    codec=ev.codec, shard_size=ev.shard_size,
                    local_shard_ids=sorted(set(ev.shards)
                                           | set(on_disk(ev.base))),
                    remote_shard_ids=ev.remote_shard_ids())
            for loc in store.locations:
                base = loc.base_name(req.collection, req.volume_id)
                if os.path.exists(base + ".vif"):
                    info = ec_files.read_vif(base + ".vif")
                    geo = EcGeometry.from_vif(info, store.ec_geometry)
                    rem = info.get("remote_shards") or {}
                    return vpb.VolumeEcShardsInfoResponse(
                        data_shards=info.get("d", 0),
                        parity_shards=info.get("p", 0),
                        dat_size=info.get("dat_size", 0),
                        codec=info.get("codec", "rs"),
                        shard_size=geo.shard_file_size(
                            info.get("dat_size", 0)),
                        local_shard_ids=on_disk(base),
                        remote_shard_ids=sorted(
                            int(k) for k in rem.get("keys", {})))
            raise KeyError(f"ec volume {req.volume_id} not found")

        @svc.unary("VolumeEcShardsRebuild", vpb.VolumeEcShardsRebuildRequest,
                   vpb.VolumeEcShardsRebuildResponse)
        @_maintenance_tagged
        def ec_rebuild(req, context):
            from ..ops import events
            failpoints.check("ec.rebuild")
            events.emit("ec.rebuild.start", vid=req.volume_id,
                        collection=req.collection, node=vs.url)
            t0 = time.perf_counter()
            stats: dict = {}
            try:
                reader, frag, remote, fold = \
                    vs._make_repair_reader(req.volume_id)
                _ensure_vif(req.volume_id, req.collection)
                rebuilt = store.rebuild_ec_shards(req.volume_id,
                                                  req.collection,
                                                  shard_reader=reader,
                                                  remote_shards=remote,
                                                  stats=stats,
                                                  fragment_reader=frag,
                                                  fold_planner=fold)
            except Exception as e:  # noqa: BLE001
                events.emit("ec.rebuild.finish", severity=events.ERROR,
                            vid=req.volume_id, node=vs.url, ok=False,
                            error=str(e)[:200])
                raise
            events.emit("ec.rebuild.finish", vid=req.volume_id, node=vs.url,
                        ok=True, rebuilt_shard_ids=list(rebuilt),
                        codec=stats.get("codec", "rs"),
                        repair_path=stats.get("path"),
                        bytes_read=stats.get("bytes_read", 0),
                        bytes_written=stats.get("bytes_written", 0),
                        duration_ms=round((time.perf_counter() - t0) * 1e3, 1),
                        # the rebuild's stage sums (ec/encoder.py)
                        batches=stats.get("batches", 0),
                        # of them, staged in buffers an earlier rebuild
                        # had filled (ec/buffers.py)
                        warm_batches=stats.get("warm_batches", 0),
                        **{k: round(v, 3) for k, v in stats.items()
                           if k.endswith("_s")})
            vs.flush_heartbeat()
            return vpb.VolumeEcShardsRebuildResponse(
                rebuilt_shard_ids=rebuilt,
                bytes_read=stats.get("bytes_read", 0),
                bytes_written=stats.get("bytes_written", 0))

        @svc.unary("VolumeEcShardsCopy", vpb.VolumeEcShardsCopyRequest,
                   vpb.VolumeEcShardsCopyResponse)
        @_maintenance_tagged
        def ec_copy(req, context):
            """Pull shard files FROM source_data_node to this server.
            All of a volume's shard files stay in ONE location: prefer
            the location already holding its .ecx. Files only: nothing is
            mounted, so there is no registration change to flush — the
            caller's VolumeEcShardsMount makes and flushes it."""
            failpoints.check("ec.shard.copy")
            src = Stub(req.source_data_node, VOLUME_SERVICE)
            loc = next((l for l in store.locations
                        if os.path.exists(
                            l.base_name(req.collection,
                                        req.volume_id) + ".ecx")),
                       None) or store._location_for(None)
            base = loc.base_name(req.collection, req.volume_id)
            exts = [ec_files.shard_ext(s) for s in req.shard_ids]
            if req.copy_ecx_file:
                exts.append(".ecx")
            if req.copy_ecj_file:
                exts.append(".ecj")
            if req.copy_vif_file:
                exts.append(".vif")
            for ext in exts:
                parts = []
                try:
                    for r in src.call_stream(
                            "CopyFile",
                            vpb.CopyFileRequest(volume_id=req.volume_id,
                                                collection=req.collection,
                                                ext=ext, is_ec_volume=True),
                            vpb.CopyFileResponse):
                        parts.append(r.file_content)
                except Exception:  # noqa: BLE001
                    if ext in (".ecj", ".ecx", ".vif"):
                        continue  # optional sidecars may not exist at source
                    raise
                with open(base + ext, "wb") as f:
                    for pc in parts:
                        f.write(pc)
            return vpb.VolumeEcShardsCopyResponse()

        @svc.stream_unary("VolumeEcShardsReceive",
                          vpb.VolumeEcShardsReceiveRequest,
                          vpb.VolumeEcShardsReceiveResponse)
        @_maintenance_tagged
        def ec_receive(requests, context):
            """The other end of a seal's push (ec/push.py): this server's
            shards of a volume arrive as the encoder writes them, and are
            fsynced with the sidecars before the .vif is written and the
            reply sent. Files only, like VolumeEcShardsCopy: the caller's
            VolumeEcShardsMount registers them."""
            def base_for(collection: str, vid: int) -> str:
                loc = next((l for l in store.locations
                            if os.path.exists(
                                l.base_name(collection, vid) + ".ecx")),
                           None) or store._location_for(None)
                return loc.base_name(collection, vid)
            sids, got = ec_push.receive(requests, base_for, receive_lock)
            return vpb.VolumeEcShardsReceiveResponse(bytes_received=got,
                                                     shard_ids=sids)

        # fork RPC: rebuild shards directly onto this server from peers
        @svc.unary("VolumeEcShardsCopyByRebuild",
                   vpb.VolumeEcShardsCopyByRebuildRequest,
                   vpb.VolumeEcShardsCopyByRebuildResponse)
        @_maintenance_tagged
        def ec_copy_by_rebuild(req, context):
            # like ec_copy, writes shard files and mounts none: the
            # caller's VolumeEcShardsMount registers them, and flushes
            loc = store._location_for(None)
            base = loc.base_name(req.collection, req.volume_id)
            # the tiny .vif sidecar still copies whole (it carries the
            # codec + geometry the rebuild must decode with); survivor
            # DATA moves only as the ranged fetches the plan asks for
            _ensure_vif(req.volume_id, req.collection, base)
            info = ec_files.read_vif(base + ".vif")
            geo = EcGeometry.from_vif(info, store.ec_geometry)
            reader, frag, remote, fold = vs._make_repair_reader(
                req.volume_id, codec=info.get("codec", "rs"))
            stats: dict = {}
            rebuilt = rebuild_shards(
                base, geo,
                store.coder(geo.d, geo.p, codec=info.get("codec", "rs")),
                wanted=list(req.shard_ids), shard_reader=reader,
                remote_shards=remote, stats=stats, fragment_reader=frag,
                fold_planner=fold)
            return vpb.VolumeEcShardsCopyByRebuildResponse(
                rebuilt_shard_ids=rebuilt,
                bytes_read=stats.get("bytes_read", 0),
                bytes_written=stats.get("bytes_written", 0))

        @svc.unary("VolumeEcShardsMount", vpb.VolumeEcShardsMountRequest,
                   vpb.VolumeEcShardsMountResponse)
        def ec_mount(req, context):
            store.mount_ec_shards(req.volume_id, req.collection)
            vs.flush_heartbeat()
            return vpb.VolumeEcShardsMountResponse()

        @svc.unary("VolumeEcShardsUnmount", vpb.VolumeEcShardsUnmountRequest,
                   vpb.VolumeEcShardsUnmountResponse)
        def ec_unmount(req, context):
            store.unmount_ec_shards(req.volume_id,
                                    list(req.shard_ids) or None)
            vs.flush_heartbeat()
            return vpb.VolumeEcShardsUnmountResponse()

        def _ec_delete(req) -> "str | None":
            ev = store.find_ec_volume(req.volume_id)
            base = None
            if ev is not None:
                base = ev.base
                store.unmount_ec_shards(req.volume_id, list(req.shard_ids))
            else:
                for loc in store.locations:
                    cand = loc.base_name(req.collection, req.volume_id)
                    if any(os.path.exists(cand + ext) for ext in (
                            *(ec_files.shard_ext(s) for s in req.shard_ids),
                            ".ecx")):
                        base = cand
                        break
            if base:
                for s in req.shard_ids:
                    p = base + ec_files.shard_ext(s)
                    if os.path.exists(p):
                        os.remove(p)
                # a remote-backed shard has no payload file here:
                # release its .vif claim instead. The remote OBJECT is
                # untouched — a move's target has already merged the
                # claim, and a plain delete leaves cleanup to the
                # lifecycle reaper that owns the remote tier.
                if os.path.exists(base + ".vif"):
                    ec_files.drop_remote_claims(base + ".vif",
                                                list(req.shard_ids))
            return base

        def _drop_sidecars_of_no_shards(base: str) -> None:
            """The volume's last shard here gone: its .ecx and .ecj go
            too, and the .vif unless a normal volume's .dat or a remote
            claim still needs it (upstream VolumeEcShardsDelete)."""
            if os.path.exists(base + ".dat") or any(
                    os.path.exists(base + ec_files.shard_ext(s))
                    for s in range(MAX_SHARD_ID)):
                return
            if os.path.exists(base + ".vif") and \
                    ec_files.read_vif(base + ".vif").get("remote_shards"):
                return
            for ext in (".ecx", ".ecj", ".vif"):
                try:
                    os.remove(base + ext)
                except FileNotFoundError:
                    pass

        @svc.unary("VolumeEcShardsDelete", vpb.VolumeEcShardsDeleteRequest,
                   vpb.VolumeEcShardsDeleteResponse)
        def ec_delete(req, context):
            with receive_lock(req.volume_id):
                base = _ec_delete(req)
                if base:
                    _drop_sidecars_of_no_shards(base)
            # a shard still mounted when the delete came was unmounted
            # above: flush. After a VolumeEcShardsUnmount (it flushed) this
            # one has nothing to report and stays all the same: a heartbeat
            # round trip is ~2 ms, the unlinking beside it 70 ms a volume
            # (the seal's timing lines, PERF.md §6, PR 32)
            vs.flush_heartbeat()
            return vpb.VolumeEcShardsDeleteResponse()

        # fork RPC: move = copy + source delete, driven from the target
        @svc.unary("VolumeEcShardsMove", vpb.VolumeEcShardsMoveRequest,
                   vpb.VolumeEcShardsMoveResponse)
        def ec_move(req, context):
            # first shards of this volume on this server need the index
            # sidecars too (reference copies .ecx/.vif on first placement,
            # command_ec_encode.go parallelCopyEcShardsFromSource);
            # look in EVERY location — existing shards may live on a
            # different disk than the emptiest one
            need_sidecars = not any(
                os.path.exists(loc.base_name(req.collection,
                                             req.volume_id) + ".ecx")
                for loc in store.locations)
            src = Stub(req.source_data_node, VOLUME_SERVICE)
            # a shard whose payload lives on the remote tier moves its
            # .vif CLAIM, not bytes: probe which of the requested sids
            # the source holds only as offloaded claims
            try:
                sinfo = src.call("VolumeEcShardsInfo",
                                 vpb.VolumeEcShardsInfoRequest(
                                     volume_id=req.volume_id,
                                     collection=req.collection),
                                 vpb.VolumeEcShardsInfoResponse)
                claim_sids = [s for s in req.shard_ids
                              if s in set(sinfo.remote_shard_ids)]
            except Exception:  # noqa: BLE001 — legacy peer: payload-only
                claim_sids = []
            payload_sids = [s for s in req.shard_ids
                            if s not in set(claim_sids)]
            ec_copy(vpb.VolumeEcShardsCopyRequest(
                volume_id=req.volume_id, collection=req.collection,
                shard_ids=payload_sids,
                copy_ecx_file=need_sidecars, copy_ecj_file=need_sidecars,
                copy_vif_file=need_sidecars,
                source_data_node=req.source_data_node), context)
            if claim_sids or need_sidecars:
                loc = next((l for l in store.locations
                            if os.path.exists(
                                l.base_name(req.collection,
                                            req.volume_id) + ".ecx")),
                           None) or store._location_for(None)
                base = loc.base_name(req.collection, req.volume_id)
            if claim_sids:
                parts = [r.file_content for r in src.call_stream(
                    "CopyFile",
                    vpb.CopyFileRequest(volume_id=req.volume_id,
                                        collection=req.collection,
                                        ext=".vif", is_ec_volume=True),
                    vpb.CopyFileResponse)]
                claims = ec_files.remote_claims(
                    json.loads(b"".join(parts)), claim_sids)
                if claims is None:
                    context.abort(9, f"source holds no remote claim "
                                     f"for shards {list(claim_sids)}")
            if need_sidecars and os.path.exists(base + ".vif"):
                # the whole-sidecar copy brought claims for shards NOT
                # moving here; exactly one server may hold each claim
                here = ec_files.read_vif(base + ".vif")
                stray = [int(k) for k in (here.get("remote_shards")
                                          or {}).get("keys", {})
                         if int(k) not in set(req.shard_ids)]
                ec_files.drop_remote_claims(base + ".vif", stray)
            if claim_sids:
                ec_files.merge_remote_claims(base + ".vif", claims)
            src.call("VolumeEcShardsDelete",
                     vpb.VolumeEcShardsDeleteRequest(
                         volume_id=req.volume_id, collection=req.collection,
                         shard_ids=req.shard_ids),
                     vpb.VolumeEcShardsDeleteResponse)
            store.mount_ec_shards(req.volume_id, req.collection)
            vs.flush_heartbeat()
            return vpb.VolumeEcShardsMoveResponse()

        @svc.unary_stream("VolumeEcShardRead", vpb.VolumeEcShardReadRequest,
                          vpb.VolumeEcShardReadResponse)
        def ec_shard_read(req, context):
            ev = store.find_ec_volume(req.volume_id)
            if ev is None:
                context.abort(5, f"ec volume {req.volume_id} not found")
            sh = ev.shards.get(req.shard_id)
            if sh is None:
                context.abort(5, f"shard {req.shard_id} not on this server")
            frag_ranges = list(zip(req.fragment_offsets,
                                   req.fragment_lengths))
            if len(req.fragment_offsets) != len(req.fragment_lengths):
                context.abort(3, "fragment_offsets/lengths length mismatch")
            cost = (sum(ln for _, ln in frag_ranges) if frag_ranges
                    else req.size)
            # a maintenance-tagged survivor read (repair plans pulling
            # ranged fetches) admits through the QoS plane and YIELDS
            # to queued foreground work; untagged shard reads are the
            # degraded-read data path and stay admission-free
            from .. import qos as qos_mod
            grant = None
            if vs.qos.enabled and \
                    qos_mod.current_class() == qos_mod.CLASS_MAINTENANCE:
                grant = vs.qos.admit_sync(
                    ev.collection or "default",
                    qos_mod.CLASS_MAINTENANCE, cost=cost)
            try:
                if frag_ranges:
                    # ranged-COMPUTE mode: gather the scattered ranges
                    # (an MSR repair plane is alpha/p layer slices) and
                    # ship ONE packed — optionally GF-combined — wire
                    # fragment instead of one RPC per range
                    yield from _serve_fragment(sh, req, frag_ranges,
                                               context)
                    return
                remaining = req.size
                offset = req.offset
                while remaining > 0:
                    chunk = min(remaining, 1 << 20)
                    data = sh.read_at(offset, chunk)
                    if not data:
                        break
                    yield vpb.VolumeEcShardReadResponse(data=data)
                    offset += len(data)
                    remaining -= len(data)
            finally:
                if grant is not None:
                    grant.release()

        def _serve_fragment(sh, req, frag_ranges, context):
            import numpy as np
            if not req.combine_rows:
                if req.gather_shard_ids:
                    # a relay gather without a combine matrix would ship
                    # MORE bytes than the callers fetching directly
                    context.abort(3, "gather requires combine_rows")
                # pack-only: stream straight from disk, range by range
                # in 1 MB chunks — a request-controlled fragment size
                # must never materialize whole in the holder's RSS
                for off, ln in frag_ranges:
                    rem, pos = ln, off
                    while rem > 0:
                        buf = sh.read_at(pos, min(rem, 1 << 20))
                        if not buf:
                            context.abort(3, f"fragment range [{off}, "
                                             f"+{ln}) beyond shard")
                        yield vpb.VolumeEcShardReadResponse(data=buf)
                        pos += len(buf)
                        rem -= len(buf)
                return
            # helper-side GF fold: rows_out = M (x) range_rows, the
            # hook for codecs whose helpers ship inner products. The
            # fold must hold all rows at once, so unlike the streamed
            # pack path its request-controlled size is CAPPED — repair
            # executors window fragments to ~window/q (ec/repair.py),
            # far below this
            from ..ops import gf8
            gather = list(zip(req.gather_shard_ids, req.gather_sources))
            if len(req.gather_shard_ids) != len(req.gather_sources):
                context.abort(3, "gather ids/sources length mismatch")
            if sum(ln for _, ln in frag_ranges) * (1 + len(gather)) \
                    > (64 << 20):
                context.abort(3, "combine fragment exceeds 64 MB; "
                                 "window the request")
            lens = {ln for _, ln in frag_ranges}
            if len(lens) != 1:
                context.abort(3, "combine needs equal-length ranges")
            total_rows = len(frag_ranges) * (1 + len(gather))
            if len(req.combine_matrix) != req.combine_rows * total_rows:
                context.abort(3, "combine_matrix shape mismatch")
            rows = []
            for off, ln in frag_ranges:
                buf = sh.read_at(off, ln)
                if len(buf) != ln:
                    context.abort(3, f"fragment range [{off}, +{ln}) "
                                     "beyond shard")
                rows.append(np.frombuffer(buf, dtype=np.uint8))
            # geo relay: gather the SAME ranges from DC-local peers so
            # the fold below covers the whole far-side group — matrix
            # columns run sid-major (own rows first, then each gathered
            # shard's) matching geo/repair_fold.stacked_matrix
            for gsid, gsrc in gather:
                try:
                    buf = vs._fetch_fragment_or_raise(
                        req.volume_id, gsid, frag_ranges, [gsrc])
                except OSError as e:
                    context.abort(14, f"gather shard {gsid} from "
                                      f"{gsrc}: {e}")
                arr = np.frombuffer(buf, dtype=np.uint8)
                rows.extend(arr.reshape(len(frag_ranges), frag_ranges[0][1]))
            mat = np.frombuffer(req.combine_matrix, dtype=np.uint8)
            mat = mat.reshape(req.combine_rows, total_rows)
            data = gf8.np_gf_apply(mat, np.stack(rows)).tobytes()
            for i in range(0, len(data), 1 << 20):
                yield vpb.VolumeEcShardReadResponse(
                    data=data[i:i + (1 << 20)])

        @svc.unary("VolumeEcBlobDelete", vpb.VolumeEcBlobDeleteRequest,
                   vpb.VolumeEcBlobDeleteResponse)
        def ec_blob_delete(req, context):
            ev = store.find_ec_volume(req.volume_id)
            if ev is None:
                context.abort(5, f"ec volume {req.volume_id} not found")
            ev.delete_needle(req.file_key)
            return vpb.VolumeEcBlobDeleteResponse()

        @svc.unary("VolumeEcShardsToVolume", vpb.VolumeEcShardsToVolumeRequest,
                   vpb.VolumeEcShardsToVolumeResponse)
        def ec_to_volume(req, context):
            store.ec_shards_to_volume(req.volume_id, req.collection)
            vs.flush_heartbeat()
            return vpb.VolumeEcShardsToVolumeResponse()

        @svc.unary("VolumeCopy", vpb.VolumeCopyRequest, vpb.VolumeCopyResponse)
        @_maintenance_tagged
        def volume_copy(req, context):
            """Pull a whole volume (.dat + .idx) from source_data_node
            (reference volume_grpc_copy.go doCopyFile flow).

            Same-server special case: when the volume is ALREADY here
            and the request names a different disk_type, this is a
            cross-tier move on one machine (volume.tier.move without a
            second server) — a local disk-to-disk copy + retire, not a
            network pull. A same-server request WITHOUT a differing
            disk_type keeps the historical 'already here' rejection."""
            v_here = store.find_volume(req.volume_id)
            if v_here is not None:
                if req.disk_type and not any(
                        loc.volumes.get(req.volume_id) is v_here
                        and loc.disk_type == req.disk_type
                        for loc in store.locations):
                    try:
                        store.move_volume_local(req.volume_id,
                                                req.disk_type)
                    except (KeyError, OSError) as e:
                        context.abort(9, f"local tier move: {e}")
                    vs.flush_heartbeat()
                    nv = store.find_volume(req.volume_id)
                    return vpb.VolumeCopyResponse(
                        last_append_at_ns=nv.last_append_at_ns)
                context.abort(6, f"volume {req.volume_id} already here")
            src = Stub(req.source_data_node, VOLUME_SERVICE)
            loc = store._location_for(req.disk_type or None)
            base = loc.base_name(req.collection, req.volume_id)
            try:
                for ext in (".dat", ".idx"):
                    with open(base + ext, "wb") as f:
                        for r in src.call_stream(
                                "CopyFile",
                                vpb.CopyFileRequest(volume_id=req.volume_id,
                                                    collection=req.collection,
                                                    ext=ext),
                                vpb.CopyFileResponse):
                            f.write(r.file_content)
            except Exception:
                # remove the partial clone: left on disk it would be
                # mounted as a live truncated volume on restart and block
                # every retry with "volume already here"
                for ext in (".dat", ".idx"):
                    try:
                        os.remove(base + ext)
                    except OSError:
                        pass
                raise
            from ..storage.volume import Volume as _Volume
            v = _Volume(loc.directory, req.collection, req.volume_id,
                        create_if_missing=False)
            with loc.lock:
                loc.volumes[req.volume_id] = v
            vs.flush_heartbeat()
            return vpb.VolumeCopyResponse(last_append_at_ns=v.last_append_at_ns)

        @svc.unary_stream("CopyFile", vpb.CopyFileRequest, vpb.CopyFileResponse)
        def copy_file(req, context):
            # a maintenance-tagged pull (VolumeCopy / shard copy from a
            # repairing peer) admits before streaming file bytes off
            # this node's disks — repair storms must not out-read the
            # tenants this node serves
            from .. import qos as qos_mod
            grant = None
            if vs.qos.enabled and \
                    qos_mod.current_class() == qos_mod.CLASS_MAINTENANCE:
                grant = vs.qos.admit_sync(req.collection or "default",
                                          qos_mod.CLASS_MAINTENANCE)
            try:
                yield from _copy_file_stream(req, context)
            finally:
                if grant is not None:
                    grant.release()

        def _copy_file_stream(req, context):
            # flush the live volume's buffered appends first — the stream
            # below reads through a fresh handle and would otherwise miss
            # them (reference syncs via the readonly flip in doCopyFile)
            v = store.find_volume(req.volume_id)
            if v is not None and req.ext in (".dat", ".idx"):
                v.sync()
            path = None
            for loc in store.locations:
                cand = loc.base_name(req.collection, req.volume_id) + req.ext
                if os.path.exists(cand):
                    path = cand
                    break
            if path is None:
                context.abort(5, f"file vol={req.volume_id}{req.ext} not found")
            with open(path, "rb") as f:
                while True:
                    chunk = f.read(1 << 20)
                    if not chunk:
                        break
                    yield vpb.CopyFileResponse(file_content=chunk)

        @svc.unary("ReadVolumeFileStatus", vpb.ReadVolumeFileStatusRequest,
                   vpb.ReadVolumeFileStatusResponse)
        def file_status(req, context):
            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not found")
            dat_size = (os.path.getsize(v.dat_path)
                        if os.path.exists(v.dat_path)
                        else v.remote_spec.get("size", 0)
                        if v.remote_spec else 0)
            return vpb.ReadVolumeFileStatusResponse(
                volume_id=req.volume_id,
                dat_file_size=dat_size,
                idx_file_size=os.path.getsize(v.idx_path),
                file_count=v.file_count,
                compaction_revision=v.super_block.compaction_revision,
                collection=v.collection)

        @svc.unary("VolumeNeedleStatus", vpb.VolumeNeedleStatusRequest,
                   vpb.VolumeNeedleStatusResponse)
        def needle_status(req, context):
            try:
                n = store.read_needle(req.volume_id, req.needle_id)
            except KeyError as e:
                context.abort(5, str(e))
            return vpb.VolumeNeedleStatusResponse(
                needle_id=n.id, cookie=n.cookie, size=len(n.data),
                last_modified=n.last_modified, crc=n.checksum,
                ttl=str(n.ttl))

        @svc.unary("Ping", vpb.PingRequest, vpb.PingResponse)
        def ping(req, context):
            now = time.time_ns()
            return vpb.PingResponse(start_time_ns=now, remote_time_ns=now,
                                    stop_time_ns=time.time_ns())

        @svc.unary("VolumeTierMoveDatToRemote",
                   vpb.VolumeTierMoveDatToRemoteRequest,
                   vpb.VolumeTierMoveDatToRemoteResponse)
        def tier_upload(req, context):
            """Seal + upload the .dat to a remote backend; the volume
            stays readable through ranged reads (reference
            volume_grpc_tier_upload.go)."""
            from ..ec import files as ec_files
            from ..storage.backend import open_remote

            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not local")
            if v.remote_spec is not None:
                context.abort(9, f"volume {req.volume_id} already tiered")
            try:
                client = open_remote(req.destination_backend_name)
            except ValueError as e:
                context.abort(3, str(e))
            was_read_only = v.read_only
            v.read_only = True
            try:
                v.sync()
                key = os.path.basename(v.dat_path)
                size = client.write_object(key, v.dat_path)
            except Exception as e:  # noqa: BLE001
                v.read_only = was_read_only  # roll back: no remote copy
                context.abort(13, f"tier upload: {e}")
            remote = {"spec": req.destination_backend_name,
                      "key": key, "size": size}
            ec_files.update_vif(v.vif_path, {"remote": remote})
            if req.keep_local_dat_file:
                # local .dat keeps serving reads; volume stays read-only
                # and marked tiered so the guards above hold
                v.remote_spec = remote
            else:
                v.close()
                os.unlink(v.dat_path)
                store.reload_volume(req.volume_id)
            return vpb.VolumeTierMoveDatToRemoteResponse(
                processed=size, processedPercentage=100.0)

        @svc.unary("VolumeTierMoveDatFromRemote",
                   vpb.VolumeTierMoveDatFromRemoteRequest,
                   vpb.VolumeTierMoveDatFromRemoteResponse)
        def tier_download(req, context):
            """Pull a tiered .dat back to local disk (reference
            volume_grpc_tier_download.go)."""
            from ..ec import files as ec_files
            from ..storage.backend import open_remote

            v = store.find_volume(req.volume_id)
            if v is None:
                context.abort(5, f"volume {req.volume_id} not local")
            if v.remote_spec is None:
                context.abort(9, f"volume {req.volume_id} not tiered")
            remote = v.remote_spec
            client = open_remote(remote["spec"])
            # download to a temp file and verify the size BEFORE touching
            # the .vif or the remote copy — a torn download must never
            # cost the only good copy
            tmp = v.dat_path + ".tiertmp"
            try:
                client.read_object_to(remote["key"], tmp)
                got = os.path.getsize(tmp)
                want = remote.get("size") or client.object_size(remote["key"])
                if got != want:
                    raise OSError(f"short download: {got} != {want}")
            except Exception as e:  # noqa: BLE001
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                context.abort(13, f"tier download: {e}")
            v.close()
            # the remote object may be deleted below: the downloaded .dat
            # and its rename must be durable before the last other copy
            # of the volume's data goes away
            fsutil.fsync_path(tmp)
            os.replace(tmp, v.dat_path)
            fsutil.fsync_dir(v.dat_path)
            ec_files.update_vif(v.vif_path, remove=("remote",))
            nv = store.reload_volume(req.volume_id)
            if not req.keep_remote_dat_file and nv is not None:
                client.delete_object(remote["key"])
            return vpb.VolumeTierMoveDatFromRemoteResponse(
                processed=remote.get("size", 0),
                processedPercentage=100.0)

        @svc.unary("VolumeEcShardsTierMoveToRemote",
                   vpb.VolumeTierMoveDatToRemoteRequest,
                   vpb.VolumeTierMoveDatToRemoteResponse)
        @_maintenance_tagged
        def ec_tier_offload(req, context):
            """Lifecycle EC→remote: offload this holder's local shard
            payloads of an EC volume to the remote tier named by
            `destination_backend_name` (the .dat tier-upload message is
            reused — same field meanings at shard granularity; see the
            volume_server.proto tiering note). The volume keeps serving
            through lazy ranged reads; sidecars stay local. Offload
            bytes admit maintenance-class so a lifecycle sweep can't
            out-read the tenants this node serves."""
            from ..ops import events
            from .. import qos as qos_mod
            grant = None
            if vs.qos.enabled:
                grant = vs.qos.admit_sync(req.collection or "default",
                                          qos_mod.CLASS_MAINTENANCE)
            moved = 0
            try:
                moved = store.offload_ec_shards(
                    req.volume_id, req.destination_backend_name,
                    collection=req.collection)
            except KeyError as e:
                context.abort(5, str(e))
            except ValueError as e:
                context.abort(3, str(e))
            except Exception as e:  # noqa: BLE001
                context.abort(13, f"ec tier offload: {e}")
            finally:
                if grant is not None:
                    if moved:
                        grant.charge(moved)
                    grant.release()
            if moved:
                events.emit("lifecycle.transition", kind="offload",
                            vid=req.volume_id, node=vs.url,
                            collection=req.collection,
                            **{"from": "ec", "to": "remote"},
                            bytes_moved=moved)
            return vpb.VolumeTierMoveDatToRemoteResponse(
                processed=moved, processedPercentage=100.0)

        @svc.unary("VolumeEcShardsTierMoveFromRemote",
                   vpb.VolumeTierMoveDatFromRemoteRequest,
                   vpb.VolumeTierMoveDatFromRemoteResponse)
        @_maintenance_tagged
        def ec_tier_promote(req, context):
            """Lifecycle remote→ec (promote-on-heat): pull this
            holder's offloaded shard payloads back to local disk."""
            from ..ops import events
            from .. import qos as qos_mod
            grant = None
            if vs.qos.enabled:
                grant = vs.qos.admit_sync(req.collection or "default",
                                          qos_mod.CLASS_MAINTENANCE)
            moved = 0
            try:
                moved = store.promote_ec_shards(
                    req.volume_id, collection=req.collection,
                    keep_remote=req.keep_remote_dat_file)
            except KeyError as e:
                context.abort(5, str(e))
            except Exception as e:  # noqa: BLE001
                context.abort(13, f"ec tier promote: {e}")
            finally:
                if grant is not None:
                    if moved:
                        grant.charge(moved)
                    grant.release()
            if moved:
                events.emit("lifecycle.transition", kind="promote",
                            vid=req.volume_id, node=vs.url,
                            collection=req.collection,
                            **{"from": "remote", "to": "ec"},
                            bytes_moved=moved)
            return vpb.VolumeTierMoveDatFromRemoteResponse(
                processed=moved, processedPercentage=100.0)

        @svc.unary("VolumeEcShardsSetDestroyTime",
                   vpb.VolumeTailReceiverRequest,
                   vpb.VolumeTailReceiverResponse)
        def ec_set_destroy_time(req, context):
            """Stamp a DestroyTime onto a local EC volume's .vif — the
            lifecycle executor's TTL verb, on the AUTHENTICATED gRPC
            plane (the cluster token gates it on guarded clusters,
            unlike a bare HTTP POST). Message reuse (no protoc in
            image): since_ns = the DestroyTime instant in NANOSECONDS,
            source_volume_server = collection; see volume_server.proto."""
            if not self._set_destroy_time(req.volume_id,
                                          req.since_ns / 1e9):
                context.abort(5, f"no ec volume {req.volume_id}")
            return vpb.VolumeTailReceiverResponse(received=1)

        @svc.unary_stream("Query", vpb.QueryRequest, vpb.QueriedStripe)
        def query(req, context):
            """S3-Select-lite scan over needles (reference
            volume_grpc_query.go:12; JSON via weed/query/json, CSV is a
            stub there — supported here)."""
            import json as _json

            from ..query import Query, query_csv_lines, query_json_lines

            q = Query(field=req.filter.field, op=req.filter.operand,
                      value=req.filter.value)
            in_fmt = req.input_serialization.format or "json"
            out_fmt = req.output_serialization.format or "json"
            out_delim = req.output_serialization.csv_delimiter or ","
            for fid in req.from_file_ids:
                try:
                    vid, key, cookie = parse_file_id(fid)
                    n = store.read_needle(
                        vid, key, cookie=cookie,
                        shard_reader=self._make_shard_reader(vid))
                except (KeyError, ValueError) as e:
                    context.abort(5, f"query {fid}: {e}")
                data = n.data
                if n.is_gzipped:
                    import gzip as _gz
                    data = _gz.decompress(data)
                if in_fmt == "csv":
                    rows = query_csv_lines(
                        data, list(req.projections), q,
                        delimiter=req.input_serialization.csv_delimiter or ",",
                        has_header=req.input_serialization.csv_has_header)
                else:
                    rows = query_json_lines(data, list(req.projections), q)
                if out_fmt == "csv":
                    import csv as _csv
                    import io as _io
                    sio = _io.StringIO()
                    wr = _csv.writer(sio, delimiter=out_delim,
                                     lineterminator="\n")
                    for row in rows:
                        wr.writerow(["" if v is None else v for v in row])
                    if rows:
                        yield vpb.QueriedStripe(
                            records=sio.getvalue().encode())
                    continue
                buf = []
                for row in rows:
                    if (in_fmt != "csv" and not req.projections
                            and len(row) == 1):
                        buf.append(_json.dumps(row[0]))  # whole document
                    else:
                        buf.append(_json.dumps(row))
                if buf:
                    yield vpb.QueriedStripe(
                        records=("\n".join(buf) + "\n").encode())

        return svc

