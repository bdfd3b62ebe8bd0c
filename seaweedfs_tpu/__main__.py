"""CLI entrypoint: python -m seaweedfs_tpu <verb> (reference weed/command).

Verbs (subset of reference command/command.go:12-44, growing):
  master   - run a master server
  volume   - run a volume server
  server   - master + volume (+filer later) in one process (command/server.go)
  shell    - admin REPL (weed shell)
  upload   - assign + upload files
  download - fetch by fid
  fix      - rebuild a .idx from a .dat (reference command/fix.go:74)
  backup   - incrementally back up a volume to a local dir (command/backup.go)
  scaffold - print default TOML config templates (command/scaffold.go)
  benchmark- built-in load test (reference command/benchmark.go)
"""

from __future__ import annotations

import os
import argparse
import sys
import tempfile
import time


def _add_master_flags(p):
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=9333)
    p.add_argument("-httpPort", type=int, default=0,
                   help="HTTP status/metrics API port (0 = off)")
    p.add_argument("-volumeSizeLimitMB", type=int, default=30_000)
    p.add_argument("-defaultReplication", default="000")
    p.add_argument("-peers", default="",
                   help="comma-separated master quorum incl. self "
                        "(enables raft leader election)")
    p.add_argument("-raftDir", default="",
                   help="directory for persistent raft state")
    p.add_argument("-maintenanceScripts", default="default",
                   help="semicolon-separated shell lines the master cron runs "
                        "(reference master.toml scripts); 'default' = "
                        "fix.replication/ec.rebuild/ec.balance/volume.balance, "
                        "'' disables")
    p.add_argument("-maintenanceIntervalS", type=float, default=0,
                   help="cron interval seconds (0 = reference default 17 min)")
    p.add_argument("-maintenanceHealthDriven", default="on",
                   choices=["on", "off"],
                   help="on (default): cron sweeps repair from the health "
                        "plane's report, most-at-risk first under the "
                        "admission budget, instead of blind ec.rebuild/"
                        "volume.fix.replication; off: legacy script list")
    p.add_argument("-maintenanceMaxConcurrentRepairs", type=int, default=2,
                   help="repairs in flight per health-driven sweep")
    p.add_argument("-ecParityShards", type=int, default=0,
                   help="parity shard count of the cluster's EC geometry, "
                        "used by the health engine to derive k = n - parity "
                        "(0 = fork default 2; MUST match ec.encode's "
                        "-parityShards or /cluster/health mis-scores stripes)")
    p.add_argument("-ecShards", default="",
                   help="cluster EC geometry as 'd,p' (e.g. 14,2 fork / "
                        "10,4 upstream); the p half feeds the health "
                        "engine like -ecParityShards")
    p.add_argument("-lifecyclePolicy", default="",
                   help="tiered-storage lifecycle policy JSON file; wires "
                        "lifecycle.apply into the maintenance cron so "
                        "cooling collections EC-encode, offload to the "
                        "remote tier and promote back on heat with zero "
                        "operator commands (status: /debug/lifecycle)")
    p.add_argument("-sloPolicy", default="",
                   help="SLO policy: JSON file path or inline JSON doc of "
                        "availability/latency objectives; the leader's "
                        "telemetry collector evaluates multi-window "
                        "burn-rate alerts from it (status: "
                        "/cluster/telemetry, shell cluster.top)")
    p.add_argument("-telemetryIntervalS", type=float, default=0,
                   help="fleet telemetry scrape interval seconds; 0 uses "
                        "SWTPU_TELEMETRY_INTERVAL_S (default 15), "
                        "negative disables the collector")
    p.add_argument("-linkCosts", default="",
                   help="geo link-cost policy: JSON file path or inline "
                        "JSON doc pricing intra-rack/cross-rack/cross-DC "
                        "bytes (plus per-DC-pair overrides, a cross-DC "
                        "byte budget and the replication lag bound); "
                        "feeds replica growth, repair planning and the "
                        "balance planners; served at /cluster/linkcosts")
    _add_security_flags(p)


def _add_security_flags(p):
    # security.toml analogue (reference weed/security, util/config.go):
    # empty keys keep security off, matching the reference default.
    p.add_argument("-jwtSigningKey", default="")
    p.add_argument("-jwtReadSigningKey", default="")
    p.add_argument("-whiteList", default="",
                   help="comma-separated IPs/CIDRs allowed without jwt")


def _make_guard(opt):
    """Flags win; absent flags fall back to security.toml on the config
    tier chain (reference util/config.go:37-48 + security/jwt wiring)."""
    from .security import Guard
    from .utils import config as cfg
    sec = cfg.load_config("security")
    sign = opt.jwtSigningKey or cfg.get_dotted(
        sec, "jwt.signing.key", "") or ""
    read = opt.jwtReadSigningKey or cfg.get_dotted(
        sec, "jwt.signing.read.key", "") or ""
    wl = opt.whiteList or cfg.get_dotted(sec, "guard.white_list", "") or ""
    if isinstance(wl, list):
        wl = ",".join(wl)
    if not (sign or read or wl):
        return None
    return Guard(white_list=[s for s in wl.split(",") if s],
                 signing_key=sign, read_signing_key=read)


def _add_volume_flags(p):
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8080)
    p.add_argument("-grpcPort", type=int, default=0)
    p.add_argument("-mserver", default="127.0.0.1:9333")
    p.add_argument("-dir", default="./data", nargs="?")
    p.add_argument("-max", type=int, default=8)
    p.add_argument("-dataCenter", default="")
    p.add_argument("-rack", default="")
    p.add_argument("-disk", default="hdd")
    p.add_argument("-coder", default="auto",
                   help="erasure coder backend: auto|jax|native|numpy")
    p.add_argument("-codec", default="rs",
                   help="erasure codec for new encodes: rs | piggyback | "
                        "msr (msr = product-matrix regenerating code, "
                        "bandwidth-optimal repair for any single loss; "
                        "rebuilds always follow each volume's .vif)")
    p.add_argument("-ecShards", default="",
                   help="default EC geometry as 'd,p' (e.g. 14,2 fork / "
                        "10,4 upstream)")
    p.add_argument("-index", default="memory",
                   help="needle map kind: memory|leveldb|sorted_file "
                        "(reference -index flag)")
    p.add_argument("-qosPolicy", default="",
                   help="multi-tenant QoS policy JSON file (tenant = "
                        "collection); hot-reloaded on mtime change, "
                        "retunable via POST /debug/qos")
    _add_security_flags(p)


def _ec_parity(opt) -> "int | None":
    """-ecShards d,p wins over the older -ecParityShards spelling."""
    if getattr(opt, "ecShards", ""):
        from .shell.ec_commands import parse_ec_shards
        return parse_ec_shards(opt.ecShards)[1]
    return opt.ecParityShards or None


def _ec_geometry(opt):
    if not getattr(opt, "ecShards", ""):
        return None
    from .ec.locate import EcGeometry
    from .shell.ec_commands import parse_ec_shards
    d, p = parse_ec_shards(opt.ecShards)
    return EcGeometry(d=d, p=p)


def run_master(argv):
    from .master.master_server import MasterServer
    p = argparse.ArgumentParser(prog="master")
    _add_master_flags(p)
    opt = p.parse_args(argv)
    import os as _os
    raft_state = None
    if opt.raftDir:
        _os.makedirs(opt.raftDir, exist_ok=True)
        raft_state = _os.path.join(opt.raftDir, f"raft-{opt.port}.json")
    from .utils import config as cfg
    mconf = cfg.load_config("master")
    if opt.maintenanceScripts == "default":
        toml_scripts = cfg.get_dotted(mconf, "master.maintenance.scripts", "")
        scripts = ([ln.strip() for ln in toml_scripts.splitlines()
                    if ln.strip()] if toml_scripts else None)
    else:
        scripts = [s for s in opt.maintenanceScripts.split(";") if s.strip()]
    if not opt.maintenanceIntervalS:
        mins = cfg.get_dotted(mconf, "master.maintenance.sleep_minutes", 0)
        opt.maintenanceIntervalS = float(mins) * 60 if mins else 0
    ms = MasterServer(ip=opt.ip, port=opt.port,
                      volume_size_limit_mb=opt.volumeSizeLimitMB,
                      default_replication=opt.defaultReplication,
                      guard=_make_guard(opt), http_port=opt.httpPort or None,
                      peers=[p for p in opt.peers.split(",") if p],
                      raft_state_path=raft_state,
                      maintenance_scripts=scripts,
                      maintenance_interval_s=opt.maintenanceIntervalS or None,
                      maintenance_health_driven=(
                          opt.maintenanceHealthDriven == "on"),
                      ec_parity_shards=_ec_parity(opt),
                      lifecycle_policy=opt.lifecyclePolicy,
                      slo_policy=opt.sloPolicy,
                      link_costs=opt.linkCosts,
                      telemetry_interval_s=opt.telemetryIntervalS or None)
    ms.admin_cron.repair_max_concurrent = opt.maintenanceMaxConcurrentRepairs
    ms.start()
    _wait_forever()


def run_volume(argv):
    from .server.volume_server import VolumeServer
    from .storage.disk_location import DiskLocation
    from .storage.store import Store
    p = argparse.ArgumentParser(prog="volume")
    _add_volume_flags(p)
    opt = p.parse_args(argv)
    store = Store(opt.ip, opt.port, f"{opt.ip}:{opt.port}",
                  [DiskLocation(opt.dir, opt.disk, opt.max,
                                needle_map_kind=opt.index)],
                  coder_name=opt.coder, ec_codec=opt.codec,
                  ec_geometry=_ec_geometry(opt))
    vs = VolumeServer(store, opt.mserver, ip=opt.ip, port=opt.port,
                      grpc_port=opt.grpcPort or None,
                      data_center=opt.dataCenter, rack=opt.rack,
                      guard=_make_guard(opt),
                      qos_policy=opt.qosPolicy or None)
    vs.start()
    _wait_forever()


def run_server(argv):
    """Single-binary dev mode (reference command/server.go:176)."""
    from .master.master_server import MasterServer
    from .server.volume_server import VolumeServer
    from .storage.disk_location import DiskLocation
    from .storage.store import Store
    p = argparse.ArgumentParser(prog="server")
    _add_master_flags(p)
    p.add_argument("-volumePort", type=int, default=8080)
    p.add_argument("-dir", default="./data")
    p.add_argument("-max", type=int, default=8)
    p.add_argument("-coder", default="auto")
    p.add_argument("-codec", default="rs",
                   help="erasure codec for new encodes: rs | piggyback | msr")
    p.add_argument("-filer", action="store_true")
    p.add_argument("-filerPort", type=int, default=8888)
    p.add_argument("-s3", action="store_true")
    p.add_argument("-s3Port", type=int, default=8333)
    p.add_argument("-webdav", action="store_true")
    p.add_argument("-webdavPort", type=int, default=7333)
    p.add_argument("-iam", action="store_true")
    p.add_argument("-iamPort", type=int, default=8111)
    opt = p.parse_args(argv)
    ms = MasterServer(ip=opt.ip, port=opt.port,
                      volume_size_limit_mb=opt.volumeSizeLimitMB,
                      default_replication=opt.defaultReplication,
                      guard=_make_guard(opt), http_port=opt.httpPort or None,
                      slo_policy=opt.sloPolicy,
                      link_costs=opt.linkCosts,
                      telemetry_interval_s=opt.telemetryIntervalS or None)
    ms.start()
    store = Store(opt.ip, opt.volumePort, f"{opt.ip}:{opt.volumePort}",
                  [DiskLocation(opt.dir, "hdd", opt.max)],
                  coder_name=opt.coder, ec_codec=opt.codec,
                  ec_geometry=_ec_geometry(opt))
    vs = VolumeServer(store, f"{opt.ip}:{opt.port}", ip=opt.ip,
                      port=opt.volumePort, guard=_make_guard(opt))
    vs.start()
    if opt.filer or opt.s3 or opt.webdav or opt.iam:
        import os as _os

        from .filer.filer_server import FilerServer
        filer_dir = _os.path.join(opt.dir, "filer")
        _os.makedirs(filer_dir, exist_ok=True)
        fs = FilerServer(master_address=f"{opt.ip}:{opt.port}",
                         store_spec=f"sqlite:{filer_dir}/filer.db",
                         ip=opt.ip, port=opt.filerPort,
                         meta_log_path=_os.path.join(filer_dir, "meta.log"))
        fs.start()
        if opt.s3:
            from .s3.s3_server import S3Gateway
            s3 = S3Gateway(fs, ip=opt.ip, port=opt.s3Port)
            s3.start()
        if opt.webdav:
            from .webdav import WebDavServer
            wd = WebDavServer(fs, ip=opt.ip, port=opt.webdavPort)
            wd.start()
        if opt.iam:
            from .iam import IamApiServer
            from .s3.auth import IdentityAccessManagement
            s3_iam = (s3.iam if opt.s3
                      else IdentityAccessManagement(None))
            IamApiServer(s3_iam, filer_server=fs, ip=opt.ip,
                         port=opt.iamPort).start()
    _wait_forever()


def run_shell(argv):
    """The shell in this process: the REPL, and every `shell -c` that the
    master's resident executor does not take (shell/executor.py)."""
    main_at = _MAIN_AT or time.perf_counter()
    from .shell.client import process_line, shell_parser
    from .shell.commands import CommandEnv, repl, run_command
    from .shell.executor import load_commands
    load_commands()
    opt = shell_parser().parse_args(argv)
    if opt.jwtSigningKey:
        from .utils.rpc import set_cluster_key
        set_cluster_key(opt.jwtSigningKey)
    env = CommandEnv(opt.master)
    if opt.filer:
        env.option["filer"] = opt.filer
    if opt.script:
        # scripted mode is CI/cron-facing: a failing command (e.g.
        # cluster.check tripping on an AT_RISK verdict, volume.scrub
        # finding corruption) must surface as a non-zero process exit,
        # not a printed-and-swallowed error like in the interactive REPL
        failed = False
        try:
            try:
                for line in opt.script.split(";"):
                    if not run_command(env, line):
                        break
            except Exception as e:  # noqa: BLE001
                print(f"error: {e}", file=sys.stderr)
                failed = True
            env.release_lock()
        finally:
            # the process's own phases, on every way out
            print("shell via=process", file=sys.stderr)
            print(process_line(env, main_at), file=sys.stderr, flush=True)
        if failed:
            sys.exit(2)
    else:
        repl(env)


def run_backup(argv):
    """Incrementally back up volumes to a local directory
    (reference command/backup.go)."""
    from .client.backup import backup_volume
    from .client.master_client import MasterClient
    p = argparse.ArgumentParser(prog="backup")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-dir", default=".")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    opt = p.parse_args(argv)
    mc = MasterClient(opt.master).start()
    try:
        mc.wait_connected()
        res = backup_volume(mc, opt.volumeId, opt.dir, opt.collection)
        print(f"backup volume {res['volume_id']}: {res['mode']}, "
              f"{res['records_applied']} records applied, "
              f"{res['size']} bytes")
    finally:
        mc.stop()


def run_filer(argv):
    """Standalone filer daemon (reference command/filer.go)."""
    from .filer.filer_server import FilerServer
    p = argparse.ArgumentParser(prog="filer")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8888)
    p.add_argument("-grpcPort", type=int, default=0)
    p.add_argument("-store", default="",
                   help="memory | sqlite:/path.db | logdb:/path.logdb | lsm:/dir "
                        "(default: filer.toml or sqlite ./filer.db)")
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-maxMB", type=int, default=4)
    p.add_argument("-encryptVolumeData", action="store_true",
                   help="AES-256-GCM encrypt chunks; keys live in filer "
                        "metadata (reference filer -encryptVolumeData)")
    p.add_argument("-noPeerMeta", action="store_true",
                   help="disable the multi-filer metadata mesh (reference "
                        "filers aggregate peer metadata by default)")
    p.add_argument("-chunkCacheMB", type=int, default=64,
                   help="in-memory chunk-cache bound on the read path")
    p.add_argument("-chunkCacheDir", default="",
                   help="optional disk tier for the chunk cache")
    p.add_argument("-s3", action="store_true",
                   help="embed an S3 gateway over this filer "
                        "(reference `weed filer -s3`)")
    p.add_argument("-s3Port", type=int, default=8333)
    opt = p.parse_args(argv)
    store = opt.store
    if not store:
        from .utils import config as cfg
        # legacy single-filer layouts keep working, but ONLY on the
        # default port — a second filer on another port must never
        # adopt (and corrupt) the shared legacy files
        legacy = "./filer.db"
        fallback = (f"sqlite:{legacy}"
                    if opt.port == 8888 and os.path.exists(legacy)
                    else f"sqlite:./filer-{opt.port}.db")
        store = cfg.get_dotted(cfg.load_config("filer"),
                               "filer.options.store", fallback)
    # per-port defaults: two filers started from one cwd (the obvious
    # way to try the peer mesh) must not share a meta log or store; a
    # pre-existing legacy ./filer-meta.log keeps its name on the
    # default port only (same rule as the store above). The log lives
    # NEXT TO the store's db file, not in the cwd — a filer pointed at
    # a scratch store (every test harness) must not shed meta logs
    # wherever it was launched from
    spec_path = store.partition(":")[2]
    meta_dir = (os.path.dirname(os.path.abspath(spec_path)) if spec_path
                else tempfile.mkdtemp(prefix=f"swtpu-filer-{opt.port}-"))
    meta_log = ("./filer-meta.log"
                if opt.port == 8888 and os.path.exists("./filer-meta.log")
                else os.path.join(meta_dir, f"filer-meta-{opt.port}.log"))
    fs = FilerServer(opt.master, store_spec=store, ip=opt.ip, port=opt.port,
                     grpc_port=opt.grpcPort or None,
                     meta_log_path=meta_log,
                     collection=opt.collection, replication=opt.replication,
                     chunk_size_mb=opt.maxMB,
                     encrypt_data=opt.encryptVolumeData,
                     meta_aggregate=not opt.noPeerMeta,
                     chunk_cache_mb=opt.chunkCacheMB,
                     chunk_cache_dir=opt.chunkCacheDir or None).start()
    if opt.s3:
        # embedded gateway rides the in-process filer: S3 GET/PUT go
        # through the streaming large-object data plane directly
        from .s3.s3_server import S3Gateway
        S3Gateway(fs, ip=opt.ip, port=opt.s3Port).start()
    _wait_forever()


def run_s3_standalone(argv):
    """Standalone S3 gateway over a remote filer (reference command/s3.go)."""
    from .client.filer_client import FilerClient
    from .s3.s3_server import S3Gateway
    p = argparse.ArgumentParser(prog="s3")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8333)
    p.add_argument("-config", default="", help="identities json file")
    opt = p.parse_args(argv)
    import json as _json
    import threading as _threading
    iam_config = None
    if opt.config:
        with open(opt.config) as f:
            iam_config = _json.load(f)
    fc = FilerClient(opt.filer)
    gw = S3Gateway(fc, ip=opt.ip, port=opt.port, iam_config=iam_config)

    def _load_filer_identities():
        entry = fc.filer.find_entry("/etc/iam", "identity.json")
        if entry is not None:
            gw.iam.load(_json.loads(fc.read_entry_bytes(entry)))
            print("s3: identities loaded from filer /etc/iam/identity.json",
                  file=sys.stderr)

    def _load_circuit_breaker():
        entry = fc.filer.find_entry("/etc/s3", "circuit_breaker.json")
        if entry is not None:
            gw.breaker.load(_json.loads(fc.read_entry_bytes(entry)))
            print("s3: circuit breaker loaded from filer "
                  "/etc/s3/circuit_breaker.json", file=sys.stderr)

    def _load_qos_policy():
        entry = fc.filer.find_entry("/etc/qos", "policy.json")
        if entry is not None:
            gw.qos.load(_json.loads(fc.read_entry_bytes(entry)))
            print("s3: qos policy loaded from filer "
                  "/etc/qos/policy.json", file=sys.stderr)

    # cluster config lives in the filer and hot-reloads on change
    # (reference auth_credentials_subscribe.go + s3api_circuit_breaker.go);
    # each loader fails independently so a bad identity file can't leave
    # the breaker silently disabled (or vice versa)
    def _load_all(stage: str):
        if not opt.config:
            try:
                _load_filer_identities()
            except Exception as e:  # noqa: BLE001
                print(f"s3: identity {stage}: {e}", file=sys.stderr)
        try:
            _load_circuit_breaker()
        except Exception as e:  # noqa: BLE001
            print(f"s3: circuit breaker {stage}: {e}", file=sys.stderr)
        try:
            _load_qos_policy()
        except Exception as e:  # noqa: BLE001
            print(f"s3: qos policy {stage}: {e}", file=sys.stderr)

    _load_all("load")

    def _watch():
        stop = _threading.Event()
        for resp in fc.filer.subscribe(time.time_ns(), stop,
                                       path_prefix="/etc"):
            _load_all("reload")

    _threading.Thread(target=_watch, daemon=True,
                      name="s3-conf-watch").start()
    gw.start()
    _wait_forever()


def run_webdav_standalone(argv):
    """Standalone WebDAV gateway over a remote filer (command/webdav.go)."""
    from .client.filer_client import FilerClient
    from .webdav import WebDavServer
    p = argparse.ArgumentParser(prog="webdav")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=7333)
    opt = p.parse_args(argv)
    WebDavServer(FilerClient(opt.filer), ip=opt.ip, port=opt.port).start()
    _wait_forever()


def run_master_follow(argv):
    """Read-only master follower (reference command/master_follower.go):
    maintains the leader's vid map via the KeepConnected push stream and
    answers LookupVolume / /dir/lookup locally — read scaling without
    raft membership."""
    from .client.master_client import MasterClient
    from .pb import master_pb2 as mpb
    from .utils.rpc import MASTER_SERVICE, RpcService, serve

    p = argparse.ArgumentParser(prog="master.follow")
    p.add_argument("-masters", default="127.0.0.1:9333",
                   help="leader quorum to follow")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=9334)
    opt = p.parse_args(argv)
    mc = MasterClient(opt.masters, client_type="master-follower").start()
    mc.wait_connected()

    svc = RpcService(MASTER_SERVICE)

    @svc.unary("LookupVolume", mpb.LookupVolumeRequest,
               mpb.LookupVolumeResponse)
    def lookup(req, ctx):
        resp = mpb.LookupVolumeResponse()
        for vid_str in req.volume_or_file_ids:
            e = resp.volume_id_locations.add(volume_or_file_id=vid_str)
            try:
                for l in mc.lookup(int(vid_str.split(",")[0])):
                    e.locations.add(url=l["url"],
                                    public_url=l["public_url"],
                                    grpc_port=l["grpc_port"])
            except Exception as ex:  # noqa: BLE001
                e.error = str(ex)
        return resp

    @svc.unary("GetMasterConfiguration",
               mpb.GetMasterConfigurationRequest,
               mpb.GetMasterConfigurationResponse)
    def conf(req, ctx):
        return mpb.GetMasterConfigurationResponse(leader=mc.leader)

    serve(f"{opt.ip}:{opt.port}", [svc])
    print(f"master follower on {opt.ip}:{opt.port} tracking {mc.leader} "
          "(lookup-only)")
    _wait_forever()


def run_filer_backup(argv):
    """Continuously mirror a filer subtree into a local directory
    (reference command/filer_backup.go): subscribe to metadata events and
    apply them through the local replication sink, resuming from the last
    applied offset persisted in the SOURCE filer's kv space."""
    import struct as _struct
    import threading as _threading

    from .client.filer_client import FilerClient
    from .replication.replicator import Replicator
    from .replication.sink import LocalSink

    p = argparse.ArgumentParser(prog="filer.backup")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-dir", required=True, help="local mirror directory")
    p.add_argument("-path", default="/", help="subtree to mirror")
    opt = p.parse_args(argv)
    fc = FilerClient(opt.filer, client_name="filer-backup")
    repl = Replicator(LocalSink(opt.dir), fc.read_entry_bytes, opt.path)
    offset_key = f"backup.offset.{opt.dir}".encode()
    raw = fc.filer.kv_get(offset_key)
    since = _struct.unpack("<q", raw)[0] if raw else 0
    stop = _threading.Event()
    print(f"backing up {opt.filer}{opt.path} -> {opt.dir} (since {since})")
    try:
        for resp in fc.filer.subscribe(since, stop, path_prefix=opt.path):
            applied = False
            for attempt in range(5):  # FilerSync-style retry + dead-letter
                try:
                    repl.replicate(resp.directory, resp.event_notification)
                    applied = True
                    break
                except Exception as e:  # noqa: BLE001
                    print(f"apply {resp.directory} (try {attempt + 1}/5): "
                          f"{e}", file=sys.stderr)
                    time.sleep(0.2 * 2 ** attempt)
            if not applied:
                print(f"DEAD-LETTER {resp.directory}: mirror may diverge; "
                      "re-run with -path to re-scan", file=sys.stderr)
            if resp.ts_ns:
                fc.filer.kv_put(offset_key,
                                _struct.pack("<q", resp.ts_ns))
    except KeyboardInterrupt:
        stop.set()


def run_iam_standalone(argv):
    """Standalone IAM API over a remote filer (reference command/iam.go)."""
    from .client.filer_client import FilerClient
    from .iam import IamApiServer
    from .s3.auth import IdentityAccessManagement
    p = argparse.ArgumentParser(prog="iam")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8111)
    opt = p.parse_args(argv)
    fc = FilerClient(opt.filer)
    IamApiServer(IdentityAccessManagement(None), filer_server=fc,
                 ip=opt.ip, port=opt.port).start()
    _wait_forever()


def run_filer_sync(argv):
    """Continuous bidirectional filer synchronization
    (reference command/filer_sync.go)."""
    from .client.filer_client import FilerClient
    from .replication.filer_sync import FilerSync
    p = argparse.ArgumentParser(prog="filer.sync")
    p.add_argument("-a", required=True, help="filer A host:port")
    p.add_argument("-b", required=True, help="filer B host:port")
    p.add_argument("-isActivePassive", action="store_true",
                   help="only sync A -> B")
    p.add_argument("-path", default="/", help="path prefix to sync")
    opt = p.parse_args(argv)
    fa, fb = FilerClient(opt.a), FilerClient(opt.b)
    FilerSync(fa, fb, path_prefix=opt.path).start()
    print(f"syncing {opt.a} -> {opt.b} under {opt.path}")
    if not opt.isActivePassive:
        FilerSync(fb, fa, path_prefix=opt.path).start()
        print(f"syncing {opt.b} -> {opt.a} under {opt.path}")
    _wait_forever()


def run_geo_sync(argv):
    """Async cross-cluster replication over an expensive link — the
    filer.sync analogue of the geo plane (geo/replication.py): distinct
    resumable offset namespace, maintenance-class applies, and the
    bounded-lag invariant published as
    SeaweedFS_geo_replication_lag_seconds{peer}."""
    from .client.filer_client import FilerClient
    from .geo.policy import LinkCostModel, load_link_costs
    from .geo.replication import GeoSync
    p = argparse.ArgumentParser(prog="geo.sync")
    p.add_argument("-a", required=True, help="local filer host:port")
    p.add_argument("-b", required=True, help="remote filer host:port")
    p.add_argument("-isActivePassive", action="store_true",
                   help="only replicate A -> B")
    p.add_argument("-path", default="/", help="path prefix to replicate")
    p.add_argument("-peerA", default="", help="peer label for the A side "
                   "(defaults to its address)")
    p.add_argument("-peerB", default="", help="peer label for the B side")
    p.add_argument("-linkCosts", default="",
                   help="link-cost policy (inline JSON or file) supplying "
                   "replication_lag_bound_s; -lagBound overrides")
    p.add_argument("-lagBound", type=float, default=-1.0,
                   help="replication lag bound in seconds (<0: use policy)")
    opt = p.parse_args(argv)
    costs = (load_link_costs(opt.linkCosts) if opt.linkCosts
             else LinkCostModel())
    bound = (opt.lagBound if opt.lagBound >= 0
             else costs.replication_lag_bound_s)
    fa, fb = FilerClient(opt.a), FilerClient(opt.b)
    GeoSync(fa, fb, peer=opt.peerA or opt.a, lag_bound_s=bound,
            path_prefix=opt.path).start()
    print(f"geo-replicating {opt.a} -> {opt.b} under {opt.path} "
          f"(lag bound {bound}s)")
    if not opt.isActivePassive:
        GeoSync(fb, fa, peer=opt.peerB or opt.b, lag_bound_s=bound,
                path_prefix=opt.path).start()
        print(f"geo-replicating {opt.b} -> {opt.a} under {opt.path}")
    _wait_forever()


def run_filer_copy(argv):
    """Copy local files/directories into the filer
    (reference command/filer_copy.go)."""
    import mimetypes
    import os as _os

    from .client.filer_client import FilerClient
    p = argparse.ArgumentParser(prog="filer.copy")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("files", nargs="+",
                   help="local files/dirs, last arg = filer dest dir")
    opt = p.parse_args(argv)
    *srcs, dest = opt.files
    if not dest.startswith("/"):
        print("destination must be an absolute filer path")
        sys.exit(1)
    fc = FilerClient(opt.filer)
    n = 0
    for src in srcs:
        if _os.path.isdir(src):
            base = _os.path.basename(src.rstrip("/"))
            for root, _dirs, names in _os.walk(src):
                rel = _os.path.relpath(root, src)
                for name in names:
                    local = _os.path.join(root, name)
                    remote = "/".join(filter(
                        lambda s: s not in ("", "."),
                        [dest.rstrip("/"), base, rel, name]))
                    with open(local, "rb") as f:
                        fc.write_file("/" + remote.lstrip("/"), f.read(),
                                      mime=mimetypes.guess_type(name)[0] or "")
                    n += 1
        else:
            name = _os.path.basename(src)
            with open(src, "rb") as f:
                fc.write_file(f"{dest.rstrip('/')}/{name}", f.read(),
                              mime=mimetypes.guess_type(name)[0] or "")
            n += 1
    print(f"copied {n} files to {opt.filer}{dest}")


def run_filer_meta_tail(argv):
    """Follow the filer metadata event stream
    (reference command/filer_meta_tail.go)."""
    import threading as _threading

    from .client.filer_client import FilerClient
    p = argparse.ArgumentParser(prog="filer.meta.tail")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-pathPrefix", default="/")
    p.add_argument("-timeAgo", type=float, default=0,
                   help="start N seconds in the past (0 = now)")
    opt = p.parse_args(argv)
    fc = FilerClient(opt.filer, client_name="meta-tail")
    since = time.time_ns() - int(opt.timeAgo * 1e9)  # swtpu-lint: disable=wallclock-duration (wire cursor: filer events carry wall-clock ts_ns)
    stop = _threading.Event()
    try:
        for resp in fc.filer.subscribe(since, stop,
                                       path_prefix=opt.pathPrefix):
            ev = resp.event_notification
            kind = ("delete" if not ev.new_entry.name
                    else "create" if not ev.old_entry.name else "update")
            name = ev.new_entry.name or ev.old_entry.name
            print(f"{resp.ts_ns} {kind:7s} {resp.directory}/{name}")
    except KeyboardInterrupt:
        stop.set()


def run_export(argv):
    """Dump a volume's live needles to local files
    (reference command/export.go)."""
    import os as _os

    from .storage.volume import Volume
    p = argparse.ArgumentParser(prog="export")
    p.add_argument("-dir", default=".", help="directory holding .dat/.idx")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    p.add_argument("-o", dest="output", default="export",
                   help="output directory")
    opt = p.parse_args(argv)
    v = Volume(opt.dir, opt.collection, opt.volumeId,
               create_if_missing=False)
    _os.makedirs(opt.output, exist_ok=True)
    keys, offs, sizes = v.nm.map.items_arrays()
    n = 0
    for i in range(keys.size):
        needle = v.read_needle(int(keys[i]), cookie=None)
        raw = (needle.name.decode(errors="replace")
               if needle.name else f"{int(keys[i]):x}")
        name = _os.path.basename(raw.replace("\\", "/"))  # no traversal
        if not name or name in (".", ".."):
            name = f"{int(keys[i]):x}"
        with open(_os.path.join(opt.output, name), "wb") as f:
            f.write(needle.data)
        n += 1
    v.close()
    print(f"exported {n} needles from volume {opt.volumeId} to {opt.output}")


def run_compact(argv):
    """Offline-vacuum a volume in place (reference command/compact.go)."""
    from .storage.vacuum import commit_compact, compact
    from .storage.volume import Volume
    p = argparse.ArgumentParser(prog="compact")
    p.add_argument("-dir", default=".")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    opt = p.parse_args(argv)
    v = Volume(opt.dir, opt.collection, opt.volumeId,
               create_if_missing=False)
    live, reclaimed = compact(v)
    v = commit_compact(v)
    v.close()
    print(f"compacted volume {opt.volumeId}: {live} live needles, "
          f"{reclaimed} bytes reclaimed")


def run_version(argv):
    from . import __version__ as ver
    print(f"seaweedfs-tpu {ver}")


def run_scaffold(argv):
    """Print default TOML config templates (reference command/scaffold.go +
    command/scaffold/*.toml); write with -output."""
    p = argparse.ArgumentParser(prog="scaffold")
    p.add_argument("-config", default="security",
                   help="master|filer|security|replication|notification|shell")
    p.add_argument("-output", default="",
                   help="directory to write <config>.toml into ('' = stdout)")
    opt = p.parse_args(argv)
    from .utils.scaffold import TEMPLATES
    body = TEMPLATES.get(opt.config)
    if body is None:
        print(f"unknown config {opt.config!r}; have {sorted(TEMPLATES)}")
        sys.exit(1)
    if opt.output:
        import os as _os
        _os.makedirs(opt.output, exist_ok=True)
        path = _os.path.join(opt.output, f"{opt.config}.toml")
        with open(path, "w") as f:
            f.write(body)
        print(f"wrote {path}")
    else:
        print(body)


def run_upload(argv):
    from .client import operation
    from .client.master_client import MasterClient
    p = argparse.ArgumentParser(prog="upload")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-collection", default="")
    p.add_argument("-replication", default="")
    p.add_argument("-jwtSigningKey", default="")
    p.add_argument("files", nargs="+")
    opt = p.parse_args(argv)
    if opt.jwtSigningKey:
        from .utils.rpc import set_cluster_key
        set_cluster_key(opt.jwtSigningKey)
    mc = MasterClient(opt.master)
    import json
    import mimetypes
    import os
    for path in opt.files:
        with open(path, "rb") as f:
            data = f.read()
        mime = mimetypes.guess_type(path)[0] or ""
        res = operation.submit(mc, data, name=os.path.basename(path),
                               mime=mime, collection=opt.collection,
                               replication=opt.replication)
        print(json.dumps({"file": path, "fid": res.fid, "size": res.size,
                          "url": f"{res.url}/{res.fid}"}))


def run_download(argv):
    from .client import operation
    from .client.master_client import MasterClient
    p = argparse.ArgumentParser(prog="download")
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-o", dest="output", default="")
    p.add_argument("-jwtSigningKey", default="")
    p.add_argument("fids", nargs="+")
    opt = p.parse_args(argv)
    if opt.jwtSigningKey:
        from .utils.rpc import set_cluster_key
        set_cluster_key(opt.jwtSigningKey)
    mc = MasterClient(opt.master)
    for fid in opt.fids:
        data = operation.read(mc, fid)
        out = opt.output or fid.replace(",", "_")
        with open(out, "wb") as f:
            f.write(data)
        print(f"{fid} -> {out} ({len(data)} bytes)")


def run_fix(argv):
    """Rebuild .idx by scanning the .dat (reference command/fix.go:74)."""
    from .storage.volume import rebuild_idx_from_dat
    p = argparse.ArgumentParser(prog="fix")
    p.add_argument("dat_path")
    opt = p.parse_args(argv)
    if not opt.dat_path.endswith(".dat"):
        p.error(f"{opt.dat_path!r} is not a .dat file")
    idx = opt.dat_path[:-4] + ".idx"
    n = rebuild_idx_from_dat(opt.dat_path, idx)
    print(f"wrote {n} entries to {idx}")


def run_benchmark(argv):
    from .bench_tool import run as bench_run
    bench_run(argv)


def _wait_forever():
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("bye")


def run_mount(argv):
    """Kernel FUSE mount (reference command/mount.go) via the built-in
    ctypes libfuse binding — no fusepy needed."""
    from .client.filer_client import FilerClient
    from .mount.fuse_binding import fuse_loop
    from .mount.weedfs import WeedFS
    p = argparse.ArgumentParser(prog="mount")
    p.add_argument("-filer", default="127.0.0.1:8888",
                   help="filer ip:port (its gRPC is port+10000)")
    p.add_argument("-filerGrpc", default="",
                   help="filer gRPC address override")
    p.add_argument("-dir", required=True, help="mountpoint")
    p.add_argument("-chunkSizeLimitMB", type=int, default=4)
    p.add_argument("-concurrentWriters", type=int, default=8)
    p.add_argument("-allowOther", action="store_true")
    p.add_argument("-cacheDir", default="",
                   help="disk tier for the chunk cache (reference -cacheDir)")
    p.add_argument("-cacheSizeMB", type=int, default=1024,
                   help="disk chunk-cache bound (reference -cacheCapacityMB)")
    opt = p.parse_args(argv)
    fc = FilerClient(opt.filer, grpc_address=opt.filerGrpc,
                     client_name="mount", cache_dir=opt.cacheDir or None,
                     cache_disk_mb=opt.cacheSizeMB)
    wfs = WeedFS(fc, chunk_size_mb=opt.chunkSizeLimitMB,
                 concurrency=opt.concurrentWriters)
    # local control socket for `shell mount.configure` (reference dials
    # /tmp/seaweedfs-mount-<hash>.sock, command_mount_configure.go)
    from .mount.control import mount_socket_path, serve_mount_control
    sock_path = mount_socket_path(opt.dir)
    stop_ctl = serve_mount_control(wfs, sock_path)
    print(f"mounting {opt.filer} at {opt.dir} (unmount: fusermount -u; "
          f"control: {sock_path})")
    try:
        code = fuse_loop(wfs, opt.dir, allow_other=opt.allowOther)
    finally:
        stop_ctl()
    wfs.destroy()
    sys.exit(code)


def run_mq_broker(argv):
    """MQ broker daemon (reference weed mq.broker)."""
    from .mq import BrokerServer
    p = argparse.ArgumentParser(prog="mq.broker")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=17777)
    p.add_argument("-master", default="127.0.0.1:9333")
    p.add_argument("-dir", default="./mq-data",
                   help="local segment directory ('' = memory-only)")
    opt = p.parse_args(argv)
    # standalone broker persists segments to a local directory; pass an
    # in-process filer instead when embedded in `server`
    BrokerServer(opt.master, ip=opt.ip, port=opt.port,
                 data_dir=opt.dir or None).start()
    _wait_forever()


def run_filer_cat(argv):
    """Print a filer file's bytes, reading chunks straight from the
    volume servers (reference command/filer_cat.go)."""
    from .client.filer_client import FilerClient
    from .filer.filer import split_path
    p = argparse.ArgumentParser(prog="filer.cat")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("path", help="absolute filer path")
    opt = p.parse_args(argv)
    fc = FilerClient(opt.filer, client_name="filer-cat")
    d, n = split_path(opt.path)
    entry = fc.filer.find_entry(d, n)
    if entry is None:
        print(f"{opt.path}: not found", file=sys.stderr)
        sys.exit(1)
    if entry.is_directory:
        print(f"{opt.path}: is a directory", file=sys.stderr)
        sys.exit(1)
    sys.stdout.buffer.write(fc.read_entry_bytes(entry))
    sys.stdout.buffer.flush()


def run_filer_meta_backup(argv):
    """Continuously back up filer METADATA into a local sqlite store
    (reference command/filer_meta_backup.go): full-tree scan on first
    run or -restart, then tail the event stream, resuming from the
    offset persisted in the backup store itself."""
    import struct as _struct
    import threading as _threading

    from .client.filer_client import FilerClient
    from .filer.filer import split_path
    from .filer.store import SqliteStore
    p = argparse.ArgumentParser(prog="filer.meta.backup")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-store", default="meta_backup.db",
                   help="sqlite backup store path")
    p.add_argument("-path", default="/", help="subtree to back up")
    p.add_argument("-restart", action="store_true",
                   help="discard the stored offset and re-scan the tree")
    opt = p.parse_args(argv)
    fc = FilerClient(opt.filer, client_name="meta-backup")
    store = SqliteStore(opt.store)
    offset_key = b"meta.backup.offset"
    raw = None if opt.restart else store.kv_get(offset_key)
    since = _struct.unpack("<q", raw)[0] if raw else 0
    if since == 0:
        t0 = fc.filer.server_now_ns()  # filer clock (skew-safe offset)
        n = 0

        def scan(directory):
            nonlocal n
            for e in fc.filer.list_entries(directory):
                store.delete_entry(directory, e.name)
                store.insert_entry(directory, e)
                n += 1
                if e.is_directory:
                    scan(join_dir(directory, e.name))

        def join_dir(d, name):
            return (d.rstrip("/") + "/" + name) if d != "/" else "/" + name

        scan(opt.path)
        since = t0
        store.kv_put(offset_key, _struct.pack("<q", since))
        print(f"full scan: {n} entries into {opt.store}")
    stop = _threading.Event()
    print(f"tailing {opt.filer}{opt.path} metadata -> {opt.store} "
          f"(since {since})")
    try:
        for resp in fc.filer.subscribe(since, stop, path_prefix=opt.path):
            ev = resp.event_notification
            try:
                if ev.HasField("old_entry") and ev.old_entry.name:
                    store.delete_entry(resp.directory, ev.old_entry.name)
                if ev.HasField("new_entry") and ev.new_entry.name:
                    d = ev.new_parent_path or resp.directory
                    store.delete_entry(d, ev.new_entry.name)
                    store.insert_entry(d, ev.new_entry)
            except Exception as e:  # noqa: BLE001
                print(f"apply {resp.directory}: {e}", file=sys.stderr)
            if resp.ts_ns:
                store.kv_put(offset_key, _struct.pack("<q", resp.ts_ns))
    except KeyboardInterrupt:
        stop.set()


def _open_sink(spec: str):
    """Replication sink from a spec string (reference replication.toml
    picks the enabled sink the same way): 'local:/dir',
    'filer:host:port[/prefix]', 's3:http://host:port/bucket[?ak:sk]'."""
    from .replication.sink import FilerSink, LocalSink, S3Sink
    kind, _, arg = spec.partition(":")
    if kind == "local":
        return LocalSink(arg)
    if kind == "filer":
        from .client.filer_client import FilerClient
        addr, slash, prefix = arg.partition("/")
        return FilerSink(FilerClient(addr), dir_prefix=slash + prefix
                         if prefix else "")
    if kind in ("s3", "b2", "gcs", "wasabi", "minio"):
        url, _, cred = arg.partition("?")
        scheme, sep, rest = url.partition("://")
        host, _, bucket = rest.partition("/")
        ak, _, sk = cred.partition(":")
        return S3Sink(f"{scheme}://{host}", bucket, ak, sk)
    if kind == "azure":
        from .remote.azure import AzureSink, parse_azure_spec
        return AzureSink(parse_azure_spec(arg))
    if kind == "gcs-json":
        from .remote.gcs import GcsSink, parse_gcs_spec
        return GcsSink(parse_gcs_spec(arg))
    raise ValueError(f"unknown sink spec {spec!r}")


def run_filer_replicate(argv):
    """Consume a notification queue and apply events through a
    replication sink (reference command/filer_replicate.go — the
    queue-decoupled alternative to filer.sync)."""
    from .client.filer_client import FilerClient
    from .notification.queues import LogFileQueue
    from .replication.replicator import Replicator
    p = argparse.ArgumentParser(prog="filer.replicate")
    p.add_argument("-filer", default="127.0.0.1:8888",
                   help="source filer (chunk reads)")
    p.add_argument("-queue", required=True,
                   help="notification source: logfile:/path (durable log "
                        "written by the filer/fs.meta.notify)")
    p.add_argument("-sink", required=True,
                   help="local:/dir | filer:host:port | "
                        "s3:http://host:port/bucket[?ak:sk]")
    p.add_argument("-offsetFile", default="",
                   help="resume-offset path (default <queue>.offset)")
    opt = p.parse_args(argv)
    kind, _, qpath = opt.queue.partition(":")
    if kind != "logfile":
        print("filer.replicate consumes a durable queue; use "
              "logfile:/path (mq consumers: use filer.sync)",
              file=sys.stderr)
        sys.exit(1)
    fc = FilerClient(opt.filer, client_name="filer-replicate")
    repl = Replicator(_open_sink(opt.sink), fc.read_entry_bytes)
    queue = LogFileQueue(qpath)
    off_path = opt.offsetFile or qpath + ".offset"
    offset = 0
    if os.path.exists(off_path):
        with open(off_path) as f:
            offset = int(f.read().strip() or 0)
    print(f"replicating {opt.queue} -> {opt.sink} (offset {offset})")
    try:
        while True:
            progressed = False
            for next_off, rec in queue.read(offset):
                try:
                    repl.replicate(rec.directory, rec.event_notification)
                except Exception as e:  # noqa: BLE001
                    print(f"apply {rec.directory}: {e}", file=sys.stderr)
                offset = next_off
                progressed = True
                with open(off_path, "w") as f:
                    f.write(str(offset))
            if not progressed:
                time.sleep(1.0)
    except KeyboardInterrupt:
        pass


def run_filer_remote_sync(argv):
    """Write LOCAL changes under remote-mounted directories back to the
    remote store (reference command/filer_remote_sync.go)."""
    import threading as _threading

    from .client.filer_client import FilerClient
    from .remote.remote_mount import _load_mappings, apply_event_to_remote
    p = argparse.ArgumentParser(prog="filer.remote.sync")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-dir", default="",
                   help="only sync this mounted directory")
    opt = p.parse_args(argv)
    from .remote.remote_mount import MOUNT_CONF
    fc = FilerClient(opt.filer, client_name="remote-sync")

    def load_mappings():
        m = _load_mappings(fc)
        return {d: v for d, v in m.items() if d == opt.dir} if opt.dir else m

    mappings = load_mappings()
    if not mappings:
        print("no remote mounts to sync", file=sys.stderr)
        sys.exit(1)
    stop = _threading.Event()
    prefix = opt.dir or "/"
    since = fc.filer.server_now_ns()  # the FILER's clock, taken BEFORE
    # the ready print: a skewed client clock would silently drop events;
    # events landing in the print->subscribe gap replay from `since`
    print(f"remote-sync watching {opt.filer}{prefix} "
          f"({len(mappings)} mounts)")
    try:
        for resp in fc.filer.subscribe(since, stop,
                                       path_prefix=prefix):
            ev0 = resp.event_notification
            if MOUNT_CONF == f"{resp.directory}/" \
                    f"{ev0.new_entry.name or ev0.old_entry.name}":
                # a remote.mount/unmount changed the mapping table
                # (visible when watching "/"): pick it up
                mappings = load_mappings()
                continue
            try:
                act = apply_event_to_remote(fc, mappings, resp.directory,
                                            resp.event_notification)
                if act:
                    print(act)
            except Exception as e:  # noqa: BLE001
                print(f"sync {resp.directory}: {e}", file=sys.stderr)
    except KeyboardInterrupt:
        stop.set()


def run_filer_remote_gateway(argv):
    """Mirror bucket creation/deletion under /buckets into a remote
    store, then behave like filer.remote.sync for their contents
    (reference command/filer_remote_gateway.go)."""
    import threading as _threading

    from .client.filer_client import FilerClient
    from .remote.remote_mount import (_load_mappings, _save_mappings,
                                      apply_event_to_remote)
    from .storage.backend import bucket_spec, open_remote
    p = argparse.ArgumentParser(prog="filer.remote.gateway")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-createBucketAt", required=True,
                   help="remote spec new buckets are created on")
    opt = p.parse_args(argv)
    root_spec = (opt.createBucketAt if ":" in opt.createBucketAt
                 else f"local:{opt.createBucketAt}")
    fc = FilerClient(opt.filer, client_name="remote-gateway")
    client = open_remote(root_spec)
    stop = _threading.Event()
    # mappings cached; this process is the only writer under /buckets so
    # its own updates keep the cache fresh (no per-event filer re-read)
    mappings = _load_mappings(fc)
    since = fc.filer.server_now_ns()  # filer clock, before the ready
    # print (see remote.sync)
    print(f"remote-gateway: /buckets <-> {opt.createBucketAt}")
    try:
        for resp in fc.filer.subscribe(since, stop,
                                       path_prefix="/buckets"):
            ev = resp.event_notification
            try:
                is_bucket_level = resp.directory == "/buckets"
                if is_bucket_level and ev.HasField("new_entry") and \
                        ev.new_entry.is_directory and ev.new_entry.name:
                    b = ev.new_entry.name
                    client.create_bucket(b)
                    mappings[f"/buckets/{b}"] = {
                        "spec": bucket_spec(root_spec, b), "prefix": ""}
                    _save_mappings(fc, mappings)
                    print(f"created bucket {b}")
                elif is_bucket_level and ev.HasField("old_entry") and \
                        ev.old_entry.is_directory and ev.old_entry.name \
                        and not (ev.HasField("new_entry")
                                 and ev.new_entry.name):
                    b = ev.old_entry.name
                    client.delete_bucket(b)
                    mappings.pop(f"/buckets/{b}", None)
                    _save_mappings(fc, mappings)
                    print(f"deleted bucket {b}")
                else:
                    act = apply_event_to_remote(fc, mappings,
                                                resp.directory, ev)
                    if act:
                        print(act)
            except Exception as e:  # noqa: BLE001
                print(f"gateway {resp.directory}: {e}", file=sys.stderr)
    except KeyboardInterrupt:
        stop.set()


def run_ftp(argv):
    """FTP gateway over a remote filer (reference weed/ftpd is an unwired
    81-line skeleton; this verb serves a working RFC 959 subset)."""
    from .client.filer_client import FilerClient
    from .ftpd import FtpServer
    p = argparse.ArgumentParser(prog="ftp")
    p.add_argument("-filer", default="127.0.0.1:8888")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=2121)
    p.add_argument("-root", default="/", help="filer subtree to expose")
    p.add_argument("-user", default="", help="require this login "
                                             "(default anonymous)")
    p.add_argument("-password", default="")
    p.add_argument("-passivePortStart", type=int, default=0)
    p.add_argument("-passivePortStop", type=int, default=0)
    opt = p.parse_args(argv)
    users = {opt.user: opt.password} if opt.user else None
    rng = ((opt.passivePortStart, opt.passivePortStop)
           if opt.passivePortStart and opt.passivePortStop else None)
    FtpServer(FilerClient(opt.filer, client_name="ftpd"), ip=opt.ip,
              port=opt.port, root=opt.root, users=users,
              passive_ports=rng).start()
    _wait_forever()


def run_update(argv):
    """Self-update verb (reference command/update.go downloads the
    latest release binary). This build is a source checkout with no
    release channel or egress; say so instead of failing cryptically."""
    p = argparse.ArgumentParser(prog="update")
    p.add_argument("-output", default="", help="(reference parity)")
    p.parse_args(argv)
    print("update: this is a source installation; update with "
          "`git pull` in the repository checkout")


def run_fuse(argv):
    """/etc/fstab-compatible mount wrapper (reference command/fuse.go):
    `swtpu fuse <mountpoint> -o "filer=host:port,chunkSizeLimitMB=4"`."""
    p = argparse.ArgumentParser(prog="fuse")
    p.add_argument("mountpoint")
    p.add_argument("-o", default="", help="comma-separated options")
    opt = p.parse_args(argv)
    opts = dict(kv.partition("=")[::2] for kv in opt.o.split(",") if kv)
    fwd = ["-dir", opt.mountpoint,
           "-filer", opts.get("filer", "127.0.0.1:8888")]
    if "chunkSizeLimitMB" in opts:
        fwd += ["-chunkSizeLimitMB", opts["chunkSizeLimitMB"]]
    if opts.get("allowOthers") in ("", "true") and "allowOthers" in opts:
        fwd += ["-allowOther"]
    run_mount(fwd)


AUTOCOMPLETE_MARK = "# swtpu-autocomplete"


def run_autocomplete(argv):
    """Install bash completion for the verb table into ~/.bashrc
    (reference command/autocomplete.go via posener/complete)."""
    rc = os.path.expanduser("~/.bashrc")
    line = (f'complete -W "{" ".join(sorted(VERBS))}" -o default swtpu '
            f"{AUTOCOMPLETE_MARK}\n")
    existing = ""
    if os.path.exists(rc):
        with open(rc) as f:
            existing = f.read()
    if AUTOCOMPLETE_MARK in existing:
        print("autocomplete already installed")
        return
    with open(rc, "a") as f:
        f.write(line)
    print(f"bash completion installed in {rc}; restart your shell")


def run_unautocomplete(argv):
    rc = os.path.expanduser("~/.bashrc")
    if not os.path.exists(rc):
        print("nothing to remove")
        return
    with open(rc) as f:
        lines = f.readlines()
    kept = [l for l in lines if AUTOCOMPLETE_MARK not in l]
    if len(kept) == len(lines):
        print("nothing to remove")
        return
    with open(rc, "w") as f:
        f.writelines(kept)
    print("bash completion removed")


VERBS = {
    "master": run_master,
    "mq.broker": run_mq_broker,
    "volume": run_volume,
    "server": run_server,
    "shell": run_shell,
    "upload": run_upload,
    "backup": run_backup,
    "scaffold": run_scaffold,
    "filer": run_filer,
    "s3": run_s3_standalone,
    "webdav": run_webdav_standalone,
    "iam": run_iam_standalone,
    "filer.backup": run_filer_backup,
    "master.follow": run_master_follow,
    "filer.sync": run_filer_sync,
    "geo.sync": run_geo_sync,
    "filer.copy": run_filer_copy,
    "filer.meta.tail": run_filer_meta_tail,
    "export": run_export,
    "compact": run_compact,
    "version": run_version,
    "download": run_download,
    "fix": run_fix,
    "benchmark": run_benchmark,
    "mount": run_mount,
    "ftp": run_ftp,
    "fuse": run_fuse,
    "filer.cat": run_filer_cat,
    "filer.meta.backup": run_filer_meta_backup,
    "filer.replicate": run_filer_replicate,
    "filer.remote.sync": run_filer_remote_sync,
    "filer.remote.gateway": run_filer_remote_gateway,
    "update": run_update,
    "autocomplete": run_autocomplete,
    "unautocomplete": run_unautocomplete,
}


def _init_tls():
    """Install cluster mTLS from security.toml [grpc] (reference tls.go)
    for every verb — daemons serve TLS, tools dial TLS."""
    try:
        from .utils.rpc import load_tls_from_security_toml, set_tls_config
        tls = load_tls_from_security_toml()
    except Exception as e:  # noqa: BLE001 — FAIL CLOSED, never plaintext
        print(f"fatal: mTLS configured but unusable: {e}", file=sys.stderr)
        sys.exit(1)
    if tls is not None:
        set_tls_config(tls)
        print("gRPC mTLS enabled (security.toml [grpc])", file=sys.stderr)


# perf_counter at main()'s first statement: the end of a `shell -c`
# process's `start` phase (shell/commands.py:process_line)
_MAIN_AT = 0.0


def main():
    global _MAIN_AT
    _MAIN_AT = time.perf_counter()
    if len(sys.argv) < 2 or sys.argv[1] in ("-h", "--help", "help"):
        print("usage: python -m seaweedfs_tpu <verb> [flags]\n\nverbs:")
        for v in VERBS:
            print(f"  {v}")
        return 0
    verb = sys.argv[1]
    if verb == "shell":
        # decided before _init_tls, which loads grpc: a `shell -c` against
        # a local master runs in the master's resident executor
        from .shell.client import client_main
        rc = client_main(sys.argv[2:], _MAIN_AT)
        if rc is not None:
            return rc
    _init_tls()
    fn = VERBS.get(verb)
    if fn is None:
        print(f"unknown verb {verb!r}", file=sys.stderr)
        return 1
    fn(sys.argv[2:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
