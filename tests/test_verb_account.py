"""The verb's account on a live in-process cluster: the shell's `timing`
line (grammar, methods partition `rpc`, none with tracing off), one
trace id from the shell's root span through the client spans to the
servers' spans, and the finish events of a seal, a rebuild and a scrub
with every old field and the new stage sums."""

import glob
import io
import os
import re
import socket

import numpy as np
import pytest
from conftest import wait_until

from seaweedfs_tpu import tracing
from seaweedfs_tpu.client import operation
from seaweedfs_tpu.client.master_client import MasterClient
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.master.master_server import MasterServer
from seaweedfs_tpu.ops import events
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import ec_commands, volume_commands  # noqa: F401
from seaweedfs_tpu.shell.commands import CommandEnv, run_command, timing_line
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.store import Store

LINE = re.compile(r"^timing (\S+) total=(\d+\.\d{3}) rpc=(\d+\.\d{3})"
                  r"((?: \w+=\d+\.\d{3}/\d+)*)$")
METHOD = re.compile(r" (\w+)=(\d+\.\d{3})/(\d+)")
# what benchmark/kinds/scrub_sweep.py reads a sweep's results by
SCRUB_LINE = re.compile(
    r"volume (\d+): (\d+) needles .* in ([0-9.]+)s \[([^\]]+)\]")


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    mport = free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.4)
    master.start()
    geo = EcGeometry(d=4, p=2, large_block=1 << 22, small_block=1 << 16)
    servers = []
    for i in range(2):
        d = tmp_path_factory.mktemp(f"acct{i}")
        port = free_port()
        store = Store("127.0.0.1", port, "",
                      [DiskLocation(str(d), max_volume_count=10)],
                      ec_geometry=geo, coder_name="numpy")
        vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                          grpc_port=free_port(), pulse_seconds=0.4)
        vs.start()
        servers.append(vs)
    wait_until(lambda: len(master.topo.nodes) >= 2, msg="servers registered")
    mc = MasterClient(f"127.0.0.1:{mport}").start()
    env = CommandEnv(f"127.0.0.1:{mport}", mc=mc, out=io.StringIO())
    yield master, servers, mc, env
    mc.stop()
    for vs in servers:
        vs.stop()
    master.stop()


def parse(err: str) -> "list[dict]":
    out = []
    for text in err.splitlines():
        if not text.startswith("timing "):
            continue
        m = LINE.match(text)
        assert m, f"not the timing grammar: {text!r}"
        out.append({"verb": m.group(1), "total": float(m.group(2)),
                    "rpc": float(m.group(3)),
                    "methods": {k: (float(s), int(n)) for k, s, n
                                in METHOD.findall(m.group(4))}})
    return out


def finish(etype: str, since: int) -> dict:
    found = events.JOURNAL.snapshot(since=since, etype=etype)
    assert len(found) == 1, found
    return found[0]["attrs"]


def last_seq() -> int:
    found = events.JOURNAL.snapshot(limit=1)
    return found[-1]["seq"] if found else 0


def test_timing_line_format():
    acct = tracing.StageAccount("shell/x")
    assert timing_line("x", 0.0123, acct) == "timing x total=0.012 rpc=0.000"
    acct.add("VolumeList", 0.2004)
    acct.add("VolumeEcShardsCopy", 1.0004, n=2)
    acct.add("VolumeEcShardsCopy", 0.5)
    line = timing_line("ec.encode", 2.5, acct)
    assert line == ("timing ec.encode total=2.500 rpc=1.700 "
                    "VolumeEcShardsCopy=1.500/3 VolumeList=0.200/1")
    assert LINE.match(line)


@pytest.fixture(scope="module")
def sealed(cluster):
    """A volume of ~24 MB sealed by the verb; what the seal left behind:
    its timing lines, its trace, its finish event."""
    master, servers, mc, env = cluster
    rng = np.random.default_rng(4)
    payloads = {}
    for _ in range(24):
        data = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
        payloads[operation.submit(mc, data, collection="acct").fid] = data
    vid = int(next(iter(payloads)).split(",")[0])
    assert {int(f.split(",")[0]) for f in payloads} == {vid}
    import contextlib
    err = io.StringIO()
    seq = last_seq()
    tracing.BUFFER.clear()
    with contextlib.redirect_stderr(err):
        run_command(env, "lock")
        run_command(env, f"ec.encode -volumeId {vid} -ecShards 4,2")
    assert "ec encoded 1 volumes" in env.out.getvalue()
    return {"vid": vid, "payloads": payloads, "err": err.getvalue(),
            "event": finish("ec.encode.finish", seq),
            "spans": tracing.BUFFER.snapshot(limit=5000)}


def test_each_command_writes_one_timing_line(sealed):
    lines = parse(sealed["err"])
    assert [ln["verb"] for ln in lines] == ["lock", "ec.encode"]
    lock, encode = lines
    assert set(lock["methods"]) == {"LeaseAdminToken"}
    assert {"VolumeEcShardsGenerateBatch", "VolumeEcShardsCopy",
            "VolumeEcShardsMount", "VolumeDelete"} <= set(encode["methods"])
    assert encode["methods"]["VolumeEcShardsGenerateBatch"][1] == 1
    for ln in lines:
        # the shell is sequential: the methods partition `rpc`, inside
        # the command's wall, most seconds first
        secs = [s for s, _ in ln["methods"].values()]
        assert sum(secs) == pytest.approx(ln["rpc"], abs=1e-9)
        assert ln["rpc"] <= ln["total"] + 0.002
        assert secs == sorted(secs, reverse=True)


def test_one_trace_from_the_shell_to_the_servers(sealed):
    roots = [s for s in sealed["spans"] if s["name"] == "shell/ec.encode"]
    assert len(roots) == 1 and roots[0]["parent_id"] == ""
    trace = [s for s in sealed["spans"]
             if s["trace_id"] == roots[0]["trace_id"]]
    client = [s for s in trace
              if s["name"] == "rpc.client/VolumeEcShardsGenerateBatch"]
    server = [s for s in trace
              if s["name"] == "rpc/VolumeEcShardsGenerateBatch"]
    assert len(client) == len(server) == 1
    assert client[0]["parent_id"] == roots[0]["span_id"]
    assert client[0]["component"] == "rpc" and ":" in client[0]["attrs"]["peer"]
    assert server[0]["parent_id"] == client[0]["span_id"]
    assert server[0]["duration_ms"] <= client[0]["duration_ms"]
    # the pipeline's own span hangs under the server's, with its stages
    (encode,) = [s for s in trace if s["name"] == "ec.encode"]
    assert {"fill_s", "dispatch_s", "drain_s", "write_block_s", "finish_s",
            "write_s", "wall_s"} <= set(encode["attrs"])
    # B's pulls from A are client spans of B's VolumeEcShardsCopy
    assert any(s["name"] == "rpc.client/CopyFile" for s in trace)
    # stage intervals stay out of the ring
    assert not any(s["name"].startswith("swtpu/") for s in sealed["spans"])


def test_encode_finish_keeps_its_fields_and_gains_two(sealed):
    ev = sealed["event"]
    for key in ("node", "ok", "vids", "duration_ms", "fill_s", "coder_s",
                "write_s", "write_block_s", "wall_s", "write_overlap",
                "writers", "mode"):
        assert key in ev, key
    assert ev["ok"] and ev["vids"] == [sealed["vid"]]
    assert ev["finish_s"] > 0
    assert 24 << 20 < ev["bytes"] < 26 << 20  # the .dat bytes sealed
    assert (ev["fill_s"] + ev["coder_s"] + ev["write_block_s"]
            + ev["finish_s"]) <= ev["wall_s"] + 0.002
    assert ev["wall_s"] * 1e3 <= ev["duration_ms"] + 1


@pytest.fixture(scope="module")
def rebuilt(cluster, sealed):
    """Two of one server's three shards lost and rebuilt by the verb;
    what the rebuild left behind: its finish event, its timing lines."""
    master, servers, mc, env = cluster
    vid = sealed["vid"]
    wait_until(lambda: sorted(master.topo.lookup_ec(vid)) == list(range(6)),
               msg="shards registered")
    victim = servers[0]
    # two of the victim's three shards go: four survive
    held = sorted(victim.store.find_ec_volume(vid).shards)[:2]
    victim.store.unmount_ec_shards(vid, held)
    for sid in held:
        (path,) = glob.glob(str(victim.store.locations[0].directory)
                            + f"/*.ec{sid:02d}")
        os.remove(path)
    victim.trigger_heartbeat()
    wait_until(lambda: sorted(master.topo.lookup_ec(vid)) == sorted(
        set(range(6)) - set(held)), msg="shards dropped from the registry")
    import contextlib
    err = io.StringIO()
    seq = last_seq()
    with contextlib.redirect_stderr(err):
        run_command(env, "ec.rebuild")
    assert f"rebuilt {len(held)} shards" in env.out.getvalue()
    return {"held": held, "err": err.getvalue(),
            "event": finish("ec.rebuild.finish", seq)}


def test_rebuild_finish_keeps_its_fields_and_gains_the_stages(
        cluster, sealed, rebuilt):
    master, servers, mc, env = cluster
    ev = rebuilt["event"]
    for key in ("vid", "node", "ok", "rebuilt_shard_ids", "codec",
                "repair_path", "bytes_read", "bytes_written", "duration_ms",
                "read_s", "dispatch_s", "drain_s", "write_s", "batches"):
        assert key in ev, key
    assert ev["ok"] and sorted(ev["rebuilt_shard_ids"]) == rebuilt["held"]
    assert ev["repair_path"] == "full" and ev["batches"] >= 1
    four = ev["read_s"] + ev["dispatch_s"] + ev["drain_s"] + ev["write_s"]
    assert 0.9 <= four / (ev["duration_ms"] / 1e3) <= 1.0, (four, ev)
    (line,) = [ln for ln in parse(rebuilt["err"])
               if ln["verb"] == "ec.rebuild"]
    assert "VolumeEcShardsRebuild" in line["methods"]
    for fid, data in list(sealed["payloads"].items())[:4]:
        assert operation.read(mc, fid) == data


def test_rebuild_finish_books_the_loads_beside_the_read_stage(rebuilt):
    """Four survivors loaded side by side, on this disk and from the
    other server: `read_s` is still the stage's wall inside the RPC, the
    loads' own seconds are summed beside it and split by kind."""
    ev = rebuilt["event"]
    for key in ("read_s", "read_busy_s", "read_local_busy_s",
                "read_remote_busy_s"):
        assert key in ev, key
    assert ev["read_local_busy_s"] > 0 and ev["read_remote_busy_s"] > 0
    # each of the four loads ran inside the stage: their sum is at most
    # d walls (that it is over one wall is held in test_stage_account.py,
    # with loads that sleep: here a busy host may start a loader late)
    assert 0 < ev["read_busy_s"] <= 4 * ev["read_s"] + 0.002
    # each of the three is rounded to a millisecond on its own
    assert ev["read_busy_s"] == pytest.approx(
        ev["read_local_busy_s"] + ev["read_remote_busy_s"], abs=0.0016)
    assert ev["read_s"] <= ev["duration_ms"] / 1e3
    assert ev["bytes_read"] == 4 * ev["bytes_written"] // 2


def test_scrub_writes_one_event_a_volume_and_prints_as_before(
        cluster, capsys):
    master, servers, mc, env = cluster
    rng = np.random.default_rng(6)
    sizes = [int(s) for s in rng.integers(100, 200_000, 30)]
    for size in sizes:
        operation.submit(mc, rng.integers(0, 256, size, dtype=np.uint8)
                         .tobytes(), collection="scrubbed")
    seq = last_seq()
    env.out.truncate(0)
    env.out.seek(0)
    run_command(env, "volume.scrub")
    printed = [SCRUB_LINE.search(ln) for ln in env.out.getvalue().splitlines()]
    printed = [m for m in printed if m]
    found = [e["attrs"] for e in events.JOURNAL.snapshot(
        since=seq, etype="volume.scrub.finish")]
    assert found and len(found) == len(printed)
    for ev, m in zip(found, printed):
        assert set(ev) == {"vid", "node", "scanned", "corrupt",
                           "bytes_checked", "bytes_dispatched", "blocks",
                           "elapsed_s", "mode", "walk_s", "pack_s",
                           "device_s", "compare_s", "device_busy_s"}
        assert (ev["vid"], ev["scanned"]) == (int(m.group(1)),
                                              int(m.group(2)))
        # the host loop, or the kernel on the CPU backend where an
        # earlier test of this process resolved one
        assert ev["mode"] == m.group(4) and ev["mode"] in ("cpu", "xla-cpu")
        assert ev["corrupt"] == 0
        assert (ev["walk_s"] + ev["pack_s"] + ev["device_s"]
                + ev["compare_s"]) >= 0.9 * ev["elapsed_s"]
        assert ev["bytes_dispatched"] == 0 or (
            ev["bytes_dispatched"] >= ev["bytes_checked"])
    assert sum(e["scanned"] for e in found) == len(sizes)
    assert sum(e["bytes_checked"] for e in found) == sum(sizes)
    # no new event type starts like the two the benchmark counts by prefix
    assert not any(e["type"].startswith(("ec.encode.finish",
                                         "ec.rebuild.finish"))
                   for e in events.JOURNAL.snapshot(since=seq))
    (line,) = parse(capsys.readouterr().err)
    assert line["verb"] == "volume.scrub" and "VolumeScrub" in line["methods"]


def test_no_timing_line_and_no_client_span_with_tracing_off(cluster, capsys):
    master, servers, mc, env = cluster
    capsys.readouterr()
    tracing.configure(sample=0)
    try:
        tracing.BUFFER.clear()
        run_command(env, "volume.list")
        assert len(tracing.BUFFER) == 0
    finally:
        tracing.configure(sample=1.0)
    assert "timing" not in capsys.readouterr().err
    run_command(env, "volume.list")
    (line,) = parse(capsys.readouterr().err)
    assert line["verb"] == "volume.list" and line["methods"]


def _copy_file(server, vid, ext=".dat"):
    from seaweedfs_tpu.pb import volume_server_pb2 as vpb
    from seaweedfs_tpu.utils.rpc import Stub, VOLUME_SERVICE
    stub = Stub(f"127.0.0.1:{server.grpc_port}", VOLUME_SERVICE)
    return stub.call_stream(
        "CopyFile", vpb.CopyFileRequest(volume_id=vid, collection="strm",
                                        ext=ext), vpb.CopyFileResponse)


@pytest.fixture(scope="module")
def streamed(cluster):
    """A 3 MiB volume: CopyFile streams its .dat in four messages."""
    master, servers, mc, env = cluster
    data = bytes(3 << 20)
    vid = int(operation.submit(mc, data, collection="strm").fid.split(",")[0])
    (server,) = [s for s in servers if s.store.find_volume(vid) is not None]
    return server, vid


@pytest.mark.parametrize("how", ["read", "cancel", "drop", "error"])
def test_a_stream_in_a_trace_is_a_grpc_call_that_ends_its_span(
        streamed, how):
    """Inside a trace `call_stream` still hands back something that
    iterates and cancels like grpc's own call; its span ends once,
    however the consumer leaves it, and the account gets the seconds
    spent waiting for the peer, not the consumer's."""
    import time
    import grpc
    server, vid = streamed
    rpcs = tracing.StageAccount("shell/test")
    token = tracing.RPC_ACCOUNT.set(rpcs)
    tracing.BUFFER.clear()
    t0 = time.perf_counter()
    try:
        with tracing.start_span("shell/test", component="shell") as root:
            stream = _copy_file(server, vid,
                                ".nope" if how == "error" else ".dat")
            if how == "read":
                got = 0
                for r in stream:
                    got += len(r.file_content)
                    time.sleep(0.05)  # the consumer's own time
                assert got > 3 << 20 and stream.code() == grpc.StatusCode.OK
            elif how == "cancel":
                next(stream)
                assert stream.cancel() in (True, False)
                stream.cancel()  # idempotent: booked once
            elif how == "drop":
                del stream  # never started
            else:
                with pytest.raises(grpc.RpcError):
                    list(stream)
    finally:
        tracing.RPC_ACCOUNT.reset(token)
    wall = time.perf_counter() - t0
    (span,) = [s for s in tracing.BUFFER.snapshot()
               if s["name"] == "rpc.client/CopyFile"]
    assert span["trace_id"] == root.context.trace_id
    assert span["status"] == {"read": "ok", "cancel": "cancelled",
                              "drop": "cancelled", "error": "error"}[how]
    assert rpcs.count("CopyFile") == 1
    assert 0 < rpcs.seconds("CopyFile") < wall
    if how == "read":  # four sleeps of the consumer's are not the peer's
        assert rpcs.seconds("CopyFile") < wall - 0.15


def test_a_stream_outside_a_trace_is_grpcs_own_call(streamed):
    server, vid = streamed
    stream = _copy_file(server, vid)
    assert type(stream).__module__.startswith("grpc")
    stream.cancel()
