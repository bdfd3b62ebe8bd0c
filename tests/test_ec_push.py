"""A seal streams each other holder's shards to it as they are written
(ec/push.py): on a live two-server cluster, B's shards are byte-identical
to an independent encode and never touch A's disk, B fsyncs them and the
`.ecx` before its `.vif`, an overlay codec's parities are still copied,
a push that fails leaves nothing behind on any holder for any volume of
the batch, a seal that names no geometry pushes at the server's own, and
a generate that names no holder writes every shard where it runs, as
before."""

import argparse
import contextlib
import io
import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest
from conftest import wait_until

from benchmark import reference
from benchmark import reference_hitchhiker as hh
from seaweedfs_tpu.client import operation
from seaweedfs_tpu.client.master_client import MasterClient
from seaweedfs_tpu.ec import files as ec_files
from seaweedfs_tpu.ec import push
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.master.master_server import MasterServer
from seaweedfs_tpu.ops import events
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import ec_commands, volume_commands  # noqa: F401
from seaweedfs_tpu.shell.commands import CommandEnv, run_command
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.utils import failpoints
from seaweedfs_tpu.utils.rpc import VOLUME_SERVICE, Stub

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LARGE, SMALL = 1 << 22, 1 << 16
SPREAD = re.compile(r"shards \[([0-9, ]*)\] -> (\S+)")


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
    geo = EcGeometry(d=14, p=2, large_block=LARGE, small_block=SMALL)
    servers = []
    for i in range(2):
        d = tmp_path_factory.mktemp(f"push{i}")
        port = free_port()
        store = Store("127.0.0.1", port, "",
                      [DiskLocation(str(d), max_volume_count=20)],
                      ec_geometry=geo, coder_name="numpy")
        vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                          grpc_port=free_port(), pulse_seconds=0.4,
                          rack=f"r{i}")
        vs.start()
        servers.append(vs)
    wait_until(lambda: len(master.topo.nodes) >= 2, msg="servers registered")
    mc = MasterClient(f"127.0.0.1:{mport}").start()
    env = CommandEnv(f"127.0.0.1:{mport}", mc=mc, out=io.StringIO())
    yield {"master": master, "servers": servers, "mc": mc, "env": env,
           "addr": f"127.0.0.1:{mport}"}
    mc.stop()
    for vs in servers:
        vs.stop()
    master.stop()


def fill(cl, collection: str, seed: int, blobs: int = 40) -> dict:
    """A volume of ~2.6 MB in `collection`: its id, payloads, the server
    holding it (A), the other one (B), and its .dat bytes."""
    rng = np.random.default_rng(seed)
    payloads = {}
    for _ in range(blobs):
        data = rng.integers(0, 256, int(rng.integers(1 << 15, 1 << 17)),
                            dtype=np.uint8).tobytes()
        payloads[operation.submit(cl["mc"], data,
                                  collection=collection).fid] = data
    vid = max({int(f.split(",")[0]) for f in payloads},
              key=lambda v: sum(f.startswith(f"{v},") for f in payloads))
    payloads = {f: b for f, b in payloads.items() if f.startswith(f"{vid},")}
    a = next(vs for vs in cl["servers"] if vs.store.find_volume(vid))
    b = next(vs for vs in cl["servers"] if vs is not a)
    v = a.store.find_volume(vid)
    v.sync()
    with open(v.file_name() + ".dat", "rb") as f:
        dat = np.frombuffer(f.read(), dtype=np.uint8)
    return {"vid": vid, "payloads": payloads, "a": a, "b": b, "dat": dat,
            "collection": collection}


def seal(cl, vol: dict, script: str) -> str:
    """Run `lock; <script>; unlock` in-process; the verb's output."""
    env = cl["env"]
    env.out = io.StringIO()
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        run_command(env, "lock")
        try:
            run_command(env, script)
        finally:
            run_command(env, "unlock")
    return env.out.getvalue()


def shard_dir(vs) -> str:
    return str(vs.store.locations[0].directory)


def on_disk(vs, vol: dict, n: int) -> "list[int]":
    base = os.path.join(shard_dir(vs), f"{vol['collection']}_{vol['vid']}")
    return [s for s in range(n) if os.path.exists(base + ec_files.shard_ext(s))]


def shard_bytes(vs, vol: dict, sid: int) -> bytes:
    base = os.path.join(shard_dir(vs), f"{vol['collection']}_{vol['vid']}")
    with open(base + ec_files.shard_ext(sid), "rb") as f:
        return f.read()


def expected_shards(dat: np.ndarray, d: int, p: int) -> np.ndarray:
    """[d + p, shard_size] of plain RS, from the reference alone."""
    size = reference.shard_file_size(dat.size, d, LARGE, SMALL)
    out = np.zeros((d + p, size), dtype=np.uint8)
    for row in range(reference.small_rows(dat.size, d, LARGE, SMALL)):
        blocks, off = reference.small_row(dat, row, d, LARGE, SMALL)
        out[:, off:off + SMALL] = np.concatenate(
            [blocks, reference.encode(blocks, p)])
    return out


def spread(out: str) -> "dict[str, list[int]]":
    return {holder: [int(x) for x in ids.split(", ") if x]
            for ids, holder in SPREAD.findall(out)}


def finish_event(since: int) -> dict:
    (ev,) = events.JOURNAL.snapshot(since=since, etype="ec.encode.finish")
    return ev["attrs"]


def last_seq() -> int:
    found = events.JOURNAL.snapshot(limit=1)
    return found[-1]["seq"] if found else 0


@pytest.mark.parametrize("d, p", [(14, 2), (10, 4)])
def test_holder_shards_are_streamed_and_byte_identical(cluster, d, p):
    vol = fill(cluster, f"rs{d}", seed=d)
    a, b, n = vol["a"], vol["b"], d + p
    seq = last_seq()
    out = seal(cluster, vol, f"ec.encode -volumeId {vol['vid']} "
                             f"-ecShards {d},{p}")
    assert "ec encoded 1 volumes" in out
    placed = spread(out)
    assert len(placed) == 2
    # what each holder has on disk is what the verb placed there: the
    # spread the copy after the generate made, half the stripe each
    b_ids = next(ids for h, ids in placed.items()
                 if h.endswith(f":{b.port}"))
    a_ids = next(ids for h, ids in placed.items()
                 if h.endswith(f":{a.port}"))
    assert on_disk(b, vol, n) == b_ids and on_disk(a, vol, n) == a_ids
    assert sorted(a_ids + b_ids) == list(range(n))
    assert len(b_ids) == n // 2
    # every shard, on either holder, is the reference's
    want = expected_shards(vol["dat"], d, p)
    for sid in range(n):
        holder = b if sid in b_ids else a
        assert shard_bytes(holder, vol, sid) == want[sid].tobytes(), sid
    # B sealed its part itself: sidecars and a .vif of the geometry
    base_b = os.path.join(shard_dir(b), f"{vol['collection']}_{vol['vid']}")
    assert os.path.exists(base_b + ".ecx")
    vif = ec_files.read_vif(base_b + ".vif")
    assert (vif["d"], vif["p"], vif["codec"]) == (d, p, "rs")
    # nothing was pulled: the push carried all of B's bytes
    ev = finish_event(seq)
    size = want.shape[1]
    assert ev["remote_bytes"] == size * len(b_ids) == ev["push_bytes"]
    assert ev["push_s"] >= 0 and ev["push_wait_s"] >= 0
    # the stripe serves every needle, source volume gone
    wait_until(lambda: sorted(cluster["master"].topo.lookup_ec(vol["vid"]))
               == list(range(n)), msg="shards registered")
    assert a.store.find_volume(vol["vid"]) is None
    for fid, data in vol["payloads"].items():
        assert operation.read(cluster["mc"], fid) == data


def test_holder_fsyncs_shards_and_index_before_its_vif(cluster, monkeypatch):
    vol = fill(cluster, "order", seed=7)
    b = vol["b"]
    real = os.fsync
    order = []

    def recording(fd):
        try:
            order.append(os.readlink(f"/proc/self/fd/{fd}"))
        except OSError:
            pass
        return real(fd)
    monkeypatch.setattr(os, "fsync", recording)
    out = seal(cluster, vol, f"ec.encode -volumeId {vol['vid']} "
                             "-ecShards 14,2")
    monkeypatch.setattr(os, "fsync", real)
    assert "ec encoded 1 volumes" in out
    base_b = os.path.join(shard_dir(b), f"order_{vol['vid']}")
    mine = [p for p in order if p.startswith(base_b)]
    vif_at = mine.index(base_b + ".vif.tmp")
    held = on_disk(b, vol, 16)
    assert held
    for sid in held:
        assert mine.index(base_b + ec_files.shard_ext(sid)) < vif_at, sid
    assert mine.index(base_b + ".ecx") < vif_at


def test_overlay_codec_streams_data_and_copies_parities(cluster):
    d, p = 10, 4
    vol = fill(cluster, "hh", seed=11)
    a, b, n = vol["a"], vol["b"], d + p
    seq = last_seq()
    out = seal(cluster, vol, f"ec.encode -volumeId {vol['vid']} "
                             f"-ecShards {d},{p} -codec piggyback")
    assert "ec encoded 1 volumes" in out and "codec piggyback" in out
    b_ids = on_disk(b, vol, n)
    assert b_ids and set(b_ids).isdisjoint(on_disk(a, vol, n))
    assert sorted(b_ids + on_disk(a, vol, n)) == list(range(n))
    ev = finish_event(seq)
    size = ev["remote_bytes"] // len(b_ids)
    # B's data shards were streamed, its parities copied after the seal
    b_data = [s for s in b_ids if s < d]
    assert b_data and any(s >= d for s in b_ids)
    assert ev["push_bytes"] == size * len(b_data) < ev["remote_bytes"]
    # the sealed stripe is the Hitchhiker reference's
    plain = expected_shards(vol["dat"], d, p)
    data = np.stack([np.frombuffer(shard_bytes(b if s in b_ids else a,
                                               vol, s), dtype=np.uint8)
                     for s in range(d)])
    assert np.array_equal(data, plain[:d])
    parity = hh.encode(data, p)
    for j in range(p):
        holder = b if d + j in b_ids else a
        assert shard_bytes(holder, vol, d + j) == parity[j].tobytes(), j
    for fid, blob in list(vol["payloads"].items())[:6]:
        assert operation.read(cluster["mc"], fid) == blob


def test_failed_push_leaves_no_seal_and_a_writable_volume(cluster):
    vol = fill(cluster, "fail", seed=5)
    a, b, vid = vol["a"], vol["b"], vol["vid"]
    failpoints.configure("ec.shard.receive", "times:1:error:holder disk")
    try:
        r = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master",
             cluster["addr"], "-c",
             f"lock; ec.encode -volumeId {vid} -ecShards 14,2; unlock"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
    finally:
        failpoints.clear("ec.shard.receive")
    assert r.returncode == 2, r.stdout + r.stderr
    assert failpoints.fired("ec.shard.receive") >= 1
    for vs in (a, b):
        stem = os.path.join(shard_dir(vs), f"fail_{vid}")
        assert not os.path.exists(stem + ".vif")
        assert on_disk(vs, vol, 16) == []
    assert not os.path.exists(os.path.join(shard_dir(b), f"fail_{vid}.ecx"))
    v = a.store.find_volume(vid)
    assert v is not None and not v.read_only
    for fid, data in vol["payloads"].items():
        assert operation.read(cluster["mc"], fid) == data
    # and it still takes writes
    fid = operation.submit(cluster["mc"], b"after", collection="fail").fid
    assert operation.read(cluster["mc"], fid) == b"after"


def two_volumes_on_one_server(cl, collection: str) -> dict:
    """A `fill`ed volume of `collection` and a second one allocated on the
    same server (A), both with data: their ids, the payloads, A and B."""
    vol = fill(cl, collection, seed=17, blobs=12)
    a, b, first = vol["a"], vol["b"], vol["vid"]
    second = first + 1000
    Stub(f"127.0.0.1:{a.grpc_port}", VOLUME_SERVICE).call(
        "AllocateVolume", vpb.AllocateVolumeRequest(
            volume_id=second, collection=collection, replication="000"),
        vpb.AllocateVolumeResponse)
    wait_until(lambda: cl["mc"].lookup(second), msg="second volume known")
    rng = np.random.default_rng(19)
    payloads = dict(vol["payloads"])
    for _ in range(300):
        data = rng.bytes(1 << 15)
        fid = operation.submit(cl["mc"], data, collection=collection).fid
        payloads[fid] = data
        if sum(f.startswith(f"{second},") for f in payloads) >= 6:
            break
    payloads = {f: d for f, d in payloads.items()
                if int(f.split(",")[0]) in (first, second)}
    assert sum(f.startswith(f"{second},") for f in payloads) >= 6
    for vid in (first, second):
        a.store.find_volume(vid).sync()
    return {"vids": [first, second], "a": a, "b": b, "payloads": payloads}


def test_failed_batch_leaves_no_holder_file_of_any_volume(cluster,
                                                          monkeypatch):
    """The batch fails on its second volume's stream after B sealed the
    first: what B holds of both is removed before the volumes are
    writable again, and B would mount nothing of them on a restart."""
    col = "fail2"
    got = two_volumes_on_one_server(cluster, col)
    a, b, (first, second) = got["a"], got["b"], got["vids"]
    first_vif = os.path.join(shard_dir(b), f"{col}_{first}.vif")
    real = push.receive
    sealed_first = []

    def receive(requests, *args):
        it = iter(requests)
        head = next(it)

        def stream():
            yield head
            for req in it:
                if req.vif and head.volume_id == second:
                    # the first volume is sealed here; this one fails at
                    # its own seal
                    wait_until(lambda: os.path.exists(first_vif),
                               timeout=60, msg="first volume sealed on B")
                    sealed_first.append(first)
                    failpoints.configure("ec.shard.receive",
                                         "times:1:error:holder disk")
                yield req
        return real(stream(), *args)
    monkeypatch.setattr(push, "receive", receive)
    env = cluster["env"]
    env.out = io.StringIO()
    servers = env.collect_volume_servers()
    srv = next(s for s in servers if s["id"].endswith(f":{a.port}"))
    opt = argparse.Namespace(dataShards=14, parityShards=2, codec="")
    try:
        with pytest.raises(Exception):
            ec_commands._encode_on_server(
                env, srv, [(first, col), (second, col)], opt, servers)
    finally:
        failpoints.clear("ec.shard.receive")
    assert failpoints.fired("ec.shard.receive") >= 1 and sealed_first
    for vid in (first, second):
        stem = os.path.join(shard_dir(b), f"{col}_{vid}")
        assert on_disk(b, {"collection": col, "vid": vid}, 16) == [], vid
        for ext in (".ecx", ".ecj", ".vif"):
            assert not os.path.exists(stem + ext), (vid, ext)
        assert b.store.find_ec_volume(vid) is None
        v = a.store.find_volume(vid)
        assert v is not None and not v.read_only, vid
    assert not os.path.exists(
        os.path.join(shard_dir(a), f"{col}_{second}.vif"))
    for fid, data in got["payloads"].items():
        assert operation.read(cluster["mc"], fid) == data


def test_a_seal_naming_no_geometry_pushes_at_the_servers_own(cluster):
    """The maintenance cron's `ec.encode` names no -ecShards: the shell
    places the shards at the geometry each server reports in its
    heartbeat, and the push carries all of B's bytes."""
    servers = cluster["env"].collect_volume_servers()
    assert [s["ec_geometry"] for s in servers] == [(14, 2)] * 2
    vol = fill(cluster, "dflt", seed=13)
    b = vol["b"]
    seq = last_seq()
    out = seal(cluster, vol, f"ec.encode -volumeId {vol['vid']}")
    assert "ec encoded 1 volumes" in out
    b_ids = next(ids for h, ids in spread(out).items()
                 if h.endswith(f":{b.port}"))
    assert on_disk(b, vol, 16) == b_ids and len(b_ids) == 8
    ev = finish_event(seq)
    assert ev["push_bytes"] == ev["remote_bytes"] > 0
    base_b = os.path.join(shard_dir(b), f"dflt_{vol['vid']}")
    assert ec_files.read_vif(base_b + ".vif")["d"] == 14


def test_generate_without_targets_writes_every_shard_here(cluster):
    vol = fill(cluster, "local", seed=3, blobs=12)
    a, vid = vol["a"], vol["vid"]
    stub = Stub(f"127.0.0.1:{a.grpc_port}", VOLUME_SERVICE)
    stub.call("VolumeMarkReadonly",
              vpb.VolumeMarkReadonlyRequest(volume_id=vid),
              vpb.VolumeMarkReadonlyResponse)
    resp = stub.call("VolumeEcShardsGenerateBatch",
                     vpb.VolumeEcShardsGenerateBatchRequest(
                         volume_ids=[vid], collection="local",
                         data_shards=14, parity_shards=2),
                     vpb.VolumeEcShardsGenerateBatchResponse, timeout=120)
    assert list(resp.encoded_volume_ids) == [vid] and not resp.pushed
    assert on_disk(a, vol, 16) == list(range(16))
    assert on_disk(vol["b"], vol, 16) == []
    want = expected_shards(vol["dat"], 14, 2)
    for sid in range(16):
        assert shard_bytes(a, vol, sid) == want[sid].tobytes(), sid


@pytest.mark.parametrize("shard_id, offset", [(0, 0), (13, 3 << 32)])
def test_data_message_is_the_request_the_holder_parses(shard_id, offset,
                                                       monkeypatch):
    """A run of strided rows leaves as messages of whole rows, each at its
    own offset, that together carry the run's bytes."""
    monkeypatch.setattr(push, "MSG_BYTES", 1400)
    buf = np.arange(5 * 3 * 700, dtype=np.uint32).astype(np.uint8)
    rows = buf.reshape(5, 3, 700)[:, 1]  # [k, chunk] of a [k, n, chunk]
    msgs = [vpb.VolumeEcShardsReceiveRequest.FromString(m)
            for m in push._run_messages(shard_id, offset, rows)]
    assert [(m.shard_id, m.offset) for m in msgs] == [
        (shard_id, offset + o) for o in (0, 1400, 2800)]
    assert b"".join(m.data for m in msgs) == rows.tobytes()
    assert not any(m.ext or m.vif for m in msgs)
