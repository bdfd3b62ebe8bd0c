"""The EC admin verbs plan from ONE topology read (shell/ec_commands.py:
_ec_volumes), on the strength of an invariant of the volume server: every
RPC that changes EC registration returns only after the master has ingested
a heartbeat carrying the change (VolumeServer.flush_heartbeat).

First half: the verbs against a fake CommandEnv — how often they read the
topology and that they never sleep. Second half: the invariant itself on a
live in-process cluster at the default pulse — the master's VolumeList, read
the moment each RPC returns, shows the change."""

import functools
import hashlib
import io
import socket
from types import SimpleNamespace

import grpc
import numpy as np
import pytest
from conftest import wait_until

from seaweedfs_tpu.client import operation
from seaweedfs_tpu.client.master_client import MasterClient
from seaweedfs_tpu.ec import files as ec_files
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.master.master_server import MasterServer
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell import ec_commands, volume_commands  # noqa: F401 (lock)
from seaweedfs_tpu.shell.commands import CommandEnv, run_command
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.utils import rpc
from seaweedfs_tpu.utils.rpc import Stub, VOLUME_SERVICE


# -- the verbs against a fake cluster -----------------------------------------

class FakeCluster:
    """Two servers' worth of topology and every RPC the EC verbs make,
    counted. Server A holds each volume's even shards, B the odd ones."""

    def __init__(self, n_volumes: int, d: int, p: int,
                 lost: "dict[int, list[int]] | None" = None):
        self.d, self.p = d, p
        self.held = {vid: {"A:1": set(range(0, d + p, 2)),
                           "B:1": set(range(1, d + p, 2))}
                     for vid in range(1, n_volumes + 1)}
        for vid, sids in (lost or {}).items():
            for on in self.held[vid].values():
                on.difference_update(sids)
        self.reads = 0
        self.sleeps: list[float] = []
        self.calls: list[tuple[str, str, int]] = []
        self.fail_rebuilds = 0      # the next n rebuild RPCs raise
        self.out = io.StringIO()

    # CommandEnv's side
    def collect_volume_servers(self) -> list:
        self.reads += 1
        servers = []
        for node in ("A:1", "B:1"):
            infos = [SimpleNamespace(
                id=vid, collection="c",
                ec_index_bits=sum(1 << s for s in on[node]))
                for vid, on in sorted(self.held.items()) if on[node]]
            servers.append({
                "id": node, "grpc_port": 2, "dc": "dc", "rack": node[0],
                "disks": {"hdd": SimpleNamespace(
                    ec_shard_infos=infos, volume_infos=[],
                    max_volume_count=100, free_volume_count=50)}})
        return servers

    def grpc_addr(self, node_id: str, grpc_port: int) -> str:
        return node_id

    def println(self, *a) -> None:
        print(*a, file=self.out)

    mc = SimpleNamespace(volume_list=lambda: SimpleNamespace(
        volume_size_limit_mb=64))
    lock_token = 1

    # the volume servers' side
    def call(self, node: str, method: str, req, resp_cls, timeout=None):
        self.calls.append((node, method, req.volume_id))
        if method == "VolumeEcShardsInfo":
            return resp_cls(data_shards=self.d, parity_shards=self.p,
                            shard_size=1 << 20)
        if method in ("VolumeEcShardsRebuild",
                      "VolumeEcShardsCopyByRebuild"):
            if self.fail_rebuilds:
                self.fail_rebuilds -= 1
                raise Unavailable(f"{node} is gone")
            on = self.held[req.volume_id]
            missing = sorted(set(range(self.d + self.p))
                             - set().union(*on.values()))
            on[node].update(missing)
            return resp_cls(rebuilt_shard_ids=missing, bytes_read=10,
                            bytes_written=len(missing))
        return resp_cls()


class Unavailable(grpc.RpcError):
    pass


@pytest.fixture
def fake(monkeypatch):
    """FakeCluster factory; ec_commands' clock and stubs are the fake's."""
    made: list[FakeCluster] = []

    class FakeStub:
        def __init__(self, address: str, service: str = ""):
            self.address = address

        def call(self, method, req, resp_cls, timeout=None):
            return made[-1].call(self.address, method, req, resp_cls)

    def make(*a, **kw) -> FakeCluster:
        made.append(FakeCluster(*a, **kw))
        monkeypatch.setattr(
            ec_commands, "time",
            SimpleNamespace(sleep=made[-1].sleeps.append))
        return made[-1]

    monkeypatch.setattr(ec_commands, "Stub", FakeStub)
    monkeypatch.setattr(rpc, "Stub", FakeStub)  # maintenance's info sweep
    return make


@pytest.mark.parametrize("d,p", [(10, 4), (14, 2)])
@pytest.mark.parametrize("n_volumes", [1, 3, 50])
def test_rebuild_reads_topology_once_and_never_sleeps(fake, n_volumes, d, p):
    damaged = n_volumes // 2 + 1
    cl = fake(n_volumes, d, p, lost={damaged: [1, d]})
    summary = ec_commands.cmd_ec_rebuild(cl, [])
    assert summary == {"rebuilt": 2, "bytes_read": 10, "bytes_written": 2}
    assert cl.reads == 1 and cl.sleeps == []
    # the host is the holder with the most shards left; only the damaged
    # volume is rebuilt and mounted, every volume's geometry is asked once
    by_method = {m: [(n, v) for n, mm, v in cl.calls if mm == m]
                 for m in {c[1] for c in cl.calls}}
    assert by_method["VolumeEcShardsRebuild"] == [("A:1", damaged)]
    assert by_method["VolumeEcShardsMount"] == [("A:1", damaged)]
    assert len(by_method["VolumeEcShardsInfo"]) == n_volumes
    assert "rebuilt 2 shards" in cl.out.getvalue()


def test_rebuild_replans_once_after_a_failed_rebuild_rpc(fake):
    cl = fake(3, 10, 4, lost={2: [3]})
    cl.fail_rebuilds = 1
    summary = ec_commands.cmd_ec_rebuild(cl, [])
    assert summary["rebuilt"] == 1
    assert cl.reads == 2 and cl.sleeps == []
    rebuilds = [c for c in cl.calls if c[1] == "VolumeEcShardsRebuild"]
    assert rebuilds == [("A:1", "VolumeEcShardsRebuild", 2)] * 2
    assert "re-plan" in cl.out.getvalue()


def test_rebuild_raises_when_the_replanned_rebuild_fails_too(fake):
    cl = fake(3, 10, 4, lost={2: [3]})
    cl.fail_rebuilds = 2
    with pytest.raises(grpc.RpcError):
        ec_commands.cmd_ec_rebuild(cl, [])
    assert cl.reads == 2 and cl.sleeps == []
    assert "rebuilt 0 shards" not in cl.out.getvalue()
    assert not [c for c in cl.calls if c[1] == "VolumeEcShardsMount"]


def test_balance_plans_from_one_topology_read(fake):
    cl = fake(3, 10, 4)
    ec_commands.cmd_ec_balance(cl, ["-dryRun"])
    assert cl.reads == 1 and cl.sleeps == []
    assert "dry run: nothing executed" in cl.out.getvalue()


def test_decode_plans_from_one_topology_read(fake):
    cl = fake(3, 10, 4)
    ec_commands.cmd_ec_decode(cl, ["-volumeId", "2"])
    assert cl.reads == 1 and cl.sleeps == []
    methods = [m for _n, m, _v in cl.calls]
    assert methods.count("VolumeEcShardsCopy") == 7     # B's odd shards
    assert methods.count("VolumeEcShardsToVolume") == 1
    assert "decoded ec volume 2" in cl.out.getvalue()


# -- the invariant, on a live cluster at the default pulse ----------------------

def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


D, P = 4, 2


class Live:
    def __init__(self, master, servers, mc):
        self.master, self.servers, self.mc = master, servers, mc
        self.out = io.StringIO()
        self.env = CommandEnv(master.address, mc=mc, out=self.out)
        self.rng = np.random.default_rng(32)
        run_command(self.env, "lock")

    def sh(self, line: str) -> str:
        self.out.truncate(0)
        self.out.seek(0)
        run_command(self.env, line)
        return self.out.getvalue()

    def ec_volume(self) -> int:
        """A fresh EC volume of a few needles, sealed and spread."""
        vid = 0
        for _ in range(6):
            blob = self.rng.integers(0, 256, 3000, dtype=np.uint8).tobytes()
            fid = operation.submit(self.mc, blob, collection="ryw").fid
            vid = vid or int(fid.split(",")[0])
        text = self.sh(f"ec.encode -volumeId {vid} -ecShards {D},{P}")
        assert "ec encoded 1 volumes" in text, text
        return vid

    def view(self, vid: int) -> "dict[str, list[int]]":
        """Server -> the vid's shards there, by the master's VolumeList
        as the shell reads it: one read, no wait."""
        _c, holders = ec_commands._ec_volumes(
            self.env.collect_volume_servers()).get(vid, ("", {}))
        out: dict[str, list[int]] = {}
        for sid, hs in sorted(holders.items()):
            for h in hs:
                out.setdefault(h["id"], []).append(sid)
        return out

    def server(self, node_id: str) -> VolumeServer:
        return next(vs for vs in self.servers
                    if f"127.0.0.1:{vs.port}" == node_id)

    def stub(self, node_id: str) -> Stub:
        return Stub(f"127.0.0.1:{self.server(node_id).grpc_port}",
                    VOLUME_SERVICE)

    def shard_sha(self, node_id: str, vid: int, sid: int) -> str:
        ev = self.server(node_id).store.find_ec_volume(vid)
        with open(ev.base + ec_files.shard_ext(sid), "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()


@pytest.fixture(scope="module")
def small_slabs():
    """The rebuild's device batch is [32, d, 1 MiB] whatever the volume's
    size, four such buffers a rebuild, touched for the first time: half a
    second a rebuild here, and the loop below makes twenty. The slab is not
    this file's subject: 4 x 16 KiB."""
    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.server import volume_server
    from seaweedfs_tpu.storage import store
    small = functools.partial(encoder.rebuild_shards, chunk=1 << 14, batch=4)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(store, "rebuild_shards", small)
        mp.setattr(volume_server, "rebuild_shards", small)
        yield


@pytest.fixture(scope="module")
def live(tmp_path_factory, small_slabs):
    master = MasterServer(port=free_port(), volume_size_limit_mb=64)
    master.start()
    geo = EcGeometry(d=D, p=P, large_block=1 << 16, small_block=1 << 12)
    servers = []
    for i in range(3):
        port = free_port()
        store = Store("127.0.0.1", port, "",
                      [DiskLocation(str(tmp_path_factory.mktemp(f"ryw{i}")),
                                    max_volume_count=40)],
                      ec_geometry=geo, coder_name="numpy")
        vs = VolumeServer(store, master.address, port=port,
                          grpc_port=free_port())   # pulse_seconds: default
        vs.start()
        servers.append(vs)
    assert all(vs.pulse_seconds == 2.0 for vs in servers)
    wait_until(lambda: len(master.topo.nodes) >= 3, msg="3 servers up")
    mc = MasterClient(master.address).start()
    yield Live(master, servers, mc)
    mc.stop()
    for vs in servers:
        try:
            vs.stop()
        except Exception:  # noqa: BLE001 — the master still has to stop
            pass
    master.stop()


def _unmount(live, node, vid, sids):
    live.stub(node).call(
        "VolumeEcShardsUnmount",
        vpb.VolumeEcShardsUnmountRequest(volume_id=vid, shard_ids=sids),
        vpb.VolumeEcShardsUnmountResponse)


def _mount(live, node, vid, sids):
    live.stub(node).call(
        "VolumeEcShardsMount",
        vpb.VolumeEcShardsMountRequest(volume_id=vid, collection="ryw",
                                       shard_ids=sids),
        vpb.VolumeEcShardsMountResponse)


def _delete(live, node, vid, sids):
    live.stub(node).call(
        "VolumeEcShardsDelete",
        vpb.VolumeEcShardsDeleteRequest(volume_id=vid, collection="ryw",
                                        shard_ids=sids),
        vpb.VolumeEcShardsDeleteResponse)


def _copy(live, node, vid, sids, source):
    live.stub(node).call(
        "VolumeEcShardsCopy",
        vpb.VolumeEcShardsCopyRequest(
            volume_id=vid, collection="ryw", shard_ids=sids,
            source_data_node=live.stub(source).address),
        vpb.VolumeEcShardsCopyResponse)


def ryw_unmount_then_mount(live, vid, node, sid, other):
    before = live.view(vid)
    _unmount(live, node, vid, [sid])
    assert sid not in live.view(vid).get(node, [])
    _mount(live, node, vid, [sid])      # mount rescans the disk
    assert live.view(vid) == before


def ryw_delete_of_mounted_shards(live, vid, node, sid, other):
    _delete(live, node, vid, [sid])
    assert sid not in live.view(vid).get(node, [])
    assert all(sid not in sids for sids in live.view(vid).values())


def ryw_rebuild(live, vid, node, sid, other):
    _unmount(live, node, vid, [sid])
    _delete(live, node, vid, [sid])
    assert all(sid not in sids for sids in live.view(vid).values())
    resp = live.stub(other).call(
        "VolumeEcShardsRebuild",
        vpb.VolumeEcShardsRebuildRequest(volume_id=vid, collection="ryw"),
        vpb.VolumeEcShardsRebuildResponse)
    assert list(resp.rebuilt_shard_ids) == [sid]
    # the rebuild re-opens the host's EC volume over what is on its disk
    assert sid in live.view(vid)[other]


def ryw_copy_registers_nothing_until_the_mount(live, vid, node, sid, other):
    before = live.view(vid)
    _copy(live, other, vid, [sid], source=node)
    assert live.view(vid) == before
    _mount(live, other, vid, [sid])
    assert sid in live.view(vid)[other] and sid in live.view(vid)[node]


def ryw_copy_by_rebuild_registers_nothing_until_the_mount(live, vid, node,
                                                          sid, other):
    _unmount(live, node, vid, [sid])
    _delete(live, node, vid, [sid])
    before = live.view(vid)
    resp = live.stub(other).call(
        "VolumeEcShardsCopyByRebuild",
        vpb.VolumeEcShardsCopyByRebuildRequest(
            volume_id=vid, collection="ryw", shard_ids=[sid]),
        vpb.VolumeEcShardsCopyByRebuildResponse)
    assert list(resp.rebuilt_shard_ids) == [sid]
    assert live.view(vid) == before
    _mount(live, other, vid, [sid])
    assert sid in live.view(vid)[other]


def ryw_move(live, vid, node, sid, other):
    live.stub(other).call(
        "VolumeEcShardsMove",
        vpb.VolumeEcShardsMoveRequest(
            volume_id=vid, collection="ryw", shard_ids=[sid],
            source_data_node=live.stub(node).address),
        vpb.VolumeEcShardsMoveResponse)
    view = live.view(vid)
    assert sid in view[other] and sid not in view.get(node, [])


def ryw_decode_to_volume(live, vid, node, sid, other):
    # the verb's gather + VolumeEcShardsToVolume + unmount/delete elsewhere
    assert "decoded ec volume" in live.sh(f"ec.decode -volumeId {vid}")
    assert live.view(vid) == {}
    assert any(v.id == vid for srv in live.env.collect_volume_servers()
               for disk in srv["disks"].values() for v in disk.volume_infos)


@pytest.mark.parametrize("case", [
    ryw_unmount_then_mount, ryw_delete_of_mounted_shards, ryw_rebuild,
    ryw_copy_registers_nothing_until_the_mount,
    ryw_copy_by_rebuild_registers_nothing_until_the_mount, ryw_move,
    ryw_decode_to_volume], ids=lambda f: f.__name__[4:])
def test_master_view_reads_your_writes(live, case):
    vid = live.ec_volume()
    view = live.view(vid)
    assert sorted(s for sids in view.values() for s in sids) == \
        list(range(D + P))
    node, other = sorted(view)[:2]
    case(live, vid, node, view[node][0], other)


def test_twenty_rounds_of_loss_and_rebuild_without_a_settle(live):
    """The race the settle poll was written for, hunted without it: lose
    shards by unmount + delete, run the verb at once, every round."""
    vid = live.ec_volume()
    live.sh("ec.rebuild")   # plain, as a cron runs it: heal what the cases left
    for k in range(20):
        view = live.view(vid)
        node = sorted(view)[k % len(view)]
        sids = view[node][:1 + k % P]
        before = {s: live.shard_sha(node, vid, s) for s in sids}
        _unmount(live, node, vid, sids)
        _delete(live, node, vid, sids)
        text = live.sh("ec.rebuild")
        assert f"rebuilt {len(sids)} shards" in text, (k, text)
        after = live.view(vid)
        host = next(n for n, held in after.items() if sids[0] in held)
        assert {s: live.shard_sha(host, vid, s) for s in sids} == before
        assert sorted(s for held in after.values() for s in held) == \
            list(range(D + P)), (k, after)
