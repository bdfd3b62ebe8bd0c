"""Cluster integration: master + 3 volume servers in-process on localhost.

The docker-compose analogue of the reference's local-cluster-compose.yml
(SURVEY.md §4.5) — multi-node behavior (heartbeats, growth, replication,
EC spread, degraded reads) without containers."""

import os
import socket
import time

import numpy as np
import pytest

from seaweedfs_tpu.client import operation
from seaweedfs_tpu.client.master_client import MasterClient
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.master.master_server import MasterServer
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.utils.rpc import Stub, VOLUME_SERVICE


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
                          pulse_seconds=0.5)
    master.start()
    servers = []
    geo = EcGeometry(d=4, p=2, large_block=1 << 20, small_block=1 << 14)
    for i in range(3):
        d = tmp_path_factory.mktemp(f"vs{i}")
        store = Store("127.0.0.1", 0, "", [DiskLocation(str(d), max_volume_count=10)],
                      ec_geometry=geo, coder_name="numpy")
        port = free_port()
        store.port = port
        store.public_url = f"127.0.0.1:{port}"
        vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                          grpc_port=free_port(), pulse_seconds=0.5,
                          rack=f"rack{i % 2}")
        vs.start()
        servers.append(vs)
    from conftest import wait_cluster_up
    wait_cluster_up(master, servers)
    mc = MasterClient(f"127.0.0.1:{mport}").start()
    yield master, servers, mc
    mc.stop()
    for vs in servers:
        try:
            vs.stop()
        except Exception:
            pass
    master.stop()


def test_write_read_delete_single(cluster):
    master, servers, mc = cluster
    payload = b"hello weedtpu" * 100
    res = operation.submit(mc, payload, name="hello.txt", mime="text/plain")
    assert res.fid and res.size > 0
    got = operation.read(mc, res.fid)
    assert got == payload
    assert operation.delete(mc, res.fid)
    with pytest.raises((KeyError, RuntimeError)):
        operation.read(mc, res.fid)


def test_replicated_write(cluster):
    master, servers, mc = cluster
    payload = os.urandom(5000)
    res = operation.submit(mc, payload, replication="001", collection="rep")
    # both replicas must hold the needle
    vid = int(res.fid.split(",")[0])
    from conftest import wait_until
    wait_until(lambda: len(master.topo.lookup(vid)) == 2,
               msg="both replicas heartbeated")
    locs = master.topo.lookup(vid)
    assert len(locs) == 2, f"expected 2 replicas, got {[n.id for n in locs]}"
    from seaweedfs_tpu.storage.types import parse_file_id
    _, key, _ = parse_file_id(res.fid)
    held = 0
    for vs in servers:
        v = vs.store.find_volume(vid)
        if v is not None:
            assert v.read_needle(key).data == payload
            held += 1
    assert held == 2


def test_replicated_write_fails_when_peer_injected_dead(cluster):
    """replicate.peer failpoint: the write-path fan-out surfaces a dead
    replica as a failed write (no silent single-copy acks), and writes
    succeed again once the fault clears — reference store_replicate.go:25
    fails the whole write when any replica fails."""
    from seaweedfs_tpu.utils import failpoints
    master, servers, mc = cluster
    payload = os.urandom(500)
    with failpoints.inject("replicate.peer", "error:peer-down"):
        with pytest.raises(Exception):
            operation.submit(mc, payload, replication="001",
                             collection="repfault")
    assert failpoints.fired("replicate.peer") >= 1
    res = operation.submit(mc, payload, replication="001",
                           collection="repfault")
    assert operation.read(mc, res.fid) == payload


def test_many_files_roundtrip(cluster):
    master, servers, mc = cluster
    rng = np.random.default_rng(0)
    blobs = {}
    for i in range(40):
        data = rng.integers(0, 256, int(rng.integers(10, 5000)),
                            dtype=np.uint8).tobytes()
        res = operation.submit(mc, data)
        blobs[res.fid] = data
    for fid, data in blobs.items():
        assert operation.read(mc, fid) == data


def test_ec_encode_spread_and_degraded_read(cluster):
    """The ec.encode flow: write blobs, encode the volume on its server,
    spread shards to other servers via VolumeEcShardsCopy, delete the
    original, read through EC incl. a degraded read after killing a shard."""
    master, servers, mc = cluster
    rng = np.random.default_rng(1)
    blobs = {}
    for i in range(30):
        data = rng.integers(0, 256, int(rng.integers(100, 20000)),
                            dtype=np.uint8).tobytes()
        res = operation.submit(mc, data, collection="ecol")
        blobs[res.fid] = data
    vid = int(next(iter(blobs)).split(",")[0])
    assert all(int(f.split(",")[0]) == vid for f in blobs)

    src_vs = next(vs for vs in servers if vs.store.find_volume(vid) is not None)
    src_stub = Stub(f"127.0.0.1:{src_vs.grpc_port}", VOLUME_SERVICE)
    src_stub.call("VolumeMarkReadonly", vpb.VolumeMarkReadonlyRequest(volume_id=vid),
                  vpb.VolumeMarkReadonlyResponse)
    src_stub.call("VolumeEcShardsGenerate",
                  vpb.VolumeEcShardsGenerateRequest(volume_id=vid, collection="ecol"),
                  vpb.VolumeEcShardsGenerateResponse, timeout=120)

    # spread: shards 0-2 stay on src; 3 -> server B; 4,5 -> server C
    others = [vs for vs in servers if vs is not src_vs]
    spread = {src_vs: [0, 1, 2], others[0]: [3], others[1]: [4, 5]}
    for vs, sids in spread.items():
        if vs is not src_vs:
            Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE).call(
                "VolumeEcShardsCopy",
                vpb.VolumeEcShardsCopyRequest(
                    volume_id=vid, collection="ecol", shard_ids=sids,
                    copy_ecx_file=True, copy_vif_file=True, copy_ecj_file=True,
                    source_data_node=f"127.0.0.1:{src_vs.grpc_port}"),
                vpb.VolumeEcShardsCopyResponse, timeout=60)
        Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE).call(
            "VolumeEcShardsMount",
            vpb.VolumeEcShardsMountRequest(volume_id=vid, collection="ecol",
                                           shard_ids=sids),
            vpb.VolumeEcShardsMountResponse)
    # remove non-local shards from src (it generated all 6)
    base = src_vs.store.find_ec_volume(vid).base
    Stub(f"127.0.0.1:{src_vs.grpc_port}", VOLUME_SERVICE).call(
        "VolumeEcShardsUnmount",
        vpb.VolumeEcShardsUnmountRequest(volume_id=vid, shard_ids=[3, 4, 5]),
        vpb.VolumeEcShardsUnmountResponse)
    from seaweedfs_tpu.ec import files as ec_files
    for sid in (3, 4, 5):
        os.remove(base + ec_files.shard_ext(sid))
    Stub(f"127.0.0.1:{src_vs.grpc_port}", VOLUME_SERVICE).call(
        "VolumeEcShardsMount",
        vpb.VolumeEcShardsMountRequest(volume_id=vid, collection="ecol",
                                       shard_ids=[0, 1, 2]),
        vpb.VolumeEcShardsMountResponse)
    # delete the original volume; reads must go through EC now
    src_stub.call("VolumeDelete", vpb.VolumeDeleteRequest(volume_id=vid),
                  vpb.VolumeDeleteResponse)
    from conftest import wait_until
    wait_until(lambda: vid in master.topo.ec_locations,
               msg="ec registry updated")
    for fid, data in list(blobs.items())[:10]:
        assert operation.read(mc, fid) == data, f"ec read {fid}"

    # degraded via FAILPOINT: one transient shard-fetch failure forces the
    # reconstruct-from-d-others path without destroying anything
    # (tests/test_failpoints.py has the facility; SURVEY §5 fault injection)
    from seaweedfs_tpu.utils import failpoints
    with failpoints.inject("ec.shard.read", "times:1:error:injected"):
        for fid, data in list(blobs.items())[16:20]:
            assert operation.read(mc, fid) == data, \
                f"ec read with injected shard fault {fid}"
    assert failpoints.fired("ec.shard.read") >= 1

    # degraded: kill shard 3's holder entirely
    others[0].stop()
    from conftest import wait_until as _wu
    _wu(lambda: len(master.topo.nodes) == 2, msg="dead holder dropped")
    for fid, data in list(blobs.items())[10:16]:
        assert operation.read(mc, fid) == data, f"degraded ec read {fid}"


def test_vacuum_via_rpc(cluster):
    master, servers, mc = cluster
    fids = []
    for i in range(20):
        res = operation.submit(mc, os.urandom(2000), collection="vac")
        fids.append(res.fid)
    vid = int(fids[0].split(",")[0])
    for fid in fids[:10]:
        operation.delete(mc, fid)
    vs = next(v for v in servers if v.store.find_volume(vid) is not None)
    stub = Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE)
    chk = stub.call("VacuumVolumeCheck", vpb.VacuumVolumeCheckRequest(volume_id=vid),
                    vpb.VacuumVolumeCheckResponse)
    assert chk.garbage_ratio > 0.3
    stub.call("VacuumVolumeCompact", vpb.VacuumVolumeCompactRequest(volume_id=vid),
              vpb.VacuumVolumeCompactResponse, timeout=60)
    stub.call("VacuumVolumeCommit", vpb.VacuumVolumeCommitRequest(volume_id=vid),
              vpb.VacuumVolumeCommitResponse)
    for fid in fids[10:]:
        assert operation.read(mc, fid)
    with pytest.raises((KeyError, RuntimeError)):
        operation.read(mc, fids[0])


def test_ec_shard_location_cache_tiers(tmp_path):
    """Shard-location lookups ride a tiered cache (store_ec.go:256-267):
    steady-state reads never touch the master; a failed read forces a
    refresh only after the 11s tier."""
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.storage.disk_location import DiskLocation
    from seaweedfs_tpu.storage.store import Store

    store = Store("127.0.0.1", 0, "",
                  [DiskLocation(str(tmp_path), max_volume_count=4)],
                  coder_name="numpy")
    vs = VolumeServer(store, "127.0.0.1:1")  # never started; no master
    calls = []

    def fake_master(vid):
        calls.append(vid)
        return {0: ["a:1"], 1: ["b:1"]}

    vs._lookup_ec_shards_master = fake_master
    assert vs._lookup_ec_shards(5) == {0: ["a:1"], 1: ["b:1"]}
    for _ in range(10):  # cache hit: no master traffic on the hot path
        vs._lookup_ec_shards(5)
    assert len(calls) == 1

    # failed read inside the 11s tier: still served from cache
    vs._lookup_ec_shards(5, failed=True)
    assert len(calls) == 1
    # age the entry past 11s: failed lookup refreshes, normal one doesn't
    locs, fetched, complete = vs._ec_loc_cache[5]
    vs._ec_loc_cache[5] = (locs, fetched - 12, complete)
    vs._lookup_ec_shards(5)
    assert len(calls) == 1
    vs._lookup_ec_shards(5, failed=True)
    assert len(calls) == 2

    # master down: stale cache still serves the read path
    def broken(vid):
        calls.append(vid)
        return None
    vs._lookup_ec_shards_master = broken
    locs, fetched, complete = vs._ec_loc_cache[5]
    vs._ec_loc_cache[5] = (locs, fetched - 3000, complete)
    assert vs._lookup_ec_shards(5) == {0: ["a:1"], 1: ["b:1"]}


def test_a_server_started_before_its_master_registers_once_it_listens(
        tmp_path):
    """A volume server whose master is not listening yet retries at once
    when the master comes up, not a pulse later (a fresh channel, not
    the failed one's reconnect backoff): a verb run right after both
    started sees it."""
    mport, port = free_port(), free_port()
    store = Store("127.0.0.1", port, f"127.0.0.1:{port}",
                  [DiskLocation(str(tmp_path), max_volume_count=4)],
                  coder_name="numpy")
    vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                      grpc_port=free_port(), pulse_seconds=2.0)
    vs.start()
    master = MasterServer(port=mport, volume_size_limit_mb=64)
    try:
        # the first heartbeats fail, no master listening: the failed
        # channel's reconnect backoff (1 s ± 20%, then 1.6 s ± 20%) and a
        # pulse's pause (2 s) both end well after 0.4 s past the start
        time.sleep(1.3)
        master.start()
        up = time.monotonic()
        while not master.topo.nodes:
            assert time.monotonic() - up < 0.4, "not registered"
            time.sleep(0.005)
    finally:
        vs.stop()
        master.stop()
