"""A degraded interval's decode (ec/degraded.py) against the benchmark's
plain reference (benchmark/reference.py), on seeded stripes: on the
coder that runs the Pallas kernel (interpreted on the CPU) the decode is
the host coder's and builds no program, every other coder keeps its own
call, and a two-server cluster at RS(14,2) and RS(10,4) with two of B's data
shards lost serves every needle byte-identical through A's degraded reads."""

import itertools
import os
import socket

import numpy as np
import pytest

from benchmark import reference
from seaweedfs_tpu.ec import degraded
from seaweedfs_tpu.ops import rs_pallas
from seaweedfs_tpu.ops.coder import get_coder

GEOMETRIES = [(14, 2), (10, 4)]
LENGTHS = [1, 1023, 1024, 4097]


def _losses(d: int, p: int):
    n = d + p
    return ([(s,) for s in range(n)]
            + list(itertools.combinations(range(n), 2)))


def _stripe(rng, d: int, p: int, length: int) -> np.ndarray:
    data = rng.integers(0, 256, (d, length), dtype=np.uint8)
    return np.concatenate([data, reference.encode(data, p)])


def _check(coder, shards, lost):
    d, p = coder.d, coder.p
    present = tuple(s for s in range(d + p) if s not in lost)[:d]
    survivors = [shards[s].tobytes() for s in present]
    got = degraded.decode_interval(coder, survivors, present, lost)
    want = reference.reconstruct(shards[list(present)], list(present),
                                 list(lost), d, p)
    assert np.array_equal(got, want), (lost, shards.shape[1])
    assert np.array_equal(got, shards[list(lost)])


@pytest.mark.parametrize("d,p", GEOMETRIES)
def test_host_path_matches_the_reference(d, p):
    """Every single lost shard and every lost pair, at every length."""
    coder = get_coder("pallas", d, p)
    assert degraded.on_host(coder) and degraded.where(coder) == "host"
    rng = np.random.default_rng([45, d, p])
    for length in LENGTHS:
        shards = _stripe(rng, d, p, length)
        for lost in _losses(d, p):
            _check(coder, shards, lost)


@pytest.mark.parametrize("name", ["numpy", "native", "jax"])
def test_a_coder_without_the_kernel_keeps_its_own_call(name, monkeypatch):
    d, p = 14, 2
    coder = get_coder(name, d, p)
    assert not degraded.on_host(coder)
    assert degraded.where(coder) == ("device" if name == "jax" else "host")
    calls = []
    own = coder.reconstruct
    monkeypatch.setattr(coder, "reconstruct",
                        lambda *a: calls.append(1) or own(*a))
    rng = np.random.default_rng(46)
    for length in (1, 4097):
        _check(coder, _stripe(rng, d, p, length), (3,))
    assert len(calls) == 2


@pytest.mark.parametrize("d,p", GEOMETRIES)
def test_the_kernels_coder_builds_no_program_on_the_request_path(
        d, p, monkeypatch):
    """Lengths and lost shards of every kind on the kernel's coder: no
    call reaches `reconstruct_jit` or `matrix_apply_jit`."""
    coder = get_coder("pallas", d, p)

    def program(*a, **kw):
        raise AssertionError("a device program was called")
    monkeypatch.setattr(rs_pallas, "reconstruct_jit", program)
    monkeypatch.setattr(rs_pallas, "matrix_apply_jit", program)
    rng = np.random.default_rng([48, d, p])
    for length, lost in [(4097, (2,)), (1 << 16, (9,)), ((1 << 20) + 1,
                                                         (d - 1, d))]:
        _check(coder, _stripe(rng, d, p, length), lost)


# -- a served degraded GET ---------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _server(tmp, mport, coder, geo):
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.storage.disk_location import DiskLocation
    from seaweedfs_tpu.storage.store import Store

    store = Store("127.0.0.1", 0, "",
                  [DiskLocation(str(tmp), max_volume_count=10)],
                  ec_geometry=geo, coder_name=coder)
    port = _free_port()
    store.port, store.public_url = port, f"127.0.0.1:{port}"
    vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                      grpc_port=_free_port(), pulse_seconds=0.3)
    vs.start()
    return vs


@pytest.mark.parametrize("d,p,lost", [(14, 2, [9, 12]), (10, 4, [7, 9])])
def test_a_served_degraded_get(tmp_path, monkeypatch, d, p, lost):
    """Master + B (`numpy`), which takes one volume of seeded needles and
    seals it at RS(d,p), then A (`-coder pallas`: the kernel interpreted)
    with the first half of the shards; two of B's data shards are then
    lost. Every needle read through A is its seeded payload, and the new
    families moved, under `where="host"`."""
    from conftest import wait_cluster_up, wait_until
    from seaweedfs_tpu.client import http_util, operation
    from seaweedfs_tpu.client.master_client import MasterClient
    from seaweedfs_tpu.ec import files as ec_files
    from seaweedfs_tpu.ec.locate import EcGeometry
    from seaweedfs_tpu.master.master_server import MasterServer
    from seaweedfs_tpu.pb import volume_server_pb2 as vpb
    from seaweedfs_tpu.stats import (DEGRADED_EC_READS, EC_RECONSTRUCT_BYTES,
                                     EC_RECONSTRUCT_SECONDS)
    from seaweedfs_tpu.utils.rpc import VOLUME_SERVICE, Stub

    from seaweedfs_tpu.profiling.flight import FLIGHT

    # the process's flight ring keeps the slowest requests: these GETs
    # are no test's business there
    monkeypatch.setattr(FLIGHT, "slow_ms", float("inf"))
    geo = EcGeometry(d=d, p=p)
    mport = _free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.3)
    master.start()
    servers, mc = [], None
    try:
        b = _server(tmp_path / "b", mport, "numpy", geo)
        servers.append(b)
        wait_cluster_up(master, servers)
        mc = MasterClient(f"127.0.0.1:{mport}").start()
        rng = np.random.default_rng(49)
        pool = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
        blobs, total = {}, 0
        while total < d * (1 << 20) + (1 << 19):  # past the last data block
            size = int(np.exp(rng.uniform(np.log(1024), np.log(600_000))))
            at = int(rng.integers(0, len(pool) - size))
            fid = operation.submit(mc, pool[at:at + size],
                                   collection="deg").fid
            blobs[fid] = pool[at:at + size]
            total += size
        vid = int(next(iter(blobs)).split(",")[0])
        assert all(int(f.split(",")[0]) == vid for f in blobs)
        stub_b = Stub(f"127.0.0.1:{b.grpc_port}", VOLUME_SERVICE)
        stub_b.call("VolumeMarkReadonly",
                    vpb.VolumeMarkReadonlyRequest(volume_id=vid),
                    vpb.VolumeMarkReadonlyResponse)
        stub_b.call("VolumeEcShardsGenerate",
                    vpb.VolumeEcShardsGenerateRequest(volume_id=vid,
                                                      collection="deg"),
                    vpb.VolumeEcShardsGenerateResponse, timeout=300)
        a = _server(tmp_path / "a", mport, "pallas", geo)
        servers.append(a)
        wait_cluster_up(master, servers)
        stub_a = Stub(f"127.0.0.1:{a.grpc_port}", VOLUME_SERVICE)
        on_a = list(range((d + p) // 2))
        stub_a.call("VolumeEcShardsCopy", vpb.VolumeEcShardsCopyRequest(
            volume_id=vid, collection="deg", shard_ids=on_a,
            copy_ecx_file=True, copy_vif_file=True, copy_ecj_file=True,
            source_data_node=f"127.0.0.1:{b.grpc_port}"),
            vpb.VolumeEcShardsCopyResponse, timeout=120)
        stub_a.call("VolumeEcShardsMount", vpb.VolumeEcShardsMountRequest(
            volume_id=vid, collection="deg", shard_ids=on_a),
            vpb.VolumeEcShardsMountResponse)
        stub_b.call("VolumeEcShardsUnmount", vpb.VolumeEcShardsUnmountRequest(
            volume_id=vid, shard_ids=on_a + lost),
            vpb.VolumeEcShardsUnmountResponse)
        base = os.path.join(str(tmp_path / "b"), f"deg_{vid}")
        for sid in on_a + lost:
            os.remove(base + ec_files.shard_ext(sid))
        stub_b.call("VolumeEcShardsMount", vpb.VolumeEcShardsMountRequest(
            volume_id=vid, collection="deg",
            shard_ids=[s for s in range(len(on_a), d + p) if s not in lost]),
            vpb.VolumeEcShardsMountResponse)
        stub_b.call("VolumeDelete", vpb.VolumeDeleteRequest(volume_id=vid),
                    vpb.VolumeDeleteResponse)

        def listed():
            locs = master.topo.ec_locations.get(vid, {})
            return set(s for s, holders in locs.items() if holders)
        wait_until(lambda: listed() == set(range(d + p)) - set(lost),
                   timeout=20, msg="the master to drop the lost shards")
        reads0 = DEGRADED_EC_READS.value()
        bytes0, device0 = (EC_RECONSTRUCT_BYTES.value("host"),
                           EC_RECONSTRUCT_BYTES.value("device"))
        stages0 = [EC_RECONSTRUCT_SECONDS.count(stage, "host")
                   for stage in ("gather", "decode")]
        for fid, want in blobs.items():
            r = http_util.get(f"http://{a.url}/{fid}")
            assert r.ok and r.content == want, fid
        reads = DEGRADED_EC_READS.value() - reads0
        assert reads > 0
        assert EC_RECONSTRUCT_BYTES.value("host") > bytes0
        assert [EC_RECONSTRUCT_SECONDS.count(stage, "host") - n for stage, n
                in zip(("gather", "decode"), stages0)] == [reads, reads]
        assert EC_RECONSTRUCT_BYTES.value("device") == device0
    finally:
        if mc is not None:
            mc.stop()
        for vs in servers:
            vs.stop()
        master.stop()
