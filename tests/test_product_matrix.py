"""Bandwidth-optimal repair: the product-matrix MSR regenerating codec
(ops/product_matrix.py) behind the ErasureCoder seam — coder math (MDS
round-trips, cut-set-bound single-loss repair for data AND parity),
fragment plans and their file/wire execution (ec/repair.py,
rebuild_shards, the ranged-compute VolumeEcShardRead mode), codec
persistence in the .vif seal, degraded interval reads, planner
byte-costing, the parity-loss plan matrix across all three codecs, the
p=2 degenerate-geometry regression matrix, and the rebuild RPC on a
mini cluster.

Correctness oracle: the codec is systematic — data shards are the raw
striped bytes — so every reconstruction must reproduce the exact bytes
originally sealed, asserted byte-for-byte.
"""

import itertools
import os
import socket

import numpy as np
import pytest

from seaweedfs_tpu.ec import files as ecf
from seaweedfs_tpu.ec.encoder import encode_volume, rebuild_shards
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ec.volume import EcVolume
from seaweedfs_tpu.ops.coder import (NumpyCoder, codec_coder, get_coder,
                                     registered_codecs, repair_read_bytes)
from seaweedfs_tpu.ops.piggyback import PiggybackCoder
from seaweedfs_tpu.ops.product_matrix import ProductMatrixCoder
from seaweedfs_tpu.stats import REPAIR_BYTES_READ

D, P = 4, 2
GEO = EcGeometry(d=D, p=P, large_block=4096, small_block=512)


def _stripe(seed=0, d=D, length=None, alpha=8):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (d, length or alpha * 32), dtype=np.uint8)


def _shards(pm, seed=0, length=None):
    data = _stripe(seed, pm.d, length, pm.alpha)
    return np.concatenate([data, pm.encode(data)], axis=0)


# -- coder math --------------------------------------------------------------

@pytest.mark.parametrize("d,p", [(4, 2), (5, 2), (4, 3), (6, 4)])
def test_mds_roundtrip_all_loss_patterns(d, p):
    pm = ProductMatrixCoder(d, p)
    n = d + p
    sh = _shards(pm, seed=d * 31 + p)
    pats = list(itertools.combinations(range(n), p))
    rng = np.random.default_rng(1)
    if len(pats) > 30:
        pats = [pats[i] for i in rng.choice(len(pats), 30, replace=False)]
    for r in range(1, p):
        pats.append(tuple(sorted(rng.choice(n, r, replace=False).tolist())))
    for lost in pats:
        present = tuple(i for i in range(n) if i not in lost)
        rec = pm.reconstruct(sh[list(present)[:d]], present, lost)
        assert np.array_equal(rec, sh[list(lost)]), (d, p, lost)
    assert pm.verify(sh)


def test_systematic_data_and_batch_semantics():
    pm, rs = ProductMatrixCoder(D, P), NumpyCoder(D, P)
    data = _stripe(2)
    # parity differs from plain RS (it's a different code) but data rows
    # are untouched by construction — encode only RETURNS parity
    assert not np.array_equal(pm.encode(data), rs.encode(data))
    batch = np.stack([_stripe(3), _stripe(4), _stripe(5)])
    bpar = pm.encode(batch)
    for i in range(3):
        assert np.array_equal(bpar[i], pm.encode(batch[i]))


def test_encode_rejects_unaligned_length():
    pm = ProductMatrixCoder(D, P)
    with pytest.raises(ValueError, match="alpha"):
        pm.encode(_stripe(1)[:, : pm.alpha * 4 + 1])


def test_backend_parity_numpy_vs_jax():
    jax = pytest.importorskip("jax")  # noqa: F841
    pn = ProductMatrixCoder(D, P, backend="numpy")
    pj = ProductMatrixCoder(D, P, backend="jax")
    data = _stripe(6)
    assert np.array_equal(pn.encode(data), pj.encode(data))
    sh = np.concatenate([data, pn.encode(data)], axis=0)
    present = tuple(range(1, D + P))
    assert np.array_equal(pj.reconstruct(sh[1: D + 1], present, (0,)),
                          pn.reconstruct(sh[1: D + 1], present, (0,)))


@pytest.mark.parametrize("d,p", [(4, 2), (6, 4)])
def test_single_loss_repair_every_node_at_cutset(d, p):
    """ANY single loss — data or parity — repairs from exactly
    (n-1)/p shard-equivalents of survivor fragments, byte-identical."""
    pm = ProductMatrixCoder(d, p)
    n = d + p
    sh = _shards(pm, seed=7 * d + p)
    L = sh.shape[-1]
    s = L // pm.alpha
    sub = sh.reshape(n, pm.alpha, s)
    for f in range(n):
        present = tuple(i for i in range(n) if i != f)
        plan = pm.repair_plan(present, (f,), L)
        assert plan is not None, f
        assert sum(ln for _, _, ln in plan) == (n - 1) * L // p
        assert {sid for sid, _, _ in plan} == set(present)
        planes = pm.grid.repair_planes(f)
        c = np.zeros((pm.grid.nbar, pm.alpha, s), dtype=np.uint8)
        for sid in present:
            c[sid, planes] = sub[sid, planes]
        out = pm.repair_decode(c, f)
        assert np.array_equal(out.reshape(-1), sh[f]), f


def test_repair_plan_none_cases():
    pm = ProductMatrixCoder(D, P)
    n = D + P
    L = pm.alpha * 16
    # multi-loss, a missing helper, alpha-unaligned, zero size
    assert pm.repair_plan(tuple(range(n - 1)), (n - 1, 0), L) is None
    assert pm.repair_plan(tuple(range(2, n)), (0,), L) is None
    assert pm.repair_plan(tuple(range(1, n)), (0,), L + 3) is None
    assert pm.repair_plan(tuple(range(1, n)), (0,), 0) is None
    # single parity: no repair gain exists (q=1)
    pm1 = ProductMatrixCoder(4, 1)
    assert pm1.repair_plan(tuple(range(1, 5)), (0,), 64) is None


def test_fragment_ranges_coalesce():
    pm = ProductMatrixCoder(D, P)
    L = pm.alpha * 16
    for f in range(D + P):
        runs = pm.repair_fragment_ranges(f, L)
        total = sum(ln for _, ln in runs)
        assert total == L // P
        # high grid columns coalesce into few contiguous runs
        x0, y0 = pm.grid.coords(f)
        assert len(runs) == pm.grid.q ** y0


# -- the satellite matrices ---------------------------------------------------

def test_registered_codecs_enumeration():
    codecs = registered_codecs()
    assert {"rs", "piggyback", "msr"} <= set(codecs)


@pytest.mark.parametrize("d,p", [(10, 4), (14, 2)])
def test_parity_loss_plan_matrix_across_codecs(d, p):
    """Parity-shard loss across all three codecs: rs and piggyback have
    no sub-d plan (piggyback MUST keep returning None there); msr plans
    (n-1)/p — strictly below d full shards."""
    n = d + p
    shard_size = 256 * 512
    present = tuple(i for i in range(n) if i != d + 1)
    for codec in ("rs", "piggyback"):
        coder = codec_coder(codec, d, p)
        assert coder.repair_plan(present, (d + 1,), shard_size) is None
        assert repair_read_bytes(codec, d, p, [d + 1],
                                 shard_size) == d * shard_size
    msr = codec_coder("msr", d, p)
    plan = msr.repair_plan(present, (d + 1,), shard_size)
    assert plan is not None
    got = sum(ln for _, _, ln in plan)
    assert got == (n - 1) * shard_size // p < d * shard_size
    assert repair_read_bytes("msr", d, p, [d + 1], shard_size) == got


@pytest.mark.parametrize("d", [4, 5, 14])
def test_p2_degenerate_geometry_matrix(d):
    """p=2 (the fork's default parity) regression matrix: piggyback
    degenerates to the trivial plan for EVERY single loss, msr still
    reaches the cut-set bound for every single loss."""
    p = 2
    n = d + p
    shard_size = 256 * 64
    pb = PiggybackCoder(d, p)
    msr = ProductMatrixCoder(d, p)
    for f in range(n):
        present = tuple(i for i in range(n) if i != f)
        assert pb.repair_plan(present, (f,), shard_size) is None
        plan = msr.repair_plan(present, (f,), shard_size)
        assert plan is not None and \
            sum(ln for _, _, ln in plan) == (n - 1) * shard_size // p
    assert repair_read_bytes("piggyback", d, p, [1],
                             shard_size) == d * shard_size
    assert repair_read_bytes("msr", d, p, [1],
                             shard_size) == (n - 1) * shard_size // 2


def test_planner_costs_msr_items():
    from seaweedfs_tpu.maintenance import build_plan

    def item(vid, missing):
        return {"kind": "ec", "id": vid, "collection": "", "severity":
                "DEGRADED", "distance_to_data_loss": 1,
                "shards_present": [], "shards_missing": missing,
                "rs": {"k": 10, "n": 14}}

    size = 1 << 20
    report = {"verdict": "DEGRADED", "nodes": [],
              "items": [item(1, [11]), item(2, [11])]}
    geom = {1: {"codec": "msr", "d": 10, "p": 4, "shard_size": size},
            2: {"codec": "rs", "d": 10, "p": 4, "shard_size": size}}
    plan = build_plan(report, probe_geometry=lambda vid, c: geom[vid])
    by_vid = {it.vid: it for it in plan.items}
    assert by_vid[1].bytes_moved == 13 * size // 4
    assert by_vid[1].repair_codec == "msr"
    assert by_vid[2].bytes_moved == 10 * size
    # cheaper msr stripe ordered first on the severity tie
    assert plan.items[0].vid == 1


# -- file-level: seal, rebuild paths, byte accounting ------------------------

def _encode(tmp_path, coder, seed=0, size=None, geo=GEO):
    size = size or geo.d * 4096 * 2 + 777
    rng = np.random.default_rng(seed)
    datp = str(tmp_path / "v.dat")
    with open(datp, "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    base = str(tmp_path / "v")
    encode_volume(datp, base, geo, coder, chunk=256, batch=4)
    orig = {i: open(base + ecf.shard_ext(i), "rb").read()
            for i in range(geo.n)}
    return base, orig


# the module's small ring, and the two shipped ones: the fork's RS(14,2)
# and upstream's RS(10,4) (alpha 256 both)
def _geo(d, p):
    return EcGeometry(d=d, p=p, large_block=4096, small_block=512)


def test_vif_seals_codec_and_streamed_equals_whole(tmp_path):
    pm = ProductMatrixCoder(D, P)
    base, orig = _encode(tmp_path, pm, seed=1)
    assert ecf.read_vif(base + ".vif")["codec"] == "msr"
    # data shards byte-identical to a plain-RS encode (systematic)
    rs_dir = tmp_path / "rs"
    rs_dir.mkdir()
    import shutil
    shutil.copy(str(tmp_path / "v.dat"), str(rs_dir / "v.dat"))
    rs_base = str(rs_dir / "v")
    encode_volume(str(rs_dir / "v.dat"), rs_base, GEO, NumpyCoder(D, P),
                  chunk=256, batch=4)
    for i in range(D):
        assert orig[i] == open(rs_base + ecf.shard_ext(i), "rb").read()
    # streamed pipeline + overlay == whole-array construction
    shard_size = len(orig[0])
    rows = np.stack([np.frombuffer(orig[i], np.uint8) for i in range(D)])
    par = pm.encode(rows)
    for j in range(P):
        assert par[j].tobytes() == orig[D + j], f"parity {j}"


@pytest.mark.parametrize("d,p,lost", [
    (D, P, 1), (D, P, D), (D, P, D + 1),
    (14, 2, 1), (14, 2, 15), (10, 4, 1), (10, 4, 11)])
def test_rebuild_single_loss_ranged_at_cutset(tmp_path, d, p, lost):
    geo, pm = _geo(d, p), ProductMatrixCoder(d, p)
    base, orig = _encode(tmp_path, pm, seed=2 + lost, geo=geo)
    shard_size = len(orig[0])
    os.remove(base + ecf.shard_ext(lost))
    stats = {}
    counted = REPAIR_BYTES_READ.value("msr")
    assert rebuild_shards(base, geo, pm, stats=stats) == [lost]
    assert open(base + ecf.shard_ext(lost), "rb").read() == orig[lost]
    assert stats["path"] == "ranged"
    assert stats["bytes_read"] == (d + p - 1) * shard_size // p
    assert stats["bytes_written"] == shard_size
    # what operators graph is what the rebuild says it read
    assert REPAIR_BYTES_READ.value("msr") - counted == stats["bytes_read"]


@pytest.mark.parametrize("d,p", [(D, P), (14, 2), (10, 4)])
def test_rebuild_multi_loss_reads_each_survivor_once(tmp_path, d, p):
    geo, pm = _geo(d, p), ProductMatrixCoder(d, p)
    base, orig = _encode(tmp_path, pm, seed=9, geo=geo)
    shard_size = len(orig[0])
    for sid in (0, d + 1):
        os.remove(base + ecf.shard_ext(sid))
    stats = {}
    assert rebuild_shards(base, geo, pm, stats=stats) == [0, d + 1]
    for sid in (0, d + 1):
        assert open(base + ecf.shard_ext(sid), "rb").read() == orig[sid]
    assert stats["path"] == "general"
    # exactly d survivors, each read exactly once — never once per loss
    assert stats["bytes_read"] == d * shard_size


def test_rebuild_remote_survivors_fetch_fragments(tmp_path):
    """Keep only the lost shard's .vif locally: every survivor is
    remote. The ranged path must pull exactly the repair-plane bytes,
    one fragment call per survivor per window."""
    pm = ProductMatrixCoder(D, P)
    base, orig = _encode(tmp_path, pm, seed=4)
    shard_size = len(orig[0])
    n = D + P
    lost = 2
    remote_dir = tmp_path / "remote"
    remote_dir.mkdir()
    import shutil
    for i in range(n):
        shutil.move(base + ecf.shard_ext(i),
                    str(remote_dir / f"s{i}"))
    frag_calls, range_calls = [], []

    def shard_reader(sid, off, ln):
        range_calls.append((sid, off, ln))
        with open(remote_dir / f"s{sid}", "rb") as f:
            f.seek(off)
            return f.read(ln)

    def fragment_reader(sid, ranges):
        frag_calls.append((sid, tuple(ranges)))
        out = b""
        with open(remote_dir / f"s{sid}", "rb") as f:
            for off, ln in ranges:
                f.seek(off)
                out += f.read(ln)
        return out

    stats = {}
    rebuilt = rebuild_shards(base, GEO, pm, wanted=[lost],
                             shard_reader=shard_reader,
                             remote_shards=[i for i in range(n)
                                            if i != lost],
                             stats=stats,
                             fragment_reader=fragment_reader)
    assert rebuilt == [lost]
    got = open(base + ecf.shard_ext(lost), "rb").read()
    assert got == orig[lost]
    assert stats["bytes_read"] == (n - 1) * shard_size // P
    assert not range_calls, "fragments must carry all remote repair reads"
    assert len({sid for sid, _ in frag_calls}) == n - 1
    # small stripe: one window -> exactly one fragment RPC per survivor
    assert len(frag_calls) == n - 1


def test_rebuild_without_fragment_reader_falls_back_to_ranges(tmp_path):
    pm = ProductMatrixCoder(D, P)
    base, orig = _encode(tmp_path, pm, seed=5)
    n = D + P
    lost = D  # parity
    remote_dir = tmp_path / "remote"
    remote_dir.mkdir()
    import shutil
    for i in range(n):
        shutil.move(base + ecf.shard_ext(i), str(remote_dir / f"s{i}"))
    calls = []

    def shard_reader(sid, off, ln):
        calls.append(sid)
        with open(remote_dir / f"s{sid}", "rb") as f:
            f.seek(off)
            return f.read(ln)

    stats = {}
    rebuilt = rebuild_shards(base, GEO, pm, wanted=[lost],
                             shard_reader=shard_reader,
                             remote_shards=[i for i in range(n)
                                            if i != lost], stats=stats)
    assert rebuilt == [lost]
    assert open(base + ecf.shard_ext(lost), "rb").read() == orig[lost]
    assert stats["bytes_read"] == (n - 1) * len(orig[0]) // P
    assert set(calls) == set(range(n)) - {lost}


def test_needle_reads_identical_across_codecs(tmp_path):
    """Data shards are untouched: the stripe locator serves needles
    from an msr volume exactly as from a plain-RS one."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume
    rng = np.random.default_rng(9)
    v = Volume(str(tmp_path), "", 1)
    payloads = {}
    for i in range(1, 30):
        data = rng.integers(0, 256, int(rng.integers(1, 3000)),
                            dtype=np.uint8).tobytes()
        v.write_needle(Needle(id=i, cookie=0xAB, data=data))
        payloads[i] = data
    v.sync()
    base = v.file_name()
    encode_volume(base + ".dat", base, GEO, ProductMatrixCoder(D, P),
                  idx_path=base + ".idx", chunk=256, batch=4)
    v.close()
    ev = EcVolume(base, 1, geo=GEO)
    assert ev.codec == "msr"
    for nid, data in payloads.items():
        assert ev.read_needle(nid, cookie=0xAB).data == data
    ev.close()


# -- degraded interval reads -------------------------------------------------

def test_interval_plan_repair_mode_cheap_and_correct():
    pm = ProductMatrixCoder(D, P)
    sh = _shards(pm, seed=11)
    n = D + P
    L = sh.shape[-1]
    s = L // pm.alpha
    sub = sh.reshape(n, pm.alpha, s)
    for f in (0, D - 1, D, n - 1):
        present = tuple(i for i in range(n) if i != f)
        for off, ln in [(0, 7), (s - 3, 6), (3 * s + 1, 1), (0, L),
                        (L - 9, 9)]:
            plan = pm.interval_plan(present, f, off, ln, L)
            assert plan.mode == "repair"
            fetched = {sid: b"".join(
                sub[sid, z, plan.inner[0]:plan.inner[1]].tobytes()
                for z in lids) for sid, lids in plan.fetch.items()}
            assert pm.interval_decode(plan, fetched) == \
                sh[f, off:off + ln].tobytes(), (f, off, ln)
        # a small span costs ~2(n-1) layer slices, nowhere near the
        # d-survivor full-column fetch
        plan = pm.interval_plan(present, f, 1, 4, L)
        w = plan.inner[1] - plan.inner[0]
        assert plan.bytes_total() <= 2 * (n - 1) * w


def test_interval_plan_general_mode_two_losses():
    pm = ProductMatrixCoder(D, P)
    sh = _shards(pm, seed=12)
    n = D + P
    L = sh.shape[-1]
    s = L // pm.alpha
    sub = sh.reshape(n, pm.alpha, s)
    for f, other in [(0, 1), (2, D), (D, D + 1)]:
        present = tuple(i for i in range(n) if i not in (f, other))
        for off, ln in [(3, 9), (2 * s - 5, 10), (0, L)]:
            plan = pm.interval_plan(present, f, off, ln, L)
            assert plan.mode == "general"
            fetched = {sid: b"".join(
                sub[sid, z, plan.inner[0]:plan.inner[1]].tobytes()
                for z in lids) for sid, lids in plan.fetch.items()}
            assert pm.interval_decode(plan, fetched) == \
                sh[f, off:off + ln].tobytes(), (f, other, off, ln)


# -- mini-cluster: rebuild RPC, fragment wire mode, degraded reads -----------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_msr_rebuild_rpc_end_to_end(tmp_path_factory):
    """Encode with -codec msr, spread RS(4,2) shards over three servers,
    lose one shard, and let VolumeEcShardsRebuild pull beta-fragments
    from every survivor through the ranged-compute VolumeEcShardRead:
    bytes_read == (n-1)/p shard-equivalents (< d full shards), the
    journal carries them, the rebuilt shard is byte-identical, and
    degraded needle reads decode through the interval planner. Also
    drives the wire fragment mode (+ GF combine) directly."""
    from conftest import wait_cluster_up, wait_until
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.master_client import MasterClient
    from seaweedfs_tpu.master.master_server import MasterServer
    from seaweedfs_tpu.ops import events, gf8
    from seaweedfs_tpu.pb import volume_server_pb2 as vpb
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.storage.disk_location import DiskLocation
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.utils.rpc import Stub, VOLUME_SERVICE

    d, p = 4, 2
    n = d + p
    geo = EcGeometry(d=d, p=p, large_block=1 << 20, small_block=1 << 14)
    mport = _free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.3, ec_parity_shards=p)
    master.start()
    servers = []
    try:
        for i in range(3):
            dd = tmp_path_factory.mktemp(f"msrvs{i}")
            port = _free_port()
            store = Store("127.0.0.1", port, f"127.0.0.1:{port}",
                          [DiskLocation(str(dd), max_volume_count=10)],
                          ec_geometry=geo, coder_name="numpy")
            vs = VolumeServer(store, f"127.0.0.1:{mport}", port=port,
                              grpc_port=_free_port(), pulse_seconds=0.3)
            vs.start()
            servers.append(vs)
        wait_cluster_up(master, servers)
        mc = MasterClient(f"127.0.0.1:{mport}").start()
        rng = np.random.default_rng(13)
        blobs = {}
        for _ in range(20):
            data = rng.integers(0, 256, int(rng.integers(800, 9000)),
                                dtype=np.uint8).tobytes()
            res = operation.submit(mc, data, collection="msr")
            blobs[res.fid] = data
        vid = int(next(iter(blobs)).split(",")[0])
        src_vs = next(vs for vs in servers
                      if vs.store.find_volume(vid) is not None)
        src = Stub(f"127.0.0.1:{src_vs.grpc_port}", VOLUME_SERVICE)
        src.call("VolumeMarkReadonly",
                 vpb.VolumeMarkReadonlyRequest(volume_id=vid),
                 vpb.VolumeMarkReadonlyResponse)
        src.call("VolumeEcShardsGenerate",
                 vpb.VolumeEcShardsGenerateRequest(
                     volume_id=vid, collection="msr", codec="msr"),
                 vpb.VolumeEcShardsGenerateResponse, timeout=120)
        rest = [vs for vs in servers if vs is not src_vs]
        want = {src_vs: [0, 1], rest[0]: [2, 3], rest[1]: [4, 5]}
        for vs, sids in want.items():
            if vs is not src_vs:
                Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE).call(
                    "VolumeEcShardsCopy",
                    vpb.VolumeEcShardsCopyRequest(
                        volume_id=vid, collection="msr", shard_ids=sids,
                        copy_ecx_file=True, copy_vif_file=True,
                        copy_ecj_file=True,
                        source_data_node=f"127.0.0.1:{src_vs.grpc_port}"),
                    vpb.VolumeEcShardsCopyResponse, timeout=60)
            Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE).call(
                "VolumeEcShardsMount",
                vpb.VolumeEcShardsMountRequest(volume_id=vid,
                                               collection="msr",
                                               shard_ids=sids),
                vpb.VolumeEcShardsMountResponse)
        src.call("VolumeEcShardsUnmount",
                 vpb.VolumeEcShardsUnmountRequest(volume_id=vid,
                                                  shard_ids=[2, 3, 4, 5]),
                 vpb.VolumeEcShardsUnmountResponse)
        src_base = src_vs.store.find_ec_volume(vid).base
        for sid in (2, 3, 4, 5):
            os.remove(src_base + ecf.shard_ext(sid))
        src.call("VolumeDelete", vpb.VolumeDeleteRequest(volume_id=vid),
                 vpb.VolumeDeleteResponse)
        wait_until(lambda: sorted(master.topo.lookup_ec(vid)) ==
                   list(range(n)), timeout=15,
                   msg="all 6 shards registered")

        holder = Stub(f"127.0.0.1:{rest[0].grpc_port}", VOLUME_SERVICE)
        info = holder.call("VolumeEcShardsInfo",
                           vpb.VolumeEcShardsInfoRequest(volume_id=vid,
                                                         collection="msr"),
                           vpb.VolumeEcShardsInfoResponse)
        assert info.codec == "msr"
        shard_size = info.shard_size
        assert shard_size > 0 and shard_size % 8 == 0  # alpha = 8

        # -- wire fragment mode: packed ranges + GF combine --------------
        ev1 = rest[0].store.find_ec_volume(vid)
        s2 = open(ev1.base + ecf.shard_ext(2), "rb").read()
        frag = b"".join(r.data for r in holder.call_stream(
            "VolumeEcShardRead",
            vpb.VolumeEcShardReadRequest(
                volume_id=vid, shard_id=2,
                fragment_offsets=[0, shard_size // 2],
                fragment_lengths=[64, 64]),
            vpb.VolumeEcShardReadResponse))
        assert frag == s2[:64] + s2[shard_size // 2:shard_size // 2 + 64]
        combined = b"".join(r.data for r in holder.call_stream(
            "VolumeEcShardRead",
            vpb.VolumeEcShardReadRequest(
                volume_id=vid, shard_id=2,
                fragment_offsets=[0, shard_size // 2],
                fragment_lengths=[64, 64],
                combine_rows=1, combine_matrix=bytes([1, 3])),
            vpb.VolumeEcShardReadResponse))
        want_c = (np.frombuffer(s2[:64], np.uint8)
                  ^ gf8.GF_MUL[3, np.frombuffer(
                      s2[shard_size // 2:shard_size // 2 + 64], np.uint8)])
        assert combined == want_c.tobytes()

        # -- lose shard 2 for good; rebuild pulls beta-fragments ---------
        original = s2
        holder.call("VolumeEcShardsUnmount",
                    vpb.VolumeEcShardsUnmountRequest(volume_id=vid,
                                                     shard_ids=[2]),
                    vpb.VolumeEcShardsUnmountResponse)
        os.remove(ev1.base + ecf.shard_ext(2))
        wait_until(lambda: 2 not in master.topo.lookup_ec(vid),
                   timeout=15, msg="shard 2 dropped from topology")

        since = events.JOURNAL.last_seq
        resp = holder.call("VolumeEcShardsRebuild",
                           vpb.VolumeEcShardsRebuildRequest(
                               volume_id=vid, collection="msr"),
                           vpb.VolumeEcShardsRebuildResponse, timeout=120)
        assert list(resp.rebuilt_shard_ids) == [2]
        rebuilt = open(ev1.base + ecf.shard_ext(2), "rb").read()
        assert rebuilt == original
        assert resp.bytes_read == (n - 1) * shard_size // p
        assert resp.bytes_read < d * shard_size
        assert resp.bytes_written == shard_size
        fins = list(events.JOURNAL.snapshot(since=since,
                                            etype="ec.rebuild.finish"))
        assert fins and fins[-1]["attrs"]["bytes_read"] == resp.bytes_read
        assert fins[-1]["attrs"]["codec"] == "msr"
        assert fins[-1]["attrs"]["repair_path"] == "ranged"

        # -- degraded reads: lose a shard, needles still serve ------------
        holder.call("VolumeEcShardsUnmount",
                    vpb.VolumeEcShardsUnmountRequest(volume_id=vid,
                                                     shard_ids=[2]),
                    vpb.VolumeEcShardsUnmountResponse)
        os.remove(ev1.base + ecf.shard_ext(2))
        wait_until(lambda: 2 not in master.topo.lookup_ec(vid),
                   timeout=15, msg="shard 2 dropped again")
        from seaweedfs_tpu.stats import DEGRADED_EC_READS
        degraded_before = DEGRADED_EC_READS.value()
        for fid, data in blobs.items():
            assert operation.read(mc, fid) == data, fid
        assert DEGRADED_EC_READS.value() > degraded_before
        mc.stop()
    finally:
        for vs in servers:
            try:
                vs.stop()
            except Exception:  # noqa: BLE001
                pass
        master.stop()
