"""Repair-traffic-aware erasure coding: the piggybacked-RS codec
(ops/piggyback.py), ranged repair plans and their file/wire execution
(ec/repair.py, rebuild_shards), codec persistence in the .vif seal,
degraded reads through piggybacked parities, planner byte-costing, and
the ranged VolumeEcShardsRebuild RPC on a mini cluster.

Correctness oracle: data shards are systematic and untouched by the
piggyback, so every reconstruction must reproduce the exact bytes the
NumpyCoder (plain RS) stripe layout puts on disk — asserted byte-for-
byte against the originally encoded shard files.
"""

import os
import socket

import numpy as np
import pytest

from seaweedfs_tpu.ec import files as ecf
from seaweedfs_tpu.ec import repair as ec_repair
from seaweedfs_tpu.ec.encoder import encode_volume, rebuild_shards
from seaweedfs_tpu.ec.locate import EcGeometry
from seaweedfs_tpu.ec.volume import EcVolume
from seaweedfs_tpu.ops.coder import NumpyCoder, get_coder, repair_read_bytes
from seaweedfs_tpu.ops.piggyback import PiggybackCoder, partition_groups
from seaweedfs_tpu.stats import REPAIR_BYTES_READ

D, P = 10, 4
GEO = EcGeometry(d=D, p=P, large_block=4096, small_block=512)


def _stripe(seed=0, d=D, length=256):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (d, length), dtype=np.uint8)


# -- coder math --------------------------------------------------------------

def test_partition_covers_data_ids_once():
    groups = partition_groups(D, P)
    assert len(groups) == P - 1
    flat = sorted(i for g in groups for i in g)
    assert flat == list(range(D))
    assert max(len(g) for g in groups) - min(len(g) for g in groups) <= 1


def test_encode_substripe_a_and_parity0_match_plain_rs():
    data = _stripe(1)
    pb, rs = PiggybackCoder(D, P), NumpyCoder(D, P)
    par, par_rs = pb.encode(data), rs.encode(data)
    half = data.shape[-1] // 2
    # substripe a of every parity and ALL of parity 0 are plain RS
    assert np.array_equal(par[:, :half], par_rs[:, :half])
    assert np.array_equal(par[0], par_rs[0])
    # piggybacked parities differ in the b-half — it's a different code
    assert not np.array_equal(par[1:, half:], par_rs[1:, half:])
    assert pb.verify(np.concatenate([data, par]))


def test_encode_rejects_odd_length():
    with pytest.raises(ValueError, match="even"):
        PiggybackCoder(D, P).encode(_stripe(2, length=255))


def test_piggyback_needs_two_parities():
    with pytest.raises(ValueError, match="p >= 2"):
        PiggybackCoder(D, 1)


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
@pytest.mark.parametrize("missing", [
    (1,),                 # single data shard — the hitchhiker case
    (D,),                 # the unpiggybacked parity
    (D + 2,),             # a piggybacked parity
    (0, 5),               # two data shards
    (3, D + 1),           # data + piggybacked parity
    (D, D + 1, D + 2, D + 3),   # parity-only wipeout
    (0, 1, D + 1, D + 3),       # n-k failures, mixed
])
def test_reconstruct_subsets_byte_identical(backend, missing):
    data = _stripe(3)
    pb = PiggybackCoder(D, P, backend=backend)
    shards = np.concatenate([data, np.asarray(pb.encode(data))])
    present = tuple(i for i in range(D + P) if i not in missing)
    survivors = shards[sorted(present)[:D]]
    out = np.asarray(pb.reconstruct(survivors, present, tuple(missing)))
    assert np.array_equal(out, shards[list(missing)])
    # batched form agrees
    out_b = np.asarray(pb.reconstruct(survivors[None], present,
                                      tuple(missing)))
    assert np.array_equal(out_b[0], shards[list(missing)])


def test_reconstructed_data_matches_plain_rs_oracle():
    """Systematic property: a rebuilt DATA shard equals what the
    NumpyCoder stripe would hold — codecs interoperate on data bytes."""
    data = _stripe(4)
    pb = PiggybackCoder(D, P)
    shards = np.concatenate([data, pb.encode(data)])
    present = tuple(i for i in range(D + P) if i != 2)
    out = pb.reconstruct(shards[sorted(present)[:D]], present, (2,))
    assert np.array_equal(out[0], data[2])


# -- repair plans ------------------------------------------------------------

def test_repair_plan_single_data_shard_ranges():
    pb = PiggybackCoder(D, P)
    size = 1 << 10
    half = size // 2
    all_ids = tuple(range(D + P))
    g, grp = pb.group_of(1)
    plan = pb.repair_plan(tuple(i for i in all_ids if i != 1), (1,), size)
    assert plan is not None
    assert all(ln == half for _, _, ln in plan)
    # b-halves: d-1 data + parity0 + the piggybacked parity g
    b_reads = sorted(s for s, off, _ in plan if off == half)
    assert b_reads == sorted([i for i in range(D) if i != 1]
                             + [D, D + g])
    # a-halves: the group minus the lost shard
    a_reads = sorted(s for s, off, _ in plan if off == 0)
    assert a_reads == sorted(i for i in grp if i != 1)
    cost = sum(ln for _, _, ln in plan)
    assert cost == (D + len(grp)) * half
    assert cost < 0.7 * D * size + 1e-9


def test_repair_plan_degenerate_cases():
    pb = PiggybackCoder(D, P)
    size = 1 << 10
    all_ids = tuple(range(D + P))
    assert pb.repair_plan(all_ids[:-1], (D + P - 1,), size) is None  # parity
    assert pb.repair_plan(all_ids[2:], (0, 1), size) is None   # multi-loss
    assert pb.repair_plan(all_ids[1:], (0,), size + 1) is None  # odd size
    # a required survivor missing -> no fast plan
    present = tuple(i for i in all_ids if i not in (1, D))
    assert pb.repair_plan(present, (1,), size) is None
    # p=2: the only group is all of [d] — nothing beats trivial
    assert PiggybackCoder(14, 2).repair_plan(
        tuple(range(1, 16)), (0,), size) is None
    # plain RS never has a sub-shard plan
    assert NumpyCoder(D, P).repair_plan(all_ids[1:], (0,), size) is None


# (codec, d, p, lost) -> survivor bytes read, in shard-equivalents: the
# exact count the plan gives and the ceiling an operator is promised
# (piggyback <= 0.7 x d at RS(10,4); msr <= 8.0 at the fork's RS(14,2)
# and <= 4.0 at upstream's RS(10,4), data or parity lost; the cut-set
# bounds are 7.5 and 3.25). Counts from the plan: no file, no clock.
@pytest.mark.parametrize("codec,d,p,lost,exact,ceiling", [
    ("rs", 10, 4, [1], 10.0, 10.0),
    ("piggyback", 10, 4, [1], 6.5, 7.0),   # (d + |S_g|) / 2, |S_g| = 3
    # multi-loss falls back to trivial under either codec
    ("piggyback", 10, 4, [0, 1], 10.0, 10.0),
    # p=2: the only group is all of [d], nothing beats trivial
    ("piggyback", 14, 2, [1], 14.0, 14.0),
    ("msr", 14, 2, [1], 7.5, 8.0),
    ("msr", 14, 2, [15], 7.5, 8.0),
    ("msr", 10, 4, [1], 3.25, 4.0),
    ("msr", 10, 4, [11], 3.25, 4.0),
])
def test_repair_read_bytes_costing(codec, d, p, lost, exact, ceiling):
    size = 1 << 20
    got = repair_read_bytes(codec, d, p, lost, size)
    assert got == exact * size
    assert got <= ceiling * size
    assert got <= repair_read_bytes("rs", d, p, lost, size) == d * size


# -- file-level: encode, seal, rebuild ---------------------------------------

def _encode(tmp_path, coder, seed=5, size=D * 4096 + 3333, name="v"):
    rng = np.random.default_rng(seed)
    datp = str(tmp_path / f"{name}.dat")
    with open(datp, "wb") as f:
        f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
    base = str(tmp_path / name)
    encode_volume(datp, base, GEO, coder, chunk=256, batch=4)
    return base, {i: open(base + ecf.shard_ext(i), "rb").read()
                  for i in range(GEO.n)}


def test_vif_seals_codec_and_whole_file_construction(tmp_path):
    pb = PiggybackCoder(D, P)
    base, orig = _encode(tmp_path, pb)
    assert ecf.read_vif(base + ".vif")["codec"] == "piggyback"
    # streamed encode (slab RS + overlay) == whole-array construction
    shards = np.stack([np.frombuffer(orig[i], np.uint8)
                       for i in range(GEO.n)])
    assert pb.verify(shards)
    # plain RS volumes seal codec "rs"
    base_rs, _ = _encode(tmp_path, NumpyCoder(D, P), name="vrs")
    assert ecf.read_vif(base_rs + ".vif")["codec"] == "rs"


def test_rebuild_single_data_shard_is_ranged_and_cheap(tmp_path):
    pb = PiggybackCoder(D, P)
    base, orig = _encode(tmp_path, pb)
    shard_size = len(orig[1])
    os.remove(base + ecf.shard_ext(1))
    stats = {}
    counted = REPAIR_BYTES_READ.value("piggyback")
    assert rebuild_shards(base, GEO, pb, stats=stats) == [1]
    assert open(base + ecf.shard_ext(1), "rb").read() == orig[1]
    assert stats["path"] == "ranged"
    _g, grp = pb.group_of(1)
    assert stats["bytes_read"] == (D + len(grp)) * shard_size // 2
    assert stats["bytes_read"] <= 0.7 * D * shard_size
    assert REPAIR_BYTES_READ.value("piggyback") - counted \
        == stats["bytes_read"]
    assert stats["bytes_written"] == shard_size
    assert stats["codec"] == "piggyback"


def test_rebuild_multi_loss_general_path(tmp_path):
    pb = PiggybackCoder(D, P)
    base, orig = _encode(tmp_path, pb, seed=6)
    for sid in (0, 4, D + 1, D + 3):   # n-k failures incl. piggy parities
        os.remove(base + ecf.shard_ext(sid))
    stats = {}
    assert rebuild_shards(base, GEO, pb, stats=stats) == [0, 4, D + 1, D + 3]
    for sid in (0, 4, D + 1, D + 3):
        assert open(base + ecf.shard_ext(sid), "rb").read() == orig[sid], sid
    assert stats["path"] == "general"


def test_rebuild_remote_survivors_fetch_sub_shard_ranges(tmp_path):
    """Survivors living elsewhere are pulled by RANGE per the plan —
    never as full shard files."""
    pb = PiggybackCoder(D, P)
    base, orig = _encode(tmp_path, pb, seed=7)
    shard_size = len(orig[0])
    remote = {}
    for sid in range(GEO.n):   # everything is remote except nothing local
        remote[sid] = orig[sid]
        os.remove(base + ecf.shard_ext(sid))
    calls = []

    def reader(sid, off, ln):
        calls.append((sid, off, ln))
        return remote[sid][off:off + ln]

    stats = {}
    rebuilt = rebuild_shards(base, GEO, pb, wanted=[2], shard_reader=reader,
                             remote_shards=[s for s in range(GEO.n)
                                            if s != 2], stats=stats)
    assert rebuilt == [2]
    assert open(base + ecf.shard_ext(2), "rb").read() == orig[2]
    assert stats["path"] == "ranged"
    assert all(ln <= shard_size // 2 for _, _, ln in calls)
    _g, grp = pb.group_of(2)
    assert sum(ln for _, _, ln in calls) == (D + len(grp)) * shard_size // 2


def test_rebuild_parity_only_with_group_member_also_missing(tmp_path):
    """Rebuild ONLY a piggybacked parity while a data shard of its
    group is also lost: the group member's a-half exists nowhere, so
    pass B must decode it from the survivors' a substripe (regression:
    this KeyError'd before the aux decode)."""
    pb = PiggybackCoder(D, P)
    base, orig = _encode(tmp_path, pb, seed=12)
    g, grp = pb.group_of(2)
    parity_sid = D + g
    os.remove(base + ecf.shard_ext(2))           # group member of parity g
    os.remove(base + ecf.shard_ext(parity_sid))
    stats = {}
    rebuilt = rebuild_shards(base, GEO, pb, wanted=[parity_sid], stats=stats)
    assert rebuilt == [parity_sid]
    assert open(base + ecf.shard_ext(parity_sid), "rb").read() == \
        orig[parity_sid]
    assert stats["path"] == "general"
    # shard 2 was NOT rebuilt (the caller didn't ask)
    assert not os.path.exists(base + ecf.shard_ext(2))


def test_rebuild_too_many_losses_still_fails(tmp_path):
    pb = PiggybackCoder(D, P)
    base, _ = _encode(tmp_path, pb, seed=8)
    for sid in range(P + 1):
        os.remove(base + ecf.shard_ext(sid))
    with pytest.raises(RuntimeError, match="cannot rebuild"):
        rebuild_shards(base, GEO, pb)


def test_needle_reads_identical_across_codecs(tmp_path):
    """Data shards are untouched: the stripe locator serves needles from
    a piggybacked volume exactly as from a plain-RS one."""
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
    encode_volume(base + ".dat", base, GEO, PiggybackCoder(D, P),
                  idx_path=base + ".idx", chunk=256, batch=4)
    v.close()
    ev = EcVolume(base, 1, geo=GEO)
    assert ev.codec == "piggyback"
    for nid, data in payloads.items():
        assert ev.read_needle(nid, cookie=0xAB).data == data
    ev.close()


def test_degraded_interval_through_piggybacked_parity(tmp_path):
    """Losing a data shard AND the unpiggybacked parity forces the
    degraded read through a piggybacked parity: the paired a-range
    strips the piggyback (ec/repair.reconstruct_interval)."""
    pb = PiggybackCoder(D, P)
    base, orig = _encode(tmp_path, pb, seed=10)
    shard_size = len(orig[0])
    half = shard_size // 2
    sh = {i: np.frombuffer(orig[i], np.uint8) for i in range(GEO.n)}
    f = 2
    present = [i for i in range(GEO.n) if i not in (f, D)][:D]
    assert any(s > D for s in present)  # a piggy parity is load-bearing
    pair_calls = []

    def fetch_pair(sid, off, ln):
        pair_calls.append((sid, off, ln))
        return sh[sid][off:off + ln].tobytes()

    for off, ln in [(0, 64), (half - 9, 30), (half + 11, 70),
                    (shard_size - 25, 25), (0, shard_size)]:
        gathered = {s: sh[s][off:off + ln].tobytes() for s in present}
        got = ec_repair.reconstruct_interval(pb, gathered, f, off, ln,
                                             shard_size, fetch_pair)
        assert got == sh[f][off:off + ln].tobytes(), (off, ln)
    assert pair_calls  # the b-half spans actually exercised the strip
    # a-half-only spans never need the pair fetch
    pair_calls.clear()
    gathered = {s: sh[s][:32].tobytes() for s in present}
    ec_repair.reconstruct_interval(pb, gathered, f, 0, 32, shard_size,
                                   fetch_pair)
    assert not pair_calls


# -- planner byte-costing ----------------------------------------------------

def test_planner_costs_items_codec_aware():
    from seaweedfs_tpu.maintenance import build_plan

    def item(vid, missing):
        return {"kind": "ec", "id": vid, "collection": "", "severity":
                "DEGRADED", "distance_to_data_loss": 1,
                "shards_present": [], "shards_missing": missing,
                "rs": {"k": D, "n": D + P}}

    report = {"verdict": "DEGRADED", "nodes": [],
              "items": [item(1, [3]), item(2, [3])]}
    size = 1 << 20
    geom = {1: {"codec": "piggyback", "d": D, "p": P, "shard_size": size},
            2: {"codec": "rs", "d": D, "p": P, "shard_size": size}}
    plan = build_plan(report, probe_geometry=lambda vid, c: geom[vid])
    by_vid = {it.vid: it for it in plan.items}
    _g, grp = PiggybackCoder(D, P).group_of(3)
    assert by_vid[1].bytes_moved == (D + len(grp)) * size // 2
    assert by_vid[1].repair_codec == "piggyback"
    assert by_vid[2].bytes_moved == D * size
    # identical distance/severity/kind/action: the cheaper codec-aware
    # reconstruction is ordered first despite the higher vid? No — vid 1
    # is both cheaper AND lower; flip the ids to prove cost wins:
    report2 = {"verdict": "DEGRADED", "nodes": [],
               "items": [item(1, [3]), item(2, [3])]}
    geom2 = {1: {"codec": "rs", "d": D, "p": P, "shard_size": size},
             2: {"codec": "piggyback", "d": D, "p": P, "shard_size": size}}
    plan2 = build_plan(report2, probe_geometry=lambda vid, c: geom2[vid])
    assert [it.vid for it in plan2.items] == [2, 1]
    assert plan2.items[0].to_dict()["bytes_moved"] < \
        plan2.items[1].to_dict()["bytes_moved"]


def test_planner_without_probe_keeps_working():
    from seaweedfs_tpu.maintenance import build_plan
    report = {"verdict": "DEGRADED", "nodes": [], "items": [
        {"kind": "ec", "id": 7, "collection": "", "severity": "DEGRADED",
         "distance_to_data_loss": 1, "shards_present": [],
         "shards_missing": [0], "rs": {"k": 4, "n": 6}}]}
    plan = build_plan(report)
    assert plan.items[0].bytes_moved == -1  # unknown, not fabricated


def test_planner_replicate_cost_from_volume_size():
    from seaweedfs_tpu.maintenance import build_plan
    report = {"verdict": "DEGRADED", "nodes": [
        {"id": "a", "used_slots": 0, "max_slots": 10},
        {"id": "b", "used_slots": 0, "max_slots": 10}],
        "items": [
            {"kind": "volume", "id": 9, "collection": "", "severity":
             "DEGRADED", "distance_to_data_loss": 1, "replica_deficit": 2,
             "size": 12345, "holders": ["a"]}]}
    plan = build_plan(report)
    assert plan.items[0].bytes_moved == 12345 * 2


# -- mini cluster: the ranged VolumeEcShardsRebuild RPC ----------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_ranged_rebuild_rpc_end_to_end(tmp_path_factory):
    """Encode a volume with -codec piggyback, spread RS(4,3) shards over
    three servers, destroy one data shard, and let VolumeEcShardsRebuild
    on a holder pull ONLY the plan's byte ranges from its peers: the
    response reports survivor bytes read < d * shard_size, the journal
    carries them, VolumeEcShardsInfo reports the sealed codec, and the
    rebuilt shard is byte-identical."""
    from conftest import wait_cluster_up, wait_until
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.master_client import MasterClient
    from seaweedfs_tpu.master.master_server import MasterServer
    from seaweedfs_tpu.ops import events
    from seaweedfs_tpu.pb import volume_server_pb2 as vpb
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.storage.disk_location import DiskLocation
    from seaweedfs_tpu.storage.store import Store
    from seaweedfs_tpu.utils.rpc import Stub, VOLUME_SERVICE

    d, p = 4, 3
    geo = EcGeometry(d=d, p=p, large_block=1 << 20, small_block=1 << 14)
    mport = _free_port()
    master = MasterServer(port=mport, volume_size_limit_mb=64,
                          pulse_seconds=0.3, ec_parity_shards=p)
    master.start()
    servers = []
    try:
        for i in range(3):
            dd = tmp_path_factory.mktemp(f"pbvs{i}")
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
            res = operation.submit(mc, data, collection="pb")
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
                     volume_id=vid, collection="pb", codec="piggyback"),
                 vpb.VolumeEcShardsGenerateResponse, timeout=120)
        rest = [vs for vs in servers if vs is not src_vs]
        want = {src_vs: [0, 1, 2], rest[0]: [3, 4], rest[1]: [5, 6]}
        for vs, sids in want.items():
            if vs is not src_vs:
                Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE).call(
                    "VolumeEcShardsCopy",
                    vpb.VolumeEcShardsCopyRequest(
                        volume_id=vid, collection="pb", shard_ids=sids,
                        copy_ecx_file=True, copy_vif_file=True,
                        copy_ecj_file=True,
                        source_data_node=f"127.0.0.1:{src_vs.grpc_port}"),
                    vpb.VolumeEcShardsCopyResponse, timeout=60)
            Stub(f"127.0.0.1:{vs.grpc_port}", VOLUME_SERVICE).call(
                "VolumeEcShardsMount",
                vpb.VolumeEcShardsMountRequest(volume_id=vid,
                                               collection="pb",
                                               shard_ids=sids),
                vpb.VolumeEcShardsMountResponse)
        src.call("VolumeEcShardsUnmount",
                 vpb.VolumeEcShardsUnmountRequest(volume_id=vid,
                                                  shard_ids=[3, 4, 5, 6]),
                 vpb.VolumeEcShardsUnmountResponse)
        src_base = src_vs.store.find_ec_volume(vid).base
        for sid in (3, 4, 5, 6):
            os.remove(src_base + ecf.shard_ext(sid))
        # drop the source volume: reads must flow through the EC stripe
        src.call("VolumeDelete", vpb.VolumeDeleteRequest(volume_id=vid),
                 vpb.VolumeDeleteResponse)
        wait_until(lambda: sorted(master.topo.lookup_ec(vid)) ==
                   list(range(7)), timeout=15,
                   msg="all 7 shards registered")

        # sealed codec + shard_size visible to the planner's probe
        holder = Stub(f"127.0.0.1:{rest[0].grpc_port}", VOLUME_SERVICE)
        info = holder.call("VolumeEcShardsInfo",
                           vpb.VolumeEcShardsInfoRequest(volume_id=vid,
                                                         collection="pb"),
                           vpb.VolumeEcShardsInfoResponse)
        assert info.codec == "piggyback"
        assert info.data_shards == d and info.parity_shards == p
        shard_size = info.shard_size
        assert shard_size > 0

        # destroy data shard 3 on its holder for good
        ev1 = rest[0].store.find_ec_volume(vid)
        original = open(ev1.base + ecf.shard_ext(3), "rb").read()
        holder.call("VolumeEcShardsUnmount",
                    vpb.VolumeEcShardsUnmountRequest(volume_id=vid,
                                                     shard_ids=[3]),
                    vpb.VolumeEcShardsUnmountResponse)
        os.remove(ev1.base + ecf.shard_ext(3))
        wait_until(lambda: 3 not in master.topo.lookup_ec(vid),
                   timeout=15, msg="shard 3 dropped from topology")

        since = events.JOURNAL.last_seq
        resp = holder.call("VolumeEcShardsRebuild",
                           vpb.VolumeEcShardsRebuildRequest(
                               volume_id=vid, collection="pb"),
                           vpb.VolumeEcShardsRebuildResponse, timeout=120)
        assert list(resp.rebuilt_shard_ids) == [3]
        rebuilt = open(ev1.base + ecf.shard_ext(3), "rb").read()
        assert rebuilt == original
        # ranged plan: (d + |group|)/2 shard-equivalents, not d
        g, grp = PiggybackCoder(d, p).group_of(3)
        assert resp.bytes_read == (d + len(grp)) * shard_size // 2
        assert resp.bytes_read < d * shard_size
        assert resp.bytes_written == shard_size
        fins = [e for e in events.JOURNAL.snapshot(
            since=since, etype="ec.rebuild.finish")]
        assert fins and fins[-1]["attrs"]["bytes_read"] == resp.bytes_read
        fin = fins[-1]["attrs"]
        assert fin["codec"] == "piggyback" and fin["repair_path"] == "ranged"
        # the rebuild's own stage account, as a plain-RS rebuild's: the
        # four stages partition the RPC, the loads are booked beside
        # `read_s` by kind, and nothing ran under a stage `codec`
        for key in ("read_s", "dispatch_s", "drain_s", "write_s",
                    "read_busy_s", "read_local_busy_s",
                    "read_remote_busy_s", "batches", "warm_batches",
                    "duration_ms"):
            assert key in fin, key
        assert 0 <= fin["warm_batches"] <= fin["batches"]
        assert "codec_s" not in fin and fin["batches"] >= 1
        four = (fin["read_s"] + fin["dispatch_s"] + fin["drain_s"]
                + fin["write_s"])
        assert 0 < four <= fin["duration_ms"] / 1e3 + 0.002
        # (a local load of a few KB is one preadv into the batch: its
        # seconds may round to 0.000 in the event)
        assert fin["read_local_busy_s"] >= 0 < fin["read_remote_busy_s"]

        # -- degraded reads through a piggybacked parity --------------------
        # lose data shard 3 AND the unpiggybacked parity 4 (both on
        # rest[0]): needle reads hitting shard 3 must reconstruct through
        # a piggybacked parity — the b-half spans strip its piggyback
        # with a paired a-range fetch (ec/repair.reconstruct_interval)
        holder.call("VolumeEcShardsUnmount",
                    vpb.VolumeEcShardsUnmountRequest(volume_id=vid,
                                                     shard_ids=[3, 4]),
                    vpb.VolumeEcShardsUnmountResponse)
        for sid in (3, 4):
            os.remove(ev1.base + ecf.shard_ext(sid))
        wait_until(lambda: sorted(master.topo.lookup_ec(vid)) ==
                   [0, 1, 2, 5, 6], timeout=15,
                   msg="shards 3+4 dropped from topology")
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
