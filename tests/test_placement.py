"""Scale-out placement & rebalance plane (seaweedfs_tpu/placement/).

Three layers under test:

  * the shared scoring core + EC shard spread (engine.py) — seeded
    property tests over randomized heterogeneous topologies pin the
    rack-cap invariant for RS(14,2) and RS(10,4) and the graceful
    degradation on too-few-racks fleets;
  * VolumeGrowth's pick paths — now driven by ONE injectable seeded
    RNG, so the same_rack/other_rack/other_dc contract is asserted
    across randomized topologies instead of hoping global `random`
    cooperates;
  * the rebalance planner (plan.py) — deterministic byte-costed plans:
    convergence, EC-shard-bytes folded into load (the old balancer's
    blind spot), replica safety, intra-rack preference, cross-rack
    caps, per-(src,dst) move grouping — and the executor's dry-run
    zero-RPC guarantee against a recording fake env.
"""

import random
from collections import Counter

import pytest

from seaweedfs_tpu.master.topology import Topology, VolumeInfo
from seaweedfs_tpu.master.volume_growth import GrowRequest, VolumeGrowth
from seaweedfs_tpu.placement import (BalanceExecutor, MovePlan, NodeView,
                                     Snapshot, build_ec_balance_plan,
                                     build_volume_balance_plan,
                                     snapshot_from_topology,
                                     spread_ec_shards)
from seaweedfs_tpu.placement.plan import Move
from seaweedfs_tpu.storage.types import ReplicaPlacement


# -- topology builders -------------------------------------------------------

def make_topo(rng: random.Random, n_dcs=1, racks_per_dc=(1, 4),
              nodes_per_rack=(1, 4), slots=(2, 30)) -> Topology:
    """A randomized heterogeneous topology: uneven racks, uneven node
    capacity — the shape the seeded spread tests sweep."""
    topo = Topology(volume_size_limit=1 << 20)
    port = 8000
    for d in range(n_dcs):
        for r in range(rng.randint(*racks_per_dc)):
            for _ in range(rng.randint(*nodes_per_rack)):
                port += 1
                topo.get_or_create_node(
                    "127.0.0.1", port, port + 10000, "", f"dc{d}",
                    f"dc{d}-r{r}", {"hdd": rng.randint(*slots)})
    return topo


def grown_views(snapshot: Snapshot):
    return {n.id: n for n in snapshot.nodes}


def fleet(n_racks: int, nodes_per_rack: int, slots: int = 20) -> Snapshot:
    nodes = [NodeView(id=f"r{r}n{i}", rack=f"r{r}", dc="dc0",
                      max_slots=slots, free_slots=slots)
             for r in range(n_racks) for i in range(nodes_per_rack)]
    return Snapshot(nodes=nodes)


# -- VolumeGrowth seeded spread properties -----------------------------------

@pytest.mark.parametrize("replication", ["000", "001", "002", "010",
                                         "011", "020", "100", "110"])
def test_growth_spread_contract_over_random_topologies(replication):
    """The xyz placement contract holds for every pick across 20 seeded
    randomized topologies: exactly 1+z servers in one rack, y more
    racks of the same DC, x other DCs — no duplicate nodes, ever."""
    rp = ReplicaPlacement.parse(replication)
    for seed in range(20):
        rng = random.Random(1000 + seed)
        topo = make_topo(rng, n_dcs=rp.other_dc + rng.randint(1, 2),
                         racks_per_dc=(rp.other_rack + 1,
                                       rp.other_rack + 3),
                         nodes_per_rack=(rp.same_rack + 1,
                                         rp.same_rack + 3))
        growth = VolumeGrowth(topo, rng=random.Random(seed))
        try:
            servers = growth.find_slots(GrowRequest(
                replication=replication))
        except RuntimeError:
            # a randomized topology may genuinely lack capacity;
            # that's a legal outcome, not a spread violation
            continue
        assert len(servers) == rp.copy_count, (seed, servers)
        ids = [n.id for n in servers]
        assert len(set(ids)) == len(ids), f"duplicate node: {ids}"
        # first 1+z in ONE rack
        main = servers[:rp.same_rack + 1]
        assert len({n.rack.id for n in main}) == 1, (seed, replication)
        main_dc = main[0].rack.dc.id
        # next y in OTHER racks of the same DC, all distinct
        others = servers[rp.same_rack + 1:
                         rp.same_rack + 1 + rp.other_rack]
        other_rack_ids = [n.rack.id for n in others]
        assert main[0].rack.id not in other_rack_ids
        assert len(set(other_rack_ids)) == len(other_rack_ids)
        assert all(n.rack.dc.id == main_dc for n in others)
        # last x in OTHER DCs
        tail = servers[rp.same_rack + 1 + rp.other_rack:]
        assert all(n.rack.dc.id != main_dc for n in tail)
        assert len({n.rack.dc.id for n in tail}) == len(tail)


def test_growth_is_reproducible_under_one_seed():
    topo = make_topo(random.Random(7), racks_per_dc=(3, 3),
                     nodes_per_rack=(2, 2))
    picks = [VolumeGrowth(topo, rng=random.Random(42)).find_slots(
        GrowRequest(replication="010")) for _ in range(2)]
    assert [n.id for n in picks[0]] == [n.id for n in picks[1]]


def test_growth_prefers_less_loaded_node():
    """Two nodes, one stuffed with volume bytes: the scored pick lands
    new volumes on the empty node (free-slot + load terms agree)."""
    topo = Topology(volume_size_limit=1 << 20)
    a = topo.get_or_create_node("127.0.0.1", 8001, 18001, "", "dc0",
                                "r0", {"hdd": 20})
    topo.get_or_create_node("127.0.0.1", 8002, 18002, "", "dc0",
                            "r0", {"hdd": 20})
    topo.sync_volumes(a, [VolumeInfo(id=i, size=1 << 19)
                          for i in range(1, 11)])
    growth = VolumeGrowth(topo, rng=random.Random(3))
    winners = Counter(growth.find_slots(GrowRequest())[0].id
                      for _ in range(12))
    assert winners == {"127.0.0.1:8002": 12}, winners


# -- EC shard spread: rack cap -----------------------------------------------

@pytest.mark.parametrize("d,p,n_racks", [(14, 2, 8), (10, 4, 4),
                                         (10, 4, 7), (4, 2, 3)])
def test_ec_spread_rack_cap_feasible(d, p, n_racks):
    """No rack holds more than p shards whenever the fleet has enough
    racks (rack loss then costs <= p shards: reconstructable)."""
    for seed in range(10):
        rng = random.Random(seed)
        snap = fleet(n_racks, rng.randint(2, 4))
        placed = spread_ec_shards(snap, d + p, p, rng=rng)
        assert len(placed) == d + p
        racks = Counter(n.rack for n in placed)
        assert max(racks.values()) <= p, (seed, racks)
        # node evenness: no node carries 2 more than another needs to
        nodes = Counter(n.id for n in placed)
        assert max(nodes.values()) - min(
            nodes.get(n.id, 0) for n in snap.nodes) <= 2


def test_ec_spread_degrades_gracefully_when_racks_too_few():
    """RS(10,4) on 2 racks cannot cap at 4/rack; the spread must still
    succeed with the most-even rack split instead of raising."""
    snap = fleet(2, 3)
    placed = spread_ec_shards(snap, 14, 4, rng=random.Random(1))
    racks = Counter(n.rack for n in placed)
    assert sum(racks.values()) == 14
    assert max(racks.values()) <= 7  # ceil(14/2): most-even fallback


def test_ec_spread_single_node_fleet_still_encodes():
    snap = fleet(1, 1)
    placed = spread_ec_shards(snap, 6, 2, rng=random.Random(0))
    assert len(placed) == 6


# -- rebalance planner: volumes ----------------------------------------------

def _vol_fleet(loads_mb, racks=None) -> Snapshot:
    """One NodeView per entry; entry = list of volume MBs on that node."""
    nodes = []
    vid = 0
    for i, vols in enumerate(loads_mb):
        n = NodeView(id=f"n{i}", rack=(racks[i] if racks else f"rk{i}"),
                     dc="dc0", max_slots=64, free_slots=64 - len(vols))
        for mb in vols:
            vid += 1
            n.volumes[vid] = {"size": mb << 20, "collection": "c"}
        nodes.append(n)
    return Snapshot(nodes=nodes)


def test_volume_plan_converges_and_is_deterministic():
    snap = _vol_fleet([[2] * 12, [], [], []],
                      racks=["a", "a", "b", "b"])
    plan = build_volume_balance_plan(snap)
    assert plan.skew_before > 10
    assert plan.skew_after <= 1.15
    # minimum move count: 3 volumes land on each of the 3 empties,
    # none churns through an overfed neighbor
    assert len(plan.moves) == 9
    assert len(plan.moves) == len({m.vid for m in plan.moves})
    replay = build_volume_balance_plan(
        _vol_fleet([[2] * 12, [], [], []],
                   racks=["a", "a", "b", "b"]))
    assert [m.to_dict() for m in plan.moves] == \
        [m.to_dict() for m in replay.moves]


def test_volume_plan_counts_ec_shard_bytes_in_load():
    """The satellite fix: a server loaded with EC shard bytes is NOT an
    attractive destination. n1 carries 24 MB of shards (and no
    volumes); the donor's volumes must flow to the truly-empty n2."""
    snap = _vol_fleet([[4, 4, 4, 4], [], []],
                      racks=["a", "a", "a"])
    by_id = grown_views(snap)
    by_id["n1"].ec_shards[99] = {"collection": "c",
                                 "shard_ids": list(range(12)),
                                 "shard_bytes": 2 << 20}
    plan = build_volume_balance_plan(snap)
    assert plan.moves, "nothing planned"
    assert all(m.dst == "n2" for m in plan.moves), \
        [(m.vid, m.dst) for m in plan.moves]


def test_volume_plan_never_lands_on_existing_holder():
    """Replica safety: a destination already holding the vid is
    excluded even when it is the emptiest."""
    snap = _vol_fleet([[8, 8, 8], [], []], racks=["a", "a", "a"])
    by_id = grown_views(snap)
    # n1 already replicates every donor volume; n2 holds nothing
    for vid, v in by_id["n0"].volumes.items():
        by_id["n1"].volumes[vid] = dict(v)
    plan = build_volume_balance_plan(snap)
    assert all(m.dst == "n2" for m in plan.moves), \
        [(m.vid, m.dst) for m in plan.moves]


def test_volume_plan_prefers_intra_rack_and_caps_cross_rack():
    # donor shares a rack with one empty peer; the other empties are
    # cross-rack — intra-rack dst must win while it can still absorb
    snap = _vol_fleet([[2, 2, 2, 2], [], [], []],
                      racks=["a", "a", "b", "b"])
    plan = build_volume_balance_plan(snap)
    intra = [m for m in plan.moves if not m.cross_rack]
    assert intra and intra[0].dst == "n1"
    # a zero cross-rack budget keeps every move inside the rack
    capped = build_volume_balance_plan(
        _vol_fleet([[2, 2, 2, 2], [], [], []],
                   racks=["a", "a", "b", "b"]),
        cross_rack_limit_bytes=0)
    assert capped.moves and all(not m.cross_rack for m in capped.moves)
    assert any("cross-rack" in n for n in capped.notes)


def test_volume_plan_collection_filter():
    snap = _vol_fleet([[4, 4, 4, 4], [], []], racks=["a", "a", "a"])
    views = grown_views(snap)
    for vid in list(views["n0"].volumes)[:2]:
        views["n0"].volumes[vid]["collection"] = "other"
    plan = build_volume_balance_plan(snap, collection="other")
    assert plan.moves
    assert all(m.collection == "other" for m in plan.moves)


def test_volume_plan_respects_move_budget():
    snap = _vol_fleet([[1] * 30, [], [], []],
                      racks=["a", "a", "b", "b"])
    plan = build_volume_balance_plan(snap, max_moves=5)
    assert len(plan.moves) == 5
    assert any("budget" in n for n in plan.notes)


def test_volume_plan_never_chains_one_volume():
    """A vid moves AT MOST ONCE per plan: the greedy loop must not
    emit A->B then B->C for the same volume (the executor runs
    distinct-vid moves concurrently — a chained pair would race)."""
    # D1={50,8}, D2={8}, D3={}: the naive greedy moves the 8 MB volume
    # D1->D2, then D2 (now 16 MB) donates the just-received volume on
    snap = _vol_fleet([[50, 8], [8], []], racks=["a", "a", "a"])
    plan = build_volume_balance_plan(snap)
    vids = [m.vid for m in plan.moves]
    assert len(vids) == len(set(vids)), f"vid moved twice: {vids}"
    # n1 may donate its OWN original volume, but never re-donate the
    # one it just received
    received = {m.vid: m.dst for m in plan.moves}
    for m in plan.moves:
        assert received.get(m.vid) == m.dst, plan.moves


def test_volume_plan_debits_destination_slots():
    """Planned moves consume destination slots: a 1-slot node takes at
    most one volume however empty it is."""
    snap = _vol_fleet([[2] * 10, [], []], racks=["a", "a", "a"])
    tight = grown_views(snap)["n2"]
    tight.free_slots = 1
    plan = build_volume_balance_plan(snap)
    landed = sum(1 for m in plan.moves if m.dst == "n2")
    assert landed <= 1, plan.moves


def test_volume_plan_immovable_giant_reaches_fixed_point():
    """One volume holding almost everything: moving it only swaps the
    imbalance, so the plan must stop (no livelock), not churn."""
    snap = _vol_fleet([[64], [1], [1]], racks=["a", "a", "a"])
    plan = build_volume_balance_plan(snap)
    assert plan.moves == []


# -- rebalance planner: ec ---------------------------------------------------

def _ec_fleet(holdings, racks, shard_bytes=1 << 20) -> Snapshot:
    nodes = []
    for i, sids in enumerate(holdings):
        n = NodeView(id=f"e{i}", rack=racks[i], dc="dc0",
                     max_slots=20, free_slots=20)
        if sids:
            n.ec_shards[5] = {"collection": "c", "shard_ids": list(sids),
                              "shard_bytes": shard_bytes}
        nodes.append(n)
    return Snapshot(nodes=nodes)


def test_ec_plan_groups_moves_per_pair_and_costs_bytes():
    """All shards leaving one (src, dst) pair ride ONE grouped move —
    one VolumeEcShardsMove RPC — with bytes_moved = shards x size."""
    snap = _ec_fleet([[0, 1, 2, 3, 4, 5], None, None],
                     racks=["a", "a", "b"])
    plan = build_ec_balance_plan(snap, default_parity=3)
    pairs = {(m.src, m.dst) for m in plan.moves}
    assert len(plan.moves) == len(pairs), "pair not grouped"
    for m in plan.moves:
        assert m.bytes_moved == len(m.shard_ids) * (1 << 20)
        assert m.shard_ids == sorted(m.shard_ids)
    # per-node evenness: 2 shards each after the plan
    final = Counter()
    final["e0"] = 6 - sum(len(m.shard_ids) for m in plan.moves)
    for m in plan.moves:
        final[m.dst] += len(m.shard_ids)
    assert set(final.values()) == {2}


def test_ec_plan_honors_rack_safety_cap():
    """parity=2 over 3 racks: no rack may end with > 2 of the 6
    shards, even where per-node evenness alone would allow it."""
    snap = _ec_fleet([[0, 1, 2, 3, 4, 5], None, None, None, None, None],
                     racks=["a", "a", "b", "b", "c", "c"])
    plan = build_ec_balance_plan(snap, default_parity=2)
    rack_of = {n.id: n.rack for n in snap.nodes}
    racks = Counter()
    racks["a"] = 6 - sum(len(m.shard_ids) for m in plan.moves)
    for m in plan.moves:
        racks[rack_of[m.dst]] += len(m.shard_ids)
    assert max(racks.values()) <= 2, racks


def test_ec_plan_uses_parity_probe():
    probed = []

    def parity_of(vid, collection):
        probed.append((vid, collection))
        return 3

    snap = _ec_fleet([[0, 1, 2, 3, 4, 5], None, None],
                     racks=["a", "b", "c"])
    build_ec_balance_plan(snap, parity_of=parity_of)
    assert probed == [(5, "c")]


# -- executor ----------------------------------------------------------------

class _RecordingEnv:
    """A CommandEnv stand-in that records every RPC-shaped touch; the
    dry-run contract is that NONE happen."""

    def __init__(self):
        self.calls = []

    def collect_volume_servers(self):
        self.calls.append("collect")
        return []

    def grpc_addr(self, node_id, grpc_port):
        self.calls.append("grpc_addr")
        return f"{node_id}:{grpc_port}"


def _plan_of(moves) -> MovePlan:
    return MovePlan(moves, skew_before=2.0, skew_after=1.0)


def test_executor_dry_run_zero_rpcs_and_journals_plan():
    from seaweedfs_tpu.ops import events
    env = _RecordingEnv()
    mv = Move(kind="volume", vid=1, collection="c", src="a", dst="b",
              bytes_moved=123, cross_rack=True)
    since = events.JOURNAL.last_seq
    res = BalanceExecutor(env).execute(_plan_of([mv]), dry_run=True)
    assert env.calls == [], "dry run touched the cluster"
    assert res == {"done": [], "failed": [], "skipped": []}
    evs = events.JOURNAL.snapshot(since=since, etype="balance")
    assert [e["type"] for e in evs] == ["balance.plan"]
    assert evs[0]["attrs"]["dry_run"] is True
    assert evs[0]["attrs"]["total_bytes"] == 123


def test_executor_budget_skips_excess_moves():
    from seaweedfs_tpu.ops import events
    env = _RecordingEnv()
    moves = [Move(kind="volume", vid=i, collection="c", src="a",
                  dst="gone", bytes_moved=1) for i in range(4)]
    since = events.JOURNAL.last_seq
    res = BalanceExecutor(env, max_moves=2).execute(_plan_of(moves))
    # the 2 admitted moves fail (endpoints gone), the rest skip
    assert len(res["skipped"]) == 2 and len(res["failed"]) == 2
    evs = events.JOURNAL.snapshot(since=since, etype="balance")
    kinds = Counter(e["type"] for e in evs)
    assert kinds["balance.skipped"] == 2 and kinds["balance.failed"] == 2


def test_executor_move_metrics_and_journal():
    """A successful move (faked transport) counts toward
    balance_moves_total{kind} / balance_bytes_moved_total{cross_rack}
    and journals balance.move with its byte cost."""
    from seaweedfs_tpu.ops import events
    from seaweedfs_tpu.stats import BALANCE_BYTES_MOVED, BALANCE_MOVES

    from seaweedfs_tpu import qos
    classes = []

    class _Exec(BalanceExecutor):
        def _move_volume(self, m):
            classes.append(qos.current_class())

    before = BALANCE_MOVES.value("volume")
    before_bytes = BALANCE_BYTES_MOVED.value("true")
    mv = Move(kind="volume", vid=9, collection="c", src="a", dst="b",
              bytes_moved=777, cross_rack=True)
    since = events.JOURNAL.last_seq
    res = _Exec(_RecordingEnv()).execute(_plan_of([mv]))
    assert len(res["done"]) == 1
    # rebalance traffic is maintenance-class at the source
    assert classes == [qos.CLASS_MAINTENANCE]
    assert BALANCE_MOVES.value("volume") == before + 1
    assert BALANCE_BYTES_MOVED.value("true") == before_bytes + 777
    moved = [e for e in events.JOURNAL.snapshot(since=since,
                                                etype="balance")
             if e["type"] == "balance.move"]
    assert moved and moved[0]["attrs"]["bytes_moved"] == 777
    assert moved[0]["attrs"]["cross_rack"] is True


# -- snapshot builders -------------------------------------------------------

def test_snapshot_from_topology_counts_ec_bytes():
    topo = Topology(volume_size_limit=10 << 20)
    node = topo.get_or_create_node("127.0.0.1", 8001, 18001, "", "dc0",
                                   "r0", {"hdd": 10})
    topo.sync_volumes(node, [VolumeInfo(id=1, size=5 << 20)])
    from seaweedfs_tpu.master.topology import EcShardInfo
    topo.sync_ec_shards(node, [EcShardInfo(7, "c", 0b111)])
    snap = snapshot_from_topology(topo)
    view = snap.nodes[0]
    assert view.rack == "r0" and view.dc == "dc0"
    assert view.volume_bytes == 5 << 20
    assert view.ec_bytes == 3 * (1 << 20)  # 3 shards x limit/10
    assert view.load_bytes == view.volume_bytes + view.ec_bytes
