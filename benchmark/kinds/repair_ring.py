"""Traffic kind `repair_ring`: EC volumes lose shards and are rebuilt,
closed loop.

Set-up writes `ec_volumes` full volumes (and one small warm-up volume)
from the seed, seals them at the configuration's geometry with one
`ec.encode` and checks the seal like a seal. Each round takes the next
loss pattern of the traffic file's fixed list (the seed picks the order
and the volume), notes the SHA-256 of the shards to go, removes them from
B — A keeps the most shards and hosts the rebuild — and, timed, runs
`lock; ec.rebuild; unlock`. Then the rebuilt shards are compared and
moved back to B, so every round starts from the same placement and every
pattern is one program in the compile cache.

A pattern is a list of positions in B's shards of the volume, data
shards first: `[0]` loses B's first data shard, `[0, 1, 2, 3]` four.
"""

from __future__ import annotations

import sys

from seaweedfs_tpu.pb import volume_server_pb2 as vpb

from benchmark import data, ecutil, stats
from benchmark.cluster import check

WARM_VID = 90


def generate(run) -> None:
    cfg, tr, s = run.config, run.traffic, run.samples
    vids = list(range(1, int(cfg["ec_volumes"]) + 1))
    s["pool"] = data.pool(run.seed, int(cfg["ec_needles"]["max"]))
    s["group"] = data.write_volumes(run.stage, cfg["collection"], vids,
                                    run.seed, cfg["ec_needles"])
    warm = {**cfg["ec_needles"], "fill_bytes": int(tr["warm_fill_bytes"])}
    s["warm"] = data.write_volume(run.stage, cfg["collection"], WARM_VID,
                                  run.seed, warm)


def _lost(run, vid: int, pattern: "list[int]") -> "list[int]":
    cfg = run.config
    d, n = cfg["data_shards"], cfg["data_shards"] + cfg["parity_shards"]
    on_b = ecutil.shards_on(run.cluster, "B", cfg["collection"], vid, n)
    order = [s for s in on_b if s < d] + [s for s in on_b if s >= d]
    check(len(order) > max(pattern),
          f"volume {vid}: B holds {on_b}, pattern {pattern} does not fit")
    return sorted(order[i] for i in pattern)


def _rebuild(run, vid: int, sids: "list[int]",
             only: bool = False) -> dict:
    """Lose `sids` of `vid` on B, then the timed verb: plain `ec.rebuild`
    as a cron runs it (`only`: just this volume, for the warm-up)."""
    cl, coll = run.cluster, run.config["collection"]
    cl.drop_shards("B", coll, vid, sids)
    which = f" -volumeId {vid}" if only else ""
    op = cl.timed_shell(f"lock; ec.rebuild{which}; unlock")
    check(op["rc"] == 0 and f"rebuilt {len(sids)} shards" in op["out"],
          f"ec.rebuild of volume {vid} shards {sids} exited {op['rc']}:\n"
          f"{op['out'][-2000:]}")
    return op


def _move_back(run, vid: int, sids: "list[int]") -> None:
    """The rebuilt shards go from A back to B: copy, mount, drop."""
    cl, coll = run.cluster, run.config["collection"]
    b = cl.stub("B")
    b.call("VolumeEcShardsCopy",
           vpb.VolumeEcShardsCopyRequest(
               volume_id=vid, collection=coll, shard_ids=sids,
               source_data_node=f"127.0.0.1:{cl.a_grpc}"),
           vpb.VolumeEcShardsCopyResponse, timeout=600)
    b.call("VolumeEcShardsMount",
           vpb.VolumeEcShardsMountRequest(volume_id=vid, collection=coll,
                                          shard_ids=sids),
           vpb.VolumeEcShardsMountResponse)
    cl.drop_shards("A", coll, vid, sids)


def install(run) -> None:
    cfg, tr, cl, s = run.config, run.traffic, run.cluster, run.samples
    d, p, coll = cfg["data_shards"], cfg["parity_shards"], cfg["collection"]
    for m in s["group"] + [s["warm"]]:
        ecutil.place(cl, run.stage, m, m.vid)
    vids = [m.vid for m in s["group"]] + [WARM_VID]
    rc, text = cl.shell(f"lock; ec.encode -collection {coll} -fullPercent 0 "
                        f"-ecShards {d},{p}; unlock")
    check(rc == 0 and f"ec encoded {len(vids)} volumes" in text,
          f"set-up ec.encode exited {rc}:\n{text[-2000:]}")
    for m in s["group"]:
        wrong = ecutil.check_sealed(cl, run.stage, m, m.vid, d, p, run.rng,
                                    s["pool"], int(tr["check_rows"]),
                                    int(tr["check_gets"]))
        check(wrong is None, f"set-up seal: {wrong}")
    # warm: every distinct pattern once, on the small volume
    distinct = []
    for pattern in tr["loss_patterns"]:
        if pattern not in distinct:
            distinct.append(pattern)
    for pattern in distinct:
        sids = _lost(run, WARM_VID, pattern)
        _rebuild(run, WARM_VID, sids, only=True)
        _move_back(run, WARM_VID, sids)
    # the timed verbs see only the ring: plain `ec.rebuild` looks at
    # every EC volume there is
    ecutil.unseal(cl, coll, WARM_VID, d + p)
    n = d + p
    print("[repair_ring] B holds " + "; ".join(
        f"{m.vid}: {ecutil.shards_on(cl, 'B', coll, m.vid, n)}"
        for m in s["group"]), file=sys.stderr, flush=True)
    order = [int(i) for i in run.rng.permutation(len(tr["loss_patterns"]))]
    s["patterns"] = [tr["loss_patterns"][i] for i in order]
    s["first_volume"] = int(run.rng.integers(0, len(s["group"])))


def run(run) -> dict:
    cfg, cl, s = run.config, run.cluster, run.samples
    d, p, coll = cfg["data_shards"], cfg["parity_shards"], cfg["collection"]
    failed, k = 0, 0
    while True:
        m = s["group"][(s["first_volume"] + k) % len(s["group"])]
        pattern = s["patterns"][k % len(s["patterns"])]
        k += 1
        with run.phase("hash+lose"):
            sids = _lost(run, m.vid, pattern)
            before = {sid: ecutil.sha256(
                ecutil.base(cl.b_dir, coll, m.vid) + ecutil.shard_ext(sid))
                for sid in sids}
        op = _rebuild(run, m.vid, sids)
        with run.phase("compare+move back"):
            after = {sid: ecutil.sha256(
                ecutil.base(cl.a_dir, coll, m.vid) + ecutil.shard_ext(sid))
                for sid in sids}
            if after != before:
                failed += 1
                print(f"[repair_ring] volume {m.vid}: rebuilt shards {sids} "
                      f"differ from the ones lost", file=sys.stderr,
                      flush=True)
            _move_back(run, m.vid, sids)
        run.op_done({**op, "label": "repair", "bytes": m.dat_bytes,
                     "vid": m.vid, "lost": sids})
        if run.past_end():
            break
    rates = [op["bytes"] / op["wall_s"] / 1e9 for op in run.ops]
    print("[repair_ring] verbs " + " ".join(
        f"{len(op['lost'])}:{op['wall_s']:.2f}" for op in run.ops) + " s",
        file=sys.stderr, flush=True)
    return {"attempted": len(run.ops), "failed": failed,
            "metrics": {"repair_GBps": stats.median(rates)}}


def verify(run) -> bool:
    """The journal must agree: every rebuild ran on A (the chip), ok, and
    brought back exactly the shards lost."""
    events = run.events("ec.rebuild.finish")
    if len(events) != len(run.ops):
        return False
    return all(e["ok"] and e["node"] == run.cluster.a_url
               and sorted(e["rebuilt_shard_ids"]) == op["lost"]
               and e["vid"] == op["vid"]
               for e, op in zip(events, run.ops))
