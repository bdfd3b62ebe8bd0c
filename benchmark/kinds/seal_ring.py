"""Traffic kind `seal_ring`: one operator sealing groups of full volumes,
closed loop.

Set-up writes `volumes_per_group` full volumes of the configuration's
needle mix (and one small warm-up volume) from the seed. Each round
places the group on A under fresh volume ids (hard links of the staged
`.dat`/`.idx`, `VolumeMount`), then — timed — runs `lock; ec.encode
-collection C -ecShards d,p; unlock`, then checks the sealed volumes
against the reference and removes their shards. Only the verb is timed;
no verb starts after the window's end.
"""

from __future__ import annotations

import sys

from benchmark import data, ecutil, stats

WARM_VID = 90


def generate(run) -> None:
    cfg, tr = run.config, run.traffic
    vids = list(range(1, int(tr["volumes_per_group"]) + 1))
    s = run.samples
    s["pool"] = data.pool(run.seed, int(cfg["needles"]["max"]))
    s["group"] = data.write_volumes(run.stage, cfg["collection"], vids,
                                    run.seed, cfg["needles"])
    warm = {**cfg["needles"], "fill_bytes": int(tr["warm_fill_bytes"])}
    s["warm"] = data.write_volume(run.stage, cfg["collection"], WARM_VID,
                                  run.seed, warm)
    s["next_vid"] = 100


def _place_group(run) -> "list[int]":
    s = run.samples
    vids = []
    for m in s["group"]:
        ecutil.place(run.cluster, run.stage, m, s["next_vid"])
        vids.append(s["next_vid"])
        s["next_vid"] += 1
    return vids


def install(run) -> None:
    """Warm: one untimed verb over the small volume loads the geometry's
    one encode program (every batch is `[32, d, 1 MiB]`, zero-padded);
    its shards go again, so that the timed verbs see only the ring."""
    cfg, cl, s = run.config, run.cluster, run.samples
    d, p = cfg["data_shards"], cfg["parity_shards"]
    ecutil.place(cl, run.stage, s["warm"], WARM_VID)
    ecutil.seal(cl, cfg["collection"], d, p, [WARM_VID], by_id=True)
    ecutil.unseal(cl, cfg["collection"], WARM_VID, d + p)
    s["vids"] = _place_group(run)


def run(run) -> dict:
    cfg, tr, cl, s = run.config, run.traffic, run.cluster, run.samples
    d, p, coll = cfg["data_shards"], cfg["parity_shards"], cfg["collection"]
    nbytes = sum(m.dat_bytes for m in s["group"])
    failed = 0
    while True:
        vids = s["vids"]
        op = ecutil.seal(cl, coll, d, p, vids)
        with run.phase("check+restore"):
            wrong = None
            for m, vid in zip(s["group"], vids):
                wrong = wrong or ecutil.check_sealed(
                    cl, run.stage, m, vid, d, p, run.rng, s["pool"],
                    int(tr["check_rows"]), int(tr["check_gets"]))
                ecutil.unseal(cl, coll, vid, d + p)
            if wrong:
                failed += 1
                print(f"[seal_ring] {wrong}", file=sys.stderr, flush=True)
            if not run.past_end():
                s["vids"] = _place_group(run)
        run.op_done({**op, "label": "seal", "bytes": nbytes, "vids": vids})
        if run.past_end():
            break
    rates = [op["bytes"] / op["wall_s"] / 1e9 for op in run.ops]
    print("[seal_ring] verbs " + " ".join(f"{op['wall_s']:.2f}"
                                          for op in run.ops) + " s",
          file=sys.stderr, flush=True)
    return {"attempted": len(run.ops), "failed": failed,
            "metrics": {"seal_GBps": stats.median(rates)}}


def verify(run) -> bool:
    """Every round was checked as it ran; the journal must agree: one
    finished batch encode per verb, all ok, on the device path."""
    events = run.events("ec.encode.finish")
    return (len(events) == len(run.ops)
            and all(e["ok"] and (run.rehearsal or e.get("mode") == "async")
                    for e in events))
