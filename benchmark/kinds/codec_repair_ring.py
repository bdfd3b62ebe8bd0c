"""Traffic kind `codec_repair_ring`: EC volumes sealed under a
repair-efficient codec lose one data shard at a time and are rebuilt,
closed loop. The rounds, the timed verb, the move back, the metric and
the journal's check are `repair_ring`'s own; what differs is here:

* the set-up seal names the configuration's `codec` (`ec.encode -codec`)
  and is checked against the Hitchhiker reference: the piggybacked
  parities, not plain RS's;
* there is no warm-up volume. A rebuild's programs are keyed on the
  shapes of the ring's own shard files, and a rebuild's first pass over a
  volume also pays for pages and buffers never touched before: so set-up
  runs every distinct pattern once on EVERY ring volume, untimed, each
  round compared and moved back like a timed one, and the window holds no
  compile, no new shape and no cold page, whatever program runs it;
* `correct` also holds the run to the codec's promise: over the window A
  read no more survivor bytes than the repair plans say, (d + |S_g|) / 2
  shard files a verb and one batch of slack
  (`SeaweedFS_repair_bytes_read_total{codec=...}` of A's `/metrics`).

A pattern is a list of positions in B's shards of the volume, data
shards first, as in `repair_ring`: B holds the even shards, so `[0]`
loses shard 0 (a piggyback group of four) and `[1]` shard 2 (of three).
"""

from __future__ import annotations

import os
import sys

import numpy as np

from seaweedfs_tpu.client import http_util

from benchmark import data, ecutil, reference
from benchmark import reference_hitchhiker as hh
from benchmark.cluster import check
from benchmark.kinds import repair_ring
from benchmark.layer_metrics import _shared


def generate(run) -> None:
    cfg, s = run.config, run.samples
    vids = list(range(1, int(cfg["ec_volumes"]) + 1))
    s["pool"] = data.pool(run.seed, int(cfg["ec_needles"]["max"]))
    s["group"] = data.write_volumes(run.stage, cfg["collection"], vids,
                                    run.seed, cfg["ec_needles"])


def check_sealed(run, m, rows: int, gets: int) -> "str | None":
    """`ecutil.check_sealed` for a Hitchhiker volume: every shard file's
    size, `rows` stripe rows (the first, the last, and the one the
    substripes' boundary runs through) against `hh.sealed_row` at every
    shard, `gets` needles read from the EC volume through A."""
    cfg, cl = run.config, run.cluster
    d, p = cfg["data_shards"], cfg["parity_shards"]
    dat = np.memmap(ecutil.base(run.stage, m.collection, m.vid) + ".dat",
                    dtype=np.uint8, mode="r")
    want_size = reference.shard_file_size(dat.size, d)
    paths = []
    for sid in range(d + p):
        found = ecutil.shard_path(cl, m.collection, m.vid, sid)
        if found is None or os.path.getsize(found[1]) != want_size:
            return f"volume {m.vid}: shard {sid} missing or not {want_size} B"
        paths.append(found[1])
    n_rows = reference.small_rows(dat.size, d)
    picks = {0, n_rows - 1, (want_size // 2) // reference.SMALL_BLOCK}
    picks.update(int(r) for r in run.rng.integers(0, n_rows,
                                                  max(0, rows - 3)))
    for row in sorted(picks):
        want, off = hh.sealed_row(dat, row, d, p)
        for sid, path in enumerate(paths):
            with open(path, "rb") as f:
                f.seek(off)
                got = f.read(reference.SMALL_BLOCK)
            if got != want[sid].tobytes():
                return f"volume {m.vid}: shard {sid} differs in row {row}"
    for i in run.rng.integers(0, len(m.keys), gets):
        i = int(i)
        r = http_util.get(f"http://{cl.a_url}/{m.fid(i)}")
        o, n = int(m.offs[i]), int(m.sizes[i])
        if not r.ok or r.content != run.samples["pool"][o:o + n]:
            return (f"volume {m.vid}: GET {m.fid(i)} from the EC volume: "
                    f"HTTP {r.status}, {len(r.content)} bytes")
    return None


def _round(run, m, pattern: "list[int]") -> None:
    """One untimed round, as a timed one: lose, rebuild, compare, move
    back."""
    cl, coll = run.cluster, run.config["collection"]
    sids = repair_ring._lost(run, m.vid, pattern)
    before = [ecutil.sha256(ecutil.base(cl.b_dir, coll, m.vid)
                            + ecutil.shard_ext(sid)) for sid in sids]
    repair_ring._rebuild(run, m.vid, sids)
    after = [ecutil.sha256(ecutil.base(cl.a_dir, coll, m.vid)
                           + ecutil.shard_ext(sid)) for sid in sids]
    check(after == before, f"warm-up: volume {m.vid}: rebuilt shards {sids} "
                           "differ from the ones lost")
    repair_ring._move_back(run, m.vid, sids)


def install(run) -> None:
    cfg, tr, cl, s = run.config, run.traffic, run.cluster, run.samples
    d, p, coll = cfg["data_shards"], cfg["parity_shards"], cfg["collection"]
    for m in s["group"]:
        ecutil.place(cl, run.stage, m, m.vid)
    rc, text = cl.shell(f"lock; ec.encode -collection {coll} -fullPercent 0 "
                        f"-ecShards {d},{p} -codec {cfg['codec']}; unlock")
    check(rc == 0 and f"ec encoded {len(s['group'])} volumes" in text
          and f"codec {cfg['codec']}" in text,
          f"set-up ec.encode exited {rc}:\n{text[-2000:]}")
    for m in s["group"]:
        wrong = check_sealed(run, m, int(tr["check_rows"]),
                             int(tr["check_gets"]))
        check(wrong is None, f"set-up seal: {wrong}")
    # warm: every distinct pattern once on every volume of the ring
    distinct = [pt for i, pt in enumerate(tr["loss_patterns"])
                if pt not in tr["loss_patterns"][:i]]
    for m in s["group"]:
        for pattern in distinct:
            _round(run, m, pattern)
    n = d + p
    print("[codec_repair_ring] B holds " + "; ".join(
        f"{m.vid}: {ecutil.shards_on(cl, 'B', coll, m.vid, n)}"
        for m in s["group"]), file=sys.stderr, flush=True)
    order = [int(i) for i in run.rng.permutation(len(tr["loss_patterns"]))]
    s["patterns"] = [tr["loss_patterns"][i] for i in order]
    s["first_volume"] = int(run.rng.integers(0, len(s["group"])))


def run(run) -> dict:
    result = repair_ring.run(run)
    print("[codec_repair_ring] verbs " + " ".join(
        f"{','.join(map(str, op['lost']))}:{op['wall_s']:.2f}"
        for op in run.ops) + " s", file=sys.stderr, flush=True)
    return result


def read_limit(run) -> int:
    """The survivor bytes the window's repairs may read, by their plans."""
    cfg = run.config
    d, p = cfg["data_shards"], cfg["parity_shards"]
    slack = int(cfg["device_batch"][0]) * int(cfg["device_batch"][2])
    return sum(hh.read_bytes(op["lost"][0], d, p,
                             reference.shard_file_size(op["bytes"], d))
               + slack for op in run.ops)


def verify(run) -> bool:
    """`repair_ring`'s check of the journal, every loss a single data
    shard, and the bytes A read against the plans' (the parent's program
    counts them too, so both sides are held to one rule)."""
    d = run.config["data_shards"]
    if not all(len(op["lost"]) == 1 and op["lost"][0] < d for op in run.ops):
        return False
    keys = ("duration_ms", "read_s", "dispatch_s", "drain_s", "write_s",
            "codec_s", "read_busy_s")
    for op, e in zip(run.ops, run.events("ec.rebuild.finish")):
        print(f"[codec_repair_ring] rebuild of {op['lost']} on "
              f"{e.get('repair_path')}: " + " ".join(
                  f"{k}={e[k]}" for k in keys if k in e),
              file=sys.stderr, flush=True)
    got = _shared.prom_delta(run, "SeaweedFS_repair_bytes_read_total",
                             codec=run.config["codec"])
    limit = read_limit(run)
    print(f"[codec_repair_ring] survivor bytes read {got:.0f} of at most "
          f"{limit} by the plans", file=sys.stderr, flush=True)
    return repair_ring.verify(run) and 0 < got <= limit
