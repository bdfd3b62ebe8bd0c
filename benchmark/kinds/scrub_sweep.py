"""Traffic kind `scrub_sweep`: bit-rot sweeps over full volumes, closed
loop.

Set-up writes the configuration's `volumes` full volumes from the seed,
flips bytes of `corrupt_per_volume` seeded needles in each `.dat`, and
mounts them on A. Each timed operation is one `volume.scrub -device on`
over everything, as an operator's cron runs it; a sweep must report
exactly the seeded corrupt set, on the device path.
"""

from __future__ import annotations

import re
import sys

from benchmark import data, ecutil, stats
from benchmark.cluster import check

_LINE = re.compile(
    r"volume (\d+): (\d+) needles .* in ([0-9.]+)s \[([^\]]+)\]"
    r"(?:.*CORRUPT: \[([^\]]*)\])?")


def generate(run) -> None:
    cfg, tr, s = run.config, run.traffic, run.samples
    vids = list(range(1, int(cfg["volumes"]) + 1))
    s["group"] = data.write_volumes(run.stage, cfg["collection"], vids,
                                    run.seed, cfg["needles"])
    s["rotten"] = set()
    for m in s["group"]:
        picks = [int(i) for i in run.rng.choice(
            len(m.keys), int(tr["corrupt_per_volume"]), replace=False)
            if m.sizes[int(i)] >= 64]
        stem = ecutil.base(run.stage, m.collection, m.vid)
        data.corrupt(stem + ".dat", stem + ".idx", m, picks)
        s["rotten"].update((m.vid, int(m.keys[i])) for i in picks)


def sweep(run, volume_id: int = 0) -> dict:
    """One timed verb (`Cluster.timed_shell`), with what it printed:
    `volumes` {vid: fields} and the `reported` corrupt (vid, key) set."""
    flag = "auto" if run.rehearsal else "on"
    which = f" -volumeId {volume_id}" if volume_id else ""
    op = run.cluster.timed_shell(f"volume.scrub -device {flag}{which}")
    volumes, reported = {}, set()
    for line in op["out"].splitlines():
        m = _LINE.search(line)
        if m:
            vid = int(m.group(1))
            volumes[vid] = {"needles": int(m.group(2)),
                            "elapsed_s": float(m.group(3)),
                            "mode": m.group(4)}
            reported.update((vid, int(x.strip(" '"), 16))
                            for x in (m.group(5) or "").split(",")
                            if x.strip())
        check("ERROR:" not in line and "scrub failed" not in line,
              f"scrub reported trouble: {line}")
    check(volumes, f"volume.scrub exited {op['rc']} and scrubbed nothing:\n"
          f"{op['out'][-2000:]}")
    return {**op, "volumes": volumes, "reported": reported}


def install(run) -> None:
    """Warm: one untimed sweep of the first volume loads the program of
    every length bucket (blocks are fixed `[8 MiB / L, L]`)."""
    s = run.samples
    for m in s["group"]:
        ecutil.place(run.cluster, run.stage, m, m.vid)
    sweep(run, s["group"][0].vid)


def run(run) -> dict:
    s = run.samples
    nbytes = sum(m.payload_bytes for m in s["group"])
    needles = sum(len(m.keys) for m in s["group"])
    want_mode = None if run.rehearsal else "device"
    failed = 0
    while True:
        op = sweep(run)
        good = (op["reported"] == s["rotten"]
                and (op["rc"] != 0) == bool(s["rotten"])
                and sorted(op["volumes"]) == [m.vid for m in s["group"]]
                and sum(v["needles"] for v in op["volumes"].values())
                == needles
                and all(want_mode in (None, v["mode"])
                        for v in op["volumes"].values()))
        if not good:
            failed += 1
            print(f"[scrub_sweep] sweep reported {sorted(op['reported'])} in "
                  f"{op['volumes']}, seeded {sorted(s['rotten'])}",
                  file=sys.stderr, flush=True)
        run.op_done({**op, "label": "scrub", "bytes": nbytes,
                     "needles": needles})
        if run.past_end():
            break
    rates = [op["bytes"] / op["wall_s"] / 1e9 for op in run.ops]
    print("[scrub_sweep] sweeps " + " ".join(f"{op['wall_s']:.2f}"
                                             for op in run.ops) + " s",
          file=sys.stderr, flush=True)
    return {"attempted": len(run.ops), "failed": failed,
            "metrics": {"scrub_GBps": stats.median(rates)}}


def verify(run) -> bool:
    return True  # every sweep was held to the seeded set as it ran
