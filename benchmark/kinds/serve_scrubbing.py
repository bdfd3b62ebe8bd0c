"""Traffic kind `serve_scrubbing`: the small-file server under its bit-rot
cron. The open-loop load of `open_loop_serve` on A, while one thread
repeats `volume.scrub -device on` over all of A's volumes, back to back,
for the whole window, as the admin cron's `volume.scrub -timeBudget 120`
does on a server whose budget outlasts the window.

Set-up is `open_loop_serve`'s, then `corrupt_per_volume` needles of each
file volume are rotted on disk, as `scrub_sweep` rots them, and taken out
of the files the GETs read. The window, its samples and its metrics are
`open_loop_serve.run`'s; every sweep is kept as an operation labelled
`scrub` with its `needles`, which the scrub readers read, and the
window's start on their clock (`window_wall`). `correct` holds
the run to the guarantees the traffic file states: every GET returns the
stored bytes, every acknowledged PUT is read back, and every sweep
reported exactly the seeded rot, on the device, having scanned at least
the needles loaded on each volume (the window's PUTs add more)."""

from __future__ import annotations

import sys
import threading
import time

from benchmark import data, ecutil
from benchmark.cluster import check
from benchmark.kinds import open_loop_serve, scrub_sweep


def generate(run) -> None:
    open_loop_serve.generate(run)
    s = run.samples
    s["rotten"], rotten_fids = set(), set()
    for m in s["group"]:
        picks = [int(i) for i in run.rng.choice(
            len(m.keys), int(run.traffic["corrupt_per_volume"]),
            replace=False) if m.sizes[int(i)] >= 64]
        stem = ecutil.base(run.stage, m.collection, m.vid)
        data.corrupt(stem + ".dat", stem + ".idx", m, picks)
        s["rotten"].update((m.vid, int(m.keys[i])) for i in picks)
        rotten_fids.update(m.fid(i).encode() for i in picks)
    s["files"] = [f for f in s["files"] if f[0] not in rotten_fids]
    s["loaded"] = {m.vid: len(m.keys) for m in s["group"]}


def install(run) -> None:
    """`open_loop_serve.install`: the warm-up requests, and one sweep,
    which loads the 1 KB bucket's one CRC program."""
    open_loop_serve.install(run)


def _scrub_loop(run, stop: threading.Event, errors: list) -> None:
    try:
        while not stop.is_set():
            op = scrub_sweep.sweep(run)
            run.op_done({**op, "label": "scrub", "needles": sum(
                v["needles"] for v in op["volumes"].values())})
    except Exception as e:  # noqa: BLE001 — the main thread reports it
        errors.append(f"{type(e).__name__}: {e}")


def run(run) -> dict:
    """`open_loop_serve.run` with this kind's scrub loop behind it (the
    serve kind's own loop counts any report as a failure)."""
    stop, errors = threading.Event(), []
    # the window's start on the sweeps' clock, for scrub_window_needles_per_s
    run.samples["window_wall"] = time.time() - (time.monotonic()
                                                - run.window[0])
    bg = threading.Thread(target=_scrub_loop, args=(run, stop, errors),
                          name="bg-scrub")
    traffic = run.traffic
    run.traffic = {**traffic, "background_scrub": False}
    bg.start()
    try:
        result = open_loop_serve.run(run)
    finally:
        run.traffic = traffic
        stop.set()
        bg.join(timeout=300)
    check(not bg.is_alive(), "the scrub loop did not end")
    check(not errors, "; ".join(errors))
    sweeps = [op for op in run.ops if op["label"] == "scrub"]
    print(f"[serve_scrubbing] {len(sweeps)} sweeps: " + " ".join(
        f"{op['wall_s']:.2f}" for op in sweeps) + " s",
        file=sys.stderr, flush=True)
    return result


def verify(run) -> bool:
    """`open_loop_serve.verify`, and every sweep of the window held to
    the seeded rot and the needles loaded."""
    if not open_loop_serve.verify(run):
        return False
    s = run.samples
    want_mode = None if run.rehearsal else "device"
    sweeps = [op for op in run.ops if op["label"] == "scrub"]
    for op in sweeps:
        vols = op["volumes"]
        if not (op["reported"] == s["rotten"]
                and all(vid in vols and vols[vid]["needles"] >= n
                        for vid, n in s["loaded"].items())
                and all(want_mode in (None, v["mode"])
                        for v in vols.values())):
            print(f"[serve_scrubbing] a sweep reported "
                  f"{sorted(op['reported'])} in {vols}, seeded "
                  f"{sorted(s['rotten'])} over {s['loaded']}",
                  file=sys.stderr, flush=True)
            return False
    return bool(sweeps)
