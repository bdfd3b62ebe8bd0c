"""Reduction of a JAX profiler trace (`*.xplane.pb`) to what the metrics
read: the device's busy time, time per program, the operations that took
most, and the longest idle gaps.

    python -m benchmark.xplane <trace dir or .xplane.pb>   # prints JSON

Runs in a process of its own, told to stay on the CPU: the benchmark's
parent never imports jax, and `ProfileData` lives in jaxlib.

A device plane is one whose name starts with `/device:TPU:`. On it the
line `XLA Ops` holds one event per operation run and `XLA Modules` one
per program run (`jit_<function>(<fingerprint>)`). Busy time is the union
of the operations' intervals — the gaps inside a program count as idle —
averaged over the device planes that ran anything.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union(intervals: "list[tuple[int, int]]") -> "list[tuple[int, int]]":
    """Merged, sorted [start, end) intervals."""
    out: "list[list[int]]" = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: "list[tuple[int, int]]", t0: int, t1: int,
         ) -> "list[tuple[int, int]]":
    """The idle intervals of [t0, t1) left by merged `busy`."""
    out, at = [], t0
    for s, e in busy:
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return [(s, e) for s, e in out if e > s]


def program_name(event_name: str) -> str:
    """`jit_encode_jit(123456)` -> `jit_encode_jit`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """An operation's event is named by its whole HLO line, `%copy.1 =
    u8[...] copy(...)`: keep `copy.1`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes: "list[dict]") -> dict:
    """`planes`: [{name, lines: {line name: [(name, start_ns, dur_ns)]}}].
    Times in the result are seconds; `t0_ns` is the first event of the
    whole trace, on any plane, and gap starts count from it."""
    starts = [s for p in planes for evs in p["lines"].values()
              for _, s, _ in evs]
    ends = [s + d for p in planes for evs in p["lines"].values()
            for _, s, d in evs]
    if not starts:
        return {"devices": 0}
    t0, t1 = min(starts), max(ends)
    busy_ns, programs, ops, idle = [], {}, {}, []
    for p in planes:
        if not p["name"].startswith(DEVICE_PLANE):
            continue
        evs = p["lines"].get(OPS_LINE) or p["lines"].get(MODULES_LINE) or []
        if not evs:
            continue
        merged = union([(s, s + d) for _, s, d in evs])
        busy_ns.append(sum(e - s for s, e in merged))
        idle.extend(gaps(merged, t0, t1))
        for name, _, d in p["lines"].get(MODULES_LINE, []):
            agg = programs.setdefault(program_name(name), [0, 0])
            agg[0] += 1
            agg[1] += d
        for name, _, d in p["lines"].get(OPS_LINE, []):
            name = op_name(name)
            ops[name] = ops.get(name, 0) + d
    if not busy_ns:
        return {"devices": 0}
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "devices": len(busy_ns),
        "t0_ns": t0,
        "span_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "programs": {k: [n, ns / 1e9] for k, (n, ns) in programs.items()},
        "device_ops": [[k, ns / 1e9] for k, ns in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "gaps": [[(s - t0) / 1e9, (e - s) / 1e9] for s, e in idle[:TOP]],
    }


def read_planes(path: str) -> "list[dict]":
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = {}
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                lines.setdefault(line.name, []).extend(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
            elif not device:
                # of the host's threads only the extent is kept: a
                # server's trace holds millions of their events
                spans = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                         for ev in line.events]
                if spans:
                    s, e = min(s for s, _ in spans), max(e for _, e in spans)
                    lines[line.name] = [("extent", s, e - s)]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def label_gaps(found: "list[list[float]]", phases: "list[list]",
               ) -> "list[list]":
    """Name each idle gap [start_s, seconds] by what the harness was doing
    for most of it; `phases` are [start_s, end_s, label] on the same
    clock. Gaps with one label are added up; longest first."""
    total: "dict[str, float]" = {}
    for start, seconds in found:
        best, best_overlap = "unlabelled", 0.0
        for p0, p1, label in phases:
            overlap = min(start + seconds, p1) - max(start, p0)
            if overlap > best_overlap:
                best, best_overlap = label, overlap
        total[best] = total.get(best, 0.0) + seconds
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    print(json.dumps(reduce_planes(read_planes(sys.argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
