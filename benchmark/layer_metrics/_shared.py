"""What several per-layer readers share. A reader is `read(run)` in a
file named after its metric; it returns None where there is nothing to
read, and the harness then leaves the metric out of the line."""

from __future__ import annotations

import re

from benchmark import roofline, stats


def ops(run, label: str) -> "list[dict]":
    return [op for op in run.ops if op["label"] == label]


def kernel_roofline(run, programs: "tuple[str, ...]",
                    needed: "tuple[float, float] | None") -> "float | None":
    """The kernel's share of its roofline in the traced window: the least
    time the chip could take for the (operations, bytes) the algorithm
    `needed` there (roofline.py: from the bytes sealed, rebuilt or
    verified, not from the shapes dispatched, so padding counts against
    the kernel), over the device time of the kernel's programs in the
    trace."""
    if not run.traced or not needed:
        return None
    seconds = sum(t for name, (_, t) in run.traced["programs"].items()
                  if name.startswith(programs))
    if not seconds:
        return None
    share, _roof = roofline.share(*needed, seconds, run.device["kind"])
    return share


def total(work: "list[tuple[float, float]]") -> "tuple[float, float] | None":
    """Sum of (operations, bytes) pairs; None of none."""
    if not work:
        return None
    return sum(o for o, _ in work), sum(b for _, b in work)


_SAMPLE = re.compile(r"^(\w+)\{([^}]*)\} (\S+)$")


def prom(text: str, name: str, **labels) -> float:
    """Sum of the samples of `name` whose labels include `labels`."""
    total = 0.0
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if not m or m.group(1) != name:
            continue
        got = dict(kv.split("=", 1) for kv in m.group(2).split(",") if kv)
        if all(got.get(k) == f'"{v}"' for k, v in labels.items()):
            total += float(m.group(3))
    return total


def prom_delta(run, name: str, **labels) -> float:
    return prom(run.metrics1, name, **labels) - prom(run.metrics0, name,
                                                     **labels)


def percentile(run, sample: str, q: float) -> "float | None":
    """The q-th percentile of one of the kind's `samples`."""
    xs = [float(x) for x in run.samples.get(sample, [])]
    return stats.percentile(xs, q) if xs else None
