"""Metric arithmetic, kept with the benchmark so that no later PR can move
it: medians, percentiles, and open-loop latency from the due time."""

from __future__ import annotations

import math


def median(xs: "list[float]") -> float:
    s = sorted(xs)
    n = len(s)
    if not n:
        raise ValueError("median of nothing")
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2


def percentile(xs: "list[float]", q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it."""
    s = sorted(xs)
    if not s:
        raise ValueError("percentile of nothing")
    return s[max(0, math.ceil(q / 100 * len(s)) - 1)]


def due_latencies_ms(due_s: "list[float]", done_s: "list[float]",
                     ok: "list[bool]") -> "list[float]":
    """Latency of each request from the time it was DUE (open loop: a
    stall charges every request queued behind it) to its last byte. A
    failed or wrong-bytes request counts as over any limit: infinity."""
    return [(t1 - t0) * 1e3 if good else math.inf
            for t0, t1, good in zip(due_s, done_s, ok)]


def sliced_percentile(at_s: "list[float]", xs: "list[float]", q: float,
                      slice_s: float = 1.0) -> float:
    """The median over the window's slices of `slice_s` seconds of each
    slice's q-th percentile: the tail of a typical second. `at_s` places
    each sample in the window. A few stalls that set the window's own
    percentile move a few slices and leave this where it was."""
    slices: "dict[int, list[float]]" = {}
    for t, x in zip(at_s, xs):
        slices.setdefault(int(t // slice_s), []).append(x)
    return median([percentile(v, q) for v in slices.values()])
