"""Needles the window's sweeps verified per second of the window: each
sweep's needles times the share of its span that lies inside the window,
summed, over the window's length. Unlike `scrub_needles_per_s` (over the
sweeps' own elapsed time) it falls where sweeps pause between volumes or
between sweeps. None where the kind kept no `window_wall` (the window's
start on time.time(), the clock of a sweep's `t0` and `t1`)."""
from benchmark.layer_metrics import _shared


def read(run, label="scrub"):
    w0 = run.samples.get("window_wall")
    if w0 is None:
        return None
    w1 = w0 + run.seconds
    total = 0.0
    for op in _shared.ops(run, label):
        span = op["t1"] - op["t0"]
        inside = min(op["t1"], w1) - max(op["t0"], w0)
        if span > 0 and inside > 0:
            total += op["needles"] * inside / span
    return total / run.seconds
