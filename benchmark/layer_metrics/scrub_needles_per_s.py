"""Needles over the scrub's own elapsed time on A (`VolumeScrub`
results), median over the window's sweeps."""
from benchmark import stats
from benchmark.layer_metrics import _shared


def read(run, label="scrub"):
    rates = [sum(v["needles"] for v in op["volumes"].values())
             / sum(v["elapsed_s"] for v in op["volumes"].values())
             for op in _shared.ops(run, label)
             if sum(v["elapsed_s"] for v in op["volumes"].values())]
    return stats.median(rates) if rates else None
