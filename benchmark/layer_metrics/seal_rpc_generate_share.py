"""Percent of the seal verbs' wall inside `VolumeEcShardsGenerateBatch`,
the one RPC that holds the encode pipeline (plus `v.sync()`, opening the
plans and the hop), from the verbs' `timing` lines."""
from benchmark.layer_metrics import _timing


def read(run):
    return _timing.method_share(run, "VolumeEcShardsGenerateBatch")
