"""Percent of the seal verbs' wall inside `VolumeEcShardsCopy`: B pulling
its half of every volume's shards from A, from the verbs' `timing`
lines."""
from benchmark.layer_metrics import _timing


def read(run):
    return _timing.method_share(run, "VolumeEcShardsCopy")
