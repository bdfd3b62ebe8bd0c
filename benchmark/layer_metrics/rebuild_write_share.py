"""Share of the rebuild RPCs' duration storing the rebuilt rows into the
shard files' mappings and flushing them (`write_s` of
`ec.rebuild.finish`)."""
from benchmark.layer_metrics import rebuild_read_share


def read(run):
    return rebuild_read_share.read(run, "write_s")
