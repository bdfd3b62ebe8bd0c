"""Share of the encode pipeline's wall blocked on the shard writers."""
from benchmark.layer_metrics import feed_fill_share


def read(run):
    return feed_fill_share.read(run, "write_block_s")
