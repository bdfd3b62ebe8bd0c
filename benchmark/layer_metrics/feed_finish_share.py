"""Share of the encode pipeline's wall sealing each volume's outputs
(`_VolumePlan.finish`: fsync of the n shard files, `.ecx`, `.vif`;
`finish_s` of `ec.encode.finish`, the sum of the `swtpu/ec.finish`
stages)."""
from benchmark.layer_metrics import feed_dispatch_share


def read(run):
    return feed_dispatch_share.read(run, "finish_s")
