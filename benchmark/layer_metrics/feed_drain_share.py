"""Share of the encode pipeline's wall blocked fetching a batch's parity
(`np.asarray(fut)`: the device's work not yet done, then device-to-host;
`drain_block_s` of `ec.encode.finish`, the sum of the `swtpu/ec.drain`
stages)."""
from benchmark.layer_metrics import feed_dispatch_share


def read(run):
    return feed_dispatch_share.read(run, "drain_block_s")
