"""Share of the rebuild RPCs' duration blocked fetching a batch's rebuilt
rows (the device's work not yet done, then device-to-host: `drain_s` of
`ec.rebuild.finish`)."""
from benchmark.layer_metrics import rebuild_read_share


def read(run):
    return rebuild_read_share.read(run, "drain_s")
