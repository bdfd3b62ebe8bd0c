"""Mean time of a GET's storage read itself on A over the window, inside
the read-pool thread (`SeaweedFS_volumeServer_store_read_seconds`)."""
from benchmark.layer_metrics import _shared

NAME = "SeaweedFS_volumeServer_store_read_seconds"


def read(run):
    count = _shared.prom_delta(run, NAME + "_count", type="get")
    if not count:
        return None
    return 1e6 * _shared.prom_delta(run, NAME + "_sum", type="get") / count
