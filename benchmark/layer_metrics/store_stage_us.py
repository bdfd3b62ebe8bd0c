"""Mean time of a GET inside the store handler on A over the window."""
from benchmark.layer_metrics import _shared

NAME = "SeaweedFS_volumeServer_stage_seconds"


def read(run):
    count = _shared.prom_delta(run, NAME + "_count", type="get", stage="store")
    if not count:
        return None
    return 1e6 * _shared.prom_delta(run, NAME + "_sum", type="get",
                                    stage="store") / count
