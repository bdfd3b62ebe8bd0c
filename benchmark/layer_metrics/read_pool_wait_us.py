"""Mean wait of a storage read for a thread of A's read pool over the
window: submit to worker pickup
(`SeaweedFS_pool_queue_wait_seconds{pool="read"}`)."""
from benchmark.layer_metrics import _shared

NAME = "SeaweedFS_pool_queue_wait_seconds"


def read(run):
    count = _shared.prom_delta(run, NAME + "_count", pool="read")
    if not count:
        return None
    return 1e6 * _shared.prom_delta(run, NAME + "_sum", pool="read") / count
