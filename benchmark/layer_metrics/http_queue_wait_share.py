"""Share of A's request time spent queued before a handler ran: the
`queue_wait` stage over all five stages of
`SeaweedFS_volumeServer_stage_seconds`, summed over the window."""
from benchmark.layer_metrics import _shared

NAME = "SeaweedFS_volumeServer_stage_seconds_sum"


def read(run):
    total = _shared.prom_delta(run, NAME)
    if not total:
        return None
    return 100.0 * _shared.prom_delta(run, NAME, stage="queue_wait") / total
