"""Probes of A's HTTP event loop that fired more than 50 ms late inside
the window (`SeaweedFS_event_loop_lag_seconds{loop="volume"}`, one probe
every 0.25 s): whether A's own loop stalls when the window's p99 does."""
from benchmark.layer_metrics import _shared

NAME = "SeaweedFS_event_loop_lag_seconds"


def read(run):
    probes = _shared.prom_delta(run, NAME + "_count", loop="volume")
    if not probes:
        return None
    return probes - _shared.prom_delta(run, NAME + "_bucket", loop="volume",
                                       le="0.05")
