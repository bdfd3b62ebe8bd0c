"""How late the load generator itself ran: send time minus due time. A
starved generator must not read as a fast server."""
from benchmark.layer_metrics import _shared


def read(run):
    return _shared.percentile(run, "late_ms", 99)
