"""The GETs' latency from the due time, 95th percentile over the window
(read per layer where another percentile is the cell's end-to-end
metric)."""
from benchmark.layer_metrics import _shared


def read(run):
    return _shared.percentile(run, "get_ms", 95)
