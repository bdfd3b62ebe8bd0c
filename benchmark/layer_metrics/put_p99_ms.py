"""As `get_p99_ms`, over the window's assign -> PUT requests."""
from benchmark.layer_metrics import _shared


def read(run):
    return _shared.percentile(run, "put_ms", 99)
