"""Survivor bytes A read for its codec repairs over the bytes it wrote
back, over the window: Δ `SeaweedFS_repair_bytes_read_total{codec}` ÷ Δ
`SeaweedFS_repair_bytes_written_total{codec}` of A's `/metrics`, for the
configuration's `codec`. (d + |S_g|) / 2 for a Hitchhiker single
data-shard repair, 6.5 or 7.0 at RS(10,4); d = 10 for plain RS. None
where nothing was written."""
from benchmark.layer_metrics import _shared


def read(run):
    codec = run.config.get("codec", "rs")
    wrote = _shared.prom_delta(run, "SeaweedFS_repair_bytes_written_total",
                               codec=codec)
    if not wrote:
        return None
    return _shared.prom_delta(run, "SeaweedFS_repair_bytes_read_total",
                              codec=codec) / wrote
