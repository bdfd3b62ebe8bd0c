"""`.dat` bytes over the encode pipeline's own wall (`ec.encode.finish`
`wall_s`): the seal without the verb around it and the spread after it."""
from benchmark import stats
from benchmark.layer_metrics import _shared


def read(run):
    by_vids = {tuple(sorted(op["vids"])): op["bytes"]
               for op in _shared.ops(run, "seal")}
    rates = [by_vids[tuple(sorted(e["vids"]))] / e["wall_s"] / 1e9
             for e in run.events("ec.encode.finish")
             if tuple(sorted(e.get("vids", []))) in by_vids and e.get("wall_s")]
    return stats.median(rates) if rates else None
