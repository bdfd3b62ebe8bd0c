"""Percent of the bytes of the shards a seal places off A that A streamed
to their holders as it wrote them (`ec/push.py`), so that no copy after
the generate had to move them: 100 x the sum of `push_bytes` over the sum
of `remote_bytes` of the window's `ec.encode.finish` events. A program
that books neither field gives None."""
from benchmark.layer_metrics import _shared


def read(run):
    seals = {tuple(sorted(op["vids"])) for op in _shared.ops(run, "seal")}
    found = [e for e in run.events("ec.encode.finish")
             if tuple(sorted(e.get("vids", []))) in seals
             and e.get("remote_bytes")]
    if not found:
        return None
    return (100.0 * sum(e.get("push_bytes", 0) for e in found)
            / sum(e["remote_bytes"] for e in found))
