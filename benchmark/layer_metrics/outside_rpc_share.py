"""Percent of the verbs' wall outside every client RPC of every command
of the script (`lock; <verb>; unlock`): interpreter start, imports,
planning, settle sleeps, exit. 1 - sum of the `timing` lines' `rpc=` over
the verbs' wall. One entry per cell, `<cell>_outside_rpc_share`."""
from benchmark.layer_metrics import _timing


def read(run):
    ops = _timing.verbs(run)
    found = [ln for op in ops for ln in _timing.lines(op["out"])]
    if not found:
        return None
    return 100.0 * (1.0 - sum(ln["rpc"] for ln in found)
                    / sum(op["wall_s"] for op in ops))
