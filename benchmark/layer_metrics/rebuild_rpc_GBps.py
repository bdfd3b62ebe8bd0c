"""Survivor bytes read over the rebuild RPC's own duration, median over
the window's `ec.rebuild.finish` events."""
from benchmark import stats


def read(run):
    rates = [e["bytes_read"] / (e["duration_ms"] / 1e3) / 1e9
             for e in run.events("ec.rebuild.finish") if e.get("duration_ms")]
    return stats.median(rates) if rates else None
