"""Share of the device's idle time in the trace that lies under any of
A's `swtpu/...` stages: what part of the idle chip the program's own
stages put a name to. The split of all idle time by innermost stage goes
to stderr, for PERF.md. One entry per cell, `<cell>_idle_explained_share`."""
import sys

from benchmark import host_spans


def read(run):
    reduced = host_spans.of(run)
    if not reduced or not reduced["spans"]:
        return None
    by_stage = host_spans.innermost(reduced["spans"],
                                    host_spans.idle(reduced))
    total = sum(by_stage.values())
    if not total:
        return None
    print("[benchmark] idle by stage: " + " ".join(
        f"{(name[len(host_spans.PREFIX):] or 'no-stage')} "
        f"{100.0 * ns / total:.1f}%"
        for name, ns in sorted(by_stage.items(), key=lambda kv: -kv[1])),
        file=sys.stderr, flush=True)
    return 100.0 * (1.0 - by_stage.get("", 0) / total)
