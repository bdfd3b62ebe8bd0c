"""The program's own stages in a JAX profiler trace, beside the device.

    python -m benchmark.host_spans <trace dir or .xplane.pb>   # prints JSON

The chip-owning server opens a `jax.profiler.TraceAnnotation` named
`swtpu/<op>.<stage>` around each stage of a seal, a rebuild and a scrub
(`seaweedfs_tpu/tracing/stages.py`). This reducer keeps, on the trace's
one clock (nanoseconds from the start of the profiling session):

* `spans`: the host planes' events whose name starts with `swtpu/`, as
  `[name, start_ns, duration_ns, {stat: value}]`, by start;
* `programs`: the device planes' `XLA Modules` events, one per program
  run, as `[program name, start_ns, duration_ns]`, by start;
* `busy`: the merged `[start_ns, end_ns)` intervals in which an
  operation ran on the device (`XLA Ops`, else `XLA Modules`), of the
  first device plane that ran anything;
* `end_ns`: the end of the last event of any plane.

Like `benchmark.xplane`, it runs in a process of its own that is told to
stay on the CPU. It runs once a run: `of(run)` keeps the result on the
run, and the readers that need it share it. A trace of a program without
annotations (an earlier commit) gives an empty `spans`, and the readers
then return None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark.xplane import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                              find_xplane, gaps, program_name, union)

PREFIX = "swtpu/"


def _number(value):
    """A stat as JSON holds it: numbers stay numbers."""
    if isinstance(value, (int, float)):
        return int(value) if float(value).is_integer() else float(value)
    return str(value)


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    spans, programs, busy, end_ns = [], [], None, 0
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        ops, modules = [], []
        for line in plane.lines:
            for ev in line.events:
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                end_ns = max(end_ns, start + dur)
                if not device:
                    if ev.name.startswith(PREFIX):
                        spans.append([ev.name, start, dur,
                                      {k: _number(v) for k, v in ev.stats}])
                elif line.name == OPS_LINE:
                    ops.append((start, start + dur))
                elif line.name == MODULES_LINE:
                    modules.append((start, start + dur))
                    programs.append([program_name(ev.name), start, dur])
        if device and busy is None and (ops or modules):
            busy = union(ops or modules)
    spans.sort(key=lambda s: (s[1], -s[2]))
    programs.sort(key=lambda p: p[1])
    return {"spans": spans, "programs": programs,
            "busy": [list(b) for b in busy or []], "end_ns": end_ns}


def of(run) -> "dict | None":
    """The reduction of this run's trace, made once and kept on the run;
    None where the run has no reduced trace (untraced, a rehearsal)."""
    if not getattr(run, "traced", None) or not run.trace_dir:
        return None
    if not hasattr(run, "host_spans"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, "-m", "benchmark.host_spans", run.trace_dir],
            cwd=checkout, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300)
        if r.returncode != 0:
            raise RuntimeError(f"host span reduction failed:\n"
                               f"{r.stderr[-2000:]}")
        run.host_spans = json.loads(r.stdout.splitlines()[-1])
    return run.host_spans


def idle(reduced: dict) -> "list[tuple[int, int]]":
    """The device's idle intervals from the session's start to the last
    event of the trace."""
    return gaps([tuple(b) for b in reduced["busy"]], 0, reduced["end_ns"])


def innermost(spans: "list[list]", intervals: "list[tuple[int, int]]",
              ) -> "dict[str, int]":
    """Nanoseconds of `intervals` (disjoint, sorted) under each span name,
    every instant counted once: where spans nest or overlap, for the one
    that began last. The key "" holds the time under no span."""
    out: "dict[str, int]" = {}
    edges = sorted({t for _, s, d, _ in spans for t in (s, s + d)})
    for lo, hi in intervals:
        cuts = [lo] + [t for t in edges if lo < t < hi] + [hi]
        for a, b in zip(cuts, cuts[1:]):
            name, began = "", -1
            for n, s, d, _ in spans:
                if s > a:
                    break  # by start: no later span covers [a, b)
                if s + d >= b and s > began:
                    name, began = n, s
            out[name] = out.get(name, 0) + (b - a)
    return out


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    print(json.dumps(reduce_file(sys.argv[1])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
