"""Launcher of the chip-owning volume server: runs the program's unchanged
`python -m seaweedfs_tpu ...` in this process, plus one control thread.

Only the process that holds the chip can trace it or read its memory
statistics, and the benchmark's parent never imports jax. So the parent
writes one command per line to this process's stdin and polls for the
reply file:

    trace_start <dir>     jax.profiler.start_trace(<dir>)
    trace_stop            jax.profiler.stop_trace()
    memory                peak bytes in use on the fullest device

Each reply is `<ctl_dir>/<n>.json` for the n-th command, written whole
(rename). The profiler is imported only when a trace is asked for.

    python benchmark/launch_a.py <ctl_dir> volume -port ... -coder auto
"""

from __future__ import annotations

import json
import os
import runpy
import sys
import threading


def _memory() -> dict:
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"memory_peak_bytes": max(peaks) if peaks else 0}


def _handle(line: str) -> dict:
    cmd, _, arg = line.strip().partition(" ")
    if cmd == "memory":
        return _memory()
    import jax.profiler
    if cmd == "trace_start":
        # the Python tracer hooks every call of every thread of a server:
        # off. Device events and the runtime's own host spans stay.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(arg, profiler_options=options)
        return {"ok": True}
    if cmd == "trace_stop":
        jax.profiler.stop_trace()
        return {"ok": True}
    raise ValueError(f"unknown command {cmd!r}")


def _control(ctl_dir: str, commands) -> None:
    for n, line in enumerate(commands):
        try:
            reply = _handle(line)
        except Exception as e:  # noqa: BLE001 — the parent reads the error
            reply = {"error": f"{type(e).__name__}: {e}"}
        tmp = os.path.join(ctl_dir, f"{n}.tmp")
        with open(tmp, "w") as f:
            json.dump(reply, f)
        os.rename(tmp, os.path.join(ctl_dir, f"{n}.json"))


def main() -> None:
    ctl_dir = sys.argv[1]
    # the control pipe is read through a duplicate: the program may do
    # what it likes with sys.stdin
    commands = os.fdopen(os.dup(0), "r")
    threading.Thread(target=_control, args=(ctl_dir, commands),
                     name="bench-control", daemon=True).start()
    sys.argv = ["seaweedfs_tpu", *sys.argv[2:]]
    runpy.run_module("seaweedfs_tpu", run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
