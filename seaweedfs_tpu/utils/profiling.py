"""Profiling triggers (reference: pprof on -debug.port via net/http/pprof,
command/imports.go:4 + grace.SetupProfiling; SURVEY §5 maps this to a
jax.profiler server for the device plane).

Two HTTP-triggered modes, wired into each daemon's status server:

* `/debug/profile?seconds=N` — sample every thread's stack for N seconds
  and return hottest lines/stacks (pprof's /debug/pprof/profile analogue).
* `/debug/jax-profiler?port=P` — start jax.profiler.start_server(P) so
  TensorBoard/xprof can connect and capture device traces.
"""

from __future__ import annotations

import io
import threading
import time

_lock = threading.Lock()
_jax_server = None


def cpu_profile(seconds: float = 5.0, top: int = 60,
                interval: float = 0.005) -> str:
    """Statistical whole-process profile: sample every thread's stack via
    sys._current_frames() for `seconds`, aggregate by frame. cProfile only
    traces the calling thread, which here would just be sleeping — sampling
    sees ALL threads, like pprof's CPU profile."""
    import sys
    from collections import Counter

    seconds = min(max(seconds, 0.1), 120.0)
    if not _lock.acquire(blocking=False):
        return "another profile is already running\n"
    try:
        me = threading.get_ident()
        leaf: Counter = Counter()
        stacks: Counter = Counter()
        samples = 0
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                samples += 1
                code = frame.f_code
                leaf[f"{code.co_filename}:{frame.f_lineno} "
                     f"({code.co_name})"] += 1
                parts = []
                f = frame
                depth = 0
                while f is not None and depth < 12:
                    parts.append(f.f_code.co_name)
                    f = f.f_back
                    depth += 1
                stacks[" <- ".join(parts)] += 1
            time.sleep(interval)
        out = io.StringIO()
        out.write(f"# sampled {samples} thread-frames over {seconds}s "
                  f"(interval {interval * 1e3:.0f} ms); cumulative view\n\n")
        out.write("== hottest lines ==\n")
        for line, n in leaf.most_common(top):
            out.write(f"{n / max(samples, 1):6.1%}  {line}\n")
        out.write("\n== hottest stacks ==\n")
        for stack, n in stacks.most_common(top // 3):
            out.write(f"{n / max(samples, 1):6.1%}  {stack}\n")
        return out.getvalue()
    finally:
        _lock.release()


def start_jax_profiler(port: int = 9999) -> str:
    """Start (once) the jax.profiler gRPC server for device traces."""
    global _jax_server
    from ..ops import device
    if device.current() is None:  # a host-coder process stays off jax
        return "no JAX backend in this process (-coder numpy|native)\n"
    with _lock:
        if _jax_server is not None:
            return f"jax profiler already running on :{_jax_server}\n"
        import jax

        jax.profiler.start_server(port)
        _jax_server = port
        return f"jax profiler listening on :{port} (connect xprof/tensorboard)\n"
