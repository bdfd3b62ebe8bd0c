"""The CRC32C scan (an unnamed lambda in `storage/scrub.py`:
`jit__lambda`). Needed: the needle bytes verified inside the trace, each
read once in 512-byte steps. A sweep floods the profiler, so the trace is
a slice of one sweep, and the program has no span per block: the slice's
bytes are the sweep's, by the slice's share of the scrub's own elapsed
time (progress taken as even). Padding of the `[8 MiB / L, L]` blocks is
the kernel's cost, not its work."""
from benchmark import roofline
from benchmark.layer_metrics import _shared


def read(run):
    if not run.trace_window:
        return None
    t0, t1 = run.trace_window
    nbytes = 0.0
    for op in _shared.ops(run, "scrub"):
        inside = min(t1, op["t1"]) - max(t0, op["t0"])
        elapsed = sum(v["elapsed_s"] for v in op["volumes"].values())
        if inside > 0 and elapsed:
            nbytes += op["bytes"] * inside / elapsed
    if not nbytes:
        return None
    return _shared.kernel_roofline(run, ("jit__lambda",),
                                   roofline.crc_ops_bytes(1, nbytes))
