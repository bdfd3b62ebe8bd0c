"""Measures where a degraded interval's decode is cheaper on the host than
on the chip: for each power-of-two length L, the microseconds of one
`[14, L] -> [1, L]` decode (one lost data shard of RS(14,2)) on the host
coder a shard holder without a chip runs (`native`, else `numpy`), and on
the device coder through `apply_matrix` (the matrix an operand; host rows
in, host bytes out, as the volume server calls it). Every shape is run
before it is timed, so no compile is timed; the compile's seconds are
printed beside it.

    python benchmark/testdata/decode_crossover.py    # on a TPU host

prints one JSON line a length. On a TPU v5e the host won at every
length (PERF.md §5), which is why `seaweedfs_tpu/ec/degraded.py` decodes
a degraded interval on the host coder of a server whose coder runs the
Pallas kernel; a length at which the device wins would be the reason to
bring a device path back.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)

D, P, LOST = 14, 2, 3
REPS = 40


def _us(fn, reps: int) -> "list[float]":
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1e6)
    return out


def main() -> int:
    import numpy as np

    from seaweedfs_tpu.ops import device, gf8
    from seaweedfs_tpu.ops.coder import get_coder

    host = get_coder(device._host_coder(), D, P)
    dev = get_coder("jax", D, P)
    present = [s for s in range(D + P) if s != LOST][:D]
    mat = gf8.decode_matrix(D, P, present)[[LOST], :]
    rng = np.random.default_rng(45)
    for k in range(10, 23):
        length = 1 << k
        x = rng.integers(0, 256, (D, length), dtype=np.uint8)
        t0 = time.perf_counter()
        got = np.asarray(dev.apply_matrix(mat, x))
        compile_s = time.perf_counter() - t0
        assert np.array_equal(got, np.asarray(host.apply_matrix(mat, x)))
        host_us, dev_us = [], []
        for _ in range(REPS):  # alternate: both see the same host state
            host_us += _us(lambda: host.apply_matrix(mat, x), 1)
            dev_us += _us(lambda: np.asarray(dev.apply_matrix(mat, x)), 1)
        row = {"L": length, "host": device._host_coder(),
               "host_us_p50": statistics.median(host_us),
               "host_us_p90": statistics.quantiles(host_us, n=10)[-1],
               "device_us_p50": statistics.median(dev_us),
               "device_us_p90": statistics.quantiles(dev_us, n=10)[-1],
               "first_call_s": compile_s,
               "platform": device.info().platform,
               "device_kind": device.info().device_kind}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
