"""The decode of one degraded interval: a lost shard's bytes over one
needle's range, from the same range of d survivors.

A GET that lands on a lost shard reconstructs only the interval it reads
(reference store_ec.go:357 recoverOneRemoteEcShardInterval), so every
degraded GET brings an interval of its own length. On a coder that runs
the Pallas kernel on its device, that decode (`reconstruct_jit`,
`[1, d, L]`, the matrix baked into the program) built a program on the
request path for nearly every GET; and the chip, timed against the host
coder at each length an interval can have (1 KiB to 4 MiB on a TPU v5e,
PERF.md §5), lost at every one. So on such a coder the interval decodes
on the host coder a shard holder without a chip runs (`native`, else
`numpy`), through decode rows cached per loss pattern. Any other coder
(the host coders, the jax coder told to compute on the CPU, the mesh)
keeps its own `reconstruct`.
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops import device, gf8
from ..ops.coder import ErasureCoder, JaxCoder, get_coder


def on_host(coder: ErasureCoder) -> bool:
    """True for a coder whose degraded decode moves to the host coder:
    one that runs the Pallas kernel on its device."""
    return isinstance(coder, JaxCoder) and coder.use_pallas


def where(coder: ErasureCoder) -> str:
    """"device" or "host": where `decode_interval` decodes on `coder`."""
    return ("device" if coder.async_dispatch and not on_host(coder)
            else "host")


@functools.lru_cache(maxsize=1024)
def _decode_rows(d: int, p: int, present: "tuple[int, ...]",
                 wanted: "tuple[int, ...]") -> np.ndarray:
    return gf8.decode_matrix(d, p, list(present))[list(wanted), :]


@functools.lru_cache(maxsize=16)
def _host_coder(d: int, p: int) -> ErasureCoder:
    return get_coder(device._host_coder(), d, p)


def decode_interval(coder: ErasureCoder, survivors: "list[bytes]",
                    present: "tuple[int, ...]",
                    wanted: "tuple[int, ...]") -> np.ndarray:
    """Rows `wanted` [|wanted|, L] from `survivors`, the same L bytes of
    each shard of `present` (sorted, d of them)."""
    rows = np.stack([np.frombuffer(b, dtype=np.uint8) for b in survivors])
    if not on_host(coder):
        return np.asarray(coder.reconstruct(rows, present, wanted))
    d, p = coder.d, coder.p
    return np.asarray(_host_coder(d, p).apply_matrix(
        _decode_rows(d, p, present, wanted), rows))
