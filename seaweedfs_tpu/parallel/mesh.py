"""Device mesh construction for the EC compute plane.

Mesh axes:
* ``data``  — stripe-batch data parallelism: different volumes/rows on
  different chips (the analogue of the reference spreading ec.encode jobs
  across volume servers, command_ec_encode.go:113-126).
* ``shard`` — shard parallelism: the n=d+p output shards are partitioned
  across chips, mirroring how shards live on distinct servers
  (balancedEcDistribution, command_ec_encode.go:333). Rebuild all_gathers
  survivors along this axis over ICI — the device-side analogue of the
  cross-host shard fetch in store_ec.go:367-400.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from ..ops import device


def build_mesh(n_devices: int | None = None, shard_axis: int | None = None,
               devices=None) -> Mesh:
    """2-D ('data', 'shard') mesh over the first n devices.

    shard_axis defaults to min(n, 4) rounded down to a divisor of n, so a
    single chip yields a 1x1 mesh and 8 virtual devices a 2x4 mesh.
    """
    if devices is None:
        device.require("a device mesh")
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise RuntimeError(
                f"requested {n_devices} devices, only {len(devices)} available "
                f"(for virtual CPU devices, XLA_FLAGS="
                f"--xla_force_host_platform_device_count must be set at "
                f"process start)")
        devices = devices[:n_devices]
    n = len(devices)
    if shard_axis is None:
        shard_axis = 1
        for cand in (4, 2):
            if n % cand == 0 and cand <= n:
                shard_axis = cand
                break
    if n % shard_axis:
        raise ValueError(f"shard axis {shard_axis} does not divide {n} devices")
    arr = np.asarray(devices).reshape(n // shard_axis, shard_axis)
    return Mesh(arr, ("data", "shard"))
