"""The device gate: the one place a process learns what it computes on.

Every device user asks here — rs_pallas.available(), JaxCoder /
PallasCoder / MeshCoder, Store's coder resolution, the scrub CRC kernel,
and parallel.mesh.build_mesh — so "is there a TPU" has one answer per
process, resolved once, and a missing chip is an error at
the caller instead of a quiet host fallback.

Rules:

* A process runs on what it was told. ``JAX_PLATFORMS=cpu`` (tests, the
  sandbox) is honoured: device coders take the XLA einsum path and
  PallasCoder interprets. In every other case a device coder needs
  ``platform == "tpu"``; a backend that does not come up, or comes up as
  anything else, raises to the caller. On a TPU nothing interprets.
* ``-coder auto`` resolves once, when the Store is built: TPU -> ``jax``,
  no TPU -> ``native`` if it loads, else ``numpy``. A process told
  ``-coder numpy|native`` never imports jax.
* One process owns a chip. `status()` is what ``GET /status`` carries, so
  a parent that stays off JAX can see what its child runs on.
* The persistent compile cache is configured here and nowhere else:
  ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself); otherwise
  ``<checkout>/.jax_cache``, a path that never moves — the path is part
  of the cache key.
"""

from __future__ import annotations

import os
import sys
import threading
from dataclasses import dataclass

from ..utils.log import logger

log = logger("device")

#: coder names that compute through JAX
DEVICE_CODERS = ("jax", "pallas", "mesh")

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class DeviceError(RuntimeError):
    """A device path was asked for and the process has no TPU."""


@dataclass(frozen=True)
class DeviceInfo:
    platform: str
    device_kind: str
    count: int


_lock = threading.Lock()
_info: "DeviceInfo | None" = None


def told_cpu() -> bool:
    """The operator pinned this process to the CPU backend."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def compile_cache_dir() -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def _probe() -> DeviceInfo:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
        # the daemons' kernels compile in 0.2-2 s on a v5e: under the
        # default 1 s floor half of them would never be cached
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()  # raises when the told platform cannot be opened
    return DeviceInfo(devs[0].platform, devs[0].device_kind, len(devs))


def info() -> DeviceInfo:
    """Bring the JAX backend up (once per process) and say what it is."""
    global _info
    with _lock:
        if _info is None:
            _info = _probe()
            log.info("jax backend: platform=%s kind=%s devices=%d "
                     "(compile cache %s)", _info.platform,
                     _info.device_kind, _info.count, compile_cache_dir())
        return _info


def current() -> "DeviceInfo | None":
    """What info() resolved, or None if this process never asked — the
    question a host-coder process may ask without importing jax."""
    return _info


def require(what: str) -> DeviceInfo:
    """Gate for device coders: a TPU, or the CPU the process was told."""
    i = info()
    if i.platform == "tpu" or told_cpu():
        return i
    raise DeviceError(
        f"{what} needs a TPU but the JAX backend came up as platform="
        f"{i.platform!r} (JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS', '')!r}); run it with -coder "
        f"native|numpy, or JAX_PLATFORMS=cpu to compute on the CPU on "
        f"purpose")


def require_tpu(what: str) -> DeviceInfo:
    """Gate for paths whose result is named after the device."""
    i = info()
    if i.platform != "tpu":
        raise DeviceError(f"{what} needs a TPU; this process runs on "
                          f"platform={i.platform!r}")
    return i


def resolve_coder(name: str) -> str:
    """``-coder NAME`` -> the backend this process will run, checked."""
    if name == "auto":
        name = "jax" if _has_tpu() else _host_coder()
    if name in DEVICE_CODERS:
        require(f"-coder {name}")
    return name


def _has_tpu() -> bool:
    if told_cpu():
        return False
    try:
        return info().platform == "tpu"
    except ImportError:
        return False


def _host_coder() -> str:
    from . import native
    return "native" if native.available() else "numpy"


def status() -> dict:
    i = _info
    return {"platform": i.platform if i else None,
            "device_kind": i.device_kind if i else None,
            "devices": i.count if i else 0,
            "jax_loaded": "jax" in sys.modules}
