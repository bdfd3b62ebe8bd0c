"""ctypes bridge to the C++ sidecar (seaweedfs_tpu/native/libswtpu.so).

Builds the library on first use (g++ via the Makefile) and degrades
gracefully to None when no toolchain is available — callers fall back to the
numpy/JAX paths. The NativeCoder here is the host coder (volume server B
in every cell of benchmark/run.py): the same AVX2 split-table algorithm
klauspost/reedsolomon uses.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from . import gf8
from .coder import ErasureCoder, register_coder

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libswtpu.so")
_lock = threading.Lock()
_lib: "ctypes.CDLL | None | bool" = None  # None=untried, False=unavailable


def load() -> "ctypes.CDLL | None":
    global _lib
    with _lock:
        if _lib is None:
            _lib = _try_load()
        return _lib or None


def _try_load():
    if not os.path.exists(_SO_PATH):
        try:
            subprocess.run(["make", "-C", _NATIVE_DIR, "-s"], check=True,
                           capture_output=True, timeout=120)
        except Exception:
            return False
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError:
        return False
    lib.rs_apply_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int64, ctypes.c_int64]
    lib.rs_apply.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int64]
    lib.crc32c_update.restype = ctypes.c_uint32
    lib.crc32c_update.argtypes = [ctypes.c_uint32, ctypes.c_void_p, ctypes.c_int64]
    lib.crc32c_batch.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                                 ctypes.c_uint32, ctypes.c_void_p]
    lib.native_features.restype = ctypes.c_int
    return lib


def available() -> bool:
    return load() is not None


def features() -> dict:
    lib = load()
    if lib is None:
        return {"available": False}
    f = lib.native_features()
    return {"available": True, "avx2": bool(f & 1), "sse42_crc": bool(f & 2)}


def _apply(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """mat [m,k] uint8, data [..., k, L] uint8 C-contiguous -> [..., m, L]."""
    lib = load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    data = np.ascontiguousarray(data, dtype=np.uint8)
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    m, k = mat.shape
    if data.ndim == 2:
        ksz, L = data.shape
        assert ksz == k
        out = np.empty((m, L), dtype=np.uint8)
        lib.rs_apply(data.ctypes.data, out.ctypes.data, mat.ctypes.data, k, m, L)
        return out
    B, ksz, L = data.shape
    assert ksz == k
    out = np.empty((B, m, L), dtype=np.uint8)
    lib.rs_apply_batch(data.ctypes.data, out.ctypes.data, mat.ctypes.data,
                       k, m, L, B)
    return out


def crc32c(data: bytes | np.ndarray, value: int = 0) -> int:
    """Hardware CRC32C with the standard init/final-xor convention."""
    lib = load()
    if lib is None:
        from .crc32c import crc32c as soft
        return soft(data, value)
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else np.ascontiguousarray(data, dtype=np.uint8)
    raw = lib.crc32c_update(value ^ 0xFFFFFFFF, arr.ctypes.data, arr.size)
    return raw ^ 0xFFFFFFFF


class NativeCoder(ErasureCoder):
    """AVX2 split-table CPU coder — the reference-equivalent baseline."""

    def __init__(self, d: int, p: int):
        super().__init__(d, p)
        if not available():
            raise RuntimeError("native library unavailable (no g++?)")
        self._parity = gf8.parity_matrix(d, p)

    def encode(self, data: np.ndarray) -> np.ndarray:
        return _apply(self._parity, data)

    def reconstruct(self, survivors, present, wanted):
        rec = gf8.decode_matrix(self.d, self.p, list(present))[list(wanted), :]
        return _apply(rec, survivors)

    def apply_matrix(self, mat, rows):
        return _apply(mat, rows)


register_coder("native", NativeCoder)
