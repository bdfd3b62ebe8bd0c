"""Pluggable ErasureCoder interface — the north-star seam.

BASELINE.json: "...gated behind a new pluggable ErasureCoder interface so the
default [CPU] path is untouched". Implementations:

* ``NumpyCoder`` — pure-numpy GF tables; correctness oracle, slow.
* ``NativeCoder`` — C++ sidecar (seaweedfs_tpu/native), AVX2 PSHUFB split
  tables: the faithful stand-in for klauspost/reedsolomon's asm, used as the
  CPU baseline that `vs_baseline` is measured against.
* ``JaxCoder`` — the TPU path (Pallas kernel, ops/rs_pallas), batching
  [B, d, L] stripe tensors through the device. Constructing one goes
  through the device gate (ops/device.py): without a TPU it raises, unless
  the process was told ``JAX_PLATFORMS=cpu``, where it runs the XLA einsum
  formulation (ops/rs_jax) so tests cover the same seam.

All coders operate on uint8 arrays shaped [d, L] / [B, d, L] and are
stateless w.r.t. data; geometry is fixed per instance.
"""

from __future__ import annotations

import abc

import numpy as np

from . import device, gf8


class ErasureCoder(abc.ABC):
    #: True when encode() returns an async handle that materializes on
    #: np.asarray (device coders); the streaming pipeline double-buffers
    #: those and takes a zero-copy synchronous fast path for the rest.
    async_dispatch = False
    #: Erasure codec this coder implements — persisted into the .vif seal
    #: so rebuild always decodes with the codec that encoded. Plain RS
    #: coders differ only in compute backend; ops/piggyback.py overrides.
    codec = "rs"

    def __init__(self, d: int, p: int):
        if d <= 0 or p <= 0 or d + p > 256:
            raise ValueError(f"invalid RS geometry ({d},{p})")
        self.d = d
        self.p = p
        self.n = d + p

    @abc.abstractmethod
    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [..., d, L] uint8 -> parity [..., p, L] uint8."""

    @abc.abstractmethod
    def reconstruct(self, survivors: np.ndarray, present: tuple[int, ...],
                    wanted: tuple[int, ...]) -> np.ndarray:
        """survivors [..., d, L] = shards sorted(present)[:d] -> [..., |wanted|, L]."""

    def repair_plan(self, present: "tuple[int, ...]",
                    wanted: "tuple[int, ...]", shard_size: int,
                    ) -> "list[tuple[int, int, int]] | None":
        """Byte ranges [(shard_id, offset, length), ...] of survivors
        sufficient to rebuild `wanted`, or None when nothing beats the
        trivial plan (read d full survivors). Plain RS has no sub-shard
        structure, so the base answer is always None; repair-efficient
        codecs (ops/piggyback.py) override."""
        return None

    def repair_linear(self, wanted: "tuple[int, ...]", shard_size: int):
        """Where `repair_plan`'s ranges rebuild `wanted` by ONE GF(2^8)
        matrix: (matrix [rows_out, len(plan)] over the plan's ranges in
        the plan's order, targets [(shard_id, offset), ...] saying where
        each output row lands, engine: the coder whose `apply_matrix`
        runs it). The rebuild then loads, dispatches, drains and writes
        it like a plain-RS batch (ec/encoder.py). None where the repair
        is not one matrix apply over equal-length ranges."""
        return None

    def apply_matrix(self, mat: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Any GF(2^8) matrix `mat` [m, k] over rows [..., k, L] uint8 ->
        [..., m, L]: what encode and reconstruct are instances of, for
        codecs whose repair is a matrix plain RS has no name for. The
        base runs the numpy tables; backends override."""
        rows = np.asarray(rows, dtype=np.uint8)
        if rows.ndim == 2:
            return gf8.np_gf_apply(mat, rows)
        return np.stack([gf8.np_gf_apply(mat, b) for b in rows])

    def verify(self, shards: np.ndarray) -> bool:
        """shards [..., n, L]: recompute parity from data rows and compare."""
        data = shards[..., : self.d, :]
        parity = shards[..., self.d:, :]
        return bool(np.array_equal(np.asarray(self.encode(data)), np.asarray(parity)))


class NumpyCoder(ErasureCoder):
    def encode(self, data: np.ndarray) -> np.ndarray:
        return self.apply_matrix(gf8.parity_matrix(self.d, self.p), data)

    def reconstruct(self, survivors, present, wanted):
        rec = gf8.decode_matrix(self.d, self.p, list(present))[list(wanted), :]
        return self.apply_matrix(rec, survivors)


class JaxCoder(ErasureCoder):
    """Device coder. Accepts numpy or jax arrays; returns device arrays
    (callers `np.asarray` when they need host bytes).

    On a TPU the Pallas kernel (ops/rs_pallas.py) carries the hot path —
    unpack/matmul/pack pinned in VMEM. The device gate decides at
    construction: no TPU raises DeviceError; a process told
    JAX_PLATFORMS=cpu runs the XLA einsum formulation (ops/rs_jax.py).
    """

    async_dispatch = True

    def __init__(self, d: int, p: int):
        super().__init__(d, p)
        self.use_pallas = device.require(
            type(self).__name__).platform == "tpu"
        self._interpret = False  # PallasCoder flips this when told CPU

    def encode(self, data):
        if self.use_pallas:
            from . import rs_pallas
            x, squeeze = _as_batch(data)
            out = rs_pallas.encode_jit(x, self.d, self.p,
                                       interpret=self._interpret)
            return out[0] if squeeze else out
        from . import rs_jax
        return rs_jax.encode_jit(data, self.d, self.p)

    def reconstruct(self, survivors, present, wanted):
        if self.use_pallas:
            from . import rs_pallas
            x, squeeze = _as_batch(survivors)
            out = rs_pallas.reconstruct_jit(
                x, tuple(sorted(present)), tuple(wanted), self.d, self.p,
                interpret=self._interpret)
            return out[0] if squeeze else out
        from . import rs_jax
        return rs_jax.reconstruct_jit(
            survivors, tuple(sorted(present)), tuple(wanted), self.d, self.p)

    def apply_matrix(self, mat, rows):
        """The matrix rides as an OPERAND: one program per shape
        [B, k, C] -> [B, m, C], whatever the matrix says."""
        if self.use_pallas:
            from . import rs_pallas
            x, squeeze = _as_batch(rows)
            out = rs_pallas.matrix_apply_jit(
                x, rs_pallas.matrix_operand(mat), interpret=self._interpret)
            return out[0] if squeeze else out
        from . import rs_jax
        return rs_jax.matrix_apply_jit(rs_jax.matrix_operand(mat), rows)


def _as_batch(arr):
    """Pallas kernels take [B, k, C]; promote [k, C] and remember to squeeze."""
    import jax.numpy as jnp
    arr = jnp.asarray(arr)
    if arr.ndim == 2:
        return arr[None], True
    return arr, False


class PallasCoder(JaxCoder):
    """Always the Pallas kernel: compiled on a TPU, interpreted in a
    process told JAX_PLATFORMS=cpu so tests cover the kernel logic."""

    def __init__(self, d: int, p: int):
        super().__init__(d, p)
        self._interpret = not self.use_pallas
        self.use_pallas = True


_REGISTRY = {"numpy": NumpyCoder, "jax": JaxCoder, "pallas": PallasCoder}

# backend names double as the plain-RS codec: NumpyCoder is the host
# oracle, so "rs" resolves there (repair costing, codec enumeration)
_REGISTRY["rs"] = NumpyCoder

# self-registering implementations live in modules nobody has imported
# yet when a CLI (or a .vif read) asks for them by name; the bool marks
# entries that register a NEW erasure codec (vs just a compute backend),
# so codec enumeration doesn't drag in jax for a help string
_LAZY = {
    "native": ("seaweedfs_tpu.ops.native", False),
    "mesh": ("seaweedfs_tpu.parallel.pipeline", False),
    "piggyback": ("seaweedfs_tpu.ops.piggyback", True),
    "msr": ("seaweedfs_tpu.ops.product_matrix", True),
}


def _lazy_load(name: str) -> None:
    mod, _ = _LAZY[name]
    __import__(mod, fromlist=["_"])


def get_coder(name: str, d: int, p: int) -> ErasureCoder:
    if name not in _REGISTRY and name in _LAZY:
        _lazy_load(name)
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown coder {name!r}; have {sorted(_REGISTRY)}") from None
    return cls(d, p)


def register_coder(name: str, cls) -> None:
    _REGISTRY[name] = cls


def registered_codecs() -> "list[str]":
    """Erasure CODEC names (one per wire/disk format, not per compute
    backend) — drives shell help/validation so a new registered codec
    shows up everywhere without hand-edited name lists. Entries may be
    classes or factory callables (mesh); factories without a `codec`
    attribute are plain-RS backends."""
    for name, (_, is_codec) in _LAZY.items():
        if is_codec and name not in _REGISTRY:
            try:
                _lazy_load(name)
            except Exception:  # noqa: BLE001  # swtpu-lint: disable=silent-except (enumeration must list what IS loadable, not fail on what isn't)
                pass
    return sorted({getattr(cls, "codec", "rs")
                   for cls in _REGISTRY.values()})


def codec_coder(codec: str, d: int, p: int,
                backend: str = "numpy") -> ErasureCoder:
    """Construct the coder for an erasure `codec` on a compute
    `backend`. Plain "rs" is the backend coder itself; layered codecs
    (piggyback, msr) wrap the backend as their inner GF engine."""
    if not codec or codec == "rs":
        return get_coder(backend if backend != "auto" else "numpy", d, p)
    if codec not in _REGISTRY and codec in _LAZY:
        _lazy_load(codec)
    cls = _REGISTRY.get(codec)
    if cls is None or getattr(cls, "codec", "rs") != codec:
        raise ValueError(
            f"unknown erasure codec {codec!r}; have {registered_codecs()}")
    # pass the backend only when the constructor takes one — probing via
    # except TypeError would also swallow TypeErrors raised INSIDE the
    # constructor and silently drop the requested backend
    import inspect
    try:
        takes_backend = "backend" in inspect.signature(cls).parameters
    except (TypeError, ValueError):  # uninspectable callable
        takes_backend = False
    if takes_backend:
        return cls(d, p, backend=backend)
    return cls(d, p)


def repair_read_bytes(codec: str, d: int, p: int, missing, shard_size: int,
                      ) -> int:
    """Survivor bytes a rebuild of `missing` must read under `codec` —
    the repair planner's byte-costing primitive. Resolves the codec
    through the registry (numpy inner backend: no data touches it, the
    coder is consulted purely for plan geometry), so any registered
    codec costs correctly without editing this helper."""
    missing = sorted(set(missing))
    coder = codec_coder(codec or "rs", d, p)
    present = tuple(i for i in range(d + p) if i not in missing)
    plan = coder.repair_plan(present, tuple(missing), shard_size)
    if plan is None:
        return d * shard_size
    return sum(ln for _, _, ln in plan)
