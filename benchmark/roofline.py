"""Peaks of the chips, and the operations and bytes each kernel's
algorithm needs, from its shapes. A kernel's roofline share is the least
time the chip could take — the larger of operations over peak and bytes
over peak — divided by the time its events took in the device trace."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": per chip.
PEAKS = {
    "TPU v5 lite": {"int8_ops": 393e12, "bf16_flops": 197e12,
                    "hbm_bytes": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}")
    return PEAKS[device_kind]


def rs_ops_bytes(batch: int, rows_in: int, rows_out: int,
                 length: int) -> "tuple[float, float]":
    """GF(2^8) matrix apply [rows_out, rows_in] over a [batch, rows_in,
    length] uint8 slab, as the bit-matrix product the MXU runs: an
    [8*rows_out, 8*rows_in] 0/1 matrix times the bit planes, one
    multiply and one add per entry and column. Bytes: every input byte
    read once, every output byte written once."""
    cols = batch * length
    return (2.0 * 8 * rows_out * 8 * rows_in * cols,
            float(cols * (rows_in + rows_out)))


def crc_ops_bytes(rows: int, length: int,
                  chunk: int = 512) -> "tuple[float, float]":
    """CRC32C of `rows` messages of `length` bytes as a GF(2)-linear scan
    in `chunk`-byte steps: per step and row a [32, 32] state update and a
    [8*chunk, 32] fold of the data bits. Bytes: each byte read once."""
    steps = length // chunk
    return (2.0 * rows * steps * (32 * 32 + 8 * chunk * 32),
            float(rows * length))


def share(ops: float, nbytes: float, seconds: float, device_kind: str,
          ops_peak: str = "int8_ops") -> "tuple[float, str]":
    """(percent of the roofline, which roof bounds)."""
    pk = peaks(device_kind)
    t_ops, t_bytes = ops / pk[ops_peak], nbytes / pk["hbm_bytes"]
    least = max(t_ops, t_bytes)
    return (100.0 * least / seconds,
            "compute" if t_ops >= t_bytes else "memory")
