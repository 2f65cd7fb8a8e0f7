"""Published peaks of the accelerators the benchmark may measure on.

Keyed by JAX's ``device_kind``. A device that is not in the table is an
error: no run falls back to a default, and none measures on a CPU.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float        # FLOP/s, dense bf16 on the matrix units
    int8_ops: float          # OP/s, dense int8
    hbm_bytes_per_s: float   # HBM bandwidth, bytes/s
    hbm_bytes: float         # HBM capacity, bytes
    source: str


TABLE: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, TPU v5e"),
}


class UnknownDevice(Exception):
    """The device is not a TPU this table knows."""


def lookup(platform: str, kind: str) -> Peaks:
    """The peaks of one chip; raises ``UnknownDevice`` for anything else."""
    if platform != "tpu":
        raise UnknownDevice(f"platform {platform!r} is not a TPU")
    if kind not in TABLE:
        raise UnknownDevice(f"device kind {kind!r} is not in bench/peaks.py "
                            f"(known: {sorted(TABLE)})")
    return TABLE[kind]
