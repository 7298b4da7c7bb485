"""The chip's peaks, and the bytes each kernel's work needs.

Peaks are per chip and keyed by ``device_kind`` as JAX reports it. A
device that is not in the table is an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str, key: str) -> float:
    try:
        return PEAKS[device_kind][key]
    except KeyError:
        raise KeyError(f"no peak {key!r} for device kind {device_kind!r} "
                       f"in bench/costs.py") from None


def prefix_scan_bytes(tasks: int) -> int:
    """One float32 work read and one float32 position written per task.
    Padding is not counted: the share reads the same work whatever
    layout implements it."""
    return 8 * int(tasks)


def least_seconds(nbytes: int, device_kind: str) -> float:
    """The least time the chip's HBM bandwidth allows for ``nbytes``;
    the prefix scan does one add per element, so bandwidth bounds it."""
    return nbytes / peak(device_kind, "hbm_bytes_per_s")
