"""Published peaks of the chips the benchmark may run on, by `device_kind`.

A device that is not in the table is an error, never a default: a
roofline share against a guessed peak is worse than none.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM2e at 819 GB/s per chip. jax reports the chip as "TPU v5 lite".
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "bytes_per_s": 819e9,
        "memory_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (bf16 peak, HBM bandwidth)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device kind {device_kind!r}; "
            f"add it to benchmark/harness/peaks.py with its source"
        ) from None


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for work of `flops` operations
    and `nbytes` bytes moved, and which of the two bounds it."""
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")
