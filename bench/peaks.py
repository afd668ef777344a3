"""Published peaks of each device the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not here is an error, not a default.

TPU v5e (JAX reports "TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(ops: float, nbytes: float, device_kind: str) -> float:
    """The least time the device could take: the larger of ops over the peak rate
    and bytes over the memory bandwidth."""
    pk = peaks(device_kind)
    return max(ops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
