"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). A device that is not in the
table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time_s(n_bytes: float, n_ops: float, device_kind: str) -> float:
    """The roofline: the larger of bytes over bandwidth and operations over
    the compute peak (elementwise work counted against the bf16 peak, the
    highest the chip publishes, so the share is never overstated)."""
    p = peaks(device_kind)
    return max(n_bytes / p["hbm_bytes_per_s"], n_ops / p["bf16_flop_per_s"])
