"""Published peaks per chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, not a default: a roofline
share against the wrong peaks would be a wrong number.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks_for"]

PEAKS = {
    # JAX's device_kind of a TPU v5e chip
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16, MXU
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
