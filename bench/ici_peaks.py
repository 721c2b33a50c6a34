"""Published chip-to-chip interconnect (ICI) peaks per chip, keyed by JAX's
``device_kind``.

A device that is not in the table is an error, not a default, as in
``peaks.py``: a share of the wrong link's peak would be a wrong number.
"""
from __future__ import annotations

__all__ = ["ICI_PEAKS", "ici_peaks_for"]

ICI_PEAKS = {
    # JAX's device_kind of a TPU v5e chip
    "TPU v5 lite": {
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, 'TPU v5e': 1,600 Gbps of "
                  "inter-chip interconnect bandwidth per chip",
    },
}


def ici_peaks_for(device_kind: str) -> dict:
    try:
        return ICI_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published interconnect peaks for device kind "
            f"{device_kind!r}; known: {sorted(ICI_PEAKS)}") from None
