"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A kind that is not in the table is an error, never a default: a roofline
share is a time on one chip over that chip's own peaks.
"""
from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e: 819 GB/s HBM, "
                  "197 TFLOP/s bf16, 16 GB HBM",
    },
}


def device_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown
    kind."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None
