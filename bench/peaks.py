"""Published peaks of each chip, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error: a share of a peak needs
the peak of the chip it ran on.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float | str]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peak(device_kind: str) -> Dict[str, float | str]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
