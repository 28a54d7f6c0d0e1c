"""Published peaks of the accelerators the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A kind that is not here is an
error: a share of a peak is never computed against a guessed peak.
"""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "https://cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind with
    none on record."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks on record for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
