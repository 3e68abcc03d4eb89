"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s.  A kind that is not here is an error.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
