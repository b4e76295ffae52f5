"""Published peaks of each accelerator, keyed by JAX's `device_kind`.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]
