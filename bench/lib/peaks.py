"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud TPU documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 819 GB/s of HBM bandwidth).  A device that is not in
the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to bench/lib/peaks.py"
        ) from None
