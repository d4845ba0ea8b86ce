"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB
HBM per chip -- Google Cloud documentation, "TPU v5e" system architecture
page.  A kind that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]
