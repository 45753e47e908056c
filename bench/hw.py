"""Per-chip peaks, keyed by the ``device_kind`` JAX reports.

Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 393 TOP/s in
int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
A float32 matmul or convolution at JAX's default precision runs as one
bf16 pass on this chip, so the bf16 peak bounds the model's FLOP rate.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks recorded for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
