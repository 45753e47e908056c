"""Per-chip peaks, keyed by the ``device_kind`` JAX reports."""
from dataclasses import dataclass


@dataclass(frozen=True)
class HwSpec:
    name: str
    peak_flops_bf16: float     # FLOP/s
    hbm_bw: float              # bytes/s
    ici_bw_per_link: float     # bytes/s per link
    hbm_bytes: float
    vmem_bytes: float


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s and
# 16 GiB of HBM, 1,600 Gbit/s of interconnect per chip (4 links).
TPU_V5E = HwSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    hbm_bytes=16 * 1024 ** 3,
    vmem_bytes=128 * 1024 ** 2,
)

# jax.devices()[0].device_kind -> peaks.  A v5e reports "TPU v5 lite".
PEAKS = {"TPU v5 lite": TPU_V5E}


def hw_for_kind(device_kind: str) -> HwSpec:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks recorded for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
