"""FLOPs and HBM bytes that each engine kernel's operation needs.

The work of the operation, not of an implementation: each input is read
once and each output written once, whatever number of passes a kernel
makes over its input.  Elementwise FLOPs count one per arithmetic
operation; a compare, a max or a round counts as one.

  * ``boundary_fuse``: one crossing ``x`` (B, N) f32 through the codec's
    quantize-dequantize, a per-example clip to norm C and added noise;
    ``noise`` (B, N) f32 is an input, the output (B, N) f32.
  * ``dp_clip``: per-example gradients (B, N) f32 clipped, summed, plus
    noise (N,) f32, into (N,) f32.
  * ``dequant_acc``: one streamed fold ``acc + w * s * wire`` over N
    elements: the accumulator (N,) f32 read and written, the wire read at
    its own width.

A share of a roofline is the least time, the larger of FLOPs over the
peak FLOP rate and bytes over the HBM bandwidth, over the kernel's
measured device time.
"""
from __future__ import annotations

from typing import Dict

F32 = 4.0
WIRE_BYTES = {"none": 4.0, "fp16": 2.0, "int8": 1.0}
# per element: |x|, max, divide, round, clip (2), multiply back
QDQ_FLOPS = {"none": 0.0, "fp16": 0.0, "int8": 7.0}


def boundary_fuse(batch: int, features: int, codec: str) -> Dict[str, float]:
    n = float(batch) * features
    # square + add for the norm, the clip scale, noise multiply + add
    flops = (QDQ_FLOPS[codec] + 5.0) * n
    return {"flops": flops, "bytes": 3 * F32 * n}


def dp_clip(batch: int, features: int) -> Dict[str, float]:
    n = float(batch) * features
    # square + add for the norm, the clip scale, the sum; noise fma
    return {"flops": 4.0 * n + 2.0 * features,
            "bytes": F32 * n + 2 * F32 * features}


def dequant_acc(n: int, codec: str) -> Dict[str, float]:
    return {"flops": 2.0 * n, "bytes": (2 * F32 + WIRE_BYTES[codec]) * n}


def least_seconds(work: Dict[str, float], peaks: Dict) -> float:
    return max(work["flops"] / peaks["flops_bf16"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
