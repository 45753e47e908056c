"""Roofline terms from a compiled dry-run artifact.

    compute_term    = per_chip_HLO_FLOPs / peak_FLOP/s
    memory_term     = per_chip_HLO_bytes_accessed / HBM_bw
    collective_term = per_chip_collective_bytes / link_bw

FLOPs/bytes come from ``compiled.cost_analysis()``. The compiled module is
the post-GSPMD *per-device* program, so its totals are already per chip
(verified against a hand-computed sharded matmul). Collective bytes are NOT
in cost_analysis: they are summed from the optimized HLO text, one entry per
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
using the op's *output* tensor bytes as the wire-bytes convention
(documented in EXPERIMENTS.md §Roofline; ring-algorithm factors of
2(n-1)/n are ignored uniformly so comparisons between iterations are
apples-to-apples).
"""
from __future__ import annotations

import dataclasses
import json
import re
from dataclasses import dataclass, field
from typing import Dict, Optional

from repro.roofline.hw import HwSpec

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """compiled.cost_analysis(), or {} where the backend reports none."""
    return compiled.cost_analysis() or {}

# e.g.  %ag = bf16[2,1024,512]{2,1,0} all-gather(...)
_SHAPE_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum output-tensor bytes per collective kind from optimized HLO."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for m in _SHAPE_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        out[kind] += _shape_bytes(dtype, dims)
        out["count"] += 1
    # tuple-result collectives (multiple operands) — grab tuple elements
    tuple_re = re.compile(
        r"=\s*\(([^)]*)\)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start)?\(")
    elem_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for m in tuple_re.finditer(hlo_text):
        kind = m.group(2)
        for e in elem_re.finditer(m.group(1)):
            out[kind] += _shape_bytes(e.group(1), e.group(2))
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    collectives: Dict[str, int] = field(default_factory=dict)
    model_flops: float = 0.0            # 6*N*D (or 6*N_active*D for MoE)
    compute_term_s: float = 0.0
    memory_term_s: float = 0.0
    collective_term_s: float = 0.0
    arg_bytes_per_device: float = 0.0
    temp_bytes_per_device: float = 0.0
    out_bytes_per_device: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_term_s, "memory": self.memory_term_s,
                 "collective": self.collective_term_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS (global 6ND, divided per chip) / per-chip HLO FLOPs."""
        if not self.hlo_flops:
            return 0.0
        return (self.model_flops / self.chips) / self.hlo_flops

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["dominant"] = self.dominant
        d["useful_flops_ratio"] = self.useful_flops_ratio
        return d

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)


def kernel_terms(compiled, hw: Optional[HwSpec]
                 ) -> Dict[str, Optional[float]]:
    """Roofline terms of ONE compiled kernel/program — the light-weight
    form the obs profiler (``repro.obs.profile``) feeds from jit artifacts:
    flops / bytes from ``cost_analysis`` plus the compute and memory terms
    against ``hw`` (None, and the terms None, where the program runs on
    a device without peaks, such as the host CPU).  No HLO parsing
    (single-device kernels have no collectives)."""
    ca = cost_analysis_dict(compiled)
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    return {"flops": flops, "bytes_accessed": byts,
            "compute_term_s": flops / hw.peak_flops_bf16 if hw else None,
            "memory_term_s": byts / hw.hbm_bw if hw else None,
            "arithmetic_intensity": flops / byts if byts else 0.0}


def fused_boundary_terms(batch: int, features: int, *,
                         hw: HwSpec, codec: str = "int8",
                         compiled=None) -> Dict[str, float]:
    """Roofline terms for the fused boundary kernel
    (``kernels/boundary_fuse``): codec qdq + per-example clip + noise
    over one flattened ``(batch, features)`` crossing tensor.

    The analytic model follows the kernel's phase structure: the input
    streams from HBM once per grid phase (2 phases for ``fp16``/``none``,
    3 for ``int8`` — the extra amax pass), the noise tile is read once
    and the output written once, all fp32:

        bytes = (phases + 2) * 4 * B * N

    FLOP count is ~2 per element per phase (qdq multiply-round, square +
    accumulate, scale-and-fma) — small against the byte traffic; the
    fused stage is memory-bound by construction, which is exactly why
    fusing three traversals into one pays.  Pass ``compiled`` (a lowered
    ``fused_boundary_flat`` jit artifact) to merge the XLA-measured
    ``kernel_terms`` under ``measured_*`` keys.
    """
    phases = 3 if codec == "int8" else 2
    n = float(batch) * float(features)
    flops = 2.0 * phases * n
    byts = (phases + 2) * 4.0 * n
    out = {"codec": codec, "batch": float(batch),
           "features": float(features), "phases": float(phases),
           "flops": flops, "bytes_accessed": byts,
           "compute_term_s": flops / hw.peak_flops_bf16,
           "memory_term_s": byts / hw.hbm_bw,
           "arithmetic_intensity": flops / byts,
           # what fusing saves vs three separate traversals (codec pass +
           # clip-norm pass + scale/noise pass, each read+write)
           "unfused_bytes_accessed": 3.0 * 2.0 * 4.0 * n}
    if compiled is not None:
        out.update({f"measured_{k}": v
                    for k, v in kernel_terms(compiled, hw).items()})
    return out


_WIRE_BYTES = {"none": 4.0, "fp16": 2.0, "int8": 1.0}


def agg_fuse_terms(num_clients: int, n: int, *, hw: HwSpec,
                   codec: str = "int8", compiled=None) -> Dict[str, float]:
    """Roofline terms for the fused dequant-reduce aggregation kernel
    (``kernels/agg_fuse``): ``num_clients`` compressed client wires of
    ``n`` elements -> one fp32 weighted mean, per-client scales applied
    inside the grid and the running sum held in a persistent VMEM
    accumulator.

    The fused kernel streams each wire from HBM exactly once at its WIRE
    dtype width (1 B ``int8``, 2 B ``fp16``, 4 B ``none``), reads the
    tiny ``(C, 2)`` weight*scale coefficient tile, and writes the fp32
    aggregate once:

        bytes = wire_b * C * N + 8 * C + 4 * N

    The decode-then-reduce baseline pays the same wire reads plus a full
    fp32 materialization per client (decode writes ``4*C*N``) that the
    reduce then reads back (``4*C*N``) — the ``unfused_bytes_accessed``
    key quantifies that, and its ratio to ``bytes_accessed`` is the
    memory-bound speedup ceiling the ``agg`` bench section measures.
    FLOPs are ~3 per element (dequant multiply, weight multiply,
    accumulate) — negligible against the traffic, so the reduce is
    memory-bound and fusion pays the full traversal saving.  Pass
    ``compiled`` (a lowered ``dequant_reduce_flat`` jit artifact) to
    merge XLA-measured ``kernel_terms`` under ``measured_*`` keys.
    """
    wire_b = _WIRE_BYTES.get(codec, 4.0)
    c, nn = float(num_clients), float(n)
    flops = 3.0 * c * nn
    byts = wire_b * c * nn + 8.0 * c + 4.0 * nn
    out = {"codec": codec, "num_clients": c, "n": nn,
           "wire_bytes_per_elem": wire_b,
           "flops": flops, "bytes_accessed": byts,
           "compute_term_s": flops / hw.peak_flops_bf16,
           "memory_term_s": byts / hw.hbm_bw,
           "arithmetic_intensity": flops / byts,
           # decode-then-reduce: wire reads + fp32 decode writes + fp32
           # reduce read-back + aggregate write
           "unfused_bytes_accessed": wire_b * c * nn + 8.0 * c * nn
                                     + 4.0 * nn}
    if compiled is not None:
        out.update({f"measured_{k}": v
                    for k, v in kernel_terms(compiled, hw).items()})
    return out


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     chips: int, model_flops: float, hw: HwSpec,
                     hlo_text: Optional[str] = None) -> RooflineReport:
    ca = cost_analysis_dict(compiled)
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes_from_hlo(text)
    mem = compiled.memory_analysis()
    rep = RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=byts,
        collective_bytes=float(coll["total"]), collectives=coll,
        model_flops=model_flops,
        compute_term_s=flops / hw.peak_flops_bf16,
        memory_term_s=byts / hw.hbm_bw,
        # per-chip wire bytes: collectives are already per-participant in
        # the SPMD module (shapes are per-shard), links per chip ~= 4 on a
        # 2D torus; use one link as the conservative convention.
        collective_term_s=float(coll["total"]) / hw.ici_bw_per_link,
        arg_bytes_per_device=getattr(mem, "argument_size_in_bytes", 0),
        temp_bytes_per_device=getattr(mem, "temp_size_in_bytes", 0),
        out_bytes_per_device=getattr(mem, "output_size_in_bytes", 0),
    )
    return rep
