"""Architecture registry: ``--arch <id>`` resolution.

Each assigned architecture lives in its own module exposing ``config()``.
``long_500k`` applicability per DESIGN.md §4: native for state-based archs,
sliding-window variant for full-attention decoders, skipped for whisper.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.config import ATTN_SLIDING, DCGAN, INPUT_SHAPES, RunConfig

# arch id -> module name
_ARCHS: Dict[str, str] = {
    "qwen3-14b": "repro.configs.qwen3_14b",
    "recurrentgemma-9b": "repro.configs.recurrentgemma_9b",
    "rwkv6-1.6b": "repro.configs.rwkv6_1b6",
    "deepseek-v2-lite-16b": "repro.configs.deepseek_v2_lite",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "whisper-base": "repro.configs.whisper_base",
    "granite-20b": "repro.configs.granite_20b",
    "qwen2-72b": "repro.configs.qwen2_72b",
    "llama3-405b": "repro.configs.llama3_405b",
    # the paper's own model, and DCGAN at its published 64x64 widths
    "dcgan-mnist": "repro.configs.dcgan_mnist",
    "dcgan64": "repro.configs.dcgan64",
}


def _family(arch: str) -> str:
    return importlib.import_module(_ARCHS[arch]).config().model.family


# the language-model archs that iter_pairs / dryrun pair with INPUT_SHAPES
ASSIGNED_ARCHS: List[str] = [a for a in _ARCHS if _family(a) != DCGAN]
SHAPES: List[str] = list(INPUT_SHAPES)

# long_500k handling per arch (DESIGN.md §4)
LONG_NATIVE = {"rwkv6-1.6b", "recurrentgemma-9b"}
LONG_SKIP = {"whisper-base"}          # decoder max positions = 448


def list_archs() -> List[str]:
    return list(_ARCHS)


def get_config(arch: str, shape: str | None = None) -> RunConfig:
    """Resolve ``--arch <id>`` (optionally bound to an input shape)."""
    if arch not in _ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCHS)}")
    cfg: RunConfig = importlib.import_module(_ARCHS[arch]).config()
    if shape is not None:
        if shape not in INPUT_SHAPES:
            raise KeyError(f"unknown shape {shape!r}; known: {SHAPES}")
        cfg = cfg.override({
            "shape.name": INPUT_SHAPES[shape].name,
            "shape.seq_len": INPUT_SHAPES[shape].seq_len,
            "shape.global_batch": INPUT_SHAPES[shape].global_batch,
            "shape.mode": INPUT_SHAPES[shape].mode,
        })
        if shape == "long_500k" and arch not in LONG_NATIVE:
            if arch in LONG_SKIP:
                raise SkippedShape(
                    f"{arch}: long_500k skipped (decoder max positions 448)")
            # dense/moe/vlm: beyond-paper sliding-window variant (DESIGN.md §4)
            cfg = cfg.override({"model.attention": ATTN_SLIDING,
                                "model.sliding_window": 4096})
        cfg = cfg.validate()
    return cfg


class SkippedShape(Exception):
    """Raised when an (arch, shape) pair is skipped by design (DESIGN.md §4)."""


def iter_pairs(include_skipped: bool = False):
    """Yield (arch, shape, cfg_or_None) for the 10x4 assignment matrix."""
    for arch in ASSIGNED_ARCHS:
        for shape in SHAPES:
            try:
                yield arch, shape, get_config(arch, shape)
            except SkippedShape:
                if include_skipped:
                    yield arch, shape, None
