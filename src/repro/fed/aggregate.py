"""Compressed-domain streaming aggregation over encoded uplinks.

The decode-then-fedavg server reduce stages one decoded fp32 tree per
client before averaging — O(C) server memory and an extra full
materialization per uplink.  This module folds each uplink's WIRE
payload (``Codec.encode_tree`` output) straight into one fp32
accumulator through the fused ``kernels/agg_fuse`` ops:

  * :class:`StreamingAggregator` — ``init / fold / finalize``: the
    engine folds each landed uplink as it arrives and holds O(1) state
    in the cohort size (one accumulator tree + a weight sum).  ``fold``
    also measures the codec's relative L2 error against the raw delta
    in the SAME traversal, so the per-client error metric no longer
    costs a second decode pass.
  * :func:`codec_rel_error` — the fold's error measurement alone, for
    executed-but-late stragglers whose update never folds.
  * :func:`decode_enc` / :func:`fused_decode_apply` — one-traversal
    decode (+ rebase) of a single encoded uplink, used by the async
    path at ARRIVE time so FINISH events queue wire payloads instead of
    decoded trees.
  * :func:`batched_reduce` — the vectorized-backend form: per-leaf wire
    stacks reduced in one fused kernel call (dense codecs) or one
    vmapped decode over the stacked client axis (top-k), sharded with
    ``sharding.stacked_shardings`` when a client mesh is attached.

Weighted mean of rebased updates equals base + weighted mean of deltas
exactly in real arithmetic but only to fma-level in float, so every
stream-vs-decode pin is tolerance-based, never bit-exact.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.agg_fuse.ops import (dequant_acc_flat, dequant_reduce_flat,
                                        scatter_acc_flat)
from repro.fed.transport import apply_delta
from repro.obs.trace import to_host

__all__ = ["StreamingAggregator", "batched_reduce", "codec_rel_error",
           "decode_enc", "fused_decode_apply"]

EncTree = List[Tuple[Any, Any]]          # per-leaf (wire, meta), leaves order


def _norm(name: str) -> str:
    return "none" if name in ("none", "", "identity") else name


def decode_enc(codec_name: str, enc: EncTree, template):
    """Decode one encoded uplink back to a tree in ``template``'s
    structure — leaf-wise identical to ``Codec.roundtrip``'s decode."""
    name = _norm(codec_name)
    leaves = []
    for (wire, meta), t in zip(enc, jax.tree.leaves(template)):
        if name == "none":
            leaves.append(wire)          # identity: the leaf itself
        elif name == "topk":
            vals, idx = wire
            leaves.append(jnp.zeros((t.size,), jnp.float32).at[idx]
                          .set(vals).reshape(t.shape))
        elif name == "int8":
            leaves.append((wire.astype(jnp.float32) * meta).reshape(t.shape))
        else:                            # fp16 (or any plain cast wire)
            leaves.append(wire.astype(jnp.float32).reshape(t.shape))
    return jax.tree.unflatten(jax.tree.structure(template), leaves)


def fused_decode_apply(codec_name: str, base, enc: EncTree):
    """Decode an encoded DELTA uplink and rebase it onto ``base`` in one
    traversal — what the async path applies per arrival."""
    return apply_delta(base, decode_enc(codec_name, enc, base))


def codec_rel_error(codec_name: str, enc: EncTree, delta) -> float:
    """Relative global-L2 error of the encoded uplink vs the raw delta —
    the decode-free form of ``transport.tree_rel_error`` (top-k never
    densifies: the error splits into on-support and dropped mass)."""
    name = _norm(codec_name)
    if name == "none" or delta is None:
        return 0.0
    num = jnp.zeros((), jnp.float32)
    den = jnp.zeros((), jnp.float32)
    for (wire, meta), d in zip(enc, jax.tree.leaves(delta)):
        f = d.astype(jnp.float32).reshape(-1)
        den += jnp.sum(f * f)
        if name == "topk":
            vals, idx = wire
            dv = f[idx]
            num += jnp.sum(f * f) - jnp.sum(dv * dv) \
                + jnp.sum((vals - dv) ** 2)
        else:
            dec = wire.astype(jnp.float32).reshape(-1)
            if name == "int8":
                dec = dec * meta
            num += jnp.sum((dec - f) ** 2)
    return math.sqrt(max(to_host(num), 0.0)) / max(math.sqrt(to_host(den)),
                                                   1e-12)


class StreamingAggregator:
    """O(1)-memory weighted mean over encoded uplinks.

    ``init(template)`` allocates one zero fp32 accumulator per leaf;
    ``fold(enc, weight)`` adds ``weight * dequant(enc)`` through the
    fused kernels (sparse top-k wires scatter straight into the dense
    accumulator); ``finalize()`` divides by the folded weight sum and
    restores leaf shapes/dtypes.  Live decoded-tree count is always 1 —
    the accumulator — independent of how many uplinks folded.
    """

    def __init__(self, codec_name: str, *, use_kernel: bool = False,
                 interpret: bool = False):
        self.codec_name = _norm(codec_name)
        self.use_kernel = bool(use_kernel)
        self.interpret = bool(interpret)
        self._acc: Optional[List[jnp.ndarray]] = None
        self._template = None
        self.wsum = 0.0
        self.folds = 0

    def init(self, template) -> None:
        """``template``: any tree with the uplink's structure and leaf
        shapes (the global tree works for both delta and param wires)."""
        self._template = template
        self._acc = [jnp.zeros((l.size,), jnp.float32)
                     for l in jax.tree.leaves(template)]
        self.wsum = 0.0
        self.folds = 0

    def fold(self, enc: EncTree, weight: float,
             delta=None) -> Optional[float]:
        """Fold one encoded uplink with fedavg weight ``weight``.  When
        the raw ``delta`` tree is passed, the codec's relative L2 error
        is measured in the same per-leaf sweep and returned."""
        assert self._acc is not None, "fold() before init()"
        w = float(weight)
        name = self.codec_name
        want_err = delta is not None and name != "none"
        dleaves = jax.tree.leaves(delta) if want_err else [None] * len(enc)
        num = jnp.zeros((), jnp.float32)
        den = jnp.zeros((), jnp.float32)
        for i, ((wire, meta), d) in enumerate(zip(enc, dleaves)):
            if name == "topk":
                vals, idx = wire
                self._acc[i] = scatter_acc_flat(
                    self._acc[i], vals, idx, w,
                    use_kernel=self.use_kernel, interpret=self.interpret)
                if want_err:
                    f = d.astype(jnp.float32).reshape(-1)
                    dv = f[idx]
                    den += jnp.sum(f * f)
                    num += jnp.sum(f * f) - jnp.sum(dv * dv) \
                        + jnp.sum((vals - dv) ** 2)
                continue
            scale = meta if name == "int8" else 1.0
            flat = wire.reshape(-1)
            self._acc[i] = dequant_acc_flat(
                self._acc[i], flat, scale, w,
                use_kernel=self.use_kernel, interpret=self.interpret)
            if want_err:
                f = d.astype(jnp.float32).reshape(-1)
                dec = flat.astype(jnp.float32)
                if name == "int8":
                    dec = dec * meta
                den += jnp.sum(f * f)
                num += jnp.sum((dec - f) ** 2)
        self.wsum += w
        self.folds += 1
        if delta is None:
            return None
        if name == "none":
            return 0.0
        return math.sqrt(max(to_host(num), 0.0)) \
            / max(math.sqrt(to_host(den)), 1e-12)

    def finalize(self):
        """Weighted mean tree (template structure/shapes/dtypes), or
        None when nothing folded."""
        if self._acc is None or self.folds == 0 or self.wsum <= 0.0:
            return None
        inv = 1.0 / self.wsum
        leaves = [(a * inv).reshape(t.shape).astype(t.dtype)
                  for a, t in zip(self._acc,
                                  jax.tree.leaves(self._template))]
        return jax.tree.unflatten(jax.tree.structure(self._template), leaves)


@functools.partial(jax.jit, static_argnames=("n",))
def _topk_batched_mean(vals: jnp.ndarray, idx: jnp.ndarray,
                       weights: jnp.ndarray, n: int) -> jnp.ndarray:
    """vmapped per-tensor decode over the stacked client axis, then the
    weighted mean — the top-k leaves' batched form."""
    w = (weights / jnp.sum(weights)).astype(jnp.float32)
    dense = jax.vmap(
        lambda v, ix: jnp.zeros((n,), jnp.float32).at[ix].set(v))(vals, idx)
    return jnp.sum(dense * w[:, None], axis=0)


def batched_reduce(codec_name: str, encs: Sequence[EncTree],
                   weights: Sequence[float], template, *,
                   use_kernel: bool = False, interpret: bool = False,
                   mesh=None):
    """Weighted mean over a whole round's encoded uplinks, one fused
    call per leaf: dense wires stack at WIRE dtype into
    ``dequant_reduce_flat``; top-k wires batch through the vmapped
    decode.  With ``mesh``, stacked leaves land on the ``clients`` mesh
    axis via ``stacked_shardings`` before the reduce."""
    assert encs, "batched_reduce over no uplinks"
    name = _norm(codec_name)
    w = jnp.asarray(list(weights), jnp.float32)
    put = lambda a: a                                       # noqa: E731
    if mesh is not None:
        from repro.sharding.specs import (client_axis_rules,
                                          stacked_shardings)
        rules = client_axis_rules(mesh)
        put = lambda a: jax.device_put(                     # noqa: E731
            a, stacked_shardings(mesh, a, rules=rules))
    tleaves = jax.tree.leaves(template)
    out = []
    for i, t in enumerate(tleaves):
        if name == "topk":
            vals = put(jnp.stack([e[i][0][0] for e in encs]))
            idx = put(jnp.stack([e[i][0][1] for e in encs]))
            out.append(_topk_batched_mean(vals, idx, w, int(t.size))
                       .reshape(t.shape).astype(t.dtype))
            continue
        wires = put(jnp.stack([e[i][0].reshape(-1) for e in encs]))
        if name == "int8":
            scales = jnp.stack([jnp.asarray(e[i][1], jnp.float32)
                                for e in encs])
        else:
            scales = jnp.ones((len(encs),), jnp.float32)
        out.append(dequant_reduce_flat(wires, scales, w,
                                       use_kernel=use_kernel,
                                       interpret=interpret, mesh=mesh)
                   .reshape(t.shape).astype(t.dtype))
    return jax.tree.unflatten(jax.tree.structure(template), out)
