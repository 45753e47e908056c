"""Wire model for the federation runtime: links, payloads, codecs.

What actually crosses the network in the paper's protocol (§3) is small and
asymmetric:

  * **downlink** (server -> client): batches of generated fakes — the server
    never ships G itself, only its outputs (the privacy argument);
  * **uplink** (client -> server): the trained discriminator parameters
    (or parameter *deltas* when a lossy codec is enabled).

PS-FedGAN (PAPERS.md) shows this partially-shared state dominates both the
communication cost and the privacy surface, so the runtime makes it
first-class: every transfer is priced by a :class:`LinkModel` and counted in
a :class:`TrafficLedger`; uplink trees can be run through pluggable
compression codecs (fp16 / int8 quantize-dequantize / top-k sparsification
with error feedback).  Each codec defines its wire format once, per tensor
(``encode`` / ``decode`` / ``leaf_bytes``); the base :class:`Codec` builds
the tree forms — ``encode_tree``, ``decode_tree`` and ``roundtrip`` — from
those, and the server reduce (``fed/aggregate``) reads wires through them.

LAN hops *inside* one client's split chain are a third budget: when
training executes through the split (``core/split.SplitExecution``), the
measured per-boundary payloads are recorded here too (``TrafficLedger``
``lan`` column) and priced by ``core/simulate.plan_epoch_time``; the
:class:`LinkModel`\\ s in this module price the WAN between the server and
each client.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


EncTree = List[Tuple[Any, Any]]      # per-leaf (wire, meta), leaves order


def tree_bytes(tree) -> int:
    """Total payload bytes of a pytree at its native dtypes."""
    return int(sum(l.size * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(tree)))


def delta_tree(params, base):
    """The uplinked update delta ``params - base``, leafwise in fp32.

    The ONE definition of delta arithmetic on the wire: the engine's codec
    path and the sequential reference loop both use this +
    :func:`apply_delta`, so their bit-exact pin is structural rather than
    two hand-kept copies."""
    return jax.tree.map(
        lambda p, b: p.astype(jnp.float32) - b.astype(jnp.float32),
        params, base)


def apply_delta(base, delta):
    """Rebase a (possibly privatized/compressed) fp32 delta onto ``base``,
    cast back to the base dtypes — inverse of :func:`delta_tree`."""
    return jax.tree.map(
        lambda b, d: (b.astype(jnp.float32)
                      + d.astype(jnp.float32)).astype(b.dtype),
        base, delta)


def tree_rel_error(approx, exact) -> float:
    """Relative global-L2 error of ``approx`` vs ``exact`` — the engine's
    per-round measurement of what a lossy codec cost the update (the
    bytes-vs-delta-error frontier the codec controller walks)."""
    num = 0.0
    den = 0.0
    for a, e in zip(jax.tree.leaves(approx), jax.tree.leaves(exact)):
        d = np.asarray(a, np.float64) - np.asarray(e, np.float64)
        num += float(np.sum(d * d))
        den += float(np.sum(np.asarray(e, np.float64) ** 2))
    return math.sqrt(num) / max(math.sqrt(den), 1e-12)


def predict_codec_bytes(name: str, leaf_sizes: Sequence[int], *,
                        dtype_bytes: int = 4, topk_frac: float = 0.01) -> int:
    """Analytic wire bytes of one uplink per codec — a pure function of
    the tree's leaf sizes, so the codec controller can rank candidates by
    cost WITHOUT spending a probe round on each (only the delta ERROR
    needs live measurement)."""
    codec = make_codec(name, topk_frac=topk_frac)
    return int(sum(codec.leaf_bytes(n, dtype_bytes) for n in leaf_sizes))


def fake_batch_bytes(batch: int, image_shape: Tuple[int, ...],
                     dtype_bytes: int = 4) -> int:
    """Downlink bytes for one batch of generated fakes."""
    n = batch
    for s in image_shape:
        n *= s
    return int(n * dtype_bytes)


@dataclass(frozen=True)
class LinkModel:
    """One-way link: fixed latency plus serialization at ``bandwidth_bps``."""
    latency_s: float = 0.050
    bandwidth_bps: float = 10e6

    def transfer_time(self, nbytes: int) -> float:
        return self.latency_s + 8.0 * nbytes / max(self.bandwidth_bps, 1.0)


@dataclass
class TrafficLedger:
    """Per-round, per-client byte accounting (benchmarks read this).

    Four budgets: WAN uplink (D params/deltas — under hierarchical
    aggregation, keyed by ``cohort<k>`` since only the pre-reduced edge
    aggregates cross the WAN), WAN downlink (fake batches), the LAN
    *inside* each client's split chain — the measured per-boundary
    payloads of executed split training
    (``core/split.SplitExecution.step_wire_bytes``), zero when the client
    trains unsplit — and the client→edge tier: bytes each client uplinks
    to its edge aggregator before the cohort pre-reduce (empty on the
    flat path).
    """
    up_bytes: Dict[str, int] = field(default_factory=dict)
    down_bytes: Dict[str, int] = field(default_factory=dict)
    lan_bytes: Dict[str, int] = field(default_factory=dict)
    edge_bytes: Dict[str, int] = field(default_factory=dict)
    # observability hook: called as observer(client_id, up, down, lan) on
    # every record (repro.obs feeds per-client wire counters from it);
    # None — the default — keeps the ledger a plain accumulator
    observer: Optional[Callable[[str, int, int, int], None]] = \
        field(default=None, repr=False, compare=False)
    # separate hook for the edge tier — keeps the 4-arg observer
    # signature stable for installed observers that predate hierarchy
    edge_observer: Optional[Callable[[str, int], None]] = \
        field(default=None, repr=False, compare=False)

    def record(self, client_id: str, *, up: int = 0, down: int = 0,
               lan: int = 0) -> None:
        self.up_bytes[client_id] = self.up_bytes.get(client_id, 0) + int(up)
        self.down_bytes[client_id] = (self.down_bytes.get(client_id, 0)
                                      + int(down))
        if lan:
            self.lan_bytes[client_id] = (self.lan_bytes.get(client_id, 0)
                                         + int(lan))
        if self.observer is not None:
            self.observer(client_id, int(up), int(down), int(lan))

    def record_edge(self, client_id: str, nbytes: int) -> None:
        """Client→edge uplink bytes (the pre-reduce hop)."""
        self.edge_bytes[client_id] = (self.edge_bytes.get(client_id, 0)
                                      + int(nbytes))
        if self.edge_observer is not None:
            self.edge_observer(client_id, int(nbytes))

    @property
    def total_up(self) -> int:
        return sum(self.up_bytes.values())

    @property
    def total_down(self) -> int:
        return sum(self.down_bytes.values())

    @property
    def total_lan(self) -> int:
        return sum(self.lan_bytes.values())

    @property
    def total_edge(self) -> int:
        return sum(self.edge_bytes.values())


# ---------------------------------------------------------------------------
# Codecs — quantize-dequantize transforms over uplink parameter trees
# ---------------------------------------------------------------------------

class Codec:
    """One uplink wire format, defined once per tensor.

    A codec writes down its format in three places and nowhere else:
    ``encode(x) -> (wire, meta)``, ``decode(wire, meta, dtype)`` and
    ``leaf_bytes`` (the priced wire bytes of one tensor).  The tree forms
    are built here from those: ``encode_tree``, ``decode_tree`` and
    ``roundtrip``.  ``encode``/``decode`` are jit-compatible pure
    functions of the tensor, which is how the split executor's boundary
    stages run them inside a training step.

    ``encodes_delta`` controls what the engine feeds it: raw parameters
    (identity — keeps the sync path bit-exact) or the update delta
    ``params - global`` (all lossy codecs: compressing deltas is the
    standard trick — they are near-zero-mean and tolerate quantization).
    ``sparse`` marks a wire of kept ``(values, flat indices)`` rather than
    a dense buffer: the server scatters it instead of dequantizing it
    (``fed/aggregate``).

    Stateful codecs (top-k with error feedback) carry a residual across
    ``encode_tree`` calls, so the engine keeps ONE codec instance PER
    CLIENT.
    """
    name = "none"
    encodes_delta = False
    sparse = False

    def encode(self, x: jnp.ndarray) -> Tuple[Any, Any]:
        """One tensor -> (wire buffer(s), decode metadata)."""
        raise NotImplementedError

    def decode(self, wire, meta, dtype=jnp.float32) -> jnp.ndarray:
        """Inverse of ``encode``: what the receiver computes on."""
        raise NotImplementedError

    def leaf_bytes(self, size: int, itemsize: int = 4) -> int:
        """Wire bytes of one tensor of ``size`` elements."""
        raise NotImplementedError

    def dequant_scale(self, meta):
        """Scale of a dense wire: ``decode(wire, meta) == wire * scale``."""
        del meta
        return 1.0

    def encode_tree(self, tree) -> Tuple[EncTree, int]:
        """Per-leaf ``(wire, meta)`` in ``jax.tree.leaves`` order plus the
        priced wire bytes."""
        leaves = jax.tree.leaves(tree)
        return ([self.encode(l) for l in leaves],
                int(sum(self.leaf_bytes(l.size, jnp.dtype(l.dtype).itemsize)
                        for l in leaves)))

    def decode_tree(self, enc: EncTree, template, dtype=None):
        """Decode an ``encode_tree`` payload into ``template``'s structure;
        each leaf is cast to ``dtype`` (default: the template leaf's)."""
        leaves = [self.decode(w, m, t.dtype if dtype is None else dtype)
                  for (w, m), t in zip(enc, jax.tree.leaves(template))]
        return jax.tree.unflatten(jax.tree.structure(template), leaves)

    def roundtrip(self, tree) -> Tuple[Any, int]:
        """``(decoded_tree, wire_bytes)`` of one trip over the wire."""
        enc, nbytes = self.encode_tree(tree)
        return self.decode_tree(enc, tree), nbytes


class IdentityCodec(Codec):
    """No compression; wire bytes = native tree bytes."""
    name = "none"
    encodes_delta = False

    def encode(self, x: jnp.ndarray) -> Tuple[Any, Any]:
        return x, None

    def decode(self, wire, meta, dtype=jnp.float32) -> jnp.ndarray:
        # the leaf itself: no cast and no device op on the lossless path
        del meta, dtype
        return wire

    def leaf_bytes(self, size: int, itemsize: int = 4) -> int:
        return size * itemsize


class FP16Codec(Codec):
    """Cast leaves to fp16 on the wire, back to native dtype on arrival."""
    name = "fp16"
    encodes_delta = True

    def encode(self, x: jnp.ndarray) -> Tuple[Any, Any]:
        return x.astype(jnp.float16), None

    def decode(self, wire, meta, dtype=jnp.float32) -> jnp.ndarray:
        del meta
        return wire.astype(dtype)

    def leaf_bytes(self, size: int, itemsize: int = 4) -> int:
        return size * 2


class Int8Codec(Codec):
    """Per-leaf symmetric int8 quantization: q = round(x / s), s = amax/127.

    Wire cost: 1 byte per element + one fp32 scale per leaf.
    """
    name = "int8"
    encodes_delta = True

    def encode(self, x: jnp.ndarray) -> Tuple[Any, Any]:
        f = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(f))
        # zero-range (all-constant-zero) delta: any positive scale maps
        # q=0 back to exact zeros; 1.0 avoids the subnormal division a
        # tiny epsilon scale would do
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(f / scale), -127, 127).astype(jnp.int8)
        return q, scale

    def decode(self, wire, meta, dtype=jnp.float32) -> jnp.ndarray:
        return (wire.astype(jnp.float32) * meta).astype(dtype)

    def dequant_scale(self, meta):
        return meta

    def leaf_bytes(self, size: int, itemsize: int = 4) -> int:
        return size + 4


class TopKCodec(Codec):
    """Magnitude top-k sparsification with error feedback (Stich et al.).

    Keeps the ``frac`` largest-|x| entries per leaf; the dropped mass is
    carried in a residual and added back before the next round's selection,
    so nothing is lost permanently — only delayed.  Wire cost: 8 bytes per
    kept entry (fp32 value + int32 index).
    """
    name = "topk"
    encodes_delta = True
    sparse = True

    def __init__(self, frac: float = 0.01, error_feedback: bool = True):
        self.frac = float(frac)
        self.error_feedback = bool(error_feedback)
        self._residual: Optional[Any] = None

    def _k(self, n: int) -> int:
        # clamp k into [1, n]: frac >= 1 (or tiny leaves) means keep
        # everything — lax.top_k raises on k > n
        return min(n, max(1, int(math.ceil(self.frac * n))))

    def encode(self, x: jnp.ndarray) -> Tuple[Any, Any]:
        """The kept values + their flat indices; ``meta`` is the shape."""
        flat = x.astype(jnp.float32).reshape(-1)
        _, idx = jax.lax.top_k(jnp.abs(flat), self._k(flat.size))
        return (flat[idx], idx.astype(jnp.int32)), x.shape

    def decode(self, wire, meta, dtype=jnp.float32) -> jnp.ndarray:
        vals, idx = wire
        return jnp.zeros((math.prod(meta),), jnp.float32).at[idx] \
            .set(vals).reshape(meta).astype(dtype)

    def leaf_bytes(self, size: int, itemsize: int = 4) -> int:
        return 8 * self._k(size)

    def encode_tree(self, tree) -> Tuple[EncTree, int]:
        """Error feedback: adds the carried residual before selection,
        then carries what was dropped — the with-residual leaf with its
        kept entries zeroed (top-k indices are distinct), so no densified
        decode is ever built."""
        if self.error_feedback and self._residual is not None:
            tree = jax.tree.map(lambda l, r: l + r.astype(l.dtype),
                                tree, self._residual)
        enc, nbytes = super().encode_tree(tree)
        if self.error_feedback:
            self._residual = jax.tree.unflatten(
                jax.tree.structure(tree),
                [l.astype(jnp.float32).reshape(-1).at[idx].set(0.0)
                 .reshape(l.shape)
                 for l, ((_, idx), _) in zip(jax.tree.leaves(tree), enc)])
        return enc, nbytes


def make_codec(name: str, *, topk_frac: float = 0.01,
               error_feedback: bool = True) -> Codec:
    """Factory keyed by ``config.FedConfig.codec``."""
    if name in ("none", "", "identity"):
        return IdentityCodec()
    if name == "fp16":
        return FP16Codec()
    if name == "int8":
        return Int8Codec()
    if name == "topk":
        return TopKCodec(topk_frac, error_feedback)
    raise ValueError(f"unknown codec {name!r}")
