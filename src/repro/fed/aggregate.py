"""The server reduce: one interface over the ``fed.server_reduce`` modes.

Every round path of the engine (``fed/engine``) encodes each client's
update once into an :class:`Uplink` and hands it to a
:class:`ServerReduce` built by :func:`make_server_reduce`, the one reader
of the mode.  The reduce decides whether and when the wire is decoded:

  * ``decode`` (:class:`DecodeReduce`) — each landed uplink is decoded,
    rebased to a full tree and staged; one FedAvg at the end.  The
    bit-exact reference; O(C) decoded trees at the server.
  * ``stream`` (:class:`StreamReduce`) — each landed WIRE folds straight
    into one fp32 accumulator through the fused ``kernels/agg_fuse`` ops
    (:class:`StreamingAggregator`); O(1) in the cohort size.
  * ``batched`` (:class:`BatchedReduce`) — the round's wires stacked per
    leaf and reduced in one fused call (:func:`batched_reduce`), placed on
    the ``clients`` mesh axis when a mesh is attached.

Each reduce also measures a client's ``codec_error`` the way its mode
always has — the decode reduce on the decoded tree, on the host in
float64 (``transport.tree_rel_error``); the wire reduces from the wire, on
the device (:func:`codec_rel_error`: top-k never densifies) — and counts
the peak number of decoded trees it holds (``peak_live_trees``).  On the
async path it decides when the decode happens: :meth:`ServerReduce.stage`
when the client sends, :meth:`ServerReduce.land` when the update arrives.

The folds read each wire through its codec (``fed/transport.Codec``):
``codec.sparse`` picks the scatter kernel over the dense dequant kernel,
and ``codec.dequant_scale`` / ``codec.decode`` give the dense values.

Weighted mean of rebased updates equals base + weighted mean of deltas
exactly in real arithmetic but only to fma-level in float, so every
stream-vs-decode pin is tolerance-based, never bit-exact.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.fed.programs import fedavg_stacked, stack_trees
from repro.fed.transport import (Codec, EncTree, apply_delta,
                                 tree_rel_error)
from repro.kernels.agg_fuse.ops import (dequant_acc_flat, dequant_reduce_flat,
                                        scatter_acc_flat)
from repro.obs.trace import SPAN_SYNC, to_host

__all__ = ["BatchedReduce", "DecodeReduce", "ServerReduce",
           "StreamReduce", "StreamingAggregator", "Uplink", "batched_reduce",
           "codec_rel_error", "make_server_reduce"]


@dataclass
class Uplink:
    """One client's encoded update as the server receives it."""
    codec: Codec
    enc: EncTree            # codec.encode_tree payload
    nbytes: int             # priced wire bytes
    base: Any               # tree the client downloaded: the rebase target
    is_delta: bool          # the wire carries ``params - base``
    _dec: Any = field(default=None, repr=False)

    @classmethod
    def plain(cls, codec: Codec, tree) -> "Uplink":
        """A full tree sent through ``codec`` as it is (not a delta)."""
        enc, nbytes = codec.encode_tree(tree)
        return cls(codec, enc, nbytes, tree, False)

    def decoded(self):
        """The wire decoded in ``base``'s structure (fp32 for a delta).
        Kept for the next :meth:`tree`, so a decode the error measurement
        made is not made again."""
        if self._dec is None:
            self._dec = self.codec.decode_tree(
                self.enc, self.base, jnp.float32 if self.is_delta else None)
        return self._dec

    def rebase(self, dec):
        """A decoded (or reduced) wire-domain tree as a full tree."""
        return apply_delta(self.base, dec) if self.is_delta else dec

    def tree(self):
        """The full update tree; releases the kept decode."""
        dec, self._dec = self.decoded(), None
        return self.rebase(dec)


def codec_rel_error(codec: Codec, enc: EncTree, delta) -> float:
    """Relative global-L2 error of the encoded uplink vs the raw delta —
    the decode-free form of ``transport.tree_rel_error`` (top-k never
    densifies: the error splits into on-support and dropped mass).
    ``delta`` is None for a lossless codec: the error is 0."""
    if delta is None:
        return 0.0
    num = jnp.zeros((), jnp.float32)
    den = jnp.zeros((), jnp.float32)
    for (wire, meta), d in zip(enc, jax.tree.leaves(delta)):
        f = d.astype(jnp.float32).reshape(-1)
        den += jnp.sum(f * f)
        if codec.sparse:
            vals, idx = wire
            dv = f[idx]
            num += jnp.sum(f * f) - jnp.sum(dv * dv) \
                + jnp.sum((vals - dv) ** 2)
        else:
            num += jnp.sum((codec.decode(wire, meta).reshape(-1) - f) ** 2)
    return math.sqrt(max(to_host(num), 0.0)) / max(math.sqrt(to_host(den)),
                                                   1e-12)


class StreamingAggregator:
    """O(1)-memory weighted mean over one codec's encoded uplinks.

    ``init(template)`` allocates one zero fp32 accumulator per leaf;
    ``fold(enc, weight)`` adds ``weight * dequant(enc)`` through the
    fused kernels (sparse wires scatter straight into the dense
    accumulator); ``finalize()`` divides by the folded weight sum and
    restores leaf shapes/dtypes.  Live decoded-tree count is always 1 —
    the accumulator — independent of how many uplinks folded.
    """

    def __init__(self, codec: Codec, *, use_kernel: bool = False,
                 interpret: bool = False):
        self.codec = codec
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

    def fold(self, enc: EncTree, weight: float) -> None:
        """Fold one encoded uplink with fedavg weight ``weight``."""
        assert self._acc is not None, "fold() before init()"
        w = float(weight)
        kw = dict(use_kernel=self.use_kernel, interpret=self.interpret)
        for i, (wire, meta) in enumerate(enc):
            if self.codec.sparse:
                vals, idx = wire
                self._acc[i] = scatter_acc_flat(self._acc[i], vals, idx, w,
                                                **kw)
            else:
                self._acc[i] = dequant_acc_flat(
                    self._acc[i], wire.reshape(-1),
                    self.codec.dequant_scale(meta), w, **kw)
        self.wsum += w
        self.folds += 1

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
def _sparse_batched_mean(vals: jnp.ndarray, idx: jnp.ndarray,
                         weights: jnp.ndarray, n: int) -> jnp.ndarray:
    """vmapped per-tensor decode over the stacked client axis, then the
    weighted mean — the sparse leaves' batched form."""
    w = (weights / jnp.sum(weights)).astype(jnp.float32)
    dense = jax.vmap(
        lambda v, ix: jnp.zeros((n,), jnp.float32).at[ix].set(v))(vals, idx)
    return jnp.sum(dense * w[:, None], axis=0)


def batched_reduce(codec: Codec, encs: Sequence[EncTree],
                   weights: Sequence[float], template, *,
                   use_kernel: bool = False, interpret: bool = False,
                   mesh=None):
    """Weighted mean over a whole round's encoded uplinks, one fused
    call per leaf: dense wires stack at WIRE dtype into
    ``dequant_reduce_flat``; sparse wires batch through the vmapped
    decode.  With ``mesh``, stacked leaves land on the ``clients`` mesh
    axis via ``stacked_shardings`` before the reduce."""
    assert encs, "batched_reduce over no uplinks"
    w = jnp.asarray(list(weights), jnp.float32)
    put = lambda a: a                                       # noqa: E731
    if mesh is not None:
        from repro.sharding.specs import (client_axis_rules,
                                          stacked_shardings)
        rules = client_axis_rules(mesh)
        put = lambda a: jax.device_put(                     # noqa: E731
            a, stacked_shardings(mesh, a, rules=rules))
    out = []
    for i, t in enumerate(jax.tree.leaves(template)):
        if codec.sparse:
            vals = put(jnp.stack([e[i][0][0] for e in encs]))
            idx = put(jnp.stack([e[i][0][1] for e in encs]))
            out.append(_sparse_batched_mean(vals, idx, w, int(t.size))
                       .reshape(t.shape).astype(t.dtype))
            continue
        wires = put(jnp.stack([e[i][0].reshape(-1) for e in encs]))
        scales = jnp.stack([jnp.asarray(codec.dequant_scale(e[i][1]),
                                        jnp.float32) for e in encs])
        out.append(dequant_reduce_flat(wires, scales, w,
                                       use_kernel=use_kernel,
                                       interpret=interpret, mesh=mesh)
                   .reshape(t.shape).astype(t.dtype))
    return jax.tree.unflatten(jax.tree.structure(template), out)


# ---------------------------------------------------------------------------
# the server reduce: one interface, one factory
# ---------------------------------------------------------------------------

class ServerReduce:
    """One tier's reduce over a round's uplinks.

    ``error(up, delta)`` is an executed uplink's ``codec_error`` (``delta``
    is the raw delta of a lossy codec, else None); ``add(up, weight)``
    takes a landed uplink; ``result()`` is the weighted mean of what was
    added since the last ``result()``, as a full tree (None when nothing
    was), and starts the next — an edge tier reduces its cohorts one
    after another through one object.  On the async path ``stage(up)`` is
    what waits in flight and ``land`` / ``drop`` take it at arrival.
    ``peak_live_trees``: the most decoded fp32 trees held at once.
    """
    # reduces per device on a multi-device clients mesh with compiled
    # Pallas kernels (a Mosaic kernel cannot take arrays spanning devices)
    per_device = False

    def __init__(self, *, use_kernel: bool = False, interpret: bool = False,
                 mesh=None):
        self.use_kernel = bool(use_kernel)
        self.interpret = bool(interpret)
        self.mesh = mesh
        self.peak_live_trees = 0

    def error(self, up: Uplink, delta) -> float:
        return codec_rel_error(up.codec, up.enc, delta)

    def add(self, up: Uplink, weight: float) -> None:
        raise NotImplementedError

    def result(self):
        raise NotImplementedError

    def stage(self, up: Uplink):
        # the wire waits in flight; arrivals decode one at a time
        self.peak_live_trees = 1
        return up

    def land(self, staged):
        return staged.tree()

    def drop(self, staged) -> None:
        pass


class DecodeReduce(ServerReduce):
    """``decode``: stage each landed uplink as a decoded full tree, then
    one FedAvg — ``core.fedavg.fedavg``, or the fedavg Pallas kernel
    (``kernels.fedavg.ops.fedavg_trees``) with ``use_kernel``.  An edge
    cohort (``edge``) averages with ``programs.fedavg_stacked``, which has
    no per-device form.  Decoded trees stay live until the round ends."""

    def __init__(self, *, edge: bool = False, **kw):
        super().__init__(**kw)
        self.edge = bool(edge)
        self.per_device = not self.edge
        self._trees: List[Any] = []
        self._weights: List[float] = []
        self._live = 0

    def error(self, up: Uplink, delta) -> float:
        if delta is None:
            return 0.0
        # reads both trees back to the host, leaf by leaf: one span for
        # the call
        with jax.profiler.TraceAnnotation(SPAN_SYNC):
            return tree_rel_error(up.decoded(), delta)

    def _hold(self, n: int) -> None:
        self._live += n
        self.peak_live_trees = max(self.peak_live_trees, self._live)

    def add(self, up: Uplink, weight: float) -> None:
        self._trees.append(up.tree())
        self._weights.append(float(weight))
        self._hold(1)

    def result(self):
        if not self._trees:
            return None
        trees, weights = self._trees, self._weights
        self._trees, self._weights = [], []
        if self.edge:
            return fedavg_stacked(stack_trees(trees), weights,
                                  use_kernel=self.use_kernel,
                                  interpret=self.interpret)
        if self.use_kernel:
            from repro.kernels.fedavg.ops import fedavg_trees
            return fedavg_trees(trees, weights, interpret=self.interpret,
                                mesh=self.mesh)
        # deferred: repro.core.__init__ imports core.gan, which imports
        # fed.engine — a top-level import here would close that cycle
        from repro.core.fedavg import fedavg
        return fedavg(trees, weights)

    def stage(self, up: Uplink):
        self._hold(1)
        return up.tree()

    def land(self, staged):
        self._live -= 1
        return staged

    def drop(self, staged) -> None:
        self._live -= 1


class StreamReduce(ServerReduce):
    """``stream``: fold each landed wire into one accumulator
    (:class:`StreamingAggregator`); no per-device form."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._agg: Optional[StreamingAggregator] = None
        self._first: Optional[Uplink] = None

    def add(self, up: Uplink, weight: float) -> None:
        if self._agg is None:
            self._agg = StreamingAggregator(up.codec,
                                            use_kernel=self.use_kernel,
                                            interpret=self.interpret)
            self._agg.init(up.base)
            self._first = up
        self._agg.fold(up.enc, weight)
        self.peak_live_trees = 1

    def result(self):
        if self._agg is None:
            return None
        mean, self._agg = self._agg.finalize(), None
        return None if mean is None else self._first.rebase(mean)


class BatchedReduce(ServerReduce):
    """``batched``: stage the landed wires, then one
    :func:`batched_reduce` over them (per device on a clients mesh)."""
    per_device = True

    def __init__(self, **kw):
        super().__init__(**kw)
        self._staged: List[Uplink] = []
        self._weights: List[float] = []

    def add(self, up: Uplink, weight: float) -> None:
        self._staged.append(up)
        self._weights.append(weight)
        self.peak_live_trees = 1

    def result(self):
        if not self._staged:
            return None
        ups, weights = self._staged, self._weights
        self._staged, self._weights = [], []
        mean = batched_reduce(ups[0].codec, [u.enc for u in ups], weights,
                              ups[0].base, use_kernel=self.use_kernel,
                              interpret=self.interpret, mesh=self.mesh)
        return ups[0].rebase(mean)


def make_server_reduce(mode: str, *, edge: bool = False,
                       use_kernel: bool = False, interpret: bool = False,
                       mesh=None) -> ServerReduce:
    """The reduce for ``fed.server_reduce`` = ``mode`` (the one place the
    mode is read).  ``edge``: one hierarchy tier's cohort reduce at its
    edge aggregator — ``decode`` averages there without a per-device
    form, and ``batched`` has no edge form, so its cohorts ``stream``."""
    kw = dict(use_kernel=use_kernel, interpret=interpret, mesh=mesh)
    if mode == "decode":
        return DecodeReduce(edge=edge, **kw)
    if mode == "batched" and not edge:
        return BatchedReduce(**kw)
    if mode in ("stream", "batched"):
        return StreamReduce(**kw)
    raise ValueError(f"unknown server reduce {mode!r}")
