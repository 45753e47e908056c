"""Model splitting (paper §3.2/§4): the SplitPlan as the *executed* local
step, not just a pricing artifact.

The planner is model-agnostic: it consumes an ordered list of
(layer_name, cost) pairs — the DCGAN discriminator's conv blocks, or any
assigned transformer architecture's blocks (the paper's technique applied
beyond GANs; see DESIGN.md §4).  A :class:`SplitPlan` records which device
trains which contiguous layer range.

Two execution layers sit on top of the plan:

  * ``split_forward`` / ``boundary_activations`` — the inference-only walk:
    portion-by-portion forward, bit-identical to the unsplit forward, with a
    hook at every device hand-off (the privacy subsystem's original
    observation point).
  * :class:`SplitExecution` — the *training* step.  It compiles the plan
    into a staged ``value_and_grad``: the forward runs device-segment by
    device-segment (``jax.vjp`` per segment), the backward walks the same
    segments in reverse, and EVERY tensor that crosses a segment boundary —
    the smashed activation on the way forward, its gradient on the way back
    — passes through a :class:`BoundaryStage` first.  With the identity
    stage the composed gradient is bit-exact with the monolithic
    ``jax.value_and_grad`` (pinned in tests/test_split_selection.py); codec
    stages (``fed/transport``) and Gaussian clip+noise stages
    (``privacy/defenses``) model lossy/noisy LAN links, exactly what
    SplitFed-style deployments ship.  Stages are applied straight-through
    (not differentiated): they model the wire, not the math.

The same object prices what it executes: ``step_wire_bytes`` measures the
per-boundary LAN payload of one local step (``tree_bytes`` of the staged
tensors / the codec's wire bytes), which ``core/simulate.plan_epoch_time``
consumes in place of the paper's fixed 50 ms hop constant and
``fed/transport.TrafficLedger`` records per round.  ``fed/programs.
make_local_step(..., split_exec=...)`` builds the client-side training step
from this staged execution, so split training composes with every backend,
scheduler, codec and privacy mode — plan → execute → measure → attack,
instead of plan → price.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.devices import Client, Device


@dataclass(frozen=True)
class Portion:
    """A contiguous run of layers assigned to one device."""
    device_id: str
    layer_names: Tuple[str, ...]
    cost: float                 # sum of layer costs (compute units)


@dataclass
class SplitPlan:
    client_id: str
    portions: List[Portion] = field(default_factory=list)

    @property
    def num_boundaries(self) -> int:
        """Device-to-device hand-offs along the chain (LAN hops, fwd)."""
        n = 0
        for a, b in zip(self.portions, self.portions[1:]):
            if a.device_id != b.device_id:
                n += 1
        return n

    def layers_in_order(self) -> List[str]:
        return [n for p in self.portions for n in p.layer_names]

    def device_loads(self) -> Dict[str, float]:
        loads: Dict[str, float] = {}
        for p in self.portions:
            loads[p.device_id] = loads.get(p.device_id, 0.0) + p.cost
        return loads

    def validate(self, layer_names: Sequence[str]) -> None:
        got = self.layers_in_order()
        if got != list(layer_names):
            raise ValueError(
                f"split plan does not cover the model in order:\n"
                f"  expected {list(layer_names)}\n  got      {got}")


class InfeasibleSplit(Exception):
    """Client lacks capacity to host the model (paper: client is dropped)."""


# ---------------------------------------------------------------------------
# split execution — numerically identical to the unsplit forward
# ---------------------------------------------------------------------------

def split_forward(x, plan: SplitPlan,
                  apply_layer: Callable[[str, object], object],
                  boundary_hook: Optional[Callable[[int, str, str, object],
                                                   None]] = None):
    """Run a forward pass portion-by-portion, as the devices would.

    ``apply_layer(name, x) -> x`` applies one named layer. On real FSL
    hardware each portion runs on its own device with activations crossing
    the LAN at portion boundaries; here the boundary is a list hop, and the
    result is bit-identical to the monolithic forward (tested property).

    ``boundary_hook(boundary_idx, from_device, to_device, activation)`` is
    called at every device-to-device hand-off with the smashed activation
    that would cross the LAN — the observation point of the privacy
    subsystem's activation-inversion attack (privacy/attacks.py).
    """
    n_boundary = 0
    for pi, portion in enumerate(plan.portions):
        for name in portion.layer_names:
            x = apply_layer(name, x)
        if boundary_hook is not None and pi + 1 < len(plan.portions):
            nxt = plan.portions[pi + 1]
            if nxt.device_id != portion.device_id:
                boundary_hook(n_boundary, portion.device_id,
                              nxt.device_id, x)
                n_boundary += 1
    return x


def boundary_activations(x, plan: SplitPlan,
                         apply_layer: Callable[[str, object], object]
                         ) -> List[Tuple[int, str, str, object]]:
    """All (boundary_idx, from_device, to_device, activation) tuples a LAN
    observer sees during one split forward pass."""
    seen: List[Tuple[int, str, str, object]] = []
    split_forward(x, plan, apply_layer,
                  boundary_hook=lambda i, a, b, act: seen.append(
                      (i, a, b, act)))
    return seen


# ---------------------------------------------------------------------------
# staged training execution: segments, boundary stages, SplitExecution
# ---------------------------------------------------------------------------

def plan_segments(plan: SplitPlan) -> List[Tuple[str, Tuple[str, ...]]]:
    """Merge consecutive same-device portions into *device segments*.

    A segment is the unit of staged execution: activations only cross the
    LAN between segments, so ``len(segments) - 1 == plan.num_boundaries``.
    """
    segs: List[Tuple[str, Tuple[str, ...]]] = []
    for p in plan.portions:
        if segs and segs[-1][0] == p.device_id:
            segs[-1] = (p.device_id, segs[-1][1] + p.layer_names)
        else:
            segs.append((p.device_id, p.layer_names))
    return segs


@dataclass(frozen=True)
class Boundary:
    """One LAN hand-off in the executed chain."""
    index: int
    from_device: str
    to_device: str
    depth: int                  # layers applied before the hand-off


def partition_params(plan: SplitPlan, params) -> List[Dict[str, Any]]:
    """Partition a {layer_name: subtree} param tree by portion: what each
    device actually holds.  Layers absent from ``params`` (shared heads
    etc.) are skipped."""
    return [{n: params[n] for n in p.layer_names if n in params}
            for p in plan.portions]


def tensor_wire_bytes(shape: Sequence[int],
                      dtype=jnp.float32) -> int:
    """Native payload bytes of one boundary tensor (identity wire)."""
    n = 1
    for s in shape:
        n *= int(s)
    return n * jnp.dtype(dtype).itemsize


class BoundaryStage:
    """What happens to a tensor as it crosses a segment boundary.

    ``apply(x, key)`` transforms the tensor (identity here); ``wire_bytes``
    prices what the transformed tensor costs on the LAN.  Stages are
    straight-through: the backward pass applies the stage to the crossing
    *gradient* but never differentiates through the stage itself — noise
    and compression model the wire, not the computation.
    """
    name = "identity"
    stochastic = False          # True => ``apply`` consumes the key

    @property
    def signature(self) -> Tuple:
        """Compilation identity: stages with equal signatures compile to
        the same staged program.  Subclasses with parameters MUST include
        them, or differently-parameterized stages would silently share one
        compiled step (``fed/programs.LocalProgram`` dedups on this)."""
        return (self.name,)

    def apply(self, x: jnp.ndarray, key=None) -> jnp.ndarray:
        del key
        return x

    def wire_bytes(self, shape: Sequence[int], dtype=jnp.float32) -> int:
        return tensor_wire_bytes(shape, dtype)


class CodecBoundaryStage(BoundaryStage):
    """Run each boundary tensor through a transport codec round-trip
    (``fed/transport``): the downstream device computes on what a
    compressed LAN link would actually deliver.

    Only stateless codecs compose with jit-compiled training steps —
    ``make_boundary_stage`` constructs top-k *without* error feedback.
    """
    stochastic = False

    def __init__(self, codec):
        if getattr(codec, "error_feedback", False):
            raise ValueError(
                "stateful codecs (top-k error feedback) cannot run inside "
                "a jitted training step; build with error_feedback=False")
        self.codec = codec
        self.name = codec.name

    @property
    def signature(self) -> Tuple:
        return (self.name, float(getattr(self.codec, "frac", 0.0)))

    def apply(self, x: jnp.ndarray, key=None) -> jnp.ndarray:
        del key
        dec, _ = self.codec.roundtrip(x)
        return dec

    def wire_bytes(self, shape: Sequence[int], dtype=jnp.float32) -> int:
        return self.codec.leaf_bytes(math.prod(shape),
                                     jnp.dtype(dtype).itemsize)


class GaussianBoundaryStage(BoundaryStage):
    """Per-example clip + Gaussian noise on every crossing tensor — the
    split-learning analogue of DP-SGD's privatized release, applied to the
    smashed activation (fwd) and its gradient (bwd) at the LAN surface the
    activation-inversion attack observes (privacy/attacks.py)."""
    name = "dp"
    stochastic = True

    def __init__(self, clip: float, sigma: float):
        self.clip = float(clip)
        self.sigma = float(sigma)

    @property
    def signature(self) -> Tuple:
        return (self.name, self.clip, self.sigma)

    def apply(self, x: jnp.ndarray, key=None) -> jnp.ndarray:
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        norms = jnp.linalg.norm(flat, axis=1)
        scale = jnp.minimum(1.0, self.clip / jnp.maximum(norms, 1e-12))
        y = flat * scale[:, None]
        if self.sigma > 0.0 and key is not None:
            y = y + self.sigma * self.clip * jax.random.normal(
                key, y.shape, jnp.float32)
        return y.reshape(x.shape).astype(x.dtype)


class ComposedBoundaryStage(BoundaryStage):
    """Sequential composition of boundary stages (applied in listed
    order, e.g. ``int8+dp`` = codec round-trip, then clip+noise).

    Wire pricing uses the FIRST codec stage in the chain (the codec's
    encoding is the payload that crosses the LAN; the clip+noise is the
    sender-side privatization of what that encoding will deliver).  The
    step key is passed to every sub-stage unchanged — only one
    stochastic stage may appear per composition, which keeps the fused
    implementation bit-compatible."""

    def __init__(self, stages: Sequence[BoundaryStage]):
        self.stages_seq = list(stages)
        if sum(1 for s in self.stages_seq if s.stochastic) > 1:
            raise ValueError("at most one stochastic stage per composition")
        self.name = "+".join(s.name for s in self.stages_seq)
        self.stochastic = any(s.stochastic for s in self.stages_seq)

    @property
    def signature(self) -> Tuple:
        return ("compose",) + tuple(s.signature for s in self.stages_seq)

    def apply(self, x: jnp.ndarray, key=None) -> jnp.ndarray:
        for s in self.stages_seq:
            x = s.apply(x, key)
        return x

    def wire_bytes(self, shape: Sequence[int], dtype=jnp.float32) -> int:
        for s in self.stages_seq:
            if isinstance(s, CodecBoundaryStage):
                return s.wire_bytes(shape, dtype)
        return tensor_wire_bytes(shape, dtype)


class FusedBoundaryStage(BoundaryStage):
    """``codec + dp`` composition in ONE traversal: quantize/dequantize,
    per-example clip and Gaussian noise fused into a single pass
    (``kernels/boundary_fuse``) instead of the three separate traversals
    ``CodecBoundaryStage`` → ``GaussianBoundaryStage`` makes over every
    shipped tensor.  Numerics are pinned against the unfused composition
    (tests/test_pipeline.py); fusable codecs are the elementwise ones
    (``fp16``, ``int8`` and the degenerate ``none``) — global top-k
    selection is not streamable tile-by-tile and stays composed."""

    FUSABLE = ("none", "fp16", "int8")
    stochastic = True

    def __init__(self, codec_name: str, clip: float, sigma: float, *,
                 use_kernel: bool = False, interpret: bool = False):
        if codec_name not in self.FUSABLE:
            raise ValueError(f"codec {codec_name!r} is not fusable "
                             f"(expected one of {self.FUSABLE})")
        self.codec_name = codec_name
        self.clip = float(clip)
        self.sigma = float(sigma)
        self.use_kernel = bool(use_kernel)
        self.interpret = bool(interpret)
        self.name = "dp" if codec_name == "none" else f"{codec_name}+dp"

    @property
    def signature(self) -> Tuple:
        return ("fused", self.codec_name, self.clip, self.sigma,
                self.use_kernel, self.interpret)

    def apply(self, x: jnp.ndarray, key=None) -> jnp.ndarray:
        from repro.kernels.boundary_fuse.ops import fused_boundary_flat
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        noise_scale = 0.0
        noise = jnp.zeros_like(flat)
        if self.sigma > 0.0 and key is not None:
            # Same draw (key, flat shape) as GaussianBoundaryStage, so
            # fused == composed holds bit-for-bit per noise sample.
            noise_scale = self.sigma * self.clip
            noise = jax.random.normal(key, flat.shape, jnp.float32)
        y = fused_boundary_flat(flat, self.clip, noise_scale, noise,
                                codec=self.codec_name,
                                use_kernel=self.use_kernel,
                                interpret=self.interpret)
        return y.reshape(x.shape).astype(x.dtype)

    def wire_bytes(self, shape: Sequence[int], dtype=jnp.float32) -> int:
        from repro.fed.transport import make_codec
        return make_codec(self.codec_name).leaf_bytes(
            math.prod(shape), jnp.dtype(dtype).itemsize)


def make_boundary_stage(split_cfg, name: Optional[str] = None
                        ) -> BoundaryStage:
    """Factory keyed by ``config.SplitConfig.boundary_stage``; ``name``
    overrides it (the split controller builds per-boundary stages from the
    same clip/sigma/frac parameters, varying only the stage kind).

    Composed names (``"fp16+dp"``, ``"int8+dp"``, ``"topk+dp"``) chain
    stages in order; when the chain is a fusable codec followed by
    ``dp`` and ``split_cfg.fuse_boundary`` is not disabled, the fused
    single-traversal implementation is selected automatically.
    """
    if name is None:
        name = getattr(split_cfg, "boundary_stage", "identity")
    if "+" in name:
        parts = [p for p in name.split("+") if p]
        if (len(parts) == 2 and parts[1] == "dp"
                and parts[0] in FusedBoundaryStage.FUSABLE
                and getattr(split_cfg, "fuse_boundary", True)):
            return FusedBoundaryStage(
                parts[0], split_cfg.stage_clip, split_cfg.stage_sigma,
                use_kernel=getattr(split_cfg, "use_kernel", False),
                interpret=getattr(split_cfg, "kernel_interpret", False))
        return ComposedBoundaryStage(
            [make_boundary_stage(split_cfg, p) for p in parts])
    if name in ("", "identity", "none"):
        return BoundaryStage()
    if name == "dp":
        return GaussianBoundaryStage(split_cfg.stage_clip,
                                     split_cfg.stage_sigma)
    from repro.fed.transport import make_codec
    return CodecBoundaryStage(make_codec(
        name, topk_frac=getattr(split_cfg, "topk_frac", 0.01),
        error_feedback=False))


class SplitExecution:
    """A :class:`SplitPlan` compiled into the executed local training step.

    ``apply_layer(name, params, x) -> x`` applies one named layer;
    ``tails`` is one scalar loss tail per forward pass (the GAN D loss is
    two passes: BCE(real, 1) and BCE(fake, 0)).  Both passes traverse the
    SAME boundaries per step — each hand-off ships one tensor per pass per
    direction.

    ``value_and_grad`` is jit/vmap-compatible and, under the identity
    stage, bit-exact with ``jax.value_and_grad`` of the monolithic loss:
    each device segment contributes its parameters' gradients through its
    own ``jax.vjp``, and the cotangent chain crosses boundaries exactly
    where the activations did (pinned property).
    """

    def __init__(self, plan: SplitPlan, apply_layer, tails: Sequence, *,
                 stage: Optional[BoundaryStage] = None,
                 stages: Optional[Sequence[BoundaryStage]] = None,
                 pipeline_microbatches: int = 1,
                 pipeline_scan: bool = False):
        """``stage`` applies one stage uniformly at every boundary;
        ``stages`` assigns a stage PER boundary (index-aligned with
        ``self.boundaries``) — the split controller's lever for noising
        only the boundaries the attack actually reads.  Passing both uses
        ``stages`` and keeps ``stage`` as the documented uniform default.

        ``pipeline_microbatches`` > 1 makes ``value_and_grad`` run the
        1F1B-pipelined step (``run_pipelined``): each batch splits into
        that many micro-batches so device segments overlap, with the
        per-batch wall time priced by ``overlap_schedule`` instead of
        the additive chain.  ``1`` (default) is the sequential step,
        bit-exact with the pre-pipeline executor.

        ``pipeline_scan`` compiles the K-micro-batch loop as ONE
        ``lax.scan`` over the chunk axis instead of K unrolled copies of
        the staged chain — trace size (and compile time) O(1) in K,
        tolerance-pinned against the unrolled loop.  Only the
        non-collecting path scans; ``collect=True`` (boundary-tensor
        capture) keeps the Python loop, whose per-chunk records it needs.
        """
        self.plan = plan
        self.apply_layer = apply_layer
        self.tails = tuple(tails)
        self.stage = stage or BoundaryStage()
        self.pipeline_microbatches = max(1, int(pipeline_microbatches))
        self.pipeline_scan = bool(pipeline_scan)
        self.segments = plan_segments(plan)
        self.boundaries: List[Boundary] = []
        depth = 0
        for i, (dev, names) in enumerate(self.segments[:-1]):
            depth += len(names)
            self.boundaries.append(Boundary(
                i, dev, self.segments[i + 1][0], depth))
        if stages is None:
            self.stages: List[BoundaryStage] = \
                [self.stage] * len(self.boundaries)
        else:
            self.stages = list(stages)
            if len(self.stages) != len(self.boundaries):
                raise ValueError(
                    f"{len(self.stages)} stages for "
                    f"{len(self.boundaries)} boundaries")
        self._shape_cache: Dict[Tuple, List[Tuple[int, ...]]] = {}

    # ------------------------------------------------------------------
    @property
    def num_boundaries(self) -> int:
        return len(self.boundaries)

    @property
    def num_passes(self) -> int:
        return len(self.tails)

    @property
    def stochastic(self) -> bool:
        """True when ANY boundary's stage consumes the noise key."""
        return any(s.stochastic for s in self.stages)

    @property
    def signature(self) -> Tuple:
        """Compilation key: two plans with the same boundary depths and
        the same (fully parameterized) per-boundary stages compile to the
        same staged program — device *identity* only affects pricing,
        never math.  Pipelined executions (``pipeline_microbatches > 1``)
        carry K in the signature: a pipelined step compiles to different
        XLA than the sequential one and must never share its cache slot
        (``fed/programs.LocalProgram`` dedups on this)."""
        base = (tuple(b.depth for b in self.boundaries),
                tuple(s.signature for s in self.stages))
        if self.pipeline_microbatches > 1:
            tag = "pipeline-scan" if self.pipeline_scan else "pipeline"
            return base + ((tag, self.pipeline_microbatches),)
        return base

    # ------------------------------------------------------------------
    def _segment_fn(self, names: Tuple[str, ...]):
        def seg(params, xs):
            out = []
            for x in xs:
                for n in names:
                    x = self.apply_layer(n, params, x)
                out.append(x)
            return tuple(out)
        return seg

    def _key(self, key, b: int, p: int, direction: int):
        """Per-(boundary, pass, direction) stage key, collision-free within
        one step (direction: 0 fwd, 1 bwd)."""
        if key is None:
            return None
        return jax.random.fold_in(
            key, 1 + (b * self.num_passes + p) * 2 + direction)

    # ------------------------------------------------------------------
    def run(self, params, batches: Sequence[jnp.ndarray], key=None,
            collect: bool = False):
        """One staged forward+backward over per-pass ``batches``.

        Returns ``(loss, grads, records)``; ``records`` (when ``collect``)
        holds the staged tensors that actually crossed each boundary:
        ``records["fwd"][b][p]`` / ``records["bwd"][b][p]`` for boundary
        ``b``, pass ``p`` — the exact artifacts a LAN observer captures.
        """
        if len(batches) != self.num_passes:
            raise ValueError(f"{len(batches)} batches for "
                             f"{self.num_passes} loss tails")
        if key is None and self.stochastic:
            # a stochastic stage must NEVER run keyless-and-noiseless: the
            # observed/collected tensors would understate the stage and
            # overstate leakage.  Default key == run_looped's default.
            key = jax.random.PRNGKey(0)
        records = {"fwd": [None] * self.num_boundaries,
                   "bwd": [None] * self.num_boundaries}
        xs = tuple(batches)
        vjps = []
        for si, (dev, names) in enumerate(self.segments):
            xs, vjp = jax.vjp(self._segment_fn(names), params, xs)
            vjps.append(vjp)
            if si < len(self.segments) - 1:
                xs = tuple(self.stages[si].apply(x, self._key(key, si, p, 0))
                           for p, x in enumerate(xs))
                if collect:
                    records["fwd"][si] = xs

        def total_loss(zs):
            return sum(tail(z) for tail, z in zip(self.tails, zs))

        loss, tail_vjp = jax.vjp(total_loss, xs)
        (g_act,) = tail_vjp(jnp.ones_like(loss))
        grads = None
        for si in range(len(self.segments) - 1, -1, -1):
            gp, g_act = vjps[si](g_act)
            grads = gp if grads is None \
                else jax.tree.map(jnp.add, grads, gp)
            if si > 0:
                g_act = tuple(
                    self.stages[si - 1].apply(g, self._key(key, si - 1, p, 1))
                    for p, g in enumerate(g_act))
                if collect:
                    records["bwd"][si - 1] = g_act
        return loss, grads, records

    def run_pipelined(self, params, batches: Sequence[jnp.ndarray],
                      key=None, collect: bool = False,
                      num_microbatches: Optional[int] = None):
        """The 1F1B-pipelined local step: split each pass's batch into K
        equal micro-batches and run the staged chain per micro-batch, so
        on real hardware segment ``s`` of micro-batch ``m`` overlaps
        segment ``s+1`` of micro-batch ``m-1`` (the schedule
        ``overlap_schedule`` prices).  Math: with equal chunks and
        mean-reducing loss tails, loss and grads are the micro-batch
        means — tolerance-pinned against the mean of per-chunk monolithic
        gradients (the exact equivalence; batch-norm layers see
        per-micro-batch statistics, the usual grad-accumulation shift
        from the full-batch gradient).

        ``K = 1`` (or a batch K does not divide — clamped to the nearest
        divisor, see ``core.pipeline.effective_microbatches``) falls
        through to ``run`` unchanged: bit-exact with the sequential
        step, pinned.  Stochastic stage keys fold the micro-batch index
        (``fold_in(key, m)``) so micro-batches draw independent noise;
        at ``K = 1`` the key is used as-is, preserving the pin.
        """
        from repro.core.pipeline import effective_microbatches
        if len(batches) != self.num_passes:
            raise ValueError(f"{len(batches)} batches for "
                             f"{self.num_passes} loss tails")
        req = self.pipeline_microbatches if num_microbatches is None \
            else int(num_microbatches)
        bsz = min(int(b.shape[0]) for b in batches)
        k = effective_microbatches(bsz, req)
        if k == 1:
            return self.run(params, batches, key, collect)
        if key is None and self.stochastic:
            key = jax.random.PRNGKey(0)
        mb = bsz // k
        if self.pipeline_scan and not collect:
            return self._run_pipelined_scan(params, batches, key, k, mb)
        loss = None
        grads = None
        recs = []
        for m in range(k):
            chunk = tuple(b[m * mb:(m + 1) * mb] for b in batches)
            mkey = None if key is None else jax.random.fold_in(key, m)
            l, g, r = self.run(params, chunk, mkey, collect)
            loss = l if loss is None else loss + l
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            recs.append(r)
        inv = 1.0 / k
        loss = loss * inv
        grads = jax.tree.map(lambda g: g * inv, grads)
        records = {"fwd": [None] * self.num_boundaries,
                   "bwd": [None] * self.num_boundaries}
        if collect:
            for d in ("fwd", "bwd"):
                for b in range(self.num_boundaries):
                    records[d][b] = tuple(
                        jnp.concatenate([r[d][b][p] for r in recs], axis=0)
                        for p in range(self.num_passes))
        return loss, grads, records

    def _run_pipelined_scan(self, params, batches, key, k: int, mb: int):
        """The K-micro-batch accumulation as ONE ``lax.scan``: chunk 0
        initializes the carry (same accumulation order as the unrolled
        loop — l0, l0+l1, ...), the scan body folds in each chunk's
        micro-batch index for its stage key exactly like the loop does.
        The staged chain is traced twice total (init + body) regardless
        of K, vs K times unrolled."""
        stacked = tuple(b[:k * mb].reshape((k, mb) + tuple(b.shape[1:]))
                        for b in batches)
        l0, g0, _ = self.run(
            params, tuple(s[0] for s in stacked),
            None if key is None else jax.random.fold_in(key, 0),
            collect=False)

        def body(carry, xs):
            m, chunk = xs
            mkey = None if key is None else jax.random.fold_in(key, m)
            l, g, _ = self.run(params, chunk, mkey, collect=False)
            cl, cg = carry
            return (cl + l, jax.tree.map(jnp.add, cg, g)), None

        (loss, grads), _ = jax.lax.scan(
            body, (l0, g0),
            (jnp.arange(1, k), tuple(s[1:] for s in stacked)))
        inv = 1.0 / k
        return (loss * inv, jax.tree.map(lambda g: g * inv, grads),
                {"fwd": [None] * self.num_boundaries,
                 "bwd": [None] * self.num_boundaries})

    def value_and_grad(self, params, real, fake, key=None):
        """The D-loss contract of ``fed/programs.make_local_step``:
        ``(params, real, fake, key) -> (loss, grads)`` through the staged
        execution — pipelined when ``pipeline_microbatches > 1``."""
        if self.pipeline_microbatches > 1:
            loss, grads, _ = self.run_pipelined(params, (real, fake), key)
        else:
            loss, grads, _ = self.run(params, (real, fake), key)
        return loss, grads

    # ------------------------------------------------------------------
    def forward_boundaries(self, params, x, key=None,
                           upto: Optional[int] = None) -> List[jnp.ndarray]:
        """The staged activations ONE forward pass ships, per boundary —
        the tensors the activation-inversion attack should target
        (post-codec, post-noise), not a separate clean forward.  ``upto``
        stops after that boundary index (an attacker at boundary b never
        needs the deeper segments' compute)."""
        if key is None and self.stochastic:
            key = jax.random.PRNGKey(0)
        out = []
        for si, (dev, names) in enumerate(self.segments[:-1]):
            for n in names:
                x = self.apply_layer(n, params, x)
            x = self.stages[si].apply(x, self._key(key, si, 0, 0))
            out.append(x)
            if upto is not None and si >= upto:
                break
        return out

    def shipped_boundaries(self, params, real, fake, key=None
                           ) -> Dict[str, List[Tuple[jnp.ndarray, ...]]]:
        """Every boundary tensor one local step ships (fwd activations and
        bwd activation-grads, both passes), as staged — per-micro-batch
        tensors concatenated back to the full-batch view when the step
        is pipelined (what the LAN observer sees is unchanged in union,
        just split across K messages)."""
        _, _, records = self.run_pipelined(params, (real, fake), key,
                                           collect=True)
        return records

    # ------------------------------------------------------------------
    def boundary_shapes(self, params, x_shape: Sequence[int],
                        dtype=jnp.float32) -> List[Tuple[int, ...]]:
        """Activation shape at each boundary for one pass of ``x_shape``
        batches (no FLOPs — ``jax.eval_shape``)."""
        ck = (tuple(x_shape), jnp.dtype(dtype).name)
        if ck not in self._shape_cache:
            def prefixes(p, x):
                out = []
                for dev, names in self.segments[:-1]:
                    for n in names:
                        x = self.apply_layer(n, p, x)
                    out.append(x)
                return out
            shapes = jax.eval_shape(
                prefixes, params,
                jax.ShapeDtypeStruct(tuple(x_shape), dtype))
            self._shape_cache[ck] = [tuple(s.shape) for s in shapes]
        return self._shape_cache[ck]

    def segment_costs(self) -> List[float]:
        """Compute units per device segment (portions merged exactly as
        ``plan_segments`` merges them)."""
        costs: List[float] = []
        prev: Optional[str] = None
        for p in self.plan.portions:
            if prev == p.device_id:
                costs[-1] += p.cost
            else:
                costs.append(p.cost)
                prev = p.device_id
        return costs

    def overlap_schedule(self, time_factors: Dict[str, float], *,
                         lan_latency_s: float = 0.050,
                         compute_unit_s: float = 0.010,
                         bwd_fwd_ratio: float = 2.0,
                         hop_bytes: Optional[Sequence[int]] = None,
                         lan_bandwidth_bps: float = 100e6,
                         pipeline_microbatches: Optional[int] = None):
        """The explicit 1F1B :class:`core.pipeline.OverlapSchedule` for
        one batch of this plan (K defaults to the executor's configured
        ``pipeline_microbatches``)."""
        from repro.core.pipeline import schedule_for
        k = self.pipeline_microbatches if pipeline_microbatches is None \
            else int(pipeline_microbatches)
        return schedule_for(
            self.segment_costs(), [dev for dev, _ in self.segments],
            time_factors, num_microbatches=k,
            compute_unit_s=compute_unit_s, bwd_fwd_ratio=bwd_fwd_ratio,
            lan_latency_s=lan_latency_s, hop_bytes=hop_bytes,
            lan_bandwidth_bps=lan_bandwidth_bps)

    def round_timeline(self, time_factors: Dict[str, float], *,
                       lan_latency_s: float = 0.050,
                       compute_unit_s: float = 0.010,
                       bwd_fwd_ratio: float = 2.0,
                       hop_bytes: Optional[Sequence[int]] = None,
                       lan_bandwidth_bps: float = 100e6,
                       pipeline_microbatches: Optional[int] = None
                       ) -> Tuple[List[Dict[str, Any]], float]:
        """The ordered phases of ONE local batch under this plan, as the
        flight recorder traces them: forward segment computes and boundary
        hops chain down the device list, then the backward pass walks the
        same chain in reverse (segment computes scaled ``bwd_fwd_ratio``).

        ``time_factors`` maps device id -> Time_Factor; ``hop_bytes``
        (optional) lists the bytes of each hop event in the flattened
        ``[b0.fwd, b0.bwd, b1.fwd, ...]`` order the trainer's
        ``_split_hop_events`` uses — given, each hop costs
        ``lan_latency_s + 8*bytes/bw``; absent, the analytic
        ``lan_latency_s`` per hop.

        Returns ``(phases, batch_time_s)``: phases are dicts with
        ``name``/``cat``/``track``/``t0``/``t1``/``args`` (times relative
        to batch start).  Sequential (``K = 1``) phases chain end to end
        and their durations sum EXACTLY to ``core/simulate.
        plan_epoch_time``'s per-batch time under the same arguments — the
        trace is the price, subdivided, never a second model of it
        (pinned in tests).  Pipelined (``K > 1``, defaulting to the
        executor's ``pipeline_microbatches``) phases come from the 1F1B
        overlap schedule — per-micro-batch spans that genuinely overlap
        across devices, with ``batch_time_s`` the schedule makespan,
        still equal to ``plan_epoch_time``'s per-batch time at the same
        K (same pin).
        """
        k = self.pipeline_microbatches if pipeline_microbatches is None \
            else int(pipeline_microbatches)
        if k > 1 and self.num_boundaries > 0:
            sched = self.overlap_schedule(
                time_factors, lan_latency_s=lan_latency_s,
                compute_unit_s=compute_unit_s, bwd_fwd_ratio=bwd_fwd_ratio,
                hop_bytes=hop_bytes, lan_bandwidth_bps=lan_bandwidth_bps,
                pipeline_microbatches=k)
            phases: List[Dict[str, Any]] = []
            for task in sched.tasks:
                if task.kind in ("fwd", "bwd"):
                    dev = task.device
                    phases.append({
                        "name": f"{task.kind} {dev} mb{task.microbatch}",
                        "cat": "segment", "track": dev,
                        "t0": task.t0, "t1": task.t1,
                        "args": {"microbatch": task.microbatch,
                                 "segment": task.index}})
                else:
                    b = self.boundaries[task.index]
                    direction = "fwd" if task.kind == "hop_fwd" else "bwd"
                    frm, to = (b.from_device, b.to_device) \
                        if direction == "fwd" \
                        else (b.to_device, b.from_device)
                    phases.append({
                        "name": f"b{b.index} {direction} {frm}->{to} "
                                f"mb{task.microbatch}",
                        "cat": "boundary", "track": frm,
                        "t0": task.t0, "t1": task.t1,
                        "args": {"boundary": b.index,
                                 "direction": direction,
                                 "microbatch": task.microbatch,
                                 "stage": self.stages[b.index].name}})
            return phases, sched.makespan
        seg_costs = self.segment_costs()
        bw = max(float(lan_bandwidth_bps), 1.0)

        def hop_time(b: int, direction: int) -> float:
            if hop_bytes is None:
                return lan_latency_s
            return lan_latency_s + 8.0 * int(hop_bytes[2 * b + direction]) / bw

        def seg_time(si: int, ratio: float) -> float:
            dev = self.segments[si][0]
            return seg_costs[si] * compute_unit_s * time_factors[dev] * ratio

        phases: List[Dict[str, Any]] = []
        t = 0.0

        def emit(name: str, cat: str, track: str, dur: float, **args):
            nonlocal t
            phases.append({"name": name, "cat": cat, "track": track,
                           "t0": t, "t1": t + dur, "args": args})
            t += dur

        for si, (dev, names) in enumerate(self.segments):
            emit(f"fwd {dev}", "segment", dev, seg_time(si, 1.0),
                 layers=len(names))
            if si < len(self.segments) - 1:
                b = self.boundaries[si]
                emit(f"b{b.index} fwd {b.from_device}->{b.to_device}",
                     "boundary", b.from_device, hop_time(si, 0),
                     boundary=b.index, direction="fwd",
                     stage=self.stages[si].name)
        for si in range(len(self.segments) - 1, -1, -1):
            dev = self.segments[si][0]
            emit(f"bwd {dev}", "segment", dev, seg_time(si, bwd_fwd_ratio))
            if si > 0:
                b = self.boundaries[si - 1]
                emit(f"b{b.index} bwd {b.to_device}->{b.from_device}",
                     "boundary", b.to_device, hop_time(si - 1, 1),
                     boundary=b.index, direction="bwd",
                     stage=self.stages[si - 1].name)
        return phases, t

    def step_wire_bytes(self, params, x_shape: Sequence[int],
                        dtype=jnp.float32) -> Tuple[int, List[Dict[str, int]]]:
        """Measured LAN bytes of ONE local step under this plan + stage.

        Returns ``(total, per_boundary)`` where ``per_boundary[b]`` has
        ``fwd``/``bwd`` bytes for one pass; the total counts both
        directions across all passes (the cotangent has the activation's
        shape, so fwd == bwd under every stage here).
        """
        per = []
        total = 0
        shapes = self.boundary_shapes(params, x_shape, dtype)
        for si, shp in enumerate(shapes):
            wb = self.stages[si].wire_bytes(shp, dtype)
            per.append({"fwd": wb, "bwd": wb})
            total += 2 * wb * self.num_passes
        return total, per
