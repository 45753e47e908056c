"""Client programs: the per-client local round as data, compiled two ways.

The paper's protocol has exactly one client-side job — run ``local_steps``
discriminator batches from the downloaded params — but the repo used to
encode it three divergent ways (sequential loop, engine callback,
vectorized vmap), each supporting a different subset of
scheduling x backend x privacy.  This module makes the local round a
first-class *program* so every combination exists:

  * :func:`make_local_step` builds ONE step definition — plain SGD/Adam or
    DP-SGD (per-example clip + Gaussian noise via ``kernels/dp_clip``,
    per-example grads from singleton-batch vmap) — selected orthogonally
    from the backend.  A ``core/split.SplitExecution`` swaps the gradient
    computation for the staged split forward/backward (boundary stages on
    every crossing tensor), again orthogonally: split x privacy x backend
    all compose.
  * :class:`LocalProgram` compiles that step two ways:
      - **loop**    — per-client Python loop over jitted steps (the seed's
                      step program; bit-exact reference numerics),
                      dispatched back to back with one loss read per
                      client, and
      - **vectorized** — the whole multi-client round as one jitted
                      program: vmap over clients, scan over batches, with
                      the DP stage *inside* the scanned step.
  * :class:`RoundExecutor` binds a program to one engine round: data
    sampling, per-client hyperparameters (``lr_scale`` / ``local_steps``
    schedules), opt-state lookup and RNG plumbing.  Execution is pure —
    optimizer states are returned in :class:`ClientResult`, never written
    back; the engine decides which clients participated and only those
    states are committed (``RoundReport.opt_states``).

RNG contract: DP noise keys depend only on (round key, client id,
execution index, batch index), so the looped and vectorized backends draw
identical noise at a fixed seed — the basis of the pinned
looped-DP == vectorized-DP test (tests/test_fed_runtime.py).

Stacked-tree utilities (:func:`stack_trees` / :func:`unstack_tree` /
:func:`fedavg_stacked`) and the :func:`sequential_d_rounds` reference lived
in the former ``fed/vectorized.py``, which this module absorbs.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.trace import SPAN_CLIENT_STEP, to_host, to_host_all

# loss_fn(params, real_batch, fake_batch) -> scalar loss
LossFn = Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray]

# The executor's real dispatch paths.  config.FED_BACKENDS additionally
# accepts "auto" — resolved by the trainer's first-round dispatch probe
# (core/gan.FSLGANTrainer._resolve_auto_backend) before any RoundExecutor
# is built, so "auto" never reaches this module.
BACKENDS = ("loop", "vectorized")


# ---------------------------------------------------------------------------
# stacked-tree utilities (absorbed from fed/vectorized.py)
# ---------------------------------------------------------------------------

def stack_trees(trees: Sequence) -> Any:
    """[tree_0 .. tree_{C-1}] -> one tree with a leading client axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack_tree(stacked, num: int) -> List[Any]:
    """Inverse of :func:`stack_trees`."""
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(num)]


def fedavg_stacked(stacked_tree, weights, *, use_kernel: bool = False,
                   interpret: bool = False):
    """Weighted average over the leading client axis of a stacked tree.

    ``use_kernel`` routes each leaf through the fedavg Pallas kernel
    (one HBM pass per element); the default is a fused tensordot, which XLA
    emits the same roofline-bound loop for on CPU.
    """
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)

    if use_kernel:
        from repro.kernels.fedavg.ops import fedavg_flat

        def avg(leaf):
            c = leaf.shape[0]
            flat = leaf.reshape(c, -1).astype(jnp.float32)
            out = fedavg_flat(flat, w, interpret=interpret)
            return out.reshape(leaf.shape[1:]).astype(leaf.dtype)
    else:
        def avg(leaf):
            acc = jnp.tensordot(w, leaf.astype(jnp.float32), axes=(0, 0))
            return acc.astype(leaf.dtype)

    return jax.tree.map(avg, stacked_tree)


def sequential_d_rounds(d_step, params_list: Sequence, opt_list: Sequence,
                        reals: jnp.ndarray, fakes: jnp.ndarray):
    """Reference semantics of the vectorized round: the seed's per-client
    Python loop over the same (C, T, B, ...) batches.  Used by the pinned
    equivalence test and the benchmark baseline."""
    out_p, out_o, out_l = [], [], []
    for i, (p, o) in enumerate(zip(params_list, opt_list)):
        losses = []
        for t in range(reals.shape[1]):
            p, o, l = d_step(p, o, reals[i, t], fakes[i, t])
            losses.append(l)
        out_p.append(p)
        out_o.append(o)
        out_l.append(jnp.stack(losses))
    return out_p, out_o, jnp.stack(out_l)


# ---------------------------------------------------------------------------
# the one step definition: plain vs DP-SGD, selected orthogonally
# ---------------------------------------------------------------------------

def make_local_step(optimizer, loss_fn: LossFn, privacy=None, *,
                    force_ref: bool = False, split_exec=None):
    """Build ``step(params, opt, real, fake, lr, key) -> (params, opt,
    loss)`` — the single client-side step both backends compile.

    ``privacy`` is a ``config.PrivacyConfig`` (or None).  When it selects
    ``dp_sgd``, the step takes per-example gradients on singleton batches
    (vmap over examples, so batchnorm statistics are per-example — the
    standard DP-SGD stance on BN), privatizes them through
    ``kernels/dp_clip`` and feeds the mean to the optimizer; otherwise it
    is the plain batch step.

    ``split_exec`` (``core/split.SplitExecution``, or None) selects HOW the
    gradient is computed, orthogonally to privacy: None differentiates the
    monolithic ``loss_fn``; a SplitExecution runs the staged split
    forward/backward — every boundary tensor through the plan's boundary
    stage — which is bit-exact with the monolithic gradient under the
    identity stage.  ``key`` feeds the stage noise (and DP-SGD noise);
    with neither, it is ignored.

    ``force_ref`` pins the pure-JAX dp_clip reference regardless of
    ``privacy.use_kernel`` — the vectorized backend sets it because the
    Pallas kernel is a per-call primitive, and inside the scanned/vmapped
    program XLA fuses the reference to the same thing.
    """
    dp = (privacy is not None and getattr(privacy, "enabled", False)
          and privacy.mode == "dp_sgd")
    if not dp:
        if split_exec is None:
            def step(params, opt, real, fake, lr, key):
                del key
                loss, grads = jax.value_and_grad(loss_fn)(params, real,
                                                          fake)
                params, opt = optimizer.update(grads, opt, params, lr)
                return params, opt, loss
        else:
            def step(params, opt, real, fake, lr, key):
                loss, grads = split_exec.value_and_grad(params, real, fake,
                                                        key)
                params, opt = optimizer.update(grads, opt, params, lr)
                return params, opt, loss
        return step

    from repro.kernels.dp_clip.ops import dp_clip_noise_tree
    clip = float(privacy.clip_norm)
    noise_scale = float(privacy.noise_multiplier) * clip
    use_kernel = bool(privacy.use_kernel) and not force_ref
    interpret = bool(privacy.kernel_interpret)

    if split_exec is None:
        def one_example(p, r, f):
            return loss_fn(p, r[None], f[None])

        grad_one = jax.value_and_grad(one_example)

        def per_example_vg(params, real, fake, key):
            del key
            return jax.vmap(grad_one, in_axes=(None, 0, 0))(params, real,
                                                            fake)
    else:
        def per_example_vg(params, real, fake, key):
            # each example's staged pass draws its own boundary-stage
            # noise; dp_clip's noise key (`key` itself) is never folded
            # with these, so the two noise sources stay independent
            def one(r, f, i):
                return split_exec.value_and_grad(
                    params, r[None], f[None], jax.random.fold_in(key, i))
            return jax.vmap(one)(real, fake, jnp.arange(real.shape[0]))

    def step(params, opt, real, fake, lr, key):
        losses, per_ex = per_example_vg(params, real, fake, key)
        summed = dp_clip_noise_tree(per_ex, clip, noise_scale, key,
                                    use_kernel=use_kernel,
                                    interpret=interpret)
        b = real.shape[0]
        grads = jax.tree.map(lambda g: g / b, summed)
        params, opt = optimizer.update(grads, opt, params, lr)
        return params, opt, jnp.mean(losses)

    return step


@jax.jit
def _unstack_batches(reals, fakes):
    """(T, B, ...) batches -> T per-step batches each, in one program: a
    pure copy, so each step sees the same bits as ``reals[t]``."""
    return ([reals[t] for t in range(reals.shape[0])],
            [fakes[t] for t in range(fakes.shape[0])])


@functools.partial(jax.jit, static_argnums=1)
def _step_keys(key, n: int):
    """``[fold_in(key, t) for t in range(n)]`` in one program."""
    return [jax.random.fold_in(key, t) for t in range(n)]


# ---------------------------------------------------------------------------
# LocalProgram: one step, two compilations
# ---------------------------------------------------------------------------

class LocalProgram:
    """The per-client local round as data: step fn + backend compilations.

    Both backends run the SAME step definition; only the dispatch differs:

      * ``run_looped``     — T jitted step calls for one client, issued
        back to back and read back once (with privacy disabled this is
        bit-exact with the seed trainer's ``_d_step`` loop);
      * ``run_vectorized`` — one jitted program for C clients: vmap over
        the stacked client axis, scan over the T batch axis, per-client
        learning rates / noise keys as vectors and a (C, T) step mask for
        heterogeneous ``local_steps`` schedules.

    ``split`` maps client ids to ``core/split.SplitExecution`` objects:
    those clients' steps execute THROUGH the split (staged segment
    forward/backward, boundary stages on every crossing tensor).  Steps are
    compiled per *split signature* — the tuple of boundary depths + stage —
    since plans sharing a signature share the staged program; the
    vectorized backend batches clients per signature group
    (``RoundExecutor``).  Unlisted clients run the monolithic step
    (signature ``None``), so split and unsplit clients coexist in one
    round.
    """

    def __init__(self, optimizer, loss_fn: LossFn, base_lr: float, *,
                 privacy=None, split=None):
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.base_lr = float(base_lr)
        self.privacy = privacy
        self.split = dict(split or {})
        self.is_dp = (privacy is not None
                      and getattr(privacy, "enabled", False)
                      and privacy.mode == "dp_sgd")
        # does the step consume its PRNG key? (DP-SGD noise and/or a
        # stochastic boundary stage) — the trainer derives round keys iff so
        self.needs_key = self.is_dp or any(
            ex.stochastic for ex in self.split.values())
        self._exec_by_sig = {}
        for ex in self.split.values():
            self._exec_by_sig.setdefault(ex.signature, ex)
        self._step_cache: Dict[Any, Any] = {}
        self._vrun_cache: Dict[Any, Any] = {}
        # the monolithic step stays a public attribute (seed-compatible)
        self.step = self._step(None)

    # ------------------------------------------------------------------
    def rebind_sigma(self, noise_multiplier: float) -> None:
        """Rebind the DP-SGD noise multiplier between rounds (the sigma
        controller's lever).  The noise scale is a compile-time constant of
        the step, so the per-signature caches are cleared and both backends
        recompile on next dispatch; the (round, client, exec, batch)
        noise-key scheme is untouched, so the rebound run stays
        deterministic per schedule.  The controller's hysteresis bounds how
        often this fires."""
        import dataclasses
        if not self.is_dp or \
                float(noise_multiplier) == self.privacy.noise_multiplier:
            return
        self.privacy = dataclasses.replace(
            self.privacy, noise_multiplier=float(noise_multiplier))
        self._step_cache.clear()
        self._vrun_cache.clear()
        self.step = self._step(None)

    # ------------------------------------------------------------------
    # per-signature compilation
    # ------------------------------------------------------------------
    def signature_for(self, cid: str):
        """Compilation key for one client: its plan's boundary-depth/stage
        signature, or None for the monolithic step.  Pipelined split
        executions (``pipeline_microbatches > 1``) carry K inside the
        signature, so their micro-batched steps compile — and the
        vectorized backend groups — separately from sequential ones."""
        ex = self.split.get(cid)
        return ex.signature if ex is not None else None

    def _step(self, sig, donate: bool = False):
        """The jitted step for one signature.  ``donate`` compiles the same
        step with its params and opt state donated, so its outputs reuse
        their buffers; only for inputs no one else holds."""
        if (sig, donate) not in self._step_cache:
            self._step_cache[(sig, donate)] = jax.jit(
                make_local_step(self.optimizer, self.loss_fn, self.privacy,
                                split_exec=self._exec_by_sig.get(sig)),
                donate_argnums=(0, 1) if donate else ())
        return self._step_cache[(sig, donate)]

    def _vrun(self, sig):
        if sig not in self._vrun_cache:
            self._vrun_cache[sig] = self._compile_vectorized(
                make_local_step(self.optimizer, self.loss_fn, self.privacy,
                                force_ref=True,
                                split_exec=self._exec_by_sig.get(sig)))
        return self._vrun_cache[sig]

    # ------------------------------------------------------------------
    @staticmethod
    def _compile_vectorized(step):
        def per_client(params, opt, reals, fakes, lr, key, mask):
            ts = jnp.arange(reals.shape[0])

            def body(carry, xs):
                p, o = carry
                real, fake, t, m = xs
                p2, o2, loss = step(p, o, real, fake, lr,
                                    jax.random.fold_in(key, t))
                # masked (padded) steps carry state through unchanged, so
                # clients with shorter local_steps schedules stop early
                # inside the shared scan length
                keep = lambda new, old: jax.tree.map(  # noqa: E731
                    lambda a, b: jnp.where(m, a, b), new, old)
                return (keep(p2, p), keep(o2, o)), jnp.where(m, loss, 0.0)

            (params, opt), losses = jax.lax.scan(
                body, (params, opt), (reals, fakes, ts, mask))
            return params, opt, losses

        return jax.jit(jax.vmap(per_client))

    # ------------------------------------------------------------------
    def run_looped(self, params, opt, reals, fakes, *,
                   lr: Optional[float] = None, key=None,
                   cid: Optional[str] = None
                   ) -> Tuple[Any, Any, List[float]]:
        """One client's round: T jitted steps over (T, B, ...) batches,
        dispatched back to back, with the T losses read back to the host
        once after the last step (one sync).  Step t's key is
        ``fold_in(key, t)`` when the step consumes it (``needs_key``),
        else ``key`` itself, unread.  ``params`` and ``opt`` are not
        donated.  ``cid`` selects the client's split-signature step
        (monolithic when omitted or unlisted)."""
        with jax.profiler.TraceAnnotation(SPAN_CLIENT_STEP):
            lr_arr = jnp.float32(self.base_lr if lr is None else lr)
            if key is None:
                key = jax.random.PRNGKey(0)
            sig = self.signature_for(cid) if cid is not None else None
            # the caller's params and opt state go to the first step; every
            # later step's are the previous step's outputs, held by this
            # loop alone, so it donates them.  On a TPU v5e a dispatch
            # that allocates its outputs (37 buffers for the DCGAN D and
            # its Adam state) costs ~2.8 ms of host time, one that reuses
            # them ~0.5 ms.
            first, rest = self._step(sig), self._step(sig, donate=True)
            n = reals.shape[0]
            reals, fakes = _unstack_batches(reals, fakes)
            keys = _step_keys(key, n) if self.needs_key else [key] * n
            losses = []
            for t in range(n):
                params, opt, l = (rest if t else first)(
                    params, opt, reals[t], fakes[t], lr_arr, keys[t])
                losses.append(l)
            return params, opt, to_host_all(losses)

    def run_vectorized(self, stacked_params, stacked_opt, reals, fakes, *,
                       lrs=None, keys=None, mask=None, signature=None):
        """C clients' rounds as ONE jitted program.

        ``reals``/``fakes``: (C, T, B, ...).  ``lrs``: (C,) per-client
        learning rates; ``keys``: (C,) PRNG keys (DP/stage noise);
        ``mask``: (C, T) bool — False entries are padding steps that leave
        the client's state untouched.  ``signature`` selects the split
        program; every stacked client must share it (``RoundExecutor``
        groups by signature).  Returns stacked (params, opt) and (C, T)
        losses (0.0 at masked slots).
        """
        c, t = reals.shape[0], reals.shape[1]
        if lrs is None:
            lrs = jnp.full((c,), self.base_lr, jnp.float32)
        if keys is None:
            keys = jnp.stack([jax.random.PRNGKey(0)] * c)
        if mask is None:
            mask = jnp.ones((c, t), bool)
        return self._vrun(signature)(
            stacked_params, stacked_opt, reals, fakes,
            jnp.asarray(lrs, jnp.float32), keys, jnp.asarray(mask, bool))


# ---------------------------------------------------------------------------
# RoundExecutor: a program bound to one engine round
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ClientHyper:
    """Per-client local-round hyperparameters (cfg.fed schedules)."""
    lr_scale: float = 1.0
    local_steps: int = 0          # 0 => the round's default


@dataclass
class ClientResult:
    """Pure output of one client execution — nothing is written back."""
    client_id: str
    params: Any
    opt_state: Any                # None for legacy bare-callable programs
    info: Dict[str, Any] = field(default_factory=dict)


class RoundExecutor:
    """What the engine schedules: ``run(cids, start_params)`` executes the
    listed clients' local rounds (one jitted program under the vectorized
    backend, jitted per-step loops otherwise) and returns pure
    :class:`ClientResult` objects.

    ``sample(cid, steps) -> (reals, fakes)`` is called once per execution
    in schedule order, so the host-RNG stream is identical across backends
    (and, with the loop backend under sync scheduling, identical to the
    seed's sequential loop).  Optimizer state reads go through a per-round
    overlay so async re-cycles of the same client chain correctly without
    mutating the trainer's committed state.
    """

    def __init__(self, program: LocalProgram, *, backend: str,
                 sample: Callable[[str, int], Tuple[jnp.ndarray, jnp.ndarray]],
                 opt_lookup: Callable[[str], Any], default_steps: int,
                 hyper: Optional[Dict[str, ClientHyper]] = None,
                 round_key=None, mesh=None, cohort_of=None):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"expected one of {BACKENDS}")
        self.program = program
        self.backend = backend
        self.sample = sample
        self.opt_lookup = opt_lookup
        self.default_steps = int(default_steps)
        self.hyper = hyper or {}
        self.round_key = round_key
        # client-axis mesh (launch/mesh.make_client_mesh): when set, the
        # vectorized dispatch places every stacked input on the mesh's
        # `clients` axis before calling the jitted program, so client
        # shards execute on separate devices.  None = single-device
        # placement (bit-exact default).
        self.mesh = mesh
        # cohort assigner (e.g. Roster.cohort_of_cid) folded into the
        # noise-key chain: (round, cohort, client, execution).  None means
        # cohort 0 for everyone — a uniform chain either way, so keys stay
        # reproducible across backends and topologies.
        self.cohort_of = cohort_of
        self._opt_overlay: Dict[str, Any] = {}
        self._exec_idx: Dict[str, int] = {}
        # stable roster index for noise-key derivation: folding in a hash
        # of the id (e.g. crc32) would hand colliding client ids identical
        # noise tensors — correlated releases the accountant would still
        # price as independent.  Unlisted clients get indices past the
        # roster in first-execution order, which is schedule-deterministic
        # (both backends execute the same schedule).
        self._cid_index: Dict[str, int] = {cid: i
                                           for i, cid in enumerate(self.hyper)}

    # ------------------------------------------------------------------
    def steps_for(self, cid: str) -> int:
        h = self.hyper.get(cid)
        return (h.local_steps or self.default_steps) if h \
            else self.default_steps

    def lr_for(self, cid: str) -> float:
        h = self.hyper.get(cid)
        return self.program.base_lr * (h.lr_scale if h else 1.0)

    def _key_for(self, cid: str):
        """Noise key for this execution: (round key, cohort, client roster
        index, exec index) — the roster's ``(round, cohort, client_id)``
        chain plus the execution counter for async re-cycles.
        Deterministic per schedule, identical across backends and
        aggregation topologies, collision-free across clients (cohort is
        folded in *before* the roster index, and the index is already
        unique across cohorts, so distinct clients can never collide)."""
        if self.round_key is None:
            return None
        if cid not in self._cid_index:
            self._cid_index[cid] = len(self._cid_index)
        i = self._exec_idx.get(cid, 0)
        self._exec_idx[cid] = i + 1
        cohort = int(self.cohort_of(cid)) if self.cohort_of else 0
        base = jax.random.fold_in(self.round_key, cohort)
        base = jax.random.fold_in(base, self._cid_index[cid])
        return jax.random.fold_in(base, i)

    def _opt_for(self, cid: str):
        if cid in self._opt_overlay:
            return self._opt_overlay[cid]
        return self.opt_lookup(cid)

    def _shard_stacked(self, trees):
        """Place stacked per-client inputs on the `clients` mesh axis.

        Every leaf's dim 0 is the client axis; other dims replicate.  A
        client count that doesn't divide the mesh replicates instead
        (sharding/specs.logical_spec policy), so ragged last groups still
        run — just without the multi-device split."""
        from repro.sharding.specs import client_axis_rules, stacked_shardings
        rules = client_axis_rules(self.mesh)
        return tuple(
            jax.device_put(t, stacked_shardings(self.mesh, t, rules=rules))
            for t in trees)

    # ------------------------------------------------------------------
    def run(self, cids: List[str], start_params) -> List[ClientResult]:
        if not cids:
            return []
        if self.backend == "vectorized":
            return self._run_vectorized(cids, start_params)
        out = []
        for cid in cids:
            steps = self.steps_for(cid)
            reals, fakes = self.sample(cid, steps)
            params, opt, losses = self.program.run_looped(
                start_params, self._opt_for(cid), reals, fakes,
                lr=self.lr_for(cid), key=self._key_for(cid), cid=cid)
            self._opt_overlay[cid] = opt
            out.append(ClientResult(cid, params, opt,
                                    {"losses": losses, "steps": steps}))
        return out

    def _run_vectorized(self, cids: List[str], start_params
                        ) -> List[ClientResult]:
        steps = [self.steps_for(cid) for cid in cids]
        t_max = max(steps)
        reals_l, fakes_l, mask_l = [], [], []
        for cid, s in zip(cids, steps):
            # sample exactly `s` batches (same host-RNG draws as the loop
            # backend); padding slots are zeros under a False mask
            r, f = self.sample(cid, s)
            if s < t_max:
                pad = lambda x: jnp.concatenate(  # noqa: E731
                    [x, jnp.zeros((t_max - s,) + x.shape[1:], x.dtype)])
                r, f = pad(r), pad(f)
            reals_l.append(r)
            fakes_l.append(f)
            mask_l.append([True] * s + [False] * (t_max - s))
        keys = [self._key_for(cid) for cid in cids]
        if keys[0] is None:
            keys = [jax.random.PRNGKey(0)] * len(cids)
        # one jitted dispatch per split signature (monolithic clients are
        # the None group).  Sampling and key derivation above already ran
        # in schedule order, so grouping only reorders the DISPATCH — the
        # host-RNG stream stays identical to the loop backend.
        sig_groups: Dict[Any, List[int]] = {}
        for i, cid in enumerate(cids):
            sig_groups.setdefault(self.program.signature_for(cid),
                                  []).append(i)
        out: List[Optional[ClientResult]] = [None] * len(cids)
        for sig, idxs in sig_groups.items():
            stacked_p = stack_trees([start_params] * len(idxs))
            stacked_o = stack_trees([self._opt_for(cids[i]) for i in idxs])
            stacked_r = jnp.stack([reals_l[i] for i in idxs])
            stacked_f = jnp.stack([fakes_l[i] for i in idxs])
            stacked_k = jnp.stack([keys[i] for i in idxs])
            stacked_m = jnp.asarray([mask_l[i] for i in idxs], bool)
            if self.mesh is not None:
                stacked_p, stacked_o, stacked_r, stacked_f, stacked_k, \
                    stacked_m = self._shard_stacked(
                        (stacked_p, stacked_o, stacked_r, stacked_f,
                         stacked_k, stacked_m))
            with jax.profiler.TraceAnnotation(SPAN_CLIENT_STEP):
                new_p, new_o, losses = self.program.run_vectorized(
                    stacked_p, stacked_o, stacked_r, stacked_f,
                    lrs=[self.lr_for(cids[i]) for i in idxs],
                    keys=stacked_k, mask=stacked_m, signature=sig)
            for j, i in enumerate(idxs):
                cid, s = cids[i], steps[i]
                p = jax.tree.map(lambda x: x[j], new_p)
                o = jax.tree.map(lambda x: x[j], new_o)
                self._opt_overlay[cid] = o
                # each loss is its own device-to-host read: s syncs per
                # client, sum(steps) for the group, all after the group's
                # one dispatch
                out[i] = ClientResult(
                    cid, p, o,
                    {"losses": [to_host(l) for l in losses[j, :s]],
                     "steps": s})
        return out


class CallableProgram:
    """Adapter: a legacy ``local_update(cid, params) -> (params, info)``
    callable as a program.  Opt state is opaque to the engine (None), so
    no ``RoundReport.opt_states`` entries are produced."""

    def __init__(self, fn):
        self.fn = fn

    def run(self, cids: List[str], start_params) -> List[ClientResult]:
        out = []
        for cid in cids:
            params, info = self.fn(cid, start_params)
            out.append(ClientResult(cid, params, None, info))
        return out


def as_program(obj):
    """Engine glue: accept a RoundExecutor-like program or a bare callable."""
    if hasattr(obj, "run"):
        return obj
    if callable(obj):
        return CallableProgram(obj)
    raise TypeError(f"not a client program: {obj!r}")
