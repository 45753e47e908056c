"""Profiling hooks: jit compile-time and per-kernel timing, feeding the
roofline model.

Everything here is measurement-only and OFF by default
(``cfg.obs.profile_kernels=False``): profiling triggers extra jit
compilations of the hot aggregation/privacy kernels (fedavg, dp_clip) on
synthetic inputs, so the gate exists to keep ``control=frozen`` runs doing
zero extra work — numerics are untouched either way (the profiled
programs never feed training state).

Each profile records the three costs a kernel pays:

  * ``lower_s`` / ``compile_s`` — jit trace + XLA compile wall time
    (the constant SplitEasy warns dominates short on-device runs);
  * ``run_s``   — best-of-N executed wall time (block_until_ready);
  * roofline terms — flops / bytes from ``compiled.cost_analysis()``
    against the peaks of the device the profile ran on, looked up by its
    ``device_kind`` (``repro.roofline.hw``).  The host CPU has no peaks:
    there the terms are None ("not measured").

Each kernel runs as the training config runs it: its ``use_kernel`` and
``kernel_interpret`` flags, never a forced interpret mode.
"""
from __future__ import annotations

import time
from dataclasses import asdict, dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.roofline.analysis import kernel_terms
from repro.roofline.hw import HwSpec, hw_for_kind


@dataclass
class KernelProfile:
    name: str
    lower_s: float
    compile_s: float
    run_s: float                  # best-of-N executed time
    runs: int
    device_kind: str = ""
    flops: float = 0.0
    bytes_accessed: float = 0.0
    compute_term_s: Optional[float] = None
    memory_term_s: Optional[float] = None
    arithmetic_intensity: float = 0.0

    def to_dict(self) -> Dict[str, Any]:
        return asdict(self)


def device_peaks() -> Optional[HwSpec]:
    """Peaks of the device JAX runs on: None on the host CPU, an error
    for an accelerator missing from ``roofline.hw.PEAKS``."""
    dev = jax.devices()[0]
    return None if dev.platform == "cpu" else hw_for_kind(dev.device_kind)


def profile_jit(name: str, fn: Callable, *args, runs: int = 3
                ) -> KernelProfile:
    """Lower + compile + time one callable on the given args.

    ``fn`` is traced fresh through ``jax.jit`` so the lower/compile split
    is measured even when the callable is already cached elsewhere.
    """
    jfn = jax.jit(fn)
    t0 = time.perf_counter()
    lowered = jfn.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    best = float("inf")
    for _ in range(max(1, runs)):
        r0 = time.perf_counter()
        out = compiled(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - r0)
    terms = kernel_terms(compiled, device_peaks())
    return KernelProfile(name=name, lower_s=t1 - t0, compile_s=t2 - t1,
                         run_s=best, runs=max(1, runs),
                         device_kind=jax.devices()[0].device_kind, **terms)


# ---------------------------------------------------------------------------
# the engine's hot kernels on synthetic inputs
# ---------------------------------------------------------------------------

def profile_fedavg(*, num_clients: int = 4, n: int = 8192,
                   use_kernel: bool = False, interpret: bool = False,
                   runs: int = 3) -> KernelProfile:
    """The fedavg aggregation: (C, N) stacked client updates -> weighted
    mean, through the Pallas kernel or its jnp reference."""
    from repro.kernels.fedavg.ops import fedavg_flat
    from repro.kernels.fedavg.ref import fedavg_ref
    key = jax.random.PRNGKey(0)
    stacked = jax.random.normal(key, (num_clients, n), jnp.float32)
    weights = jnp.ones((num_clients,), jnp.float32)

    def fn(s, w):
        if use_kernel:
            return fedavg_flat(s, w, interpret=interpret)
        return fedavg_ref(s, w / jnp.sum(w))

    kind = "kernel" if use_kernel else "ref"
    return profile_jit(f"fedavg_{kind}_c{num_clients}_n{n}", fn, stacked,
                       weights, runs=runs)


def profile_dp_clip(*, batch: int = 8, n: int = 4096, clip: float = 1.0,
                    sigma: float = 1.0, use_kernel: bool = False,
                    interpret: bool = False, runs: int = 3
                    ) -> KernelProfile:
    """The dp_clip privatization: per-example (B, N) grads -> clipped,
    noised sum (the DP-SGD inner release)."""
    from repro.kernels.dp_clip.ops import dp_clip_noise_flat
    key = jax.random.PRNGKey(1)
    k1, k2 = jax.random.split(key)
    stacked = jax.random.normal(k1, (batch, n), jnp.float32)
    noise = jax.random.normal(k2, (n,), jnp.float32)
    c = jnp.asarray(clip, jnp.float32)
    s = jnp.asarray(sigma * clip, jnp.float32)

    def fn(g, nz):
        return dp_clip_noise_flat(g, c, s, nz, use_kernel=use_kernel,
                                  interpret=interpret)

    kind = "kernel" if use_kernel else "ref"
    return profile_jit(f"dp_clip_{kind}_b{batch}_n{n}", fn, stacked, noise,
                       runs=runs)


def profile_boundary_fuse(*, batch: int = 8, n: int = 4096,
                          codec: str = "int8", clip: float = 1.0,
                          sigma: float = 0.5, use_kernel: bool = False,
                          interpret: bool = False, runs: int = 3
                          ) -> KernelProfile:
    """The fused boundary-crossing stage (kernels/boundary_fuse): codec
    qdq + per-example clip + Gaussian noise over one flattened (B, N)
    boundary tensor — what every hop of a composed ``codec+dp`` split
    stage pays."""
    from repro.kernels.boundary_fuse.ops import fused_boundary_flat
    key = jax.random.PRNGKey(2)
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (batch, n), jnp.float32)
    noise = jax.random.normal(k2, (batch, n), jnp.float32)
    c = jnp.asarray(clip, jnp.float32)
    s = jnp.asarray(sigma * clip, jnp.float32)

    def fn(t, nz):
        return fused_boundary_flat(t, c, s, nz, codec=codec,
                                   use_kernel=use_kernel,
                                   interpret=interpret)

    kind = "kernel" if use_kernel else "ref"
    return profile_jit(f"boundary_fuse_{codec}_{kind}_b{batch}_n{n}",
                       fn, x, noise, runs=runs)


def profile_agg_fuse(*, num_clients: int = 4, n: int = 8192,
                     codec: str = "int8", use_kernel: bool = False,
                     interpret: bool = False, runs: int = 3
                     ) -> KernelProfile:
    """The fused dequant-reduce server aggregation (kernels/agg_fuse):
    (C, N) compressed client wires + per-client scales -> one fp32
    weighted mean without materializing decoded trees — what
    ``fed.server_reduce != 'decode'`` replaces decode-then-fedavg with."""
    from repro.kernels.agg_fuse.ops import dequant_reduce_flat
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    if codec == "int8":
        wires = jax.random.randint(k1, (num_clients, n), -127, 128,
                                   jnp.int32).astype(jnp.int8)
        scales = jax.random.uniform(k2, (num_clients,), jnp.float32,
                                    1e-3, 1e-1)
    else:
        wires = jax.random.normal(k1, (num_clients, n), jnp.float32)
        if codec == "fp16":
            wires = wires.astype(jnp.float16)
        scales = jnp.ones((num_clients,), jnp.float32)
    weights = jnp.ones((num_clients,), jnp.float32)

    def fn(w, s, wt):
        return dequant_reduce_flat(w, s, wt, use_kernel=use_kernel,
                                   interpret=interpret)

    kind = "kernel" if use_kernel else "ref"
    return profile_jit(f"agg_fuse_{codec}_{kind}_c{num_clients}_n{n}",
                       fn, wires, scales, weights, runs=runs)


def profile_engine_kernels(cfg, *, runs: int = 3
                           ) -> Dict[str, Dict[str, Any]]:
    """Profile the kernels one engine round leans on, sized from ``cfg``
    (aggregation width = number of clients; dp_clip on when the privacy
    subsystem is; boundary_fuse when the split stage composes) and run
    with ``cfg``'s kernel flags.  Returns ``{name: profile dict}`` — what
    the recorder writes to ``profile.json``."""
    fed, priv, split = cfg.fed, cfg.privacy, cfg.split
    num_clients = max(2, cfg.fsl.num_clients)
    profiles = [profile_fedavg(num_clients=num_clients,
                               use_kernel=fed.kernel_aggregation,
                               interpret=fed.kernel_interpret, runs=runs)]
    if priv.enabled:
        profiles.append(profile_dp_clip(
            use_kernel=priv.use_kernel, interpret=priv.kernel_interpret,
            runs=runs))
    stage = split.boundary_stage
    if "+" in stage:
        codec = stage.split("+")[0]
        if codec in ("fp16", "int8"):
            profiles.append(profile_boundary_fuse(
                codec=codec, use_kernel=split.use_kernel,
                interpret=split.kernel_interpret, runs=runs))
    # compressed-domain server reduce (kernels/agg_fuse): profiled when a
    # dense lossy uplink codec is configured — the fused dequant-reduce is
    # what fed.server_reduce != "decode" folds each uplink through
    if fed.codec in ("fp16", "int8"):
        profiles.append(profile_agg_fuse(
            num_clients=num_clients, codec=fed.codec,
            use_kernel=fed.kernel_aggregation,
            interpret=fed.kernel_interpret, runs=runs))
    return {p.name: p.to_dict() for p in profiles}
