"""Bring the FSL-GAN round up on a TPU at the paper's full width.

Run from the root of a checkout:

    python chip_smoke.py             # one chip: the main and kernels phases
    python chip_smoke.py --chips 4   # four chips: the sharded clients mesh

Everything runs in this one process, which holds the chips throughout.
Without a TPU it exits non-zero and prints no result.  Each phase prints
its compile and run seconds and its differences from a reference; the
last line of stdout is ``{"ok": true, "device": {...}}``.

  * main — ``FSLGANTrainer`` on ``dcgan-mnist`` as configured (DCGAN
    base_filters 64, batch 256, 5 clients x 4 devices, sorted_multi),
    two ``train_epoch(24)`` rounds; round 1 is checked against
    ``train_epoch_sequential`` on a trainer with the same seed.
  * kernels — one round with every engine kernel compiled for the chip
    (fused int8+dp boundary, DP-SGD clip+noise, int8 uplinks folded by
    the streaming dequant kernel) against the jnp reference round, at the
    default and at "highest" matmul precision (see ROUND_TOL); then each
    kernel op, compiled natively, against its ``ref.py`` on tensors the
    round produced.
  * --chips 4 — the vectorized backend on a 4-device ``clients`` mesh
    with 4 clients, server reduce ``decode`` and ``batched``, against the
    same round unsharded on one device; then the reduce ops on the mesh
    against one device on the round's tensors.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P    # noqa: E402

from repro.configs.registry import get_config                 # noqa: E402
from repro.core.gan import FSLGANTrainer, d_loss_fn           # noqa: E402
from repro.data import partition_dirichlet, synthetic_mnist   # noqa: E402
from repro.fed.transport import make_codec                    # noqa: E402
from repro.kernels.agg_fuse import (dequant_acc_flat,         # noqa: E402
                                    dequant_acc_ref, dequant_reduce_flat,
                                    scatter_acc_flat, scatter_acc_ref)
from repro.kernels.boundary_fuse import (fused_boundary_flat,  # noqa: E402
                                         fused_boundary_ref)
from repro.kernels.dp_clip.ops import (dp_clip_noise_flat,    # noqa: E402
                                       flatten_per_example)
from repro.kernels.fedavg.ops import fedavg_flat              # noqa: E402
from repro.kernels.fedavg.ref import fedavg_ref               # noqa: E402
from repro.launch.cache import use_compile_cache              # noqa: E402
from repro.launch.mesh import mesh_chips                      # noqa: E402
from repro.models.dcgan import disc_apply_layer               # noqa: E402
from repro.roofline.hw import hw_for_kind                     # noqa: E402

ROUND_BATCHES = 24        # the paper's batches per client per epoch
KERNEL_BATCHES = 2        # kernels / 4-chip rounds, as tests/test_agg_stream
# max |diff| of the aggregated D for one round against its reference
# round: the fma-level per-round tolerance tests/test_agg_stream.py pins.
# Biases of convs that feed a batch norm are compared apart: their
# gradient is zero in exact arithmetic (tests/test_population_scale.py
# leaves them out too).  Adam's first step, lr * g / |g|, moves a weight
# whose gradient is near zero by up to 2 lr on a last-bit change of that
# gradient, so two different programs for the same math are held to it
# only where their last bits stay close: the kernels round against the
# jnp round with every f32 matmul and conv traced at "highest" precision
# (at the default one bf16 pass, a last-bit difference at the boundary
# kernel becomes a bf16 rounding step in the next conv).  Other pairs
# (the kernels round at the default precision, the sharded round against
# one device) are held on their loss, which such a move shifts only by
# |g| times its size, and their D difference is reported.
ROUND_TOL = 2e-5
# |diff| of a round's mean local D loss: the loss pin of
# tests/test_population_scale.py's sharded round
LOSS_TOL = 1e-5
# kernel op vs ref.py: the tolerances of the repo's interpret-mode pins
OP_TOL = {"fedavg": (1e-5, 1e-6), "dequant_reduce": (1e-5, 1e-6),
          "dequant_acc": (1e-5, 1e-6), "scatter_acc": (1e-6, 1e-6),
          "boundary_fuse": (1e-5, 1e-5), "dp_clip": (1e-5, 1e-5)}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Phase:
    """One phase: its wall seconds, split into XLA compile seconds (JAX's
    backend-compile events) and the rest, and its failed checks."""
    compile_s = 0.0

    def __init__(self, name: str):
        self.name = name
        self.failed = []

    def __enter__(self):
        Phase.compile_s = 0.0
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        self.compile = Phase.compile_s
        self.run = wall - self.compile
        return False

    def report(self, **fields):
        print(json.dumps({"phase": self.name,
                          "compile_s": self.compile, "run_s": self.run,
                          **fields}), flush=True)

    def check(self, name: str, cond: bool, detail) -> None:
        if not cond:
            self.failed.append(f"{self.name}: {name}: {detail}")


def _on_event(event: str, secs: float, **_) -> None:
    if event == _COMPILE_EVENT:
        Phase.compile_s += secs


def _client_data(num_clients: int):
    imgs, labels = synthetic_mnist(60000, seed=0)
    return partition_dirichlet(imgs, labels, num_clients, alpha=0.5, seed=0)


def _global_d(tr: FSLGANTrainer):
    return tr.state.d_params[tr.client_ids[0]]


def _d_diff(a, b):
    """Max |a - b| over two discriminators -> (live params, biases of the
    convs that feed a batch norm)."""
    live, dead = 0.0, 0.0
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        d = float(jnp.max(jnp.abs(x - y)))
        layer, leaf = path[0].key, path[-1].key
        if leaf == "b" and layer.startswith("conv") and layer != "conv0":
            dead = max(dead, d)
        else:
            live = max(live, d)
    return live, dead


def _flat(tree) -> jnp.ndarray:
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                            for l in jax.tree.leaves(tree)])


# ---------------------------------------------------------------------------
# main: the paper's round through the normal entry points
# ---------------------------------------------------------------------------

def phase_main() -> Phase:
    cfg = get_config("dcgan-mnist")
    parts = _client_data(cfg.fsl.num_clients)
    with Phase("main") as ph:
        tr = FSLGANTrainer(cfg, parts, seed=0)
        t0 = time.perf_counter()
        m1 = tr.train_epoch(batches_per_client=ROUND_BATCHES)
        first_round_s = time.perf_counter() - t0
        d_round1 = _global_d(tr)
        t0 = time.perf_counter()
        m2 = tr.train_epoch(batches_per_client=ROUND_BATCHES)
        jax.block_until_ready(_global_d(tr))
        second_round_s = time.perf_counter() - t0
        ref = FSLGANTrainer(cfg, parts, seed=0)
        mr = ref.train_epoch_sequential(batches_per_client=ROUND_BATCHES)
        live, dead = _d_diff(d_round1, _global_d(ref))
    losses = [m1["d_loss"], m1["g_loss"], m2["d_loss"], m2["g_loss"]]
    ph.report(first_round_s=first_round_s, second_round_s=second_round_s,
              d_loss=[m1["d_loss"], m2["d_loss"]],
              g_loss=[m1["g_loss"], m2["g_loss"]],
              num_clients=m1["num_clients"], ref_d_loss=mr["d_loss"],
              d_max_abs_diff_vs_sequential=live,
              bn_conv_bias_max_abs_diff=dead, tol=ROUND_TOL)
    ph.check("losses finite", all(np.isfinite(losses)), losses)
    ph.check("round 1 vs train_epoch_sequential", live <= ROUND_TOL, live)
    return ph


# ---------------------------------------------------------------------------
# kernels: every engine kernel compiled for the chip
# ---------------------------------------------------------------------------

KERNEL_KNOBS = {
    "split.enabled": True, "split.boundary_stage": "int8+dp",
    "split.stage_clip": 1.0, "split.stage_sigma": 0.5,
    "fed.codec": "int8",
    "privacy.enabled": True, "privacy.mode": "dp_sgd",
    "privacy.clip_norm": 1.0, "privacy.noise_multiplier": 1.0,
}
KERNEL_ON = {"split.use_kernel": True, "privacy.use_kernel": True,
             "fed.kernel_aggregation": True, "fed.server_reduce": "stream"}
KERNEL_OFF = {"split.use_kernel": False, "privacy.use_kernel": False,
              "fed.kernel_aggregation": False, "fed.server_reduce": "decode"}


def _native(fn, *args, **static):
    """Compile ``fn`` for the device and run it -> (output, whether the
    compiled program holds a Mosaic kernel)."""
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    return compiled(*args), "tpu_custom_call" in compiled.as_text()


def _compare(results: dict, name: str, native_out, want) -> None:
    got, native = native_out
    rtol, atol = OP_TOL[name.split("[")[0]]
    got, want = np.asarray(got), np.asarray(want)
    err = np.abs(got - want)
    bad = int(np.sum(err > atol + rtol * np.abs(want)))
    results[name] = {"native": native, "max_abs_diff": float(err.max()),
                     "mismatched": bad, "size": int(want.size)}


def _kernel_ops(tr: FSLGANTrainer, parts) -> dict:
    """Each engine kernel op, compiled natively, against its ref.py on
    tensors the kernels round produced: the aggregated D, its conv0
    boundary activation on a real batch, per-example gradients, and the
    clients' Adam first moments as uplink payloads."""
    c = tr.c
    d = _global_d(tr)
    rep = tr.engine.last_report
    cids = sorted(rep.opt_states)
    ups = jnp.stack([_flat(rep.opt_states[cid]["m"]) for cid in cids])
    weights = jnp.asarray([float(len(parts[cid])) for cid in cids])
    w = weights / jnp.sum(weights)
    real = jnp.asarray(parts[cids[0]][:tr.batch_size])
    fake = tr._gen(tr.state.g_params, tr._z(tr.batch_size))
    key = jax.random.PRNGKey(11)
    out: dict = {}

    _compare(out, "fedavg", _native(fedavg_flat, ups, weights),
             fedavg_ref(ups, w))
    for codec in ("int8", "fp16"):
        encs = [make_codec(codec).encode(u) for u in ups]
        wires = jnp.stack([e[0] for e in encs])
        scales = jnp.asarray([float(e[1]) if codec == "int8" else 1.0
                              for e in encs], jnp.float32)
        got = _native(dequant_reduce_flat, wires, scales, weights,
                      use_kernel=True)
        want = dequant_reduce_flat(wires, scales, weights, use_kernel=False)
        _compare(out, f"dequant_reduce[{codec}]", got, want)
        acc = _flat(d)                   # the op donates its accumulator
        got = _native(dequant_acc_flat, jnp.copy(acc), wires[0], scales[0],
                      w[0], use_kernel=True)
        _compare(out, f"dequant_acc[{codec}]", got,
                 dequant_acc_ref(acc, wires[0], w[0], scales[0]))
    (vals, idx), _ = make_codec("topk", topk_frac=0.01,
                                error_feedback=False).encode(ups[0])
    acc = _flat(d)
    got = _native(scatter_acc_flat, jnp.copy(acc), vals, idx, w[0],
                  use_kernel=True)
    _compare(out, "scatter_acc", got, scatter_acc_ref(acc, vals, idx, w[0]))

    act = disc_apply_layer("conv0", d, real, c)
    act = act.reshape(act.shape[0], -1)
    noise = jax.random.normal(key, act.shape, jnp.float32)
    for codec in ("int8", "fp16"):
        got = _native(fused_boundary_flat, act, 1.0, 0.5, noise,
                      codec=codec, use_kernel=True)
        _compare(out, f"boundary_fuse[{codec}]", got,
                 fused_boundary_ref(act, 1.0, 0.5, noise, codec=codec))

    per_ex = jax.jit(jax.vmap(jax.grad(lambda p, r, f: d_loss_fn(
        p, r[None], f[None], c)), in_axes=(None, 0, 0)))(d, real, fake)
    flat, _ = flatten_per_example(per_ex)
    noise = jax.random.normal(key, (flat.shape[1],), jnp.float32)
    got = _native(dp_clip_noise_flat, flat, 1.0, 1.0, noise, use_kernel=True)
    _compare(out, "dp_clip", got,
             dp_clip_noise_flat(flat, 1.0, 1.0, noise, use_kernel=False))
    return out


def _round_kernels(tr: FSLGANTrainer) -> int:
    """Mosaic kernels in the compiled local step the kernels round ran."""
    sig = tr.program.signature_for(tr.client_ids[0])
    step = tr.program._step(sig)
    cid = tr.client_ids[0]
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    img = jax.ShapeDtypeStruct((tr.batch_size, tr.c.image_size,
                                tr.c.image_size, tr.c.channels), jnp.float32)
    text = step.lower(
        jax.tree.map(spec, tr.state.d_params[cid]),
        jax.tree.map(spec, tr.state.d_opt[cid]), img, img,
        jax.ShapeDtypeStruct((), jnp.float32),
        spec(jax.random.PRNGKey(0))).compile().as_text()
    return text.count("custom_call_target=\"tpu_custom_call\"")


def phase_kernels() -> Phase:
    base = get_config("dcgan-mnist")
    parts = _client_data(base.fsl.num_clients)
    rounds = {}
    with Phase("kernels") as ph:
        # the default precision, then every f32 matmul and conv at fp32
        for precision in (None, "highest"):
            with jax.default_matmul_precision(precision):
                tr, ref = (FSLGANTrainer(
                    base.override({**KERNEL_KNOBS, **k}), parts, seed=0)
                    for k in (KERNEL_ON, KERNEL_OFF))
                mk = tr.train_epoch(batches_per_client=KERNEL_BATCHES)
                mr = ref.train_epoch(batches_per_client=KERNEL_BATCHES)
            live, dead = _d_diff(_global_d(tr), _global_d(ref))
            rounds[str(precision)] = {
                "d_loss": mk["d_loss"], "g_loss": mk["g_loss"],
                "ref_d_loss": mr["d_loss"], "d_max_abs_diff": live,
                "bn_conv_bias_max_abs_diff": dead}
            ph.check(f"losses finite at {precision}",
                     all(np.isfinite([mk["d_loss"], mk["g_loss"]])), mk)
            if precision is None:
                ph.check("round d_loss vs jnp reference round",
                         abs(mk["d_loss"] - mr["d_loss"]) <= LOSS_TOL,
                         (mk["d_loss"], mr["d_loss"]))
                step_kernels = _round_kernels(tr)
                ops = _kernel_ops(tr, parts)
            else:
                ph.check(f"round D vs jnp reference round at {precision}",
                         live <= ROUND_TOL, live)
    ph.report(rounds=rounds, round_tol=ROUND_TOL, loss_tol=LOSS_TOL,
              step_tpu_custom_calls=step_kernels, ops=ops)
    # the DP-SGD step holds the dp_clip kernel and the boundary kernel
    ph.check("kernels in the round's step", step_kernels >= 2, step_kernels)
    for name, r in ops.items():
        ph.check(f"{name} vs ref.py", r["native"] and not r["mismatched"], r)
    return ph


# ---------------------------------------------------------------------------
# --chips 4: the sharded clients mesh
# ---------------------------------------------------------------------------

def _sharded_reduce_ops(tr: FSLGANTrainer) -> dict:
    """The server reduces on the clients mesh against the same op on one
    device, on this round's tensors: the clients' Adam first moments as
    (C, N) rows placed on the mesh's ``clients`` axis."""
    mesh = tr._client_mesh()
    rep = tr.engine.last_report
    cids = sorted(rep.opt_states)
    ups = np.stack([np.asarray(_flat(rep.opt_states[cid]["m"]))
                    for cid in cids])
    weights = jnp.arange(1.0, len(cids) + 1.0)
    rows = NamedSharding(mesh, P("clients"))
    out: dict = {}
    _compare(out, "fedavg", _native(fedavg_flat, jax.device_put(ups, rows),
                                    weights, mesh=mesh),
             fedavg_flat(jnp.asarray(ups), weights))
    encs = [make_codec("int8").encode(jnp.asarray(u)) for u in ups]
    wires = np.stack([np.asarray(e[0]) for e in encs])
    scales = jnp.asarray([float(e[1]) for e in encs], jnp.float32)
    _compare(out, "dequant_reduce[int8]",
             _native(dequant_reduce_flat, jax.device_put(wires, rows),
                     scales, weights, use_kernel=True, mesh=mesh),
             dequant_reduce_flat(jnp.asarray(wires), scales, weights,
                                 use_kernel=True))
    return out


def phase_chips4() -> Phase:
    base = get_config("dcgan-mnist").override({
        "fsl.num_clients": 4, "fed.backend": "vectorized",
        "fed.codec": "int8", "fed.kernel_aggregation": True})
    parts = _client_data(4)
    ph = Phase("chips4")
    for reduce in ("decode", "batched"):
        with Phase(f"chips4[{reduce}]") as sub:
            over = {"fed.server_reduce": reduce}
            tr = FSLGANTrainer(base.override(
                {**over, "fed.shard_clients": True}), parts, seed=0)
            placed = []
            run = tr.program.run_vectorized

            def spy(params, opt, reals, fakes, **kw):
                placed.append(reals.sharding)
                return run(params, opt, reals, fakes, **kw)

            tr.program.run_vectorized = spy
            ms = tr.train_epoch(batches_per_client=KERNEL_BATCHES)
            one = FSLGANTrainer(base.override(over), parts, seed=0)
            mo = one.train_epoch(batches_per_client=KERNEL_BATCHES)
            live, dead = _d_diff(_global_d(tr), _global_d(one))
            ops = _sharded_reduce_ops(tr) if reduce == "decode" else {}
        spans = [len(s.device_set) for s in placed]
        replicated = [s.is_fully_replicated for s in placed]
        sub.report(d_loss=ms["d_loss"], ref_d_loss=mo["d_loss"],
                   shards=tr.feedback[-1].shards, input_devices=spans,
                   replicated=replicated,
                   d_max_abs_diff_vs_one_device=live,
                   bn_conv_bias_max_abs_diff=dead, loss_tol=LOSS_TOL,
                   ops=ops)
        sub.check("clients mesh", mesh_chips(tr._client_mesh()) == 4,
                  tr.feedback[-1].shards)
        sub.check("inputs sharded over 4 devices",
                  spans == [4] and not any(replicated), (spans, replicated))
        sub.check("losses finite",
                  all(np.isfinite([ms["d_loss"], ms["g_loss"]])), ms)
        sub.check("round d_loss vs one device",
                  abs(ms["d_loss"] - mo["d_loss"]) <= LOSS_TOL,
                  (ms["d_loss"], mo["d_loss"]))
        for name, r in ops.items():
            sub.check(f"{name} on the mesh vs one device",
                      r["native"] and not r["mismatched"], r)
        ph.failed += sub.failed
    return ph


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    failed = []
    try:
        peaks = hw_for_kind(dev.device_kind).name
    except ValueError as e:
        peaks = None
        failed.append(str(e))
    print(json.dumps({"device_kind": dev.device_kind,
                      "device_count": len(devices), "peaks": peaks,
                      "compile_cache": use_compile_cache()}), flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    phases = [phase_chips4] if args.chips == 4 \
        else [phase_main, phase_kernels]
    for phase in phases:
        try:
            failed += phase().failed
        except Exception:                 # report it, run the next phase
            traceback.print_exc()
            failed.append(f"{phase.__name__}: raised")
    if failed:
        print("chip_smoke FAILED:\n  " + "\n  ".join(failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
