"""Training launcher.

Two modes:
  * ``--smoke``  reduced config on the host devices — runs real steps on
    synthetic data and prints losses (what CI exercises).
  * full config — builds the production mesh (requires the real pod or the
    dry-run device-count env) and runs the same loop.

FSL mode (``--fsl``) trains per-client replicas with FedAvg every
``fsl.local_steps`` steps — the paper's cadence applied to an LM.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b --smoke --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.launch.cache import use_compile_cache
from repro.launch.distributed import log_topology, maybe_initialize_distributed
from repro.config import RunConfig, reduce_for_smoke
from repro.configs.registry import get_config
from repro.data import synthetic_lm_batch
from repro.models.transformer import lm_init
from repro.optim import make_optimizer
from repro.runtime import make_fsl_train_step, make_train_step


def train_loop(cfg: RunConfig, steps: int, fsl_clients: int = 0,
               ckpt_dir: str = "", log_every: int = 1, seed: int = 0):
    m = cfg.model
    key = jax.random.PRNGKey(seed)
    dt = jnp.dtype(cfg.parallel.param_dtype)
    params = lm_init(key, m, dt)
    opt = make_optimizer(cfg.optim)
    opt_state = opt.init(params)
    b, seq = cfg.shape.global_batch, cfg.shape.seq_len
    if m.encdec.enabled:
        seq = min(seq, m.encdec.max_target_positions)

    fsl = fsl_clients > 0
    if fsl:
        step_fn = jax.jit(make_fsl_train_step(cfg, fsl_clients))
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (fsl_clients, *x.shape)),
            params)
        opt_state = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (fsl_clients, *x.shape)),
            opt_state)
    else:
        step_fn = jax.jit(make_train_step(cfg))

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    history = []
    t0 = time.time()
    for i in range(steps):
        batch = synthetic_lm_batch(b * max(1, fsl_clients), seq,
                                   m.vocab_size, seed=seed + i)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if m.encdec.enabled:
            batch["enc_embeds"] = 0.1 * jax.random.normal(
                jax.random.fold_in(key, i),
                (batch["tokens"].shape[0], m.encdec.encoder_seq, m.d_model),
                jnp.dtype(cfg.parallel.compute_dtype))
        if fsl:
            batch = jax.tree.map(
                lambda x: x.reshape(fsl_clients, b, *x.shape[1:]), batch)
        params, opt_state, metrics = step_fn(params, opt_state, batch,
                                             jnp.asarray(i, jnp.int32))
        loss = float(metrics["loss"])
        history.append(loss)
        if i % log_every == 0:
            print(f"step {i:5d} loss={loss:.4f} "
                  f"aux={float(metrics['aux_loss']):.4f} "
                  f"({time.time() - t0:.1f}s)", flush=True)
        if mgr and (i + 1) % 50 == 0:
            mgr.save(i + 1, params)
    return params, history


def main():
    if maybe_initialize_distributed():
        log_topology()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--smoke-seq", type=int, default=64)
    ap.add_argument("--smoke-batch", type=int, default=4)
    ap.add_argument("--fsl", type=int, default=0,
                    help="train N federated client replicas (FSL mode)")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    cfg = get_config(args.arch, args.shape)
    if args.smoke:
        cfg = reduce_for_smoke(cfg, seq_len=args.smoke_seq,
                               batch=args.smoke_batch)
    _, history = train_loop(cfg, args.steps, args.fsl, args.ckpt)
    print(f"final loss {history[-1]:.4f} (from {history[0]:.4f})")


if __name__ == "__main__":
    use_compile_cache()
    main()
