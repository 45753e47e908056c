"""Privacy subsystem demo: attack -> metric -> DP defense, end to end.

Walks the honest-but-curious threat model against the paper's protocol on
the synthetic dataset, at smoke scale:

  1. train a few FSL-GAN rounds (no privacy) and ATTACK the artifacts the
     runtime ships — gradient inversion of the uplinked D gradient,
     activation inversion at a split boundary, membership inference on the
     trained D;
  2. MEASURE the leakage — reconstruction PSNR/SSIM, distance correlation
     per split depth, attack AUC;
  3. DEFEND with DP-SGD (per-example clip + Gaussian noise through the
     kernels/dp_clip path) and re-run the gradient inversion: PSNR drops
     while the RDP accountant prices the epsilon spent.

Run: PYTHONPATH=src python examples/privacy_frontier_demo.py [--epochs 2]
"""
import argparse
import functools

import jax
import jax.numpy as jnp

from repro.config import DCGANConfig
from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer, d_loss_fn
from repro.data import partition_dirichlet, synthetic_mnist
from repro.kernels.dp_clip.ops import dp_clip_noise_tree
from repro.launch.cache import use_compile_cache
from repro.privacy import (ActivationInversionAttack, best_match_psnr,
                           distance_correlation, invert_gradients,
                           make_prefix_fn, membership_inference,
                           plan_boundary_depths, psnr, ssim)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--sigma", type=float, default=1.0,
                    help="DP noise multiplier for the defended run")
    args = ap.parse_args()

    base = {"shape.global_batch": 8, "fsl.num_clients": args.clients,
            "model.dcgan.base_filters": 8}
    imgs, labels = synthetic_mnist(600, seed=0)
    parts = partition_dirichlet(imgs, labels, args.clients, alpha=0.5,
                                seed=0)
    c = DCGANConfig(base_filters=8)
    loss_fn = functools.partial(d_loss_fn, c=c)

    # --- 1. undefended training ------------------------------------------
    print("=== training (no privacy) ===")
    tr = FSLGANTrainer(get_config("dcgan-mnist").override(base), parts,
                       seed=0)
    for ep in range(args.epochs):
        m = tr.train_epoch(batches_per_client=4)
        print(f"  ep {ep}: d={m['d_loss']:.3f} g={m['g_loss']:.3f}")
    params = tr.state.d_params[tr.client_ids[0]]

    # --- 2a. gradient inversion of the uplinked D gradient ---------------
    print("\n=== attack 1: gradient inversion (server-side) ===")
    victim = jnp.asarray(parts["c0"][:1])
    fake = 0.3 * jax.random.normal(jax.random.PRNGKey(3), victim.shape)
    g = jax.grad(loss_fn)(params, victim, fake)
    rec, hist = invert_gradients(loss_fn, params, g, fake, victim.shape,
                                 steps=200, key=jax.random.PRNGKey(7))
    print(f"  reconstruction: PSNR={best_match_psnr(rec, victim):.2f}dB "
          f"SSIM={ssim(rec, victim):.3f} match_loss={hist[-1]:.4f}")

    # --- 2b. activation inversion at the split boundaries ----------------
    print("\n=== attack 2: activation inversion (LAN observer) ===")
    plan = next(iter(tr.plans.values()))
    depths = plan_boundary_depths(plan) or [1]
    aux, _ = synthetic_mnist(256, seed=5)          # attacker's shadow data
    probe = jnp.asarray(parts["c0"][:16])
    for depth in sorted(set(depths)):
        atk = ActivationInversionAttack(make_prefix_fn(params, c, depth),
                                        (28, 28, 1), seed=0)
        atk.train(aux, steps=150, batch=32)
        rec_a = atk.reconstruct(probe)
        dcor = distance_correlation(probe, atk.prefix(probe))
        print(f"  boundary depth {depth}: PSNR={psnr(rec_a, probe):.2f}dB "
              f"dCor={dcor:.3f}")

    # --- 2c. membership inference on the trained D -----------------------
    print("\n=== attack 3: membership inference ===")
    nonmember, _ = synthetic_mnist(64, seed=99)
    mi = membership_inference(params, c, parts["c0"][:64], nonmember)
    print(f"  AUC={mi['auc']:.3f} advantage={mi['advantage']:.3f}")

    # --- 3. DP-SGD defense + re-attack ------------------------------------
    print(f"\n=== defense: DP-SGD (sigma={args.sigma}) ===")
    tr_dp = FSLGANTrainer(get_config("dcgan-mnist").override({
        **base, "privacy.enabled": True,
        "privacy.noise_multiplier": args.sigma,
        "privacy.sample_rate": 0.1}), parts, seed=0)
    for ep in range(args.epochs):
        m = tr_dp.train_epoch(batches_per_client=4)
        print(f"  ep {ep}: d={m['d_loss']:.3f} g={m['g_loss']:.3f} "
              f"epsilon={m['dp_epsilon']:.2f}")
    dp_params = tr_dp.state.d_params[tr_dp.client_ids[0]]
    per_ex = jax.vmap(
        lambda r, f: jax.grad(loss_fn)(dp_params, r[None], f[None]),
        in_axes=(0, 0))(victim, fake)
    g_dp = dp_clip_noise_tree(per_ex, 1.0, args.sigma,
                              jax.random.PRNGKey(11), use_kernel=False)
    rec_dp, _ = invert_gradients(loss_fn, dp_params, g_dp, fake,
                                 victim.shape, steps=200,
                                 key=jax.random.PRNGKey(7))
    print(f"  re-attack under DP: PSNR={best_match_psnr(rec_dp, victim):.2f}dB"
          f" (vs {best_match_psnr(rec, victim):.2f}dB undefended) at "
          f"epsilon={tr_dp.accountant.epsilon(1e-5)[0]:.2f}")


if __name__ == "__main__":
    use_compile_cache()
    main()
