"""Quickstart: the three layers of the framework in ~a minute on CPU.

1. paper core   — split a discriminator across heterogeneous devices and
                  price the four selection strategies (Fig 2 machinery)
2. FSL-GAN      — two federated clients train a DCGAN for two rounds
3. substrate    — a reduced assigned architecture takes two LM train steps

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.config import DCGANConfig, reduce_for_smoke
from repro.configs.registry import get_config
from repro.core import FSLGANTrainer, make_pool, strategy_sweep
from repro.data import partition_dirichlet, synthetic_lm_batch, synthetic_mnist
from repro.launch.cache import use_compile_cache
from repro.models.dcgan import disc_layer_costs, disc_layer_names
from repro.models.transformer import lm_init
from repro.optim import make_optimizer
from repro.runtime import make_train_step


def demo_split_planning():
    print("=== 1. split planning & strategy pricing (paper Fig 2) ===")
    c = DCGANConfig()
    costs = disc_layer_costs(c)
    total = sum(costs.values())
    layers = [(n, 4 * costs[n] / total) for n in disc_layer_names(c)]
    pool = make_pool("paper", 5, 4, seed=0)
    res = strategy_sweep(pool, layers, seeds=range(3), compute_unit_s=0.2)
    for strat, (mean, std) in sorted(res.items(), key=lambda kv: kv[1][0]):
        print(f"  {strat:16s} slowest-client epoch: {mean:7.2f}s ± {std:.2f}")


def demo_fsl_gan():
    print("=== 2. FSL-GAN: 2 clients, 2 rounds ===")
    cfg = get_config("dcgan-mnist").override({
        "shape.global_batch": 16, "fsl.num_clients": 2,
        "model.dcgan.base_filters": 8})
    imgs, labels = synthetic_mnist(200, seed=0)
    parts = partition_dirichlet(imgs, labels, 2, alpha=0.5, seed=0)
    tr = FSLGANTrainer(cfg, parts, seed=0)
    for ep in range(2):
        m = tr.train_epoch(batches_per_client=2)
        print(f"  round {ep}: d_loss={m['d_loss']:.3f} g_loss={m['g_loss']:.3f}")
    print(f"  generated {tr.generate(2).shape} images; plans: "
          f"{ {cid: len(p.portions) for cid, p in tr.plans.items()} } portions")


def demo_lm_substrate():
    print("=== 3. assigned-arch substrate: olmoe-1b-7b (reduced) ===")
    cfg = reduce_for_smoke(get_config("olmoe-1b-7b", "train_4k"),
                           seq_len=32, batch=4)
    m = cfg.model
    params = lm_init(jax.random.PRNGKey(0), m)
    opt = make_optimizer(cfg.optim)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg))
    for i in range(2):
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_lm_batch(4, 32, m.vocab_size, seed=i).items()}
        params, opt_state, metrics = step(params, opt_state, batch,
                                          jnp.asarray(i, jnp.int32))
        print(f"  step {i}: loss={float(metrics['loss']):.3f} "
              f"(aux={float(metrics['aux_loss']):.4f})")


if __name__ == "__main__":
    use_compile_cache()
    demo_split_planning()
    demo_fsl_gan()
    demo_lm_substrate()
    print("quickstart OK")
