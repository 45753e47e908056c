"""Federation runtime demo: async vs sync scheduling, codecs, stragglers.

Runs the same FSL-GAN workload (paper §3, smoke scale) under four runtime
configurations and prints, per epoch, the virtual round time (the paper's
Fig-2 wall-clock model extended with WAN transfers), uplink traffic, and
losses:

  sync            the paper's barrier FedAvg (bit-identical to the seed)
  sync+deadline   barrier with straggler dropout at a deadline
  fedasync+int8   staleness-weighted async aggregation, int8 uplink codec
  fedbuff+topk    buffered async aggregation, top-k sparsified uplink

``--backend vectorized`` compiles each scenario's client program as one
jitted vmap-over-clients round instead of the per-client loop (the
scheduling x backend matrix of fed/programs.py — any scenario composes
with either backend).

Run: PYTHONPATH=src python examples/fed_async_demo.py [--epochs 4]
                                                      [--backend loop]
"""
import argparse

from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.launch.cache import use_compile_cache

SCENARIOS = {
    "sync": {},
    "sync+deadline": {"fed.deadline_s": 2.4e4},
    "fedasync+int8": {"fed.mode": "fedasync", "fed.codec": "int8",
                      "fed.async_cycles": 2},
    "fedbuff+topk": {"fed.mode": "fedbuff", "fed.codec": "topk",
                     "fed.topk_frac": 0.05, "fed.buffer_size": 2,
                     "fed.async_cycles": 2},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--batches-per-client", type=int, default=4)
    ap.add_argument("--backend", choices=("loop", "vectorized"),
                    default="loop")
    args = ap.parse_args()

    imgs, labels = synthetic_mnist(1000, seed=0)
    parts = partition_dirichlet(imgs, labels, args.clients, alpha=0.5,
                                seed=0)

    for name, over in SCENARIOS.items():
        cfg = get_config("dcgan-mnist").override({
            "shape.global_batch": 16, "fsl.num_clients": args.clients,
            "model.dcgan.base_filters": 8, **over})
        tr = FSLGANTrainer(cfg, parts, seed=0)
        print(f"\n=== {name} ===")
        for ep in range(args.epochs):
            m = tr.train_epoch(batches_per_client=args.batches_per_client,
                               backend=args.backend)
            print(f"  ep {ep}: d={m['d_loss']:.3f} g={m['g_loss']:.3f} "
                  f"round={m['round_time_s']:.0f}s "
                  f"clients={m['num_clients']:.0f} "
                  f"drop={m['stragglers']:.0f} "
                  f"stale={m['mean_staleness']:.2f} "
                  f"up={m['up_mbytes']:.3f}MB", flush=True)
        led = tr.engine.ledger
        print(f"  totals: up={led.total_up/1e6:.3f}MB "
              f"down={led.total_down/1e6:.3f}MB "
              f"virtual clock={tr.engine.clock:.0f}s")


if __name__ == "__main__":
    use_compile_cache()
    main()
