"""End-to-end driver (the paper's experiment): FSL-GAN on (synthetic) MNIST.

Trains the DCGAN with the full FSL pipeline — central generator, federated
split discriminators, device-selection planning, FedAvg each round — for a
few hundred discriminator steps, then reports losses, the Fig-4 style
image-quality proxies, and writes artifacts under experiments/gan/.

Run: PYTHONPATH=src python examples/fsl_gan_mnist.py [--epochs 12]
(~3-5 min on this container's CPU at the default reduced width.)
"""
import argparse
import json
import os
import time

import numpy as np

from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.launch.cache import use_compile_cache

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "gan")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--batches-per-client", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--base-filters", type=int, default=16)
    ap.add_argument("--selection", default="sorted_multi")
    args = ap.parse_args()

    cfg = get_config("dcgan-mnist").override({
        "shape.global_batch": args.batch_size,
        "fsl.num_clients": args.clients,
        "fsl.selection": args.selection,
        "model.dcgan.base_filters": args.base_filters})
    imgs, labels = synthetic_mnist(4000, seed=0)
    parts = partition_dirichlet(imgs, labels, args.clients, alpha=0.5, seed=0)
    print(f"clients: { {k: len(v) for k, v in parts.items()} } examples")

    tr = FSLGANTrainer(cfg, parts, seed=0)
    for cid, plan in tr.plans.items():
        print(f"  {cid} plan: " + " | ".join(
            f"{p.device_id}:{','.join(p.layer_names)}" for p in plan.portions))

    t0 = time.time()
    hist = []
    steps = 0
    for ep in range(args.epochs):
        m = tr.train_epoch(batches_per_client=args.batches_per_client)
        steps += args.clients * args.batches_per_client
        hist.append(m)
        print(f"epoch {ep:3d}: d={m['d_loss']:.3f} g={m['g_loss']:.3f} "
              f"({steps} disc steps, {time.time()-t0:.0f}s)", flush=True)

    gen = tr.generate(64)
    mse = float(np.mean((gen.mean(0) - imgs.mean(0)) ** 2))
    os.makedirs(OUT, exist_ok=True)
    np.save(os.path.join(OUT, "generated.npy"), gen)
    with open(os.path.join(OUT, "history.json"), "w") as f:
        json.dump({"history": hist, "mean_image_mse": mse,
                   "total_disc_steps": steps}, f, indent=2)
    print(f"done: {steps} discriminator steps, mean-image MSE {mse:.4f}, "
          f"artifacts in {os.path.abspath(OUT)}")


if __name__ == "__main__":
    use_compile_cache()
    main()
