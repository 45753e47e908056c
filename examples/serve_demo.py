"""Batched serving demo: prefill a mixed-length request batch, then greedy
decode — the production serving path at smoke scale.

Run: PYTHONPATH=src python examples/serve_demo.py [--arch rwkv6-1.6b]
"""
import argparse

import numpy as np

from repro.config import reduce_for_smoke
from repro.configs.registry import get_config
from repro.data import synthetic_tokens
from repro.launch.cache import use_compile_cache
from repro.launch.serve import Request, serve_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--gen-tokens", type=int, default=12)
    args = ap.parse_args()

    cfg = reduce_for_smoke(get_config(args.arch, "decode_32k"), seq_len=64,
                           batch=args.requests)
    rng = np.random.default_rng(0)
    reqs = [Request(i, synthetic_tokens(1, int(rng.integers(8, 33)),
                                        cfg.model.vocab_size, seed=i)[0])
            for i in range(args.requests)]
    serve_batch(cfg, reqs, args.gen_tokens)


if __name__ == "__main__":
    use_compile_cache()
    main()
