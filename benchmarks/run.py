"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Set BENCH_FAST=1 for a quick
pass (fewer epochs/seeds).

Every trainer bench records flight-recorder artifacts under
``benchmarks/obs/`` (see _obs.py).  The BENCH_*.json baselines some
benches (re)write are regression-gated: after a bench pass, run

    python -m repro.obs.regress --bench-dir benchmarks --baseline-git HEAD

to compare the fresh numbers against the committed baselines (CI does
this in the bench-regress job and fails on regression).

  bench_time          Fig 2  epoch time vs splitting strategy
  bench_convergence   Fig 3  generator loss vs #discriminators
  bench_images        Fig 4  image-quality proxies
  bench_kernels       —      Pallas kernels vs oracles (+ µs, interpret)
  bench_lm_train      —      LM substrate + FSL cadence
  bench_roofline      —      roofline table from dry-run artifacts
  bench_fed_runtime   —      federation runtime: loop vs vectorized client-
                             program dispatch, codec wire bytes, sync/async
                             rounds; writes BENCH_fed_runtime.json
  bench_privacy       —      privacy frontier: split-depth leakage, DP
                             sigma sweep (eps/utility/inversion PSNR),
                             dp_clip kernel; writes BENCH_privacy.json
  bench_control       —      closed-loop control plane: adaptive codec vs
                             the static frontier, sigma budget spend,
                             deadline retuning; writes BENCH_control.json
"""
from __future__ import annotations

import os
import sys
import time
import traceback

from repro.launch.cache import use_compile_cache


def main() -> None:
    fast = os.environ.get("BENCH_FAST", "0") == "1"
    from benchmarks import (bench_control, bench_convergence,
                            bench_fed_runtime, bench_heterogeneity,
                            bench_images, bench_kernels, bench_lm_train,
                            bench_privacy, bench_roofline, bench_time)
    modules = [
        ("bench_time", bench_time),
        ("bench_fed_runtime", bench_fed_runtime),
        ("bench_control", bench_control),
        ("bench_privacy", bench_privacy),
        ("bench_kernels", bench_kernels),
        ("bench_lm_train", bench_lm_train),
        ("bench_images", bench_images),
        ("bench_convergence", bench_convergence),
        ("bench_heterogeneity", bench_heterogeneity),
        ("bench_roofline", bench_roofline),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            rows = mod.run(fast=fast)
            for rname, us, derived in rows:
                print(f"{rname},{us:.1f},{derived}", flush=True)
        except Exception as e:
            failures += 1
            traceback.print_exc(file=sys.stderr)
            print(f"{name},0.0,ERROR {type(e).__name__}: {e}", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    use_compile_cache()
    main()
