import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) pair this lowers + compiles the real
step function (train_step / prefill / decode) against ShapeDtypeStruct
stand-ins on the production mesh — 256 fake host devices for the single-pod
(16,16) mesh and 512 for the multi-pod (2,16,16) mesh — then prints
``memory_analysis()`` (fits?) and ``cost_analysis()`` (FLOPs/bytes for
§Roofline), and writes a JSON artifact per combination under
``experiments/dryrun/``.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import RunConfig
from repro.configs.registry import (ASSIGNED_ARCHS, SHAPES, SkippedShape,
                                    get_config)
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.launch import specs as S
from repro.roofline import analyze_compiled
from repro.roofline.hw import TPU_V5E       # the production pod's chip
from repro.runtime import make_decode_step, make_prefill_step, make_train_step
from repro.sharding.specs import (make_activation_policy,
                                  set_activation_policy)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def lower_one(cfg: RunConfig, mesh, donate: bool = True):
    """Build + lower + compile the step for cfg on mesh. Returns (lowered,
    compiled, seconds)."""
    rules = S.make_rules(cfg, mesh)
    set_activation_policy(make_activation_policy(mesh, rules))
    try:
        pshapes = S.param_shapes(cfg)
        psh = S.param_shardings(cfg, mesh, pshapes)
        ins = S.input_specs(cfg)
        bsh = S.batch_shardings(cfg, mesh, ins)
        rep = NamedSharding(mesh, P())
        mode = cfg.shape.mode
        t0 = time.time()
        with mesh:
            if mode == "train":
                oshapes = S.opt_shapes(cfg, pshapes)
                osh = S.opt_shardings(cfg, mesh, pshapes)
                step = make_train_step(cfg)
                jitted = jax.jit(
                    step,
                    in_shardings=(psh, osh, bsh, rep),
                    out_shardings=(psh, osh, rep),
                    donate_argnums=(0, 1))
                lowered = jitted.lower(
                    pshapes, oshapes, ins,
                    jax.ShapeDtypeStruct((), jnp.int32))
            elif mode == "prefill":
                stepf = make_prefill_step(cfg)
                jitted = jax.jit(stepf, in_shardings=(psh, bsh))
                lowered = jitted.lower(pshapes, ins)
            else:  # decode
                stepf = make_decode_step(cfg)
                jitted = jax.jit(
                    stepf,
                    in_shardings=(psh, bsh["token"], bsh["state"],
                                  bsh["index"]),
                    out_shardings=(rep, bsh["state"]),
                    donate_argnums=(2,))
                lowered = jitted.lower(pshapes, ins["token"], ins["state"],
                                       ins["index"])
            compiled = lowered.compile()
        return lowered, compiled, time.time() - t0
    finally:
        set_activation_policy(None)


def run_pair(arch: str, shape: str, multi_pod: bool,
             out_dir: str = OUT_DIR, verbose: bool = True,
             probes: bool = True) -> Optional[Dict[str, Any]]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{arch}_{shape}_{mesh_name}.json")
    try:
        cfg = get_config(arch, shape)
    except SkippedShape as e:
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "status": "skipped", "reason": str(e)}
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2)
        if verbose:
            print(f"[skip] {arch} x {shape}: {e}")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        lowered, compiled, secs = lower_one(cfg, mesh)
    except Exception as e:
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"[FAIL] {arch} x {shape} ({mesh_name}): {e}")
        return rec

    mem = compiled.memory_analysis()
    # tokens per step for MODEL_FLOPS = 6*N*D (decode: 1 token per seq)
    b, seq = S.batch_tokens(cfg)
    if cfg.shape.mode == "decode":
        tokens = b
    else:
        tokens = b * seq
    n_active = cfg.model.active_param_count()
    factor = 6.0 if cfg.shape.mode == "train" else 2.0
    model_flops = factor * n_active * tokens
    rep = analyze_compiled(
        compiled, arch=arch, shape=shape, mesh_name=mesh_name,
        chips=mesh_chips(mesh), model_flops=model_flops, hw=TPU_V5E)
    # loop-aware terms via probe differencing (cost_analysis counts while
    # bodies once — see roofline/probes.py)
    try:
        if not probes:
            raise RuntimeError("probes disabled (--no-probes)")
        from repro.roofline.probes import probe_costs
        pc = probe_costs(cfg, mesh)
        rep.hlo_flops = pc["flops"]["total"]
        rep.hlo_bytes = pc["bytes"]["total"]
        rep.collective_bytes = pc["coll"]["total"]
        rep.compute_term_s = rep.hlo_flops / TPU_V5E.peak_flops_bf16
        rep.memory_term_s = rep.hlo_bytes / TPU_V5E.hbm_bw
        rep.collective_term_s = rep.collective_bytes / TPU_V5E.ici_bw_per_link
        probe_terms = pc
    except Exception as e:  # probes are best-effort; keep raw numbers
        probe_terms = {"error": f"{type(e).__name__}: {e}"}
    rec = rep.to_dict()
    rec.update(status="ok", compile_s=secs, mode=cfg.shape.mode,
               params=cfg.model.param_count(),
               active_params=n_active, tokens_per_step=tokens,
               probe_terms=probe_terms)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=2)
    if verbose:
        print(f"[ok] {arch:22s} {shape:12s} {mesh_name:10s} "
              f"compile={secs:6.1f}s flops/chip={rep.hlo_flops:.3e} "
              f"coll={rep.collective_bytes:.3e}B dom={rep.dominant} "
              f"mem(args+tmp)={(rep.arg_bytes_per_device + rep.temp_bytes_per_device)/2**30:.2f}GiB")
        print(f"     memory_analysis: {mem}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-probes", action="store_true",
                    help="compile-proof only (skip probe cost accounting)")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    pairs = []
    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = SHAPES if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                out_path = os.path.join(args.out,
                                        f"{a}_{s}_{mesh_name}.json")
                if args.skip_existing and os.path.exists(out_path):
                    with open(out_path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[cached] {a} x {s} ({mesh_name})")
                        continue
                rec = run_pair(a, s, mp, args.out,
                               probes=not args.no_probes)
                if rec and rec.get("status") == "error":
                    failures += 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
