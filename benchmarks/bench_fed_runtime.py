"""Federation runtime benchmarks (fed/ subsystem).

Three questions the runtime makes measurable:

  1. **Dispatch**: the client program's two backends — per-client loop of
     jitted steps vs ONE jitted vmap/scan program (fed/programs.py) —
     against the seed's sequential reference, under the engine.
  2. **Wire**: per-round uplink bytes and virtual round time under each
     codec (none / fp16 / int8 / topk) — what actually crosses the network
     per PS-FedGAN's accounting.
  3. **Scheduling**: sync barrier vs FedAsync vs FedBuff virtual wall-clock
     per round, with and without a straggler deadline.
  4. **Pipeline**: micro-batched split execution — virtual round time vs
     the number of micro-batches K (the 1F1B overlap schedule from
     core/pipeline feeding plan_epoch_time), plus the fused boundary
     stage (kernels/boundary_fuse) against the unfused composition.

Besides CSV rows, writes machine-readable ``BENCH_fed_runtime.json`` next
to this file (gitignored; parity with ``BENCH_privacy.json``) so the
dispatch/wire/scheduling trajectory is trackable across PRs.
"""
from __future__ import annotations

import json
import os
import time
from typing import List, Tuple

import numpy as np

from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist

from benchmarks._obs import finish, obs_over

JSON_PATH = os.path.join(os.path.dirname(__file__), "BENCH_fed_runtime.json")


def _cfg(clients: int, **over):
    base = {"shape.global_batch": 16, "fsl.num_clients": clients,
            "model.dcgan.base_filters": 8}
    base.update(over)
    return get_config("dcgan-mnist").override(base)


def _parts(clients: int):
    imgs, labels = synthetic_mnist(200 * clients, seed=0)
    return partition_dirichlet(imgs, labels, clients, alpha=0.5, seed=0)


def _time_epochs(step, reps: int) -> float:
    step()                                   # warm-up / compile
    t0 = time.time()
    for _ in range(reps):
        step()
    return (time.time() - t0) * 1e6 / reps   # us per epoch


def run(fast: bool = False) -> List[Tuple[str, float, str]]:
    clients = 3 if fast else 4
    batches = 2 if fast else 4
    reps = 2 if fast else 3
    parts = _parts(clients)
    rows: List[Tuple[str, float, str]] = []
    results = {"config": {"clients": clients, "batches": batches,
                          "reps": reps, "fast": fast}}

    # 1. dispatch: seed reference vs engine loop vs engine vectorized -----
    tr_seq = FSLGANTrainer(_cfg(clients), parts, seed=0)
    us_seq = _time_epochs(
        lambda: tr_seq.train_epoch_sequential(batches_per_client=batches),
        reps)
    tr_loop = FSLGANTrainer(_cfg(clients), parts, seed=0)
    us_loop = _time_epochs(
        lambda: tr_loop.train_epoch(batches_per_client=batches,
                                    backend="loop"), reps)
    tr_vec = FSLGANTrainer(_cfg(clients), parts, seed=0)
    us_vec = _time_epochs(
        lambda: tr_vec.train_epoch(batches_per_client=batches,
                                   backend="vectorized"), reps)
    rows.append(("fed_round_sequential", us_seq,
                 f"clients={clients} batches={batches}"))
    rows.append(("fed_round_engine[loop]", us_loop,
                 "engine sync, per-client jitted steps (bit-exact)"))
    rows.append(("fed_round_engine[vectorized]", us_vec,
                 f"speedup={us_loop / max(us_vec, 1e-9):.2f}x vs loop "
                 "(one jitted vmap program)"))
    # backend="auto": one-shot timed probe on the first round picks the
    # faster dispatch for this host (fixes the vectorized-on-CPU trap)
    tr_auto = FSLGANTrainer(_cfg(clients), parts, seed=0)
    us_auto = _time_epochs(
        lambda: tr_auto.train_epoch(batches_per_client=batches,
                                    backend="auto"), reps)
    auto_fb = next(fb for fb in tr_auto.feedback if fb.backend_probe_us)
    rows.append(("fed_round_engine[auto]", us_auto,
                 f"chose {auto_fb.backend} (probe: "
                 + " ".join(f"{k}={v:.0f}us"
                            for k, v in sorted(
                                auto_fb.backend_probe_us.items())) + ")"))
    results["dispatch"] = {
        "sequential_us": us_seq, "engine_loop_us": us_loop,
        "engine_vectorized_us": us_vec,
        "engine_auto_us": us_auto,
        "auto_choice": auto_fb.backend,
        "auto_probe_us": dict(auto_fb.backend_probe_us),
        "vectorized_speedup_vs_loop": us_loop / max(us_vec, 1e-9),
        "vectorized_speedup_vs_sequential": us_seq / max(us_vec, 1e-9)}

    # 2. codec sweep: uplink bytes + virtual round time --------------------
    results["codecs"] = {}
    for codec in ("none", "fp16", "int8", "topk"):
        tr = FSLGANTrainer(_cfg(clients, **{"fed.codec": codec,
                                            "fed.topk_frac": 0.05}),
                           parts, seed=0)
        t0 = time.time()
        m = tr.train_epoch(batches_per_client=batches)
        us = (time.time() - t0) * 1e6
        rows.append((f"fed_codec[{codec}]", us,
                     f"up_mb={m['up_mbytes']:.4f} "
                     f"down_mb={m['down_mbytes']:.4f} "
                     f"round_s={m['round_time_s']:.1f} "
                     f"d_loss={m['d_loss']:.3f}"))
        results["codecs"][codec] = {
            "us_per_epoch": us, "up_mbytes": m["up_mbytes"],
            "down_mbytes": m["down_mbytes"],
            "round_time_s": m["round_time_s"],
            "d_loss": None if not np.isfinite(m["d_loss"])
            else m["d_loss"]}

    # 3. scheduling: sync vs async vs buffered, straggler deadline ---------
    scenarios = {
        "sync": {},
        "sync_deadline": {"fed.deadline_s": 2.5e4},
        "fedasync": {"fed.mode": "fedasync", "fed.async_cycles": 2},
        "fedbuff": {"fed.mode": "fedbuff", "fed.buffer_size": 2,
                    "fed.async_cycles": 2},
    }
    results["scheduling"] = {}
    for name, over in scenarios.items():
        # each scheduling scenario leaves a recorded trace + metrics run
        # under benchmarks/obs/ (sync barrier vs async event loop spans)
        tr = FSLGANTrainer(_cfg(clients, **over,
                                **obs_over(f"fed_sched_{name}")),
                           parts, seed=0)
        t0 = time.time()
        ms = [tr.train_epoch(batches_per_client=batches)
              for _ in range(2 if fast else 3)]
        m = ms[-1]
        us = (time.time() - t0) * 1e6 / len(ms)
        rows.append((f"fed_sched[{name}]", us,
                     f"round_s={m['round_time_s']:.1f} "
                     f"clients={m['num_clients']:.0f} "
                     f"stragglers={m['stragglers']:.0f} "
                     f"staleness={m['mean_staleness']:.2f} "
                     f"d_loss={m['d_loss']:.3f}"))
        results["scheduling"][name] = {
            "us_per_epoch": us, "round_time_s": m["round_time_s"],
            "num_clients": m["num_clients"],
            "stragglers": m["stragglers"],
            "mean_staleness": m["mean_staleness"],
            "d_loss": None if not np.isfinite(m["d_loss"])
            else m["d_loss"],
            "trace_spans": len(tr.recorder.tracer.spans)}
        finish(tr)

    # 4. pipeline: micro-batched split execution vs K ----------------------
    results["pipeline"] = {}
    pipe_metrics = {}
    for k in (1, 2, 4):
        tr = FSLGANTrainer(_cfg(clients, **{
            "split.enabled": True,
            "split.pipeline_microbatches": k}), parts, seed=0)
        t0 = time.time()
        m = tr.train_epoch(batches_per_client=batches)
        us = (time.time() - t0) * 1e6
        fb = tr.feedback[-1]
        rows.append((f"fed_pipeline[k{k}]", us,
                     f"round_s={m['round_time_s']:.1f} "
                     f"overlap_speedup={fb.pipeline_speedup:.2f} "
                     f"d_loss={m['d_loss']:.3f}"))
        pipe_metrics[k] = m
        results["pipeline"][f"k{k}"] = {
            "us_per_epoch": us, "round_time_s": m["round_time_s"],
            "pipeline_speedup": fb.pipeline_speedup,
            "d_loss": None if not np.isfinite(m["d_loss"])
            else m["d_loss"]}
    r1 = pipe_metrics[1]["round_time_s"]
    r4 = pipe_metrics[4]["round_time_s"]
    d1, d4 = pipe_metrics[1]["d_loss"], pipe_metrics[4]["d_loss"]
    results["pipeline"]["round_speedup_k4_vs_k1"] = r1 / max(r4, 1e-9)
    # acceptance gates: overlap must shorten the virtual round, and the
    # micro-batched loss must track the monolithic one closely
    results["pipeline"]["speedup_ok"] = bool(r4 < r1)
    results["pipeline"]["numerics_ok"] = bool(
        abs(d4 - d1) <= 1e-2 * max(abs(d1), 1e-9))

    # fused boundary stage vs the unfused two-stage composition
    import jax
    import jax.numpy as jnp
    from repro.core.split import ComposedBoundaryStage, FusedBoundaryStage, \
        make_boundary_stage
    from repro.roofline.analysis import fused_boundary_terms
    from repro.roofline.hw import TPU_V5E      # analytic v5e prediction
    bsz, feat = 16, 6272          # one conv0 crossing of the smoke model
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (bsz, feat), jnp.float32)
    scfg = _cfg(clients, **{"split.boundary_stage": "int8+dp",
                            "split.stage_clip": 1.0,
                            "split.stage_sigma": 0.5}).split
    fused = make_boundary_stage(scfg, "int8+dp")
    composed = ComposedBoundaryStage(
        [make_boundary_stage(scfg, "int8"), make_boundary_stage(scfg, "dp")])
    assert isinstance(fused, FusedBoundaryStage)

    def _stage_us(stage):
        def step():
            out = stage.apply(x, key)
            jax.block_until_ready(out)
            return out
        out = step()
        t0 = time.time()
        for _ in range(reps):
            step()
        return out, (time.time() - t0) * 1e6 / reps

    out_c, us_c = _stage_us(composed)
    out_f, us_f = _stage_us(fused)
    err = float(jnp.max(jnp.abs(out_c - out_f)))
    rows.append(("fed_boundary_fuse[int8+dp]", us_f,
                 f"composed={us_c:.0f}us speedup={us_c / max(us_f, 1e-9):.2f}x "
                 f"max_err={err:.2e}"))
    results["pipeline"]["boundary_fuse"] = {
        "composed_us": us_c, "fused_us": us_f,
        "fused_speedup": us_c / max(us_f, 1e-9),
        "max_abs_err": err,
        # fma re-association under jit puts the two paths ~1 ulp apart
        "fused_matches": bool(err <= 1e-5),
        "roofline": fused_boundary_terms(bsz, feat, hw=TPU_V5E,
                                         codec="int8")}

    # 5. scale: rounds-per-second vs population + sharded dispatch ---------
    from repro.fed.roster import Roster
    from repro.fed.transport import tree_bytes
    results["config"]["devices"] = len(jax.devices())
    update_b = tree_bytes(
        tr_vec.state.d_params[next(iter(tr_vec.state.d_params))])
    participants, r_cohorts = (8, 4) if fast else (32, 8)
    pops = (100, 10_000, 1_000_000)
    results["scale"] = {"populations": {}, "update_bytes": int(update_b),
                        "participants": participants,
                        "cohorts": r_cohorts,
                        "fan_in": participants / r_cohorts}
    eps_by_pop = []
    for pop in pops:
        r = Roster(pop, participants=participants, cohorts=r_cohorts,
                   seed=0)
        t0 = time.time()
        s = r.sample_round(0)
        sample_us = (time.time() - t0) * 1e6
        flat_rps = r.rounds_per_second(update_b, down_bytes=update_b)
        hier_rps = r.rounds_per_second(update_b, down_bytes=update_b,
                                       hierarchical=True)
        wan_flat = r.wan_bytes_per_round(update_b)
        wan_hier = r.wan_bytes_per_round(update_b, hierarchical=True)
        eps = r.amplified_epsilon(1.1, rounds=100)
        eps_by_pop.append(eps)
        rows.append((f"fed_scale[pop{pop}]", sample_us,
                     f"rps={flat_rps:.4f} hier_rps={hier_rps:.4f} "
                     f"wan_mb={wan_flat / 1e6:.2f}->"
                     f"{wan_hier / 1e6:.2f} eps100={eps:.3f}"))
        results["scale"]["populations"][str(pop)] = {
            "sample_us": sample_us,
            "rounds_per_s_flat": flat_rps,
            "rounds_per_s_hier": hier_rps,
            "wan_bytes_flat": int(wan_flat),
            "wan_bytes_hier": int(wan_hier),
            "amplified_epsilon_100r": eps,
            "deterministic": bool(s == r.sample_round(0))}
    p = results["scale"]["populations"]
    results["scale"]["analytic_wan_cut_ok"] = bool(all(
        v["wan_bytes_flat"] >= results["scale"]["fan_in"]
        * v["wan_bytes_hier"] for v in p.values()))
    results["scale"]["deterministic"] = bool(all(
        v["deterministic"] for v in p.values()))
    # subsampling amplification: epsilon shrinks as the population grows
    results["scale"]["epsilon_monotone_ok"] = bool(
        all(a > b for a, b in zip(eps_by_pop, eps_by_pop[1:])))

    # measured two-tier round at the bench's client count: the hierarchy
    # must cut WAN uplink by >= the cohort fan-in (clients / cohorts)
    e_cohorts = 2
    tr_flat = FSLGANTrainer(_cfg(clients), parts, seed=0)
    m_flat = tr_flat.train_epoch(batches_per_client=batches,
                                 backend="vectorized")
    tr_hier = FSLGANTrainer(
        _cfg(clients, **{"fed.hierarchy_cohorts": e_cohorts}),
        parts, seed=0)
    m_hier = tr_hier.train_epoch(batches_per_client=batches,
                                 backend="vectorized")
    up_flat = tr_flat.engine.ledger.total_up
    up_hier = tr_hier.engine.ledger.total_up
    fan_in = clients / e_cohorts
    rows.append((f"fed_scale[hier_c{e_cohorts}]", 0.0,
                 f"wan_up {up_flat}->{up_hier} "
                 f"cut={up_flat / max(up_hier, 1):.2f}x "
                 f"(fan_in={fan_in:.1f}) "
                 f"edge={tr_hier.engine.ledger.total_edge}"))
    results["scale"]["hier_round"] = {
        "cohorts": e_cohorts,
        "wan_up_bytes_flat": int(up_flat),
        "wan_up_bytes_hier": int(up_hier),
        "edge_bytes": int(tr_hier.engine.ledger.total_edge),
        "wan_cut": up_flat / max(up_hier, 1),
        "fan_in": fan_in,
        "wan_cut_ok": bool(up_flat >= fan_in * up_hier),
        "d_loss_delta": None
        if not (np.isfinite(m_flat["d_loss"])
                and np.isfinite(m_hier["d_loss"]))
        else abs(m_flat["d_loss"] - m_hier["d_loss"])}

    # sharded vs unsharded vectorized dispatch (multi-device only: CPU
    # runs get > 1 device via --xla_force_host_platform_device_count)
    tr_unsh = FSLGANTrainer(_cfg(clients), parts, seed=0)
    us_unsh = _time_epochs(
        lambda: tr_unsh.train_epoch(batches_per_client=batches,
                                    backend="vectorized"), reps)
    tr_sh = FSLGANTrainer(_cfg(clients, **{"fed.shard_clients": True}),
                          parts, seed=0)
    us_sh = _time_epochs(
        lambda: tr_sh.train_epoch(batches_per_client=batches,
                                  backend="vectorized"), reps)
    shards = tr_sh.feedback[-1].shards
    rows.append(("fed_scale[sharded]", us_sh,
                 f"unsharded={us_unsh:.0f}us shards={shards} "
                 f"devices={len(jax.devices())} "
                 f"speedup={us_unsh / max(us_sh, 1e-9):.2f}x"))
    results["scale"]["sharded"] = {
        "devices": len(jax.devices()), "shards": int(shards),
        "unsharded_us": us_unsh, "sharded_us": us_sh,
        "speedup": us_unsh / max(us_sh, 1e-9)}

    # 6. agg: compressed-domain streaming server reduce ---------------------
    # decode-then-fedavg (stage one decoded fp32 tree per client, stack,
    # weighted mean — what server_reduce="decode" pays) vs the streaming
    # fold (fold each int8 wire into one persistent accumulator) vs the
    # batched vmap decode-reduce.  Client counts are FIXED at 4/16/64 in
    # both fast and full mode — the regress gate's boolean rules
    # (numerics_ok, speedup_ok@64) must hold at any size, so only the
    # leaf width shrinks under fast.
    from repro.fed.aggregate import StreamingAggregator, batched_reduce
    from repro.fed.programs import fedavg_stacked, stack_trees
    from repro.fed.transport import make_codec
    from repro.roofline.analysis import agg_fuse_terms
    from repro.roofline.hw import TPU_V5E
    leaf = (1 << 14) if fast else (1 << 17)
    template = {"w": jnp.zeros((leaf,), jnp.float32),
                "b": jnp.zeros((leaf // 4,), jnp.float32)}
    n_total = sum(l.size for l in jax.tree.leaves(template))
    results["agg"] = {"codec": "int8", "elems": int(n_total),
                      "roofline_c64": agg_fuse_terms(64, n_total, hw=TPU_V5E,
                                                     codec="int8")}

    def _best_us(fn, reps_):
        fn()                                   # warm-up / compile
        best = float("inf")
        for _ in range(max(1, reps_)):
            t0 = time.time()
            fn()
            best = min(best, time.time() - t0)
        return best * 1e6

    int8 = make_codec("int8")
    for c in (4, 16, 64):
        akey = jax.random.PRNGKey(c)
        encs, wire_b = [], 0
        for i in range(c):
            ki = jax.random.fold_in(akey, i)
            d = {"w": 0.1 * jax.random.normal(ki, (leaf,), jnp.float32),
                 "b": 0.1 * jax.random.normal(jax.random.fold_in(ki, 1),
                                              (leaf // 4,), jnp.float32)}
            enc, nb = int8.encode_tree(d)
            encs.append(enc)
            wire_b += nb
        agg_w = [1.0 + (i % 3) for i in range(c)]    # non-uniform weights

        def _decode_reduce():
            trees = [int8.decode_tree(e, template) for e in encs]
            out = fedavg_stacked(stack_trees(trees), agg_w)
            jax.block_until_ready(out)
            return out

        def _stream():
            agg = StreamingAggregator(int8)
            agg.init(template)
            for e, w in zip(encs, agg_w):
                agg.fold(e, w)
            out = agg.finalize()
            jax.block_until_ready(out)
            return out

        def _batched():
            out = batched_reduce(int8, encs, agg_w, template)
            jax.block_until_ready(out)
            return out

        want = _decode_reduce()
        dec_us = _best_us(_decode_reduce, reps)
        str_us = _best_us(_stream, reps)
        bat_us = _best_us(_batched, reps)

        def _rel(got):
            num = den = 0.0
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                num += float(jnp.sum((a - b) ** 2))
                den += float(jnp.sum(b ** 2))
            return (num ** 0.5) / max(den ** 0.5, 1e-12)

        err_s, err_b = _rel(_stream()), _rel(_batched())
        speedup = dec_us / max(str_us, 1e-9)
        rows.append((f"fed_agg[c{c}]", str_us,
                     f"decode={dec_us:.0f}us batched={bat_us:.0f}us "
                     f"fused_speedup={speedup:.2f}x "
                     f"err={max(err_s, err_b):.2e} "
                     f"trees {c}->1"))
        results["agg"][f"c{c}"] = {
            "decode_us": dec_us, "stream_us": str_us, "batched_us": bat_us,
            "fused_speedup": speedup,
            "speedup_ok": bool(speedup >= 1.2),
            # one weighted mean's reassociation: fma-level
            "numerics_ok": bool(err_s <= 2e-5 and err_b <= 2e-5),
            "rel_err_stream": err_s, "rel_err_batched": err_b,
            "wire_bytes": int(wire_b),
            # peak live decoded fp32 trees at the server: the decode
            # reduce stages one per client, the fold holds one accumulator
            "peak_trees_decode": c, "peak_trees_stream": 1}

    with open(JSON_PATH, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    rows.append(("fed_runtime_json", 0.0, f"wrote {JSON_PATH}"))
    return rows
