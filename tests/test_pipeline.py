"""Pipelined split execution: the 1F1B overlap schedule (core/pipeline),
micro-batched SplitExecution, the fused boundary kernel
(kernels/boundary_fuse), and the trainer wiring (auto backend, configured
LAN latency)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.config import DCGANConfig, SplitConfig
from repro.core.devices import Client, Device
from repro.core.gan import bce_logits, d_loss_fn
from repro.core.pipeline import (OverlapSchedule, effective_microbatches,
                                 overlap_schedule, schedule_for)
from repro.core.selection import make_plan
from repro.core.simulate import plan_epoch_time
from repro.core.split import (ComposedBoundaryStage, FusedBoundaryStage,
                              GaussianBoundaryStage, SplitExecution,
                              make_boundary_stage)
from repro.kernels.boundary_fuse.kernel import boundary_fuse_kernel
from repro.kernels.boundary_fuse.ref import CODECS, fused_boundary_ref
from repro.models.dcgan import (disc_apply_layer, disc_layer_costs,
                                disc_layer_names)

_C = DCGANConfig(base_filters=4)
_TAILS = (functools.partial(bce_logits, target=1.0),
          functools.partial(bce_logits, target=0.0))


def _client(caps, tfs):
    return Client("c0", [Device(f"d{i}", tf, cap)
                         for i, (cap, tf) in enumerate(zip(caps, tfs))])


def _exec_fixture(caps, tfs, strategy="sorted_multi", seed=3, stage=None,
                  stages=None, pipeline_microbatches=1):
    costs = disc_layer_costs(_C)
    layers = [(n, costs[n]) for n in disc_layer_names(_C)]
    plan = make_plan(_client(caps, tfs), layers, strategy, seed)
    return SplitExecution(plan, functools.partial(disc_apply_layer, c=_C),
                          _TAILS, stage=stage, stages=stages,
                          pipeline_microbatches=pipeline_microbatches)


def _batches(n=4, seed=0):
    k = jax.random.PRNGKey(seed)
    real = jax.random.normal(jax.random.fold_in(k, 1), (n, 28, 28, 1))
    fake = jax.random.normal(jax.random.fold_in(k, 2), (n, 28, 28, 1))
    return real, fake


# ---------------------------------------------------------------------------
# overlap schedule (core/pipeline)
# ---------------------------------------------------------------------------

def test_effective_microbatches_divisor_clamp():
    assert effective_microbatches(16, 4) == 4
    assert effective_microbatches(16, 5) == 4     # nearest divisor below
    assert effective_microbatches(16, 100) == 16
    assert effective_microbatches(6, 4) == 3
    assert effective_microbatches(7, 4) == 1      # prime batch
    assert effective_microbatches(1, 8) == 1      # per-example DP steps
    assert effective_microbatches(0, 8) == 1


def test_k1_schedule_is_additive_model_exactly():
    """Degenerate K=1 pin: the schedule's makespan IS the strictly
    additive per-batch time, bit for bit (same accumulation order)."""
    sched = overlap_schedule([0.3, 0.1, 0.2], [0.6, 0.2, 0.4],
                             num_microbatches=1,
                             hop_fwd_s=[0.05, 0.05], hop_bwd_s=[0.05, 0.05])
    assert sched.makespan == sched.sequential_s
    assert sched.speedup == 1.0


def test_overlap_schedule_shortens_multi_device_chain():
    sched = overlap_schedule([0.3, 0.1, 0.2], [0.6, 0.2, 0.4],
                             num_microbatches=4,
                             hop_fwd_s=[0.01, 0.01], hop_bwd_s=[0.01, 0.01])
    assert sched.makespan < sched.sequential_s
    assert sched.speedup > 1.0
    # conserved work: each segment computes its full fwd+bwd time
    np.testing.assert_allclose(sched.segment_work_s(),
                               [0.9, 0.3, 0.6], rtol=1e-12)


def test_overlap_schedule_respects_dependencies():
    """No micro-batch runs segment s before its segment s-1 + hop, and a
    device never runs two tasks at once."""
    sched = overlap_schedule([0.3, 0.1], [0.6, 0.2], num_microbatches=4,
                             hop_fwd_s=[0.02], hop_bwd_s=[0.02])
    fin = {(t.kind, t.microbatch, t.index): t
           for t in sched.tasks if t.kind in ("fwd", "bwd")}
    for (kind, m, si), t in fin.items():
        if kind == "fwd" and si > 0:
            assert t.t0 >= fin[("fwd", m, si - 1)].t1 + 0.02 - 1e-12
        if kind == "bwd":
            if si == sched.num_segments - 1:
                assert t.t0 >= fin[("fwd", m, si)].t1 - 1e-12
            else:
                assert t.t0 >= fin[("bwd", m, si + 1)].t1 + 0.02 - 1e-12
    for dev in sched.devices:
        spans = sorted((t.t0, t.t1) for t in sched.tasks
                       if t.kind in ("fwd", "bwd") and t.device == dev)
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            assert b0 >= a1 - 1e-12


@settings(max_examples=40, deadline=None)
@given(
    segs=st.lists(st.tuples(st.floats(0.01, 2.0), st.floats(0.01, 2.0)),
                  min_size=1, max_size=5),
    k=st.integers(min_value=1, max_value=8),
    hop=st.floats(0.0, 0.5),
)
def test_overlap_schedule_properties(segs, k, hop):
    """Property: for ANY chain, the overlapped makespan never exceeds the
    additive time, per-segment work is conserved, and K=1 is exact."""
    fwd = [f for f, _ in segs]
    bwd = [b for _, b in segs]
    hops = [hop] * (len(segs) - 1)
    sched = overlap_schedule(fwd, bwd, num_microbatches=k,
                             hop_fwd_s=hops, hop_bwd_s=hops)
    assert sched.makespan <= sched.sequential_s + 1e-9
    np.testing.assert_allclose(
        sched.segment_work_s(), [f + b for f, b in segs], rtol=1e-9)
    if k == 1:
        assert sched.makespan == sched.sequential_s


def test_schedule_for_prices_hop_bytes_per_microbatch():
    """Micro-batch hops pay full latency but 1/K of the serialization."""
    tf = {"d0": 1.0, "d1": 1.0}
    sched = schedule_for([2.0, 2.0], ["d0", "d1"], tf, num_microbatches=4,
                         lan_latency_s=0.01, hop_bytes=[1_000_000] * 2,
                         lan_bandwidth_bps=100e6)
    per_mb = 0.01 + 8.0 * 1_000_000 * 0.25 / 100e6
    full = 0.01 + 8.0 * 1_000_000 / 100e6
    assert sched.hop_fwd_s == (pytest.approx(per_mb),)
    assert sched.hop_fwd_full_s == (pytest.approx(full),)


# ---------------------------------------------------------------------------
# pipelined SplitExecution
# ---------------------------------------------------------------------------

def test_pipelined_k1_bitexact_sequential():
    """K=1 pin: run_pipelined IS run — same floats, bit for bit."""
    ex = _exec_fixture([2, 2], [1.0, 2.0])
    params = jax.tree.map(
        lambda s: jax.random.normal(jax.random.PRNGKey(1), s.shape),
        jax.eval_shape(lambda: __import__("repro.models.dcgan",
                                          fromlist=["disc_init"])
                       .disc_init(jax.random.PRNGKey(0), _C)))
    real, fake = _batches()
    sl, sg, _ = ex.run(params, (real, fake))
    pl, pg, _ = ex.run_pipelined(params, (real, fake), num_microbatches=1)
    np.testing.assert_array_equal(np.asarray(pl), np.asarray(sl))
    for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(sg)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipelined_matches_monolithic_grad():
    """K>1 pin: the pipelined step equals the mean of per-chunk MONOLITHIC
    gradients (tight — the staged chain never changes the math), and stays
    close to the full-batch gradient (loose — the discriminator's batch
    norm uses per-micro-batch statistics, the standard grad-accumulation
    shift, so full-batch equality is approximate by construction)."""
    from repro.models.dcgan import disc_init
    params = disc_init(jax.random.PRNGKey(0), _C)
    real, fake = _batches(n=8)
    ml, mg = jax.value_and_grad(d_loss_fn)(params, real, fake, _C)
    for k in (2, 4):
        ex = _exec_fixture([2, 2], [1.0, 2.0], pipeline_microbatches=k)
        pl, pg = ex.value_and_grad(params, real, fake)
        # tight: mean over chunks of the monolithic chunk gradient
        mb = 8 // k
        cl = [jax.value_and_grad(d_loss_fn)(
            params, real[m * mb:(m + 1) * mb], fake[m * mb:(m + 1) * mb],
            _C) for m in range(k)]
        rl = sum(l for l, _ in cl) / k
        rg = jax.tree.map(lambda *gs: sum(gs) / k, *[g for _, g in cl])
        np.testing.assert_allclose(np.asarray(pl), np.asarray(rl),
                                   atol=1e-5, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(rg)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)
        # loose: tracks the full-batch objective through the BN shift
        np.testing.assert_allclose(np.asarray(pl), np.asarray(ml),
                                   rtol=0.05)


def test_pipeline_k_in_signature():
    a = _exec_fixture([2, 2], [1.0, 2.0])
    b = _exec_fixture([2, 2], [1.0, 2.0], pipeline_microbatches=4)
    c = _exec_fixture([2, 2], [1.0, 2.0], pipeline_microbatches=4)
    assert a.signature != b.signature
    assert b.signature == c.signature
    assert ("pipeline", 4) in b.signature


def test_shipped_boundaries_full_batch_view_when_pipelined():
    """What the LAN observer sees is unchanged in union: per-micro-batch
    shipped tensors concatenate back to the full batch."""
    from repro.models.dcgan import disc_init
    params = disc_init(jax.random.PRNGKey(0), _C)
    real, fake = _batches(n=8)
    ex = _exec_fixture([2, 2], [1.0, 2.0], pipeline_microbatches=4)
    recs = ex.shipped_boundaries(params, real, fake)
    for d in ("fwd", "bwd"):
        for b in range(ex.num_boundaries):
            for p in range(ex.num_passes):
                assert recs[d][b][p].shape[0] == 8


# ---------------------------------------------------------------------------
# fused boundary stage + kernel
# ---------------------------------------------------------------------------

def _scfg(**over):
    base = dict(enabled=True, stage_clip=1.0, stage_sigma=0.5)
    base.update(over)
    return SplitConfig(**base)


@pytest.mark.parametrize("name", ["int8+dp", "fp16+dp"])
def test_make_boundary_stage_selects_fused(name):
    fused = make_boundary_stage(_scfg(), name)
    assert isinstance(fused, FusedBoundaryStage)
    unfused = make_boundary_stage(_scfg(fuse_boundary=False), name)
    assert isinstance(unfused, ComposedBoundaryStage)
    # global top-k needs the whole tensor — never fused
    assert isinstance(make_boundary_stage(_scfg(), "topk+dp"),
                      ComposedBoundaryStage)


@pytest.mark.parametrize("name", ["int8+dp", "fp16+dp"])
def test_fused_stage_matches_composed(name):
    """The single-traversal fused stage computes what the two-stage
    composition computes, both GAN passes, within fma re-association
    tolerance (the fused path runs under jit)."""
    fused = make_boundary_stage(_scfg(), name)
    composed = make_boundary_stage(_scfg(fuse_boundary=False), name)
    key = jax.random.PRNGKey(7)
    for p in range(2):
        x = jax.random.normal(jax.random.fold_in(key, 10 + p),
                              (8, 7, 7, 4), jnp.float32) * 3.0
        kp = jax.random.fold_in(key, p)
        np.testing.assert_allclose(
            np.asarray(fused.apply(x, kp)),
            np.asarray(composed.apply(x, kp)), atol=3e-6, rtol=3e-6)


def test_fused_execution_matches_composed_execution():
    """Full staged run (fwd + bwd crossings) under the fused stage equals
    the unfused composition — loss and every gradient leaf."""
    from repro.models.dcgan import disc_init
    params = disc_init(jax.random.PRNGKey(0), _C)
    real, fake = _batches(n=4)
    key = jax.random.PRNGKey(11)
    ex_f = _exec_fixture([2, 2], [1.0, 2.0],
                         stage=make_boundary_stage(_scfg(), "int8+dp"))
    ex_c = _exec_fixture(
        [2, 2], [1.0, 2.0],
        stage=make_boundary_stage(_scfg(fuse_boundary=False), "int8+dp"))
    fl, fg, _ = ex_f.run(params, (real, fake), key)
    cl, cg, _ = ex_c.run(params, (real, fake), key)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(cl),
                               atol=3e-6, rtol=3e-6)
    for a, b in zip(jax.tree.leaves(fg), jax.tree.leaves(cg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-6, rtol=3e-6)


@pytest.mark.parametrize("codec", list(CODECS))
def test_boundary_fuse_kernel_matches_ref(codec):
    """The Pallas kernel (interpret mode) against the jnp oracle, padded
    and unpadded widths."""
    key = jax.random.PRNGKey(3)
    for n in (64, 100):          # 100 exercises the zero-pad path
        x = jax.random.normal(jax.random.fold_in(key, n), (4, n),
                              jnp.float32) * 2.0
        noise = jax.random.normal(jax.random.fold_in(key, n + 1), (4, n),
                                  jnp.float32)
        clip = jnp.asarray(0.8, jnp.float32)
        scale = jnp.asarray(0.4, jnp.float32)
        out = boundary_fuse_kernel(x, clip, scale, noise, codec=codec,
                                   block_n=32, interpret=True)
        ref = fused_boundary_ref(x, clip, scale, noise, codec=codec)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_fused_stage_sigma_zero_is_deterministic():
    """sigma=0 never draws noise — keyed and keyless applies agree (the
    stage stays declared stochastic, matching GaussianBoundaryStage)."""
    stage = FusedBoundaryStage("int8", 1.0, 0.0)
    x = jnp.linspace(-2.0, 2.0, 32).reshape(4, 8)
    a = stage.apply(x, None)
    b = stage.apply(x, jax.random.PRNGKey(9))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# codec buffer entry points (fed/transport)
# ---------------------------------------------------------------------------

def _codec_ref(codec, x, frac):
    """Each codec's quantize-dequantize in plain jnp, as its own formula."""
    if codec == "fp16":
        return x.astype(jnp.float16).astype(x.dtype)
    if codec == "int8":
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return (jnp.clip(jnp.round(x / scale), -127, 127) * scale
                ).astype(x.dtype)
    flat = x.reshape(-1)
    k = min(flat.size, max(1, int(np.ceil(frac * flat.size))))
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    mask = jnp.zeros_like(flat).at[idx].set(1.0)
    return (flat * mask).reshape(x.shape).astype(x.dtype)


@pytest.mark.parametrize("codec", ["fp16", "int8", "topk"])
def test_codec_encode_decode_matches_roundtrip(codec):
    from repro.fed.transport import make_codec
    c = make_codec(codec, topk_frac=0.1, error_feedback=False)
    x = jax.random.normal(jax.random.PRNGKey(5), (17, 9), jnp.float32) * 4.0
    wire, meta = c.encode(x)
    dec = c.decode(wire, meta, x.dtype)
    rt, _ = c.roundtrip(x)
    ref = _codec_ref(codec, x, 0.1)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(ref))


# ---------------------------------------------------------------------------
# pricing: plan_epoch_time + round_timeline under K
# ---------------------------------------------------------------------------

def _plan_client():
    costs = disc_layer_costs(_C)
    layers = [(n, costs[n]) for n in disc_layer_names(_C)]
    client = _client([2, 2], [1.0, 2.0])
    return make_plan(client, layers, "sorted_multi", seed=3), client


def test_plan_epoch_time_pipelined_never_slower():
    plan, client = _plan_client()
    assert plan.num_boundaries >= 1
    t1 = plan_epoch_time(plan, client, batches_per_epoch=4)
    tk = plan_epoch_time(plan, client, batches_per_epoch=4,
                         pipeline_microbatches=4)
    assert tk <= t1
    # K=1 through the schedule path is the legacy additive number
    assert plan_epoch_time(plan, client, batches_per_epoch=4,
                           pipeline_microbatches=1) == t1


def test_round_timeline_pipelined_agrees_with_plan_epoch_time():
    """The trace is the price, subdivided: the pipelined timeline's batch
    time equals plan_epoch_time's per-batch makespan, and its spans
    genuinely overlap across devices."""
    plan, client = _plan_client()
    ex = _exec_fixture([2, 2], [1.0, 2.0], pipeline_microbatches=4)
    tf = {d.device_id: d.time_factor for d in client.devices}
    phases, batch_s = ex.round_timeline(tf, lan_latency_s=0.01)
    expect = plan_epoch_time(plan, client, batches_per_epoch=1,
                             lan_latency_s=0.01, pipeline_microbatches=4)
    assert batch_s == pytest.approx(expect, rel=1e-12)
    comp = [p for p in phases if p["cat"] == "segment"]
    assert any(a["track"] != b["track"]
               and a["t0"] < b["t1"] and b["t0"] < a["t1"]
               for a in comp for b in comp)
    # sequential timeline unchanged by the K=1 default
    seq_phases, seq_s = ex.round_timeline(tf, lan_latency_s=0.01,
                                          pipeline_microbatches=1)
    assert seq_s >= batch_s
    assert all(b["t0"] >= a["t1"] - 1e-12
               for a, b in zip(seq_phases, seq_phases[1:]))


# ---------------------------------------------------------------------------
# config + trainer wiring
# ---------------------------------------------------------------------------

def test_split_config_validates_pipeline_fields():
    with pytest.raises(ValueError):
        SplitConfig(pipeline_microbatches=0)
    with pytest.raises(ValueError):
        SplitConfig(lan_latency_s=-0.1)


def test_trainer_lan_latency_wiring():
    from repro.configs.registry import get_config
    from repro.core.gan import FSLGANTrainer
    from repro.data import partition_dirichlet, synthetic_mnist
    imgs, labels = synthetic_mnist(64, seed=0)
    parts = partition_dirichlet(imgs, labels, 2, alpha=0.5, seed=0)
    base = get_config("dcgan-mnist").override({
        "shape.global_batch": 8, "fsl.num_clients": 2,
        "model.dcgan.base_filters": 8})
    tr = FSLGANTrainer(base, parts, seed=0)
    assert tr._lan_latency_s() == base.fsl.lan_latency_s
    tr2 = FSLGANTrainer(base.override({"split.lan_latency_s": 0.012}),
                        parts, seed=0)
    assert tr2._lan_latency_s() == 0.012


def test_trainer_auto_backend_and_pipeline_feedback():
    """One round with backend='auto' + a pipelined split: the probe picks
    a concrete backend, records its timings once, and the feedback carries
    the pipeline fields the deadline controller rescales with."""
    from repro.configs.registry import get_config
    from repro.core.gan import FSLGANTrainer
    from repro.data import partition_dirichlet, synthetic_mnist
    imgs, labels = synthetic_mnist(64, seed=0)
    parts = partition_dirichlet(imgs, labels, 2, alpha=0.5, seed=0)
    cfg = get_config("dcgan-mnist").override({
        "shape.global_batch": 8, "fsl.num_clients": 2,
        "model.dcgan.base_filters": 8,
        "split.enabled": True, "split.pipeline_microbatches": 2})
    tr = FSLGANTrainer(cfg, parts, seed=0)
    m = tr.train_epoch(batches_per_client=1, backend="auto")
    fb = tr.feedback[-1]
    assert fb.backend in ("loop", "vectorized")
    assert set(fb.backend_probe_us) == {"loop", "vectorized"}
    assert all(v > 0 for v in fb.backend_probe_us.values())
    assert fb.pipeline_microbatches == 2
    assert fb.pipeline_speedup >= 1.0
    assert np.isfinite(m["d_loss"])
    # probe runs once; later rounds reuse the cached choice
    tr.train_epoch(batches_per_client=1, backend="auto")
    assert tr.feedback[-1].backend == fb.backend
    assert not tr.feedback[-1].backend_probe_us
