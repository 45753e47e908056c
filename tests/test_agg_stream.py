"""Compressed-domain streaming aggregation (kernels/agg_fuse +
fed/aggregate): the fused dequant-reduce server path vs the decode-then-
fedavg reference.

Pinned invariants:
  * the Pallas kernels (dense dequant-reduce, per-wire dequant-acc, sparse
    scatter-acc) match their jnp references, including the zero-pad path
    (N not a block multiple) and colliding top-k indices;
  * StreamingAggregator.fold/finalize == stack-decode-then-weighted-mean
    per codec x weighting, on ragged leaf shapes;
  * trainer-level: one engine round under ``fed.server_reduce`` in
    {stream, batched} pins the decode reference to fma-level tolerance
    across codec x {flat sync, hierarchical, async} with IDENTICAL wire
    bytes and codec_error accounting (the equivalence is per-round: float
    reassociation differences are fma-level in one round but chaotically
    amplified by GAN training dynamics over many rounds, so multi-epoch
    trajectories are NOT comparable);
  * O(1) server memory: ``RoundReport.peak_live_trees`` stays constant in
    the cohort size under stream/batched while the decode reduce stages
    one decoded tree per landed client.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.fed.aggregate import (StreamingAggregator, batched_reduce,
                                 codec_rel_error, decode_enc)
from repro.fed.programs import fedavg_stacked, stack_trees
from repro.fed.transport import make_codec
from repro.kernels.agg_fuse import (dequant_acc_flat, dequant_acc_ref,
                                    dequant_reduce_flat, dequant_reduce_ref,
                                    scatter_acc_flat, scatter_acc_ref)


# ---------------------------------------------------------------------------
# kernels vs jnp references (pad path + collisions)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [4096, 5000])    # block multiple + pad path
@pytest.mark.parametrize("wire_dtype", [jnp.int8, jnp.float16])
def test_dequant_reduce_kernel_matches_ref(n, wire_dtype):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    c = 5
    if wire_dtype == jnp.int8:
        wires = jax.random.randint(k1, (c, n), -127, 128,
                                   jnp.int32).astype(jnp.int8)
        scales = jax.random.uniform(k2, (c,), jnp.float32, 1e-3, 1e-1)
    else:
        wires = jax.random.normal(k1, (c, n), jnp.float32).astype(wire_dtype)
        scales = jnp.ones((c,), jnp.float32)
    weights = jax.random.uniform(k3, (c,), jnp.float32, 0.5, 2.0)
    ker = dequant_reduce_flat(wires, scales, weights,
                              use_kernel=True, interpret=True)
    ref = dequant_reduce_flat(wires, scales, weights, use_kernel=False)
    assert ker.shape == (n,) and ker.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [4096, 5000])
def test_dequant_acc_kernel_matches_ref(n):
    key = jax.random.PRNGKey(1)
    k1, k2 = jax.random.split(key)
    acc = jax.random.normal(k1, (n,), jnp.float32)
    wire = jax.random.randint(k2, (n,), -127, 128,
                              jnp.int32).astype(jnp.int8)
    out_k = dequant_acc_flat(jnp.copy(acc), wire, 0.031, 1.7,
                             use_kernel=True, interpret=True)
    out_r = dequant_acc_ref(acc, wire, 1.7, 0.031)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)


def test_scatter_acc_kernel_sums_colliding_indices():
    n = 5000                                     # forces the pad path too
    acc = jnp.zeros((n,), jnp.float32)
    idx = jnp.asarray([0, 1, 1, 4999, 4999, 4999, 123], jnp.int32)
    vals = jnp.asarray([1., 2., 3., 4., 5., 6., 7.], jnp.float32)
    out_k = scatter_acc_flat(jnp.copy(acc), vals, idx, 2.0,
                             use_kernel=True, interpret=True)
    out_r = scatter_acc_ref(acc, vals, idx, 2.0)
    # collisions must SUM (matching .at[idx].add), not overwrite
    assert float(out_r[1]) == pytest.approx(10.0)
    assert float(out_r[4999]) == pytest.approx(30.0)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)


def test_dequant_reduce_ref_is_weighted_mean():
    wires = jnp.asarray([[2.0, 4.0], [6.0, 8.0]], jnp.float32)
    ones = jnp.ones((2,), jnp.float32)
    out = dequant_reduce_flat(wires, ones, jnp.asarray([1.0, 3.0]))
    np.testing.assert_allclose(np.asarray(out), [5.0, 7.0], rtol=1e-6)
    coefs = jnp.asarray([[0.5, 1.0], [1.0, 1.0]], jnp.float32)  # (w, s)
    np.testing.assert_allclose(
        np.asarray(dequant_reduce_ref(wires, coefs)),
        np.asarray(wires[0]) * 0.5 + np.asarray(wires[1]), rtol=1e-6)


# ---------------------------------------------------------------------------
# StreamingAggregator == stack-decode-then-weighted-mean (unit level)
# ---------------------------------------------------------------------------

def _delta_tree(seed):
    k = jax.random.PRNGKey(seed)
    return {"w": 0.1 * jax.random.normal(k, (33, 7)),     # ragged, pad path
            "b": {"x": 0.1 * jax.random.normal(jax.random.fold_in(k, 1),
                                               (11,))}}


@pytest.mark.parametrize("codec_name", ["none", "fp16", "int8", "topk"])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_streaming_fold_matches_decode_then_fedavg(codec_name, weighted,
                                                   use_kernel):
    deltas = [_delta_tree(s) for s in range(3)]
    weights = [1.0, 2.5, 0.5] if weighted else [1.0, 1.0, 1.0]
    encs = []
    for i, d in enumerate(deltas):
        codec = make_codec(codec_name, topk_frac=0.25, error_feedback=False)
        encs.append(codec.encode_tree(d)[0])
    agg = StreamingAggregator(codec_name, use_kernel=use_kernel,
                              interpret=True)
    agg.init(deltas[0])
    for enc, w in zip(encs, weights):
        agg.fold(enc, w)
    got = agg.finalize()
    # reference: decode every wire, stack, weighted fedavg
    decoded = [decode_enc(codec_name, enc, deltas[0]) for enc in encs]
    want = fedavg_stacked(stack_trees(decoded), weights)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("codec_name", ["none", "fp16", "int8", "topk"])
def test_batched_reduce_matches_streaming(codec_name):
    deltas = [_delta_tree(10 + s) for s in range(4)]
    weights = [2.0, 1.0, 3.0, 0.5]
    encs = []
    for d in deltas:
        codec = make_codec(codec_name, topk_frac=0.25, error_feedback=False)
        encs.append(codec.encode_tree(d)[0])
    agg = StreamingAggregator(codec_name, interpret=True)
    agg.init(deltas[0])
    for enc, w in zip(encs, weights):
        agg.fold(enc, w)
    got_s = agg.finalize()
    got_b = batched_reduce(codec_name, encs, weights, deltas[0],
                           use_kernel=False, interpret=True)
    for a, b in zip(jax.tree.leaves(got_s), jax.tree.leaves(got_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_fold_reports_codec_error_matching_densified():
    """The in-fold rel_error (computed without densifying top-k wires)
    equals the decode-and-compare definition ``_codec_roundtrip`` uses."""
    d = _delta_tree(3)
    for codec_name in ("fp16", "int8", "topk"):
        codec = make_codec(codec_name, topk_frac=0.25, error_feedback=False)
        enc, _ = codec.encode_tree(d)
        err = codec_rel_error(codec_name, enc, d)
        dec = decode_enc(codec_name, enc, d)
        df = jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(d)])
        cf = jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                              for l in jax.tree.leaves(dec)])
        want = float(jnp.linalg.norm(cf - df)
                     / jnp.maximum(jnp.linalg.norm(df), 1e-12))
        assert err == pytest.approx(want, rel=1e-4, abs=1e-6)


# ---------------------------------------------------------------------------
# trainer-level: stream/batched pin the decode reference per round
# ---------------------------------------------------------------------------

def _cfg(**over):
    base = {"shape.global_batch": 8, "fsl.num_clients": 3,
            "model.dcgan.base_filters": 8}
    base.update(over)
    return get_config("dcgan-mnist").override(base)


@pytest.fixture(scope="module")
def parts():
    imgs, labels = synthetic_mnist(180, seed=0)
    return partition_dirichlet(imgs, labels, 3, alpha=0.5, seed=0)


def _one_round(parts, **over):
    tr = FSLGANTrainer(_cfg(**over), parts, seed=0)
    m = tr.train_epoch(batches_per_client=2)
    return tr, m


def _max_param_diff(ta, tb):
    """Max |diff| over the aggregated discriminator — the tree the server
    reduce produces.  The generator is excluded: its post-round Adam
    updates normalize gradients by ~zero second moments early in training,
    amplifying fma-level aggregate differences to O(lr) immediately."""
    d = 0.0
    cid = ta.client_ids[0]
    for a, b in zip(jax.tree.leaves(ta.state.d_params[cid]),
                    jax.tree.leaves(tb.state.d_params[cid])):
        d = max(d, float(jnp.max(jnp.abs(a - b))))
    return d


TOPOLOGIES = {
    "flat": {},
    "hier": {"fed.hierarchy_cohorts": 2},        # ragged cohorts: 2 + 1
    "async": {"fed.mode": "fedasync", "fed.async_cycles": 2},
}


@pytest.mark.parametrize("codec", ["none", "fp16", "int8", "topk"])
@pytest.mark.parametrize("topo", sorted(TOPOLOGIES))
def test_stream_round_pins_decode_round(parts, codec, topo):
    over = dict(TOPOLOGIES[topo])
    over["fed.codec"] = codec
    ta, ma = _one_round(parts, **over)
    tb, mb = _one_round(parts, **dict(over, **{"fed.server_reduce":
                                               "stream"}))
    # per-round equivalence: one reduce's float reassociation is fma-level
    assert _max_param_diff(ta, tb) <= 2e-5
    # wire accounting must be EXACTLY unchanged — encode_tree prices the
    # same bytes the decode roundtrip does
    assert ma["up_mbytes"] == mb["up_mbytes"]
    assert ma.get("edge_mbytes") == mb.get("edge_mbytes")
    if codec != "none":
        assert mb["codec_error"] == pytest.approx(ma["codec_error"],
                                                  rel=1e-3, abs=1e-6)


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_batched_round_pins_decode_round(parts, codec):
    ta, ma = _one_round(parts, **{"fed.codec": codec})
    tb, mb = _one_round(parts, **{"fed.codec": codec,
                                  "fed.server_reduce": "batched"})
    assert _max_param_diff(ta, tb) <= 2e-5
    assert ma["up_mbytes"] == mb["up_mbytes"]


def test_peak_live_trees_is_o1_under_stream(parts):
    """The decode reduce stages one decoded tree per landed client; the
    compressed-domain fold holds only the fp32 accumulator."""
    ta, _ = _one_round(parts, **{"fed.codec": "int8"})
    assert ta.engine.last_report.peak_live_trees == 3     # O(C)
    tb, _ = _one_round(parts, **{"fed.codec": "int8",
                                 "fed.server_reduce": "stream"})
    assert tb.engine.last_report.peak_live_trees == 1     # O(1)
    tc, _ = _one_round(parts, **{"fed.codec": "int8",
                                 "fed.server_reduce": "batched"})
    assert tc.engine.last_report.peak_live_trees == 1
    # hierarchical: decode stages landed trees + reductions; stream holds
    # one accumulator per cohort round-trip but never the member trees
    th, _ = _one_round(parts, **{"fed.codec": "int8",
                                 "fed.hierarchy_cohorts": 2,
                                 "fed.server_reduce": "stream"})
    assert th.engine.last_report.peak_live_trees <= 3
    thd, _ = _one_round(parts, **{"fed.codec": "int8",
                                  "fed.hierarchy_cohorts": 2})
    assert thd.engine.last_report.peak_live_trees >= 5    # 3 landed + 2 red


def test_server_reduce_validated():
    with pytest.raises(ValueError):
        _cfg(**{"fed.server_reduce": "bogus"})


@pytest.mark.parametrize("over", [
    {"fed.server_reduce": "stream"},
    {"fed.server_reduce": "decode", "fed.hierarchy_cohorts": 2},
])
def test_native_kernel_reduce_without_mesh_form_refuses_mesh(over):
    """A compiled Mosaic kernel cannot take arrays spanning devices: the
    stream fold and the edge-cohort reduce have no per-device form, so a
    multi-device clients mesh is refused up front (decode and batched
    reduce per device instead)."""
    from jax.sharding import AbstractMesh
    from repro.fed.engine import ClientSpec, FederationEngine
    fed = _cfg(**{"fed.codec": "int8", "fed.kernel_aggregation": True,
                  **over}).fed
    specs = [ClientSpec(f"c{i}", 1.0, 1.0) for i in range(4)]
    mesh = AbstractMesh((4,), ("clients",))
    with pytest.raises(ValueError, match="clients mesh"):
        FederationEngine(fed, specs).set_mesh(mesh)
    for ok in ({"fed.server_reduce": "batched"},
               {**over, "fed.kernel_interpret": True}):
        fed = _cfg(**{"fed.codec": "int8", "fed.kernel_aggregation": True,
                      **ok}).fed
        FederationEngine(fed, specs).set_mesh(mesh)
