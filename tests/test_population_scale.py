"""Population-scale rounds: client-axis sharding specs + mesh helpers,
two-tier hierarchical aggregation, the lazy Roster, the scanned pipeline
loop, and the deterministic (round, cohort, client) key chain.

Pinned invariants:
  * sharding/specs: the `clients` logical axis shards only when the client
    count divides the mesh (drop-to-replicate policy), and
    stacked_shardings mirrors the tree structure exactly;
  * launch/mesh: host/client mesh shapes, mesh_chips;
  * a hierarchical round's aggregate == the flat FedAvg engine's to fp32
    tolerance at equal knobs — with the WAN uplink cut by >= the cohort
    fan-in factor (the ISSUE 9 acceptance pin; multi-shard variant runs
    whenever >= 4 simulated devices exist);
  * Roster resampling is reproducible, cohort-consistent, and its
    subsampled epsilon beats full participation;
  * SplitExecution.pipeline_scan == the unrolled micro-batch loop at
    K in {2, 4}, with and without a stochastic boundary stage.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.fed.aggregate import Uplink, make_server_reduce
from repro.fed.hierarchy import (CohortReduction, HierarchicalAggregator,
                                 assign_cohorts)
from repro.fed.programs import RoundExecutor, fedavg_stacked, stack_trees
from repro.fed.roster import Roster
from repro.fed.transport import IdentityCodec
from repro.launch.mesh import make_client_mesh, make_host_mesh, mesh_chips
from repro.sharding.specs import (client_axis_rules, logical_spec,
                                  stacked_shardings, tree_shardings, Lg)

_MULTI = len(jax.devices()) >= 4


# ---------------------------------------------------------------------------
# sharding/specs: client-axis rules (device-free via AbstractMesh)
# ---------------------------------------------------------------------------

def _amesh(n=4):
    return AbstractMesh((n,), ("clients",))


def test_client_axis_shards_only_when_divisible():
    m = _amesh(4)
    rules = client_axis_rules(m)
    assert logical_spec(m, rules, (8, 3), ("clients", None)) == P("clients")
    # 6 % 4 != 0 -> the whole dim replicates instead of failing
    assert logical_spec(m, rules, (6, 3), ("clients", None)) == P()
    # dim exactly the mesh size shards; 1 (fewer clients than shards) can't
    assert logical_spec(m, rules, (4,), ("clients",)) == P("clients")
    assert logical_spec(m, rules, (1, 5), ("clients", None)) == P()


def test_client_axis_rules_fall_back_without_clients_axis():
    m = AbstractMesh((2,), ("data",))
    rules = client_axis_rules(m)
    assert rules.mesh_axes_for("clients") is None
    assert logical_spec(m, rules, (8,), ("clients",)) == P()


def test_stacked_shardings_mirror_tree_structure():
    m = _amesh(4)
    tree = {"w": jnp.zeros((8, 3, 3)), "b": {"x": jnp.zeros((8,))}}
    sh = stacked_shardings(m, tree)
    assert jax.tree.structure(sh) == jax.tree.structure(tree)
    assert sh["w"].spec == P("clients")
    assert sh["b"]["x"].spec == P("clients")
    ragged = {"w": jnp.zeros((6, 3))}
    assert stacked_shardings(m, ragged)["w"].spec == P()


def test_tree_shardings_rejects_structure_mismatch():
    m = _amesh(2)
    rules = client_axis_rules(m)
    tree = {"a": jnp.zeros((2, 2))}
    bad_spec = {"a": Lg("clients", None), "extra": Lg(None)}
    with pytest.raises(ValueError, match="mismatch"):
        tree_shardings(m, rules, tree, bad_spec)


# ---------------------------------------------------------------------------
# launch/mesh
# ---------------------------------------------------------------------------

def test_host_mesh_covers_all_devices():
    m = make_host_mesh()
    assert m.axis_names == ("data", "model")
    assert mesh_chips(m) == len(jax.devices())


def test_client_mesh_shape_and_cap():
    m = make_client_mesh()
    assert m.axis_names == ("clients",)
    assert mesh_chips(m) == len(jax.devices())
    assert mesh_chips(make_client_mesh(max_devices=1)) == 1


# ---------------------------------------------------------------------------
# hierarchy: weighted-mean-of-weighted-means == flat FedAvg
# ---------------------------------------------------------------------------

def _tree(v, seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": v + 0.1 * jax.random.normal(k, (4, 3)),
            "b": jnp.full((2,), float(v))}


def test_assign_cohorts_contiguous_and_balanced():
    got = assign_cohorts([f"c{i}" for i in range(7)], 3)
    assert got == {0: ["c0", "c1", "c2"], 1: ["c3", "c4", "c5"],
                   2: ["c6"]}
    # explicit assigner wins over contiguous slicing
    got = assign_cohorts(["a", "b", "c"], 2, cohort_of=lambda c: c == "b")
    assert got == {0: ["a", "c"], 1: ["b"]}


@pytest.mark.parametrize("use_kernel", [False, True])
def test_two_tier_reduce_matches_flat_fedavg(use_kernel):
    ups = {f"c{i}": (Uplink.plain(IdentityCodec(), _tree(float(i), seed=i)),
                     1.0 + i) for i in range(6)}
    h = HierarchicalAggregator(2)
    reds = h.reduce_all(ups, make_server_reduce(
        "decode", edge=True, use_kernel=use_kernel, interpret=True))
    assert [r.cohort for r in reds] == [0, 1]
    assert sum(len(r.members) for r in reds) == 6
    flat = fedavg_stacked(stack_trees([u[0].base for u in ups.values()]),
                          [u[1] for u in ups.values()])
    two = fedavg_stacked(stack_trees([r.aggregate for r in reds]),
                         [r.weight for r in reds])
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(two)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# engine: hierarchical round pins the flat engine (acceptance)
# ---------------------------------------------------------------------------

def _cfg(**over):
    base = {"shape.global_batch": 8, "fsl.num_clients": 4,
            "model.dcgan.base_filters": 8}
    base.update(over)
    return get_config("dcgan-mnist").override(base)


@pytest.fixture(scope="module")
def parts():
    imgs, labels = synthetic_mnist(200, seed=0)
    return partition_dirichlet(imgs, labels, 4, alpha=0.5, seed=0)


def _dead_bias(path) -> bool:
    keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    return (len(keys) == 2 and keys[1] == "b"
            and str(keys[0]).startswith("conv") and keys[0] != "conv0")


def _assert_live_params_close(ta, tb, tol=5e-6):
    cid = next(iter(ta.state.d_params))
    fa, _ = jax.tree_util.tree_flatten_with_path(ta.state.d_params[cid])
    fb, _ = jax.tree_util.tree_flatten_with_path(tb.state.d_params[cid])
    for (pa, a), (_, b) in zip(fa, fb):
        if _dead_bias(pa):
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol)


def _run_flat_vs_hier(parts, **hier_over):
    tf_ = FSLGANTrainer(_cfg(), parts, seed=0)
    mf = tf_.train_epoch(batches_per_client=2, backend="vectorized")
    over = {"fed.hierarchy_cohorts": 2}
    over.update(hier_over)
    th = FSLGANTrainer(_cfg(**over), parts, seed=0)
    mh = th.train_epoch(batches_per_client=2, backend="vectorized")
    return tf_, mf, th, mh


def test_hierarchical_round_pins_flat_engine(parts):
    tf_, mf, th, mh = _run_flat_vs_hier(parts)
    assert mf["num_clients"] == mh["num_clients"]
    assert abs(mf["d_loss"] - mh["d_loss"]) < 1e-5
    _assert_live_params_close(tf_, th)
    # WAN uplink cut by >= the cohort fan-in factor (4 clients / 2
    # cohorts): only one pre-reduced tree per cohort crossed the WAN
    fan_in = 4 / 2
    assert tf_.engine.ledger.total_up >= fan_in * th.engine.ledger.total_up
    # the client->edge hop carries what the WAN no longer does
    assert th.engine.ledger.total_edge == tf_.engine.ledger.total_up
    fb = th.feedback[-1]
    assert fb.cohorts == 2 and fb.edge_bytes > 0
    assert all(k.startswith("cohort")
               for k, v in th.engine.ledger.up_bytes.items() if v)


@pytest.mark.skipif(not _MULTI, reason="needs >= 4 simulated devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
def test_sharded_hierarchical_round_pins_flat_engine(parts):
    tf_, mf, th, mh = _run_flat_vs_hier(parts,
                                        **{"fed.shard_clients": True})
    assert abs(mf["d_loss"] - mh["d_loss"]) < 1e-5
    _assert_live_params_close(tf_, th)
    assert th.feedback[-1].shards == len(jax.devices())


@pytest.mark.skipif(not _MULTI, reason="needs >= 4 simulated devices")
def test_sharded_round_places_stacked_inputs_on_clients_mesh():
    mesh = make_client_mesh()
    tree = {"w": jnp.zeros((8, 3)), "b": jnp.zeros((8,))}
    sh = stacked_shardings(mesh, tree)
    placed = jax.device_put(tree, sh)
    assert len(placed["w"].sharding.device_set) == len(jax.devices())
    # non-divisible stack replicates rather than failing (6 % 4)
    ragged = jax.device_put(jnp.zeros((6, 3)),
                            stacked_shardings(mesh, jnp.zeros((6, 3))))
    assert ragged.sharding.is_fully_replicated


def test_hierarchical_round_emits_cohort_spans(parts):
    from repro.obs.trace import Tracer
    th = FSLGANTrainer(_cfg(**{"fed.hierarchy_cohorts": 2}), parts, seed=0)
    th._ensure_engine(2)
    tr = Tracer("t")
    th.engine.set_tracer(tr)
    th.train_epoch(batches_per_client=2, backend="vectorized")
    cohort_spans = [s for s in tr.spans if s.cat == "cohort"]
    assert len(cohort_spans) == 2
    rnd = next(s for s in tr.spans if s.cat == "round")
    assert all(s.parent_id == rnd.span_id for s in cohort_spans)
    assert all(s.args["wan_bytes"] > 0 for s in cohort_spans)


# ---------------------------------------------------------------------------
# roster: deterministic sampling, amplification, analytic pricing
# ---------------------------------------------------------------------------

def test_roster_resampling_reproducible_and_cohort_consistent():
    r = Roster(10_000, participants=16, cohorts=4, seed=3)
    s1, s2 = r.sample_round(7), r.sample_round(7)
    assert s1 == s2
    assert len(set(s1.client_ids)) == 16
    assert s1.client_ids != r.sample_round(8).client_ids
    for cid, c in zip(s1.client_ids, s1.cohorts):
        lo, hi = r.cohort_range(c)
        assert lo <= cid < hi
        assert r.cohort_of(cid) == c


def test_roster_key_chain_varies_each_component():
    r = Roster(1000, participants=8, cohorts=2, seed=0)
    base = r.client_key(1, 0, 42)
    for other in (r.client_key(2, 0, 42), r.client_key(1, 1, 42),
                  r.client_key(1, 0, 43)):
        assert not np.array_equal(np.asarray(base), np.asarray(other))
    np.testing.assert_array_equal(np.asarray(base),
                                  np.asarray(r.client_key(1, 0, 42)))


def test_roster_large_population_samples_lazily():
    r = Roster(1_000_000, participants=64, cohorts=8, seed=1)
    s = r.sample_round(0)
    assert len(set(s.client_ids)) == 64
    assert s == r.sample_round(0)
    assert r.sample_rate == 64 / 1_000_000


def test_roster_subsampling_amplifies_epsilon():
    r = Roster(100_000, participants=100, cohorts=4, seed=0)
    amplified = r.amplified_epsilon(1.1, rounds=50)
    full = Roster(100, participants=100, seed=0).amplified_epsilon(
        1.1, rounds=50)
    assert amplified < full / 10
    acct = r.accountant(1.1)
    acct.step(50)
    assert abs(acct.epsilon(1e-5)[0] - amplified) < 1e-9


def test_roster_analytic_pricing_monotone():
    r = Roster(10_000, participants=32, cohorts=4, seed=0)
    # the sync barrier's order-statistic quantile grows with more
    # participants, and hierarchy trades WAN bytes for an edge hop
    bigger = Roster(10_000, participants=256, cohorts=4, seed=0)
    assert bigger.barrier_compute_s() > r.barrier_compute_s()
    nb = 1 << 20
    assert r.wan_bytes_per_round(nb) == 32 * nb
    assert r.wan_bytes_per_round(nb, hierarchical=True) == 4 * nb
    assert r.wan_bytes_per_round(nb) \
        >= (32 / 4) * r.wan_bytes_per_round(nb, hierarchical=True)
    specs = r.specs_for_round(3)
    assert len(specs) == 32
    assert all(s.compute_time_s > 0 for s in specs)
    assert all(r.cohort_of_cid(s.client_id) == c
               for s, c in zip(specs, r.sample_round(3).cohorts))


# ---------------------------------------------------------------------------
# executor key chain: (round, cohort, client, execution)
# ---------------------------------------------------------------------------

def _executor(cohort_of=None, key=0):
    return RoundExecutor(
        program=None, backend="loop", sample=lambda cid, s: (None, None),
        opt_lookup=lambda cid: None, default_steps=1,
        round_key=jax.random.PRNGKey(key), cohort_of=cohort_of)


def test_executor_keys_deterministic_and_cohort_aware():
    a = _executor(cohort_of=lambda cid: 1)
    b = _executor(cohort_of=lambda cid: 1)
    np.testing.assert_array_equal(np.asarray(a._key_for("c0")),
                                  np.asarray(b._key_for("c0")))
    # a different cohort (or none) derives a different stream
    c = _executor(cohort_of=lambda cid: 2)
    d = _executor(cohort_of=None)
    k = _executor(cohort_of=lambda cid: 1)._key_for("c0")
    for other in (c._key_for("c0"), d._key_for("c0")):
        assert not np.array_equal(np.asarray(k), np.asarray(other))
    # re-execution (async cycles) advances the exec index
    e = _executor(cohort_of=lambda cid: 1)
    assert not np.array_equal(np.asarray(e._key_for("c0")),
                              np.asarray(e._key_for("c0")))


# ---------------------------------------------------------------------------
# scanned pipeline loop (split.pipeline_scan)
# ---------------------------------------------------------------------------

def _split_fixture(pipeline_microbatches, pipeline_scan, stage=None):
    from repro.config import DCGANConfig
    from repro.core.devices import Client, Device
    from repro.core.gan import bce_logits
    from repro.core.selection import make_plan
    from repro.core.split import SplitExecution
    from repro.models.dcgan import (disc_apply_layer, disc_layer_costs,
                                    disc_layer_names)
    c = DCGANConfig(base_filters=4)
    costs = disc_layer_costs(c)
    layers = [(n, costs[n]) for n in disc_layer_names(c)]
    plan = make_plan(Client("c0", [Device("d0", 1.0, 2),
                                   Device("d1", 0.5, 2)]),
                     layers, "sorted_multi", 3)
    tails = (functools.partial(bce_logits, target=1.0),
             functools.partial(bce_logits, target=0.0))
    ex = SplitExecution(plan, functools.partial(disc_apply_layer, c=c),
                        tails, stage=stage,
                        pipeline_microbatches=pipeline_microbatches,
                        pipeline_scan=pipeline_scan)
    return ex, c


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("dp", [False, True])
def test_pipeline_scan_pins_unrolled_loop(k, dp):
    from repro.core.split import GaussianBoundaryStage
    from repro.models.dcgan import disc_init
    stage = GaussianBoundaryStage(1.0, 0.1) if dp else None
    loop, c = _split_fixture(k, False, stage=stage)
    scan, _ = _split_fixture(k, True, stage=stage)
    params = disc_init(jax.random.PRNGKey(0), c)
    kk = jax.random.PRNGKey(7)
    real = jax.random.normal(jax.random.fold_in(kk, 1), (8, 28, 28, 1))
    fake = jax.random.normal(jax.random.fold_in(kk, 2), (8, 28, 28, 1))
    ll, lg, _ = loop.run_pipelined(params, (real, fake),
                                   key=jax.random.PRNGKey(5))
    sl, sg, _ = scan.run_pipelined(params, (real, fake),
                                   key=jax.random.PRNGKey(5))
    np.testing.assert_allclose(float(ll), float(sl), atol=1e-5)
    for a, b in zip(jax.tree.leaves(lg), jax.tree.leaves(sg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # distinct compile cache slots: scanned XLA != unrolled XLA
    assert loop.signature != scan.signature


def test_pipeline_scan_collect_falls_back_to_loop():
    scan, c = _split_fixture(4, True)
    from repro.models.dcgan import disc_init
    params = disc_init(jax.random.PRNGKey(0), c)
    k = jax.random.PRNGKey(3)
    real = jax.random.normal(jax.random.fold_in(k, 1), (8, 28, 28, 1))
    fake = jax.random.normal(jax.random.fold_in(k, 2), (8, 28, 28, 1))
    _, _, recs = scan.run_pipelined(params, (real, fake), collect=True)
    # collect needs per-chunk records — the loop path serves them intact
    assert all(r is not None for r in recs["fwd"])
    assert recs["fwd"][0][0].shape[0] == 8


def test_pipeline_scan_k1_is_bitexact_run():
    scan, c = _split_fixture(1, True)
    from repro.models.dcgan import disc_init
    params = disc_init(jax.random.PRNGKey(0), c)
    k = jax.random.PRNGKey(3)
    real = jax.random.normal(jax.random.fold_in(k, 1), (4, 28, 28, 1))
    fake = jax.random.normal(jax.random.fold_in(k, 2), (4, 28, 28, 1))
    l1, g1, _ = scan.run_pipelined(params, (real, fake))
    l2, g2, _ = scan.run(params, (real, fake))
    assert float(l1) == float(l2)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
