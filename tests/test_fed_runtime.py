"""Federation runtime (fed/): codecs, events, engine, client programs.

Pinned invariants:
  * engine sync mode (loop backend) == seed sequential loop, bit-for-bit
    at fixed seed;
  * the program's vectorized backend == sequential per-client D-steps to
    fp32 tolerance (live params; BN-cancelled conv biases are analytically
    dead and excluded), with privacy off AND with DP-SGD on (looped-DP ==
    vectorized-DP — the ISSUE 3 acceptance pin);
  * every backend x privacy x codec cell trains (matrix smoke test);
  * dropped stragglers commit no optimizer state (ISSUE 3 regression);
  * codec round-trip error bounds; wire-byte accounting sanity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.fed.events import (ARRIVE, FINISH, BernoulliAvailability,
                              EventQueue)
from repro.fed.policies import ClientUpdate, FedAsync, FedBuff
from repro.fed.programs import (fedavg_stacked, sequential_d_rounds,
                                stack_trees, unstack_tree)
from repro.fed.transport import (FP16Codec, IdentityCodec, Int8Codec,
                                 LinkModel, TopKCodec, TrafficLedger,
                                 fake_batch_bytes, make_codec, tree_bytes)


def _tree(seed=0, scale=1.0):
    k = jax.random.PRNGKey(seed)
    return {"w": scale * jax.random.normal(k, (16, 8)),
            "b": {"x": scale * jax.random.normal(jax.random.fold_in(k, 1),
                                                 (32,))}}


# ---------------------------------------------------------------------------
# transport: codecs + byte accounting
# ---------------------------------------------------------------------------

def test_tree_bytes_counts_native_dtypes():
    t = {"a": jnp.zeros((4, 4), jnp.float32), "b": jnp.zeros(10, jnp.int8)}
    assert tree_bytes(t) == 4 * 4 * 4 + 10
    assert fake_batch_bytes(16, (28, 28, 1)) == 16 * 28 * 28 * 4


def test_identity_codec_exact():
    t = _tree()
    dec, nbytes = IdentityCodec().roundtrip(t)
    for a, b in zip(jax.tree.leaves(dec), jax.tree.leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert nbytes == tree_bytes(t)


def test_fp16_codec_error_bound_and_bytes():
    t = _tree(scale=2.0)
    dec, nbytes = FP16Codec().roundtrip(t)
    for a, b in zip(jax.tree.leaves(dec), jax.tree.leaves(t)):
        a, b = np.asarray(a), np.asarray(b)
        # fp16 has 10 mantissa bits: relative error <= 2^-11 per element
        assert np.max(np.abs(a - b)) <= np.max(np.abs(b)) * 2 ** -10
    assert nbytes == tree_bytes(t) // 2


def test_int8_codec_error_bound_and_bytes():
    t = _tree(scale=3.0)
    dec, nbytes = Int8Codec().roundtrip(t)
    for a, b in zip(jax.tree.leaves(dec), jax.tree.leaves(t)):
        a, b = np.asarray(a), np.asarray(b)
        # quantization step is amax/127; round-to-nearest error <= step/2
        step = np.max(np.abs(b)) / 127.0
        assert np.max(np.abs(a - b)) <= step * 0.5 + 1e-7
    # 1 byte/elem + 4-byte scale per leaf
    n_elem = sum(l.size for l in jax.tree.leaves(t))
    assert nbytes == n_elem + 4 * len(jax.tree.leaves(t))


def test_topk_codec_sparsity_bytes_and_full_frac_exact():
    t = _tree()
    dec, nbytes = TopKCodec(frac=0.25, error_feedback=False).roundtrip(t)
    for a, b in zip(jax.tree.leaves(dec), jax.tree.leaves(t)):
        a, b = np.asarray(a), np.asarray(b)
        k = int(np.ceil(0.25 * b.size))
        assert np.count_nonzero(a) <= k
        # kept entries are exact; dropped entries are the smallest-|x|
        kept = a != 0
        np.testing.assert_allclose(a[kept], b[kept], atol=1e-7)
    kept_total = sum(int(np.ceil(0.25 * l.size)) for l in jax.tree.leaves(t))
    assert nbytes == kept_total * 8
    # frac=1.0 keeps everything
    dec_full, _ = TopKCodec(frac=1.0, error_feedback=False).roundtrip(t)
    for a, b in zip(jax.tree.leaves(dec_full), jax.tree.leaves(t)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_topk_error_feedback_conserves_mass():
    """decoded + residual == input (+ prior residual): nothing is lost,
    only delayed to a later round."""
    codec = TopKCodec(frac=0.1, error_feedback=True)
    t = _tree()
    dec, _ = codec.roundtrip(t)
    for d, r, x in zip(jax.tree.leaves(dec),
                       jax.tree.leaves(codec._residual),
                       jax.tree.leaves(t)):
        np.testing.assert_allclose(np.asarray(d) + np.asarray(r),
                                   np.asarray(x), atol=1e-6)
    # second round: the residual re-enters selection
    zero = jax.tree.map(jnp.zeros_like, t)
    dec2, _ = codec.roundtrip(zero)
    assert any(np.count_nonzero(np.asarray(l)) for l in jax.tree.leaves(dec2))


def test_int8_codec_zero_range_delta_roundtrips_exact():
    """All-constant (zero-range) deltas — the common case for frozen or
    converged leaves — must round-trip without NaN."""
    zero = {"w": jnp.zeros((8, 8)), "b": jnp.zeros((16,))}
    dec, nbytes = Int8Codec().roundtrip(zero)
    for l in jax.tree.leaves(dec):
        a = np.asarray(l)
        assert np.all(np.isfinite(a))
        np.testing.assert_array_equal(a, np.zeros_like(a))
    assert nbytes == (8 * 8 + 16) + 4 * 2
    # constant nonzero: q = +/-127 exactly, so the round-trip is exact
    const = {"w": jnp.full((8, 8), -0.37), "b": jnp.full((16,), 0.5)}
    dec_c, _ = Int8Codec().roundtrip(const)
    for a, b in zip(jax.tree.leaves(dec_c), jax.tree.leaves(const)):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_topk_codec_k_geq_n_keeps_everything():
    """frac >= 1 (k >= n per leaf) must not IndexError in lax.top_k and
    must be the identity on the delta."""
    t = _tree()
    for frac in (1.0, 1.5, 7.0):
        dec, nbytes = TopKCodec(frac=frac, error_feedback=False).roundtrip(t)
        for a, b in zip(jax.tree.leaves(dec), jax.tree.leaves(t)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-7)
        # k clamps at n: wire bytes never exceed 8 bytes/element
        n_elem = sum(l.size for l in jax.tree.leaves(t))
        assert nbytes == n_elem * 8
    # tiny leaf (n=1) with tiny frac: k clamps up to 1, not 0
    tiny = {"s": jnp.asarray([3.0])}
    dec, nbytes = TopKCodec(frac=1e-6, error_feedback=False).roundtrip(tiny)
    np.testing.assert_allclose(np.asarray(dec["s"]), [3.0])
    assert nbytes == 8


def test_make_codec_factory():
    assert make_codec("none").name == "none"
    assert make_codec("fp16").name == "fp16"
    assert make_codec("int8").name == "int8"
    assert make_codec("topk", topk_frac=0.5).frac == 0.5
    with pytest.raises(ValueError):
        make_codec("gzip")


def test_link_model_and_ledger():
    link = LinkModel(latency_s=0.1, bandwidth_bps=8e6)
    assert link.transfer_time(0) == pytest.approx(0.1)
    assert link.transfer_time(1_000_000) == pytest.approx(0.1 + 1.0)
    led = TrafficLedger()
    led.record("c0", up=10, down=20)
    led.record("c0", up=5)
    led.record("c1", down=7)
    assert led.total_up == 15 and led.total_down == 27


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------

def test_event_queue_orders_by_time_then_insertion():
    q = EventQueue()
    q.push(2.0, FINISH, "b")
    q.push(1.0, FINISH, "a")
    q.push(1.0, ARRIVE, "c")          # same time: insertion order breaks tie
    order = [(e.time, e.client_id) for e in q.drain()]
    assert order == [(1.0, "a"), (1.0, "c"), (2.0, "b")]


def test_bernoulli_availability_deterministic_and_varied():
    tr = BernoulliAvailability(0.5, seed=3)
    draws = [tr.available(f"c{i}", r) for i in range(4) for r in range(8)]
    assert draws == [tr.available(f"c{i}", r)
                     for i in range(4) for r in range(8)]
    assert any(draws) and not all(draws)


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------

def test_fedasync_staleness_discounts_rate():
    pol = FedAsync(alpha=0.5, staleness_power=1.0)
    assert pol.rate(0) == pytest.approx(0.5)
    assert pol.rate(3) == pytest.approx(0.5 / 4)
    g, u = _tree(0), _tree(1)
    mixed, bumped = pol.on_update(g, ClientUpdate("c0", u, 1.0, staleness=0))
    assert bumped
    want = jax.tree.map(lambda a, b: 0.5 * a + 0.5 * b, g, u)
    for a, b in zip(jax.tree.leaves(mixed), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_fedbuff_fires_at_buffer_size_and_flushes():
    pol = FedBuff(buffer_size=2, server_lr=1.0, staleness_power=0.0)
    g = _tree(0)
    g1, bumped1 = pol.on_update(g, ClientUpdate("c0", _tree(1), 1.0))
    assert not bumped1           # buffered, global untouched
    g2, bumped2 = pol.on_update(g1, ClientUpdate("c1", _tree(2), 1.0))
    assert bumped2               # K=2 reached: buffer mean replaces global
    want = jax.tree.map(lambda a, b: (a + b) / 2, _tree(1), _tree(2))
    for a, b in zip(jax.tree.leaves(g2), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # round-end flush of a partial buffer
    g3, _ = pol.on_update(g2, ClientUpdate("c2", _tree(3), 1.0))
    g4 = pol.on_round_end(g3)
    for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(_tree(3))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# engine + trainer (smoke scale)
# ---------------------------------------------------------------------------

def _cfg(**over):
    base = {"shape.global_batch": 8, "fsl.num_clients": 2,
            "model.dcgan.base_filters": 8}
    base.update(over)
    return get_config("dcgan-mnist").override(base)


@pytest.fixture(scope="module")
def parts():
    imgs, labels = synthetic_mnist(120, seed=0)
    return partition_dirichlet(imgs, labels, 2, alpha=0.5, seed=0)


def test_engine_sync_reproduces_seed_trainer_bit_for_bit(parts):
    ta = FSLGANTrainer(_cfg(), parts, seed=0)
    tb = FSLGANTrainer(_cfg(), parts, seed=0)
    for _ in range(2):
        ma = ta.train_epoch(batches_per_client=2)          # engine path
        mb = tb.train_epoch_sequential(batches_per_client=2)  # seed loop
        for k in ("d_loss", "g_loss", "num_clients"):
            assert ma[k] == mb[k]
    for cid in ta.state.d_params:
        for a, b in zip(jax.tree.leaves(ta.state.d_params[cid]),
                        jax.tree.leaves(tb.state.d_params[cid])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ta.state.g_params),
                    jax.tree.leaves(tb.state.g_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _dead_bias(path) -> bool:
    """Conv biases under batchnorm: BN mean-subtraction cancels them, so
    their gradient is fp cancellation noise that Adam amplifies to O(lr)."""
    keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    return (len(keys) == 2 and keys[1] == "b"
            and str(keys[0]).startswith("conv") and keys[0] != "conv0")


def test_vectorized_round_matches_sequential(parts):
    tr = FSLGANTrainer(_cfg(), parts, seed=0)
    st = tr.state
    active = tr._active_clients()
    B, T = tr.batch_size, 2
    reals = jnp.stack([jnp.stack([tr._sample_real(cid, B) for _ in range(T)])
                       for cid in active])
    fakes = jnp.stack([jnp.stack([tr._gen(st.g_params, tr._z(B))
                                  for _ in range(T)]) for cid in active])

    sp = stack_trees([st.d_params[c] for c in active])
    so = stack_trees([st.d_opt[c] for c in active])
    vp, vo, v_losses = tr.program.run_vectorized(sp, so, reals, fakes)
    seq_p, seq_o, s_losses = sequential_d_rounds(
        tr._d_step, [st.d_params[c] for c in active],
        [st.d_opt[c] for c in active], reals, fakes)

    np.testing.assert_allclose(np.asarray(v_losses), np.asarray(s_losses),
                               atol=1e-5, rtol=1e-5)
    for i, cid in enumerate(active):
        got = jax.tree_util.tree_flatten_with_path(
            jax.tree.map(lambda x: x[i], vp))[0]
        want = jax.tree_util.tree_flatten_with_path(seq_p[i])[0]
        for (path, a), (_, b) in zip(got, want):
            if _dead_bias(path):
                continue
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5,
                                       err_msg=jax.tree_util.keystr(path))
        # functional equivalence including dead params: BN cancels them
        from repro.models.dcgan import disc_apply
        x = tr._sample_real(active[0], 4)
        np.testing.assert_allclose(
            np.asarray(disc_apply(jax.tree.map(lambda v: v[i], vp), x, tr.c)),
            np.asarray(disc_apply(seq_p[i], x, tr.c)), atol=1e-4, rtol=1e-4)


def test_fedavg_stacked_kernel_matches_host():
    trees = [_tree(i) for i in range(3)]
    stacked = stack_trees(trees)
    w = [1.0, 2.0, 3.0]
    host = fedavg_stacked(stacked, w)
    kern = fedavg_stacked(stacked, w, use_kernel=True, interpret=True)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(kern)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # unstack round-trips
    back = unstack_tree(stacked, 3)
    for t, u in zip(trees, back):
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(u)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_byte_accounting_and_codec_compression(parts):
    t_raw = FSLGANTrainer(_cfg(), parts, seed=0)
    m_raw = t_raw.train_epoch(batches_per_client=1)
    t_int8 = FSLGANTrainer(_cfg(**{"fed.codec": "int8"}), parts, seed=0)
    m_int8 = t_int8.train_epoch(batches_per_client=1)
    # downlink (fakes) identical; uplink ~4x smaller under int8
    assert m_int8["down_mbytes"] == m_raw["down_mbytes"]
    assert m_int8["up_mbytes"] < 0.3 * m_raw["up_mbytes"]
    # raw uplink == native D param bytes x clients
    d_bytes = tree_bytes(t_raw.state.d_params["c0"])
    assert m_raw["up_mbytes"] == pytest.approx(
        2 * d_bytes / 1e6, rel=1e-6)
    # engine's cumulative ledger matches the round report
    assert t_raw.engine.ledger.total_up == int(m_raw["up_mbytes"] * 1e6)


def test_straggler_deadline_drops_updates(parts):
    t = FSLGANTrainer(_cfg(**{"fed.deadline_s": 1.0}), parts, seed=0)
    m = t.train_epoch(batches_per_client=1)
    assert m["num_clients"] == 0.0 and m["stragglers"] == 2.0
    assert m["round_time_s"] == pytest.approx(1.0)


def test_async_modes_train_and_record_staleness(parts):
    for mode in ("fedasync", "fedbuff"):
        t = FSLGANTrainer(_cfg(**{"fed.mode": mode, "fed.async_cycles": 2}),
                          parts, seed=0)
        m = t.train_epoch(batches_per_client=1)
        assert np.isfinite(m["d_loss"]) and np.isfinite(m["g_loss"])
        assert m["num_clients"] == 2.0
        assert m["round_time_s"] > 0.0
        rep_events = t.engine  # 2 clients x 2 cycles = 4 arrivals expected
        assert rep_events.round_idx == 1


def test_availability_trace_gates_participation(parts):
    t = FSLGANTrainer(_cfg(**{"fed.availability": 0.5,
                              "fed.availability_seed": 3}), parts, seed=0)
    ns = [t.train_epoch(batches_per_client=1)["num_clients"]
          for _ in range(4)]
    assert min(ns) < 2.0            # somebody was down at least once


# ---------------------------------------------------------------------------
# client programs: backend x privacy orthogonality (ISSUE 3)
# ---------------------------------------------------------------------------

def _d_param_trees_close(ta, tb, atol=5e-5, rtol=5e-5):
    """Compare per-client D params, skipping BN-cancelled dead biases."""
    for cid in ta.state.d_params:
        got = jax.tree_util.tree_flatten_with_path(ta.state.d_params[cid])[0]
        want = jax.tree_util.tree_flatten_with_path(tb.state.d_params[cid])[0]
        for (path, a), (_, b) in zip(got, want):
            if _dead_bias(path):
                continue
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=atol, rtol=rtol,
                                       err_msg=f"{cid}/"
                                       + jax.tree_util.keystr(path))


def test_engine_vectorized_backend_matches_loop(parts):
    """The engine's batched vectorized dispatch == the loop backend (and
    hence the seed loop) to fp32 tolerance, at fixed seed."""
    ta = FSLGANTrainer(_cfg(), parts, seed=0)
    tb = FSLGANTrainer(_cfg(), parts, seed=0)
    for _ in range(2):
        ma = ta.train_epoch(batches_per_client=2, backend="loop")
        mb = tb.train_epoch(batches_per_client=2, backend="vectorized")
        assert ma["num_clients"] == mb["num_clients"]
        assert ma["up_mbytes"] == mb["up_mbytes"]
        np.testing.assert_allclose(ma["d_loss"], mb["d_loss"],
                                   atol=1e-5, rtol=1e-5)
    _d_param_trees_close(ta, tb)


def test_looped_dp_matches_vectorized_dp_fixed_seed(parts):
    """ISSUE 3 acceptance pin: DP-SGD through the loop backend and through
    the vectorized (vmap/scan, clip+noise inside the scanned step) backend
    draw the same noise and produce the same training to fp32 tolerance."""
    over = {"privacy.enabled": True, "privacy.noise_multiplier": 0.8}
    ta = FSLGANTrainer(_cfg(**over), parts, seed=0)
    tb = FSLGANTrainer(_cfg(**over), parts, seed=0)
    for _ in range(2):
        ma = ta.train_epoch(batches_per_client=2, backend="loop")
        mb = tb.train_epoch(batches_per_client=2, backend="vectorized")
        np.testing.assert_allclose(ma["d_loss"], mb["d_loss"],
                                   atol=1e-5, rtol=1e-5)
        assert ma["dp_epsilon"] == mb["dp_epsilon"]
    assert ta.accountant.steps == tb.accountant.steps == 2 * 2 * 2
    _d_param_trees_close(ta, tb)


MATRIX_BACKENDS = ("loop", "vectorized")
MATRIX_PRIVACY = {
    "none": {},
    "dp_sgd": {"privacy.enabled": True, "privacy.noise_multiplier": 0.5},
    "uplink": {"privacy.enabled": True, "privacy.mode": "uplink",
               "privacy.noise_multiplier": 0.5},
}
MATRIX_CODECS = ("none", "fp16", "int8", "topk")


@pytest.mark.parametrize("backend", MATRIX_BACKENDS)
@pytest.mark.parametrize("privacy", sorted(MATRIX_PRIVACY))
@pytest.mark.parametrize("codec", MATRIX_CODECS)
def test_backend_privacy_codec_matrix(parts, backend, privacy, codec):
    """Every backend x privacy x codec cell trains: finite losses, both
    clients participate, and privacy modes account a positive epsilon.
    Neither NotImplementedError wall exists any more."""
    over = {"fed.codec": codec, "fed.topk_frac": 0.25,
            **MATRIX_PRIVACY[privacy]}
    t = FSLGANTrainer(_cfg(**over), parts, seed=0)
    m = t.train_epoch(batches_per_client=1, backend=backend)
    assert np.isfinite(m["d_loss"]) and np.isfinite(m["g_loss"])
    assert m["num_clients"] == 2.0
    if privacy == "none":
        assert "dp_epsilon" not in m
    else:
        assert 0 < m["dp_epsilon"] < float("inf")


@pytest.mark.parametrize("mode,backend", [("fedasync", "vectorized"),
                                          ("fedbuff", "loop")])
def test_async_scheduling_composes_with_backends_and_dp(parts, mode,
                                                        backend):
    """Scheduling x backend x privacy: async modes execute the program
    per-arrival under either backend, DP-SGD included."""
    t = FSLGANTrainer(_cfg(**{"fed.mode": mode, "fed.async_cycles": 2,
                              "privacy.enabled": True,
                              "privacy.noise_multiplier": 0.5}),
                      parts, seed=0)
    m = t.train_epoch(batches_per_client=1, backend=backend)
    assert np.isfinite(m["d_loss"]) and m["num_clients"] == 2.0
    # 2 clients x 2 cycles x 1 batch DP releases
    assert t.accountant.steps == 4


def test_straggler_drop_commits_no_opt_state(parts):
    """ISSUE 3 regression: a client that RUNS but whose update lands after
    the deadline must leave the trainer's opt state untouched (the old
    ``_local_update_fn`` mutated ``st.d_opt`` as a side effect, leaving it
    ahead of the re-broadcast params)."""
    # c1 runs 3x the batches => strictly the slowest; pick a deadline after
    # its compute finishes but before its uplink lands
    over = {"fed.client_local_steps": {"c1": 3}}
    probe = FSLGANTrainer(_cfg(**over), parts, seed=0)
    eng = probe._ensure_engine(1)
    batch_b = fake_batch_bytes(probe.batch_size, (28, 28, 1))
    # downlink is priced per client: c1's 3-step schedule downloads 3x
    down_t = {cid: eng.downlink.transfer_time(
        eng.specs[cid].local_steps * batch_b) for cid in ("c0", "c1")}
    up_t = {cid: eng.uplink.transfer_time(
        tree_bytes(probe.state.d_params[cid])) for cid in ("c0", "c1")}
    finish = {cid: down_t[cid] + eng.specs[cid].compute_time_s + up_t[cid]
              for cid in ("c0", "c1")}
    assert finish["c1"] > finish["c0"]
    deadline = finish["c1"] - up_t["c1"] / 2
    assert down_t["c1"] + eng.specs["c1"].compute_time_s < deadline
    assert finish["c0"] < deadline

    t = FSLGANTrainer(_cfg(**over, **{"fed.deadline_s": deadline}),
                      parts, seed=0)
    m = t.train_epoch(batches_per_client=1)
    assert m["num_clients"] == 1.0 and m["stragglers"] == 1.0
    # c1 executed (its losses are in the round mean) ...
    assert len(t.state.history["d_loss"]) == 1 and np.isfinite(m["d_loss"])
    # ... but committed nothing: opt state still at initialization, while
    # the survivor advanced
    assert int(t.state.d_opt["c1"]["step"]) == 0
    assert int(t.state.d_opt["c0"]["step"]) == 1
    # and the wire accounting matches the per-client schedule: c1's 3-step
    # round downloaded 3x the fake payload
    assert t.engine.ledger.down_bytes["c1"] == 3 * batch_b
    assert t.engine.ledger.down_bytes["c0"] == batch_b


def test_split_loop_backend_bit_exact_vs_unsplit_sequential(parts):
    """ISSUE 4 acceptance pin: with cfg.split enabled (identity stage) the
    local step executes THROUGH the plan — staged segment forward/backward
    with boundary hand-offs — yet training is bit-for-bit the seed's
    monolithic sequential loop."""
    ta = FSLGANTrainer(_cfg(**{"split.enabled": True}), parts, seed=0)
    tb = FSLGANTrainer(_cfg(), parts, seed=0)
    assert any(ex.num_boundaries > 0 for ex in ta.split_execs.values())
    for _ in range(2):
        ma = ta.train_epoch(batches_per_client=2, backend="loop")
        mb = tb.train_epoch_sequential(batches_per_client=2)
        assert ma["d_loss"] == mb["d_loss"]
        assert ma["g_loss"] == mb["g_loss"]
    for cid in ta.state.d_params:
        for a, b in zip(jax.tree.leaves(ta.state.d_params[cid]),
                        jax.tree.leaves(tb.state.d_params[cid])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_split_vectorized_backend_matches_loop(parts):
    """ISSUE 4 acceptance pin: the split-executed step under the
    vectorized backend (clients grouped per split signature, one jitted
    vmap/scan program per group) == the loop backend to fp32 tolerance."""
    ta = FSLGANTrainer(_cfg(**{"split.enabled": True}), parts, seed=0)
    tb = FSLGANTrainer(_cfg(**{"split.enabled": True}), parts, seed=0)
    # the paper pool gives these two clients DIFFERENT boundary
    # signatures, so this exercises the per-signature grouped dispatch
    sigs = {ta.program.signature_for(cid) for cid in ta._active_clients()}
    assert len(sigs) == 2
    for _ in range(2):
        ma = ta.train_epoch(batches_per_client=2, backend="loop")
        mb = tb.train_epoch(batches_per_client=2, backend="vectorized")
        np.testing.assert_allclose(ma["d_loss"], mb["d_loss"],
                                   atol=1e-5, rtol=1e-5)
        assert ma["lan_mbytes"] == mb["lan_mbytes"] > 0
    _d_param_trees_close(ta, tb)


def test_split_reports_measured_lan_bytes(parts):
    """ISSUE 4 acceptance: train_epoch with cfg.split reports nonzero
    measured LAN boundary bytes equal to tree_bytes of the boundary
    tensors the step actually ships (x steps x clients)."""
    t = FSLGANTrainer(_cfg(**{"split.enabled": True}), parts, seed=0)
    steps = 2
    m = t.train_epoch(batches_per_client=steps)
    expect = 0
    for cid in t._active_clients():
        ex = t.split_execs[cid]
        real = jnp.zeros((t.batch_size, 28, 28, 1))
        rec = ex.shipped_boundaries(t.state.d_params[cid], real, real)
        expect += steps * sum(tree_bytes(x) for d in ("fwd", "bwd")
                              for pair in rec[d] for x in pair)
    assert expect > 0
    assert m["lan_mbytes"] == pytest.approx(expect / 1e6)
    assert t.engine.ledger.total_lan == expect
    assert m["max_device_load"] > 0
    # round time is priced from the measured bytes, not the bare constant
    eng_split = t.engine.specs["c0"].compute_time_s
    t_unsplit = FSLGANTrainer(_cfg(), parts, seed=0)
    t_unsplit.train_epoch(batches_per_client=steps)
    assert eng_split != t_unsplit.engine.specs["c0"].compute_time_s
    # unsplit rounds ship nothing over the LAN
    assert t_unsplit.engine.ledger.total_lan == 0
    assert "lan_mbytes" not in t_unsplit.state.history


@pytest.mark.parametrize("stage,backend", [("int8", "loop"),
                                           ("fp16", "vectorized"),
                                           ("dp", "vectorized")])
def test_split_boundary_stage_matrix(parts, stage, backend):
    """Codec/DP boundary stages compose with both backends: training stays
    finite, the staged round differs from the identity-stage one, and
    codec stages shrink the measured LAN bytes."""
    over = {"split.enabled": True, "split.boundary_stage": stage,
            "split.stage_sigma": 0.3}
    t = FSLGANTrainer(_cfg(**over), parts, seed=0)
    m = t.train_epoch(batches_per_client=1, backend=backend)
    assert np.isfinite(m["d_loss"]) and m["num_clients"] == 2.0
    t0 = FSLGANTrainer(_cfg(**{"split.enabled": True}), parts, seed=0)
    m0 = t0.train_epoch(batches_per_client=1, backend=backend)
    assert m["d_loss"] != m0["d_loss"]
    if stage in ("int8", "fp16"):
        assert 0 < m["lan_mbytes"] < m0["lan_mbytes"]
    else:
        assert m["lan_mbytes"] == m0["lan_mbytes"]


def test_split_stochastic_stage_backends_draw_same_noise(parts):
    """The dp boundary stage's noise keys derive from (round, client,
    exec, batch, boundary), so loop and vectorized backends draw identical
    noise — same pin as DP-SGD, now for the stage."""
    over = {"split.enabled": True, "split.boundary_stage": "dp",
            "split.stage_clip": 5.0, "split.stage_sigma": 0.4}
    ta = FSLGANTrainer(_cfg(**over), parts, seed=0)
    tb = FSLGANTrainer(_cfg(**over), parts, seed=0)
    ma = ta.train_epoch(batches_per_client=2, backend="loop")
    mb = tb.train_epoch(batches_per_client=2, backend="vectorized")
    np.testing.assert_allclose(ma["d_loss"], mb["d_loss"],
                               atol=1e-5, rtol=1e-5)
    _d_param_trees_close(ta, tb)


def test_sequential_reference_refuses_lossy_boundary_stage(parts):
    """train_epoch_sequential trains the monolithic D — identical to the
    split step only under the identity stage.  A lossy stage must be
    refused, not silently diverge from every engine path."""
    t = FSLGANTrainer(_cfg(**{"split.enabled": True,
                              "split.boundary_stage": "int8"}),
                      parts, seed=0)
    with pytest.raises(ValueError, match="identity-stage"):
        t.train_epoch_sequential(batches_per_client=1)
    # identity stage keeps the reference valid (the bit-exact pin)
    t2 = FSLGANTrainer(_cfg(**{"split.enabled": True}), parts, seed=0)
    m = t2.train_epoch_sequential(batches_per_client=1)
    assert np.isfinite(m["d_loss"])


def test_split_composes_with_dp_sgd_and_codec(parts):
    """Split execution x DP-SGD x uplink codec in one round, both
    backends agreeing — the fourth axis joins the matrix instead of
    becoming a divergent path."""
    over = {"split.enabled": True, "fed.codec": "int8",
            "privacy.enabled": True, "privacy.noise_multiplier": 0.5}
    ta = FSLGANTrainer(_cfg(**over), parts, seed=0)
    tb = FSLGANTrainer(_cfg(**over), parts, seed=0)
    ma = ta.train_epoch(batches_per_client=1, backend="loop")
    mb = tb.train_epoch(batches_per_client=1, backend="vectorized")
    np.testing.assert_allclose(ma["d_loss"], mb["d_loss"],
                               atol=1e-5, rtol=1e-5)
    assert ma["dp_epsilon"] == mb["dp_epsilon"] > 0
    assert ma["lan_mbytes"] == mb["lan_mbytes"] > 0
    _d_param_trees_close(ta, tb)


def test_per_client_schedules_thread_through_backends(parts):
    """cfg.fed.client_lr_scales / client_local_steps reach both backends:
    per-client step counts differ, scaling the LR changes training, and
    the two backends agree on the scheduled round."""
    over = {"fed.client_lr_scales": {"c0": 0.25},
            "fed.client_local_steps": {"c1": 3}}
    ta = FSLGANTrainer(_cfg(**over), parts, seed=0)
    tb = FSLGANTrainer(_cfg(**over), parts, seed=0)
    ma = ta.train_epoch(batches_per_client=1, backend="loop")
    mb = tb.train_epoch(batches_per_client=1, backend="vectorized")
    # heterogeneous local_steps: c1 ran 3 batches, c0 ran 1
    assert int(ta.state.d_opt["c1"]["step"]) == 3
    assert int(ta.state.d_opt["c0"]["step"]) == 1
    assert int(tb.state.d_opt["c1"]["step"]) == 3
    np.testing.assert_allclose(ma["d_loss"], mb["d_loss"],
                               atol=1e-5, rtol=1e-5)
    _d_param_trees_close(ta, tb)
    # the lr_scale actually bites: the aggregated model differs from the
    # default-schedule run (losses can't show it — they are evaluated
    # before each step's update)
    tc = FSLGANTrainer(_cfg(**{"fed.client_local_steps": {"c1": 3}}),
                       parts, seed=0)
    tc.train_epoch(batches_per_client=1)
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(tc.state.d_params["c0"]),
                               jax.tree.leaves(ta.state.d_params["c0"])))
    assert diff > 1e-6
