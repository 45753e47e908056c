"""The program's own spans on the profiler's clock (``fsl.*``, named in
``repro.obs.trace``) in a profiler trace of one ``mnist-paper`` round,
on the CPU at the benchmark tests' small sizes."""
import glob
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import harness
from bench.tests import small
from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.obs import trace as ot

CELL = "mnist-paper"
LAYERS = (ot.SPAN_INPUT, ot.SPAN_CLIENT_STEP, ot.SPAN_REDUCE,
          ot.SPAN_GENERATOR)
NAMES = (ot.SPAN_ROUND, ot.SPAN_SYNC) + LAYERS


def program_spans(log_dir):
    """``(start_ns, end_ns, name)`` of every host event named by a
    ``SPAN_*`` constant, in the trace written under ``log_dir``."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(int(e.start_ns), int(e.end_ns), e.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events
            if e.name in NAMES]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    _, _, conf, cell = harness.load_cell(CELL)
    conf, cell = small.tweak(conf, cell)
    system = importlib.import_module(f"bench.systems.{conf['family']}") \
        .System(conf, cell, harness.seeds_from(2 ** 32 + 5))
    system.round()                      # compiles outside the trace
    log_dir = str(tmp_path_factory.mktemp("trace"))
    harness.start_trace(log_dir)
    try:
        system.round()
    finally:
        jax.profiler.stop_trace()
    return program_spans(log_dir), cell


def test_layer_spans_lie_inside_the_round(traced):
    spans, _ = traced
    rounds = [(s, e) for s, e, n in spans if n == ot.SPAN_ROUND]
    assert len(rounds) == 1
    inner = [(s, e, n) for s, e, n in spans if n in LAYERS]
    assert {n for _, _, n in inner} == set(LAYERS)
    assert all(any(rs <= s and e <= re for rs, re in rounds)
               for s, e, _ in inner)


def test_one_input_and_one_client_step_span_per_client(traced):
    spans, cell = traced
    names = [n for _, _, n in spans]
    assert names.count(ot.SPAN_INPUT) == cell["clients"]
    assert names.count(ot.SPAN_CLIENT_STEP) == cell["clients"]
    assert names.count(ot.SPAN_REDUCE) == names.count(ot.SPAN_GENERATOR) == 1


def test_one_sync_span_per_loss_read(traced):
    spans, cell = traced
    batches = cell["batches_per_client"]
    syncs = [(s, e) for s, e, n in spans if n == ot.SPAN_SYNC]
    # one read of all its local losses per client, one per G step
    assert len(syncs) == cell["clients"] + batches
    # the local steps' reads nest in their client's span, the G steps'
    # in the generator's
    outer = [(s, e) for s, e, n in spans
             if n in (ot.SPAN_CLIENT_STEP, ot.SPAN_GENERATOR)]
    assert all(any(os_ <= s and e <= oe for os_, oe in outer)
               for s, e in syncs)


def test_reduce_span_bounds_host_time(traced):
    spans, _ = traced
    (s, e), = [(s, e) for s, e, n in spans if n == ot.SPAN_REDUCE]
    gens = [gs for gs, _, n in spans if n == ot.SPAN_GENERATOR]
    steps = [ce for _, ce, n in spans if n == ot.SPAN_CLIENT_STEP]
    # the reduce takes host time, after every client's steps and before G
    assert e > s
    assert max(steps) <= s and e <= min(gens)


# ``run_looped`` against a hand loop: one step, a ``fold_in(key, t)`` key
# and a blocking ``float(loss)`` per batch
LOOPED = {
    "plain": {},
    "dp_sgd": {"privacy.enabled": True, "privacy.noise_multiplier": 0.8},
    "split": {"split.enabled": True},
}


def _hand_loop(program, cid, params, opt, reals, fakes, lr, key):
    step = program._step(program.signature_for(cid))
    losses = []
    for t in range(reals.shape[0]):
        params, opt, loss = step(params, opt, reals[t], fakes[t],
                                 jnp.float32(lr), jax.random.fold_in(key, t))
        losses.append(float(loss))
    return params, opt, losses


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case", sorted(LOOPED))
def test_run_looped_is_the_per_step_loop_with_one_read(case, tmp_path):
    imgs, labels = synthetic_mnist(120, seed=0)
    parts = partition_dirichlet(imgs, labels, 2, alpha=0.5, seed=0)
    cfg = get_config("dcgan-mnist").override(
        {"shape.global_batch": 8, "fsl.num_clients": 2,
         "model.dcgan.base_filters": 8, **LOOPED[case]})
    tr = FSLGANTrainer(cfg, parts, seed=0)
    program, cid = tr.program, tr._active_clients()[0]
    assert program.needs_key == (case == "dp_sgd")
    if case == "split":
        assert tr.split_execs[cid].num_boundaries > 0
    params, opt = tr.state.d_params[cid], tr.state.d_opt[cid]
    kr, kf = jax.random.split(jax.random.PRNGKey(1))
    reals = jax.random.normal(kr, (3, 8, 28, 28, 1))
    fakes = jax.random.normal(kf, (3, 8, 28, 28, 1))
    key, other = jax.random.PRNGKey(7), jax.random.PRNGKey(8)

    def looped(k):
        return program.run_looped(params, opt, reals, fakes, lr=1e-3,
                                  key=k, cid=cid)

    got = looped(key)
    # the later steps donate their own buffers, never the caller's
    assert not any(x.is_deleted() for x in jax.tree.leaves((params, opt)))
    want = _hand_loop(program, cid, params, opt, reals, fakes, 1e-3, key)
    _assert_same(got[:2], want[:2])
    assert got[2] == want[2]
    # a step that reads no key gives the same result whatever key it gets
    if program.needs_key:
        assert looped(other)[2] != got[2]
    else:
        _assert_same(looped(other), got)

    harness.start_trace(str(tmp_path))
    try:
        looped(key)
    finally:
        jax.profiler.stop_trace()
    names = [n for _, _, n in program_spans(str(tmp_path))]
    assert names.count(ot.SPAN_SYNC) == names.count(ot.SPAN_CLIENT_STEP) == 1
