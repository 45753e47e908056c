"""The input sampling layer: one client's T batches drawn by one gather,
one latent copy and one generator program (``core/gan``
``FSLGANTrainer._sample_round_batches``)."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.obs import trace as ot

B = 8


@pytest.fixture(scope="module")
def parts():
    imgs, labels = synthetic_mnist(120, seed=0)
    return partition_dirichlet(imgs, labels, 2, alpha=0.5, seed=0)


@pytest.fixture
def trainer(parts):
    cfg = get_config("dcgan-mnist").override(
        {"shape.global_batch": B, "fsl.num_clients": 2,
         "model.dcgan.base_filters": 8})
    return FSLGANTrainer(cfg, parts, seed=0)


def _spy(tr, name, calls):
    """Wrap ``tr.<name>`` on the instance, recording each call's
    arguments and result under ``calls[name]``."""
    orig = getattr(tr, name)

    def wrapped(*args):
        out = orig(*args)
        calls.setdefault(name, []).append((args, out))
        return out
    setattr(tr, name, wrapped)


@pytest.mark.parametrize("steps", [1, 3])
def test_one_gather_one_latent_copy_one_generator_program(trainer, steps):
    tr = trainer
    calls = {}
    for name in ("_sample_real", "_z", "_gen"):
        _spy(tr, name, calls)
    cid = tr._active_clients()[0]
    reals, fakes = tr._sample_round_batches(cid, steps)
    assert {k: len(v) for k, v in calls.items()} == \
        {"_sample_real": 1, "_z": 1, "_gen": 1}
    assert calls["_sample_real"][0][0] == (cid, steps * B)
    assert calls["_z"][0][0] == (steps * B,)
    c = tr.c
    assert reals.shape == (steps, B, c.image_size, c.image_size, c.channels)
    assert fakes.shape == reals.shape


def test_reals_are_rows_of_the_client(trainer):
    tr = trainer
    for cid in tr._active_clients():
        reals, _ = tr._sample_round_batches(cid, 3)
        rows = tr.client_data[cid].reshape(len(tr.client_data[cid]), -1)
        for row in np.asarray(reals).reshape(3 * B, -1):
            assert np.any(np.all(rows == row, axis=1))


def test_fakes_are_each_batch_generated_alone(trainer):
    tr = trainer
    calls = {}
    _spy(tr, "_z", calls)
    steps = 3
    _, fakes = tr._sample_round_batches(tr._active_clients()[0], steps)
    (_, z), = calls["_z"]
    g = tr.state.g_params
    for t in range(steps):
        np.testing.assert_array_equal(
            np.asarray(fakes[t]), np.asarray(tr._gen(g, z[t * B:(t + 1) * B])))


def test_each_batch_keeps_its_own_batchnorm_statistics(trainer):
    tr = trainer
    g = tr.state.g_params
    z = jax.random.normal(jax.random.PRNGKey(3), (3, B, tr.c.latent_dim))
    moved = z.at[1].multiply(4.0).at[1].add(2.0)
    a, b = np.asarray(tr._gen(g, z)), np.asarray(tr._gen(g, moved))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[2], b[2])
    assert not np.allclose(a[1], b[1])


def test_input_span_carries_its_batches(trainer, tmp_path):
    tr = trainer
    cid = tr._active_clients()[0]
    tr._sample_round_batches(cid, 2)            # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(tr._sample_round_batches(cid, 2))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    stats = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host")
             for line in plane.lines for e in line.events
             if e.name == ot.SPAN_INPUT]
    assert stats == [{"batches": 2}]
