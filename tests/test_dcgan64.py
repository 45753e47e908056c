"""DCGAN's published 64x64 G (``gen_layout="radford"``) beside the
FSL-GAN paper's 28x28 G: stage shapes, parameter totals by hand, the
analytic count against the built trees, the layers' named scopes in the
compiled programs."""
import contextlib
import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import DCGANConfig
from repro.configs.registry import ASSIGNED_ARCHS, get_config, list_archs
from repro.core.gan import d_loss_fn, g_loss_fn
from repro.models import dcgan
from repro.models.dcgan import disc_init, gen_apply, gen_init, gen_specs

PUBLISHED = get_config("dcgan64").model.dcgan


def _shapes(tree):
    return jax.tree.map(lambda x: tuple(x.shape), tree)


def _total(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def _trees(c):
    key = jax.random.PRNGKey(0)
    return (jax.eval_shape(lambda: gen_init(key, c)),
            jax.eval_shape(lambda: disc_init(key, c)))


def test_config_is_the_published_model():
    assert PUBLISHED == DCGANConfig(image_size=64, channels=3, latent_dim=100,
                                    base_filters=128, conv_blocks=4,
                                    gen_layout="radford")
    cfg = get_config("dcgan64")
    assert cfg.shape.global_batch == 128
    assert (cfg.optim.name, cfg.optim.lr, cfg.optim.beta1) == ("adam", 2e-4,
                                                               0.5)
    assert (cfg.fsl.num_clients, cfg.fsl.devices_per_client,
            cfg.fsl.selection) == (5, 4, "sorted_multi")


def test_gan_archs_are_not_paired_with_lm_shapes():
    assert {"dcgan-mnist", "dcgan64"} <= set(list_archs())
    assert not {"dcgan-mnist", "dcgan64"} & set(ASSIGNED_ARCHS)


def test_published_g_stage_shapes():
    """4x4x1024 -> 8x8x512 -> 16x16x256 -> 32x32x128 -> 64x64x3."""
    g, _ = _trees(PUBLISHED)
    jaxpr = jax.make_jaxpr(functools.partial(gen_apply, c=PUBLISHED))(
        g, jax.ShapeDtypeStruct((2, 100), jnp.float32))
    reshapes = [e.outvars[0].aval.shape for e in jaxpr.eqns
                if e.primitive.name == "reshape"]
    convs = [e.outvars[0].aval.shape for e in jaxpr.eqns
             if e.primitive.name == "conv_general_dilated"]
    assert reshapes[0] == (2, 4, 4, 1024)
    assert convs == [(2, 8, 8, 512), (2, 16, 16, 256), (2, 32, 32, 128),
                     (2, 64, 64, 3)]
    assert _shapes(g)["deconv3"] == {"w": (5, 5, 128, 3), "b": (3,)}
    out = jax.eval_shape(functools.partial(gen_apply, c=PUBLISHED), g,
                         jax.ShapeDtypeStruct((2, 100), jnp.float32))
    assert out.shape == (2, 64, 64, 3)


def test_published_parameter_totals_by_hand():
    g, d = _trees(PUBLISHED)
    # G: projection 100 -> 4*4*1024 with a bias per output and BN(1024),
    # then 5x5 transposed convs with a bias each and BN on all but the last
    g_hand = (100 * 16384 + 16384 + 2 * 1024
              + 25 * 1024 * 512 + 512 + 2 * 512
              + 25 * 512 * 256 + 256 + 2 * 256
              + 25 * 256 * 128 + 128 + 2 * 128
              + 25 * 128 * 3 + 3)
    # D: 5x5 convs 3 -> 128 (no BN) -> 256 -> 512 -> 1024 (BN), then a
    # linear head from 4*4*1024 to one logit
    d_hand = (25 * 3 * 128 + 128
              + 25 * 128 * 256 + 256 + 2 * 256
              + 25 * 256 * 512 + 512 + 2 * 512
              + 25 * 512 * 1024 + 1024 + 2 * 1024
              + 16384 + 1)
    assert (g_hand, d_hand) == (18_872_323, 17_234_689)
    assert (_total(g), _total(d)) == (g_hand, d_hand)
    assert get_config("dcgan64").model.param_count() == g_hand + d_hand


@pytest.mark.parametrize("c", [
    DCGANConfig(),
    DCGANConfig(base_filters=8, conv_blocks=2),
    DCGANConfig(image_size=32, channels=3, base_filters=8, conv_blocks=5),
    DCGANConfig(image_size=64, channels=3, base_filters=8, conv_blocks=4,
                gen_layout="radford"),
    DCGANConfig(image_size=32, channels=1, base_filters=4, conv_blocks=2,
                gen_layout="radford"),
    PUBLISHED,
], ids=lambda c: f"{c.gen_layout}-{c.image_size}x{c.channels}"
                 f"-f{c.base_filters}-b{c.conv_blocks}")
def test_param_count_counts_the_built_trees(c):
    g, d = _trees(c)
    from repro.config.base import _dcgan_params
    assert _dcgan_params(c) == _total(g) + _total(d)


def test_mnist_g_tree_unchanged():
    c = get_config("dcgan-mnist").model.dcgan
    assert c.gen_layout == "mnist"
    g, _ = _trees(c)
    bn = lambda n: {"scale": (n,), "bias": (n,)}   # noqa: E731
    assert _shapes(g) == {
        "proj": {"w": (100, 12544), "b": (12544,), "bn": bn(256)},
        "deconv0": {"w": (5, 5, 256, 128), "b": (128,), "bn": bn(128)},
        "deconv1": {"w": (5, 5, 128, 64), "b": (64,), "bn": bn(64)},
        "out": {"w": (5, 5, 64, 1), "b": (1,)}}
    assert jax.tree.structure(gen_specs(c)) == jax.tree.structure(g)


def test_published_spec_tree_matches_params():
    g, _ = _trees(PUBLISHED)
    assert jax.tree.structure(gen_specs(PUBLISHED)) == jax.tree.structure(g)


def test_radford_layout_needs_a_power_of_two_side():
    with pytest.raises(ValueError, match="image_size"):
        DCGANConfig(image_size=28, gen_layout="radford")
    with pytest.raises(ValueError, match="gen_layout"):
        DCGANConfig(gen_layout="dcgan")


SMALL = [DCGANConfig(base_filters=4, conv_blocks=3),
         DCGANConfig(image_size=64, channels=3, base_filters=4,
                     conv_blocks=4, gen_layout="radford")]


@pytest.mark.parametrize("c", SMALL, ids=lambda c: c.gen_layout)
def test_layer_scopes_in_compiled_programs(c):
    """The generator's program and the D and G steps carry every layer's
    scope in their op names."""
    g, d = _trees(c)
    z = jax.ShapeDtypeStruct((2, c.latent_dim), jnp.float32)
    img = jax.ShapeDtypeStruct((2, c.image_size, c.image_size, c.channels),
                               jnp.float32)
    gen_hlo = jax.jit(functools.partial(gen_apply, c=c)).lower(
        g, z).compile().as_text()
    d_hlo = jax.jit(jax.grad(functools.partial(d_loss_fn, c=c))).lower(
        d, img, img).compile().as_text()
    g_hlo = jax.jit(jax.grad(functools.partial(g_loss_fn, c=c))).lower(
        g, d, z).compile().as_text()
    gen_scopes = [f"gen.{k}" for k in g]
    disc_scopes = [f"disc.{k}" for k in d]
    assert all(s in gen_hlo for s in gen_scopes), gen_hlo
    assert all(s in d_hlo for s in disc_scopes)
    assert all(s in g_hlo for s in gen_scopes + disc_scopes)


@pytest.mark.parametrize("c", SMALL, ids=lambda c: c.gen_layout)
def test_layer_scopes_change_no_number(c, monkeypatch):
    key = jax.random.PRNGKey(1)
    g, d = jax.jit(lambda k: (gen_init(k, c), disc_init(k, c)))(key)
    z = jax.random.normal(key, (4, c.latent_dim))

    def run():
        fake = jax.jit(functools.partial(gen_apply, c=c))(g, z)
        grads = jax.jit(jax.grad(functools.partial(d_loss_fn, c=c)))(
            d, fake * 0.5, fake)
        return fake, grads
    scoped = run()
    monkeypatch.setattr(dcgan, "_scope",
                        lambda *_: contextlib.nullcontext())
    bare = run()
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name, size, channels", [
    ("dcgan-mnist", 28, 1), ("dcgan64", 16, 3)])
def test_real_batches_are_the_drawn_rows(name, size, channels):
    """A real batch is the rows the host RNG drew, gathered on the device
    in the images' shape; a client array put in place is drawn from."""
    from repro.core.gan import FSLGANTrainer
    cfg = get_config(name).override(
        {"shape.global_batch": 8, "fsl.num_clients": 2,
         "model.dcgan.base_filters": 4, "model.dcgan.image_size": size,
         "model.dcgan.conv_blocks": 2})
    rng = np.random.default_rng(0)
    data = {f"c{i}": rng.standard_normal(
        (30 + i, size, size, channels)).astype(np.float32) for i in range(2)}
    tr = FSLGANTrainer(cfg, data, seed=5)
    for cid in ("c0", "c1", "c0"):
        host = copy.deepcopy(tr._rng)
        real = tr._sample_real(cid, 8)
        idx = host.integers(0, len(data[cid]), 8)
        np.testing.assert_array_equal(np.asarray(real), data[cid][idx])
    tr.client_data["c1"] = data["c1"][:10] * 2.0
    host = copy.deepcopy(tr._rng)
    real = tr._sample_real("c1", 8)
    np.testing.assert_array_equal(
        np.asarray(real), tr.client_data["c1"][host.integers(0, 10, 8)])
