"""The dcgan64 family's FLOP count at the published widths, by hand, and
its data generator."""
import numpy as np
import pytest

from bench.data_rgb import rgb_blobs
from bench.flops import dcgan64 as flops

PUBLISHED = {"image_size": 64, "channels": 3, "latent_dim": 100,
             "base_filters": 128, "conv_blocks": 4}


def test_round_flops_at_the_published_widths():
    mac = 2.0 * 25                       # FLOPs of a 5x5 tap pair
    # D forward per image: 5x5 stride-2 convs to 32, 16, 8, 4 pixels a side
    d_conv0 = mac * 3 * 128 * 32 * 32
    d_fwd = (d_conv0 + mac * 128 * 256 * 16 * 16 + mac * 256 * 512 * 8 * 8
             + mac * 512 * 1024 * 4 * 4 + 2.0 * 4 * 4 * 1024)
    # G forward per latent: the projection, then transposed convs counted
    # on their 4, 8, 16, 32 input pixels a side
    g_proj = 2.0 * 100 * 4 * 4 * 1024
    g_fwd = (g_proj + mac * 1024 * 512 * 4 * 4 + mac * 512 * 256 * 8 * 8
             + mac * 256 * 128 * 16 * 16 + mac * 128 * 3 * 32 * 32)
    assert d_fwd == pytest.approx(1.278e9, rel=1e-3)
    assert g_fwd == pytest.approx(1.281e9, rel=1e-3)
    d_step = 2 * 128 * (3 * d_fwd - d_conv0)        # real + fake batch
    fake_batch = 128 * g_fwd
    g_step = 128 * (3 * g_fwd - g_proj + 2 * d_fwd)
    r = flops.round_flops(PUBLISHED, 128, 5, 12)
    assert r["d_steps"] == pytest.approx(60 * d_step, rel=1e-12)
    assert r["fake_batches"] == pytest.approx(60 * fake_batch, rel=1e-12)
    assert r["g_steps"] == pytest.approx(12 * g_step, rel=1e-12)
    assert r["total"] / 1e12 == pytest.approx(78.25, abs=0.005)
    assert r["d_steps"] / r["total"] == pytest.approx(0.75, abs=0.005)


def test_gen_layers_follow_the_image_size():
    names = [n for n, _ in flops.gen_layers({**PUBLISHED, "image_size": 32})]
    assert names == ["proj", "deconv0", "deconv1", "deconv2"]


def test_data_deterministic_ranged_and_classed():
    a, la = rgb_blobs(600, 3)
    b, lb = rgb_blobs(600, 3)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(la, lb)
    assert a.shape == (600, 64, 64, 3) and a.dtype == np.float32
    assert a.min() >= -1.0 and a.max() <= 1.0
    assert set(np.unique(la)) == set(range(10))
    means = np.stack([a[la == l].mean(0) for l in range(10)])
    dist = np.linalg.norm((means[:, None] - means[None]).reshape(100, -1),
                          axis=1)
    assert (dist[~np.eye(10, dtype=bool).reshape(-1)] > 5.0).all()
