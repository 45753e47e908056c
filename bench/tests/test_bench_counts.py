"""FLOP and byte counts from shapes, and the copied traffic generators."""
import json
import os

import numpy as np
import pytest

from bench import data, kernel_work
from bench.flops import dcgan

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))


# the same DCGAN code at 64x64x3 with 4 conv blocks: the FLOP count at a
# second shape, where the convs dominate
DCGAN64 = {"image_size": 64, "channels": 3, "latent_dim": 100,
           "base_filters": 64, "conv_blocks": 4}


def _model(name):
    if name == "dcgan64":
        return DCGAN64
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_dcgan_mnist_layer_counts_by_hand():
    d = _model("dcgan-mnist")
    # 5x5 convs: 2 * 25 * cin * cout * output pixels
    assert dict(dcgan.disc_layers(d)) == {
        "conv0": 2 * 25 * 1 * 64 * 14 * 14,
        "conv1": 2 * 25 * 64 * 128 * 7 * 7,
        "conv2": 2 * 25 * 128 * 256 * 4 * 4,
        "classifier": 2 * 4 * 4 * 256}
    # transposed convs on their input pixels (7x7, then 14x14)
    assert dict(dcgan.gen_layers(d)) == {
        "proj": 2 * 100 * 7 * 7 * 256,
        "deconv0": 2 * 25 * 256 * 128 * 7 * 7,
        "deconv1": 2 * 25 * 128 * 64 * 14 * 14,
        "out": 2 * 25 * 64 * 1 * 28 * 28}


@pytest.mark.parametrize("name,batches,d_step,fake,g_step,total", [
    # D forward 46.92 MFLOP, G forward 165.58 MFLOP per image
    ("dcgan-mnist", 24, 71_748_288_512, 42_388_684_800, 150_546_939_904,
     120 * 71_748_288_512 + 120 * 42_388_684_800 + 24 * 150_546_939_904),
    # D forward 324.42 MFLOP, G forward 891.29 MFLOP per image
    ("dcgan64", 12, 493_275_316_224, 228_170_137_600,
     847_257_796_608,
     60 * 493_275_316_224 + 60 * 228_170_137_600 + 12 * 847_257_796_608),
])
def test_round_flops_by_hand(name, batches, d_step, fake, g_step, total):
    d = _model(name)
    s = dcgan.step_flops(d, 256)
    assert (s["d_step"], s["fake_batch"], s["g_step"]) == (d_step, fake,
                                                           g_step)
    r = dcgan.round_flops(d, 256, 5, batches)
    assert r["total"] == total
    assert r["d_steps"] == 5 * batches * d_step


def test_round_flops_near_the_rough_estimates():
    # ~17.6 TFLOP a dcgan-mnist round and ~55 a dcgan64 round when every
    # pass counts 3x its forward; these counts leave out the work no pass
    # needs (D's weight gradients in the G step, the images' gradient),
    # so they sit a little lower
    m = dcgan.round_flops(_model("dcgan-mnist"), 256, 5, 24)["total"]
    c = dcgan.round_flops(_model("dcgan64"), 256, 5, 12)["total"]
    assert 0.95 * 17.6e12 < m < 17.6e12
    assert 0.95 * 55e12 < c < 55e12


def test_kernel_work_reads_each_input_once():
    b, n = 256, 12544
    bf = kernel_work.boundary_fuse(b, n, "int8")
    assert bf == {"flops": 12.0 * b * n, "bytes": 12.0 * b * n}
    assert kernel_work.boundary_fuse(b, n, "none")["bytes"] == 12.0 * b * n
    dp = kernel_work.dp_clip(256, 1_030_913)
    assert dp["bytes"] == 4.0 * 256 * 1_030_913 + 8.0 * 1_030_913
    assert dp["flops"] == 4.0 * 256 * 1_030_913 + 2.0 * 1_030_913
    acc = kernel_work.dequant_acc(1_030_913, "int8")
    assert acc == {"flops": 2.0 * 1_030_913, "bytes": 9.0 * 1_030_913}
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert kernel_work.least_seconds(acc, peaks) == pytest.approx(
        9.0 * 1_030_913 / 819e9)


def test_mnist_blobs_equals_the_programs_generator():
    from repro.data.synthetic import synthetic_mnist
    a, la = data.mnist_blobs(300, seed=7)
    b, lb = synthetic_mnist(300, seed=7)
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(a, b)


def test_partition_equals_the_programs():
    from repro.data.federated import partition_dirichlet
    imgs, labels = data.mnist_blobs(500, seed=3)
    a = data.partition_dirichlet(imgs, labels, 5, 0.5, seed=4)
    b = partition_dirichlet(imgs, labels, 5, alpha=0.5, seed=4)
    assert list(a) == list(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
