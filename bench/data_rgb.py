"""Seeded 64x64x3 image data and its federated split, for the dcgan64
family.

``rgb_blobs`` is the benchmark's own generator, so that a change to the
program cannot move the yardstick: each of 10 classes is a fixed mixture
of 3 coloured gaussian strokes on a tinted background; each sample is its
class's image shifted (by up to size/16 pixels each way, wrapping),
scaled in intensity by 0.8-1.2 and noised (deviation 0.05).  The split is
``bench.data.partition_dirichlet``.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import partition_dirichlet


def class_prototype(label: int, size: int) -> np.ndarray:
    """(size, size, 3) in [0, 1]: 3 gaussian strokes of their own colours
    over a dim background of the class's tint."""
    rng = np.random.default_rng(4321 + label)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    img = np.broadcast_to(rng.uniform(0.0, 0.3, 3).astype(np.float32),
                          (size, size, 3)).copy()
    for _ in range(3):
        cx, cy = rng.uniform(0.25, 0.75, 2)
        sx, sy = rng.uniform(0.05, 0.18, 2)
        rot = rng.uniform(0, np.pi)
        colour = rng.uniform(0.2, 1.0, 3).astype(np.float32)
        dx, dy = xx - cx, yy - cy
        rx = dx * np.cos(rot) + dy * np.sin(rot)
        ry = -dx * np.sin(rot) + dy * np.cos(rot)
        stroke = np.exp(-(rx ** 2 / (2 * sx ** 2) + ry ** 2 / (2 * sy ** 2)))
        img += stroke[..., None] * colour
    return img / img.max()


def rgb_blobs(n: int, seed: int, size: int = 64
              ) -> Tuple[np.ndarray, np.ndarray]:
    """-> images (n, size, size, 3) float32 in [-1, 1], labels (n,) int32.
    Drawn on the default device in chunks of 8,192 images."""
    labels = np.random.default_rng(seed).integers(0, 10, n).astype(np.int32)
    protos = np.stack([class_prototype(l, size) for l in range(10)])
    key = jax.random.PRNGKey(seed)
    chunk = min(n, 8192)
    imgs = np.empty((n, size, size, 3), np.float32)
    for a in range(0, n, chunk):
        lab = np.zeros(chunk, np.int32)
        lab[:n - a] = labels[a:a + chunk]          # the last chunk padded
        x = _chunk(jax.random.fold_in(key, a), protos, lab,
                   max(size // 16, 1))
        imgs[a:a + chunk] = np.asarray(x)[:n - a]
    return imgs, labels


@functools.partial(jax.jit, static_argnums=3)
def _chunk(key, protos, labels, jitter: int):
    ks, ka, kn = jax.random.split(key, 3)
    n = labels.shape[0]
    shift = jax.random.randint(ks, (n, 2), -jitter, jitter + 1)
    amp = jax.random.uniform(ka, (n, 1, 1, 1), minval=0.8, maxval=1.2)
    x = jax.vmap(lambda l, s: jnp.roll(protos[l], (s[0], s[1]), (0, 1)))(
        labels, shift)
    x = x * amp + 0.05 * jax.random.normal(kn, x.shape)
    return jnp.clip(x, 0, 1) * 2.0 - 1.0


GENERATORS = {"rgb_blobs": rgb_blobs}


def client_data(spec: Dict, num_clients: int, seed: int,
                image_size: int, channels: int) -> Dict[str, np.ndarray]:
    """The configuration's data set, split over its clients."""
    if channels != 3:
        raise ValueError(f"{spec['generator']} draws 3 channels, not "
                         f"{channels}")
    gen = GENERATORS[spec["generator"]]
    imgs, labels = gen(int(spec["images"]), seed, size=image_size)
    return partition_dirichlet(imgs, labels, num_clients,
                               float(spec["dirichlet_alpha"]), seed)
