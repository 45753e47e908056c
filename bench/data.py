"""Seeded image data and its federated split: the traffic generator.

``mnist_blobs`` is the program's ``data.synthetic_mnist`` copied into the
benchmark (with the per-sample roll vectorised; the output is the same
bit for bit), so that a change to the program cannot move the yardstick.
``partition_dirichlet`` is the program's label-skewed split, copied
likewise.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def class_prototype(label: int, size: int) -> np.ndarray:
    """A fixed mixture of 3 gaussian strokes per class, peak 1."""
    rng = np.random.default_rng(1234 + label)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    img = np.zeros((size, size), np.float32)
    for _ in range(3):
        cx, cy = rng.uniform(0.25, 0.75, 2)
        sx, sy = rng.uniform(0.05, 0.18, 2)
        rot = rng.uniform(0, np.pi)
        dx, dy = xx - cx, yy - cy
        rx = dx * np.cos(rot) + dy * np.sin(rot)
        ry = -dx * np.sin(rot) + dy * np.cos(rot)
        img += np.exp(-(rx ** 2 / (2 * sx ** 2) + ry ** 2 / (2 * sy ** 2)))
    return img / img.max()


def mnist_blobs(n: int, seed: int, size: int = 28
                ) -> Tuple[np.ndarray, np.ndarray]:
    """-> images (n, size, size, 1) float32 in [-1, 1], labels (n,) int32."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, n).astype(np.int32)
    imgs = np.empty((n, size, size, 1), np.float32)
    ar = np.arange(size)
    for lbl in range(10):
        sel = labels == lbl
        k = int(sel.sum())
        if k == 0:
            continue
        base = class_prototype(lbl, size)
        # per-sample jitter: shift + intensity + noise
        shift = rng.integers(-2, 3, (k, 2))
        amp = rng.uniform(0.8, 1.2, (k, 1, 1)).astype(np.float32)
        noise = rng.normal(0, 0.05, (k, size, size)).astype(np.float32)
        rows = (ar[None, :] - shift[:, :1]) % size
        cols = (ar[None, :] - shift[:, 1:]) % size
        batch = base[rows[:, :, None], cols[:, None, :]]
        batch = np.clip(batch * amp + noise, 0, 1)
        imgs[sel, :, :, 0] = batch * 2.0 - 1.0
    return imgs, labels


GENERATORS = {"mnist_blobs": mnist_blobs}


def partition_dirichlet(data: np.ndarray, labels: np.ndarray,
                        num_clients: int, alpha: float, seed: int
                        ) -> Dict[str, np.ndarray]:
    """Label-skewed split: client k's label distribution ~ Dir(alpha)."""
    rng = np.random.default_rng(seed)
    client_idx = [[] for _ in range(num_clients)]
    for l in np.unique(labels):
        idx = rng.permutation(np.where(labels == l)[0])
        props = rng.dirichlet([alpha] * num_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for k, chunk in enumerate(np.split(idx, cuts)):
            client_idx[k].extend(chunk.tolist())
    out = {}
    for k in range(num_clients):
        sel = np.asarray(sorted(client_idx[k]), int)
        if len(sel) == 0:                 # guarantee non-empty clients
            sel = np.asarray([int(rng.integers(0, len(data)))])
        out[f"c{k}"] = data[sel]
    return out


def client_data(spec: Dict, num_clients: int, seed: int,
                image_size: int, channels: int) -> Dict[str, np.ndarray]:
    """The configuration's data set, split over its clients."""
    if channels != 1:
        raise ValueError(f"{spec['generator']} draws 1 channel, not {channels}")
    gen = GENERATORS[spec["generator"]]
    imgs, labels = gen(int(spec["images"]), seed, size=image_size)
    return partition_dirichlet(imgs, labels, num_clients,
                               float(spec["dirichlet_alpha"]), seed)
