"""Public fedavg op: pytree <-> flat glue around the Pallas kernel."""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.fedavg.kernel import fedavg_kernel
from repro.sharding.specs import psum_client_rows, stacked_shardings


@functools.partial(jax.jit, static_argnames=("interpret", "mesh"))
def fedavg_flat(stacked: jnp.ndarray, weights: jnp.ndarray,
                interpret: bool = False, mesh=None) -> jnp.ndarray:
    """stacked: (C, N) -> (N,). The kernel pads N to its tile internally.
    ``mesh``: a mesh with a ``clients`` axis the rows are sharded over
    (``sharding.specs.psum_client_rows``)."""
    w = weights / jnp.sum(weights)
    kernel = functools.partial(fedavg_kernel, interpret=interpret)
    if mesh is None:
        return kernel(stacked, w)
    return psum_client_rows(kernel, mesh, stacked, w)


def fedavg_trees(trees: Sequence, weights: Optional[Sequence[float]] = None,
                 interpret: bool = False, mesh=None):
    """Kernel-backed FedAvg over a list of identical-structure pytrees;
    with ``mesh``, each leaf's client stack is placed on its ``clients``
    axis first."""
    if weights is None:
        weights = [1.0] * len(trees)
    w = jnp.asarray(weights, jnp.float32)
    flats, treedef = zip(*[jax.tree.flatten(t) for t in trees])
    treedef = jax.tree.structure(trees[0])
    out_leaves = []
    for leaves in zip(*flats):
        shape, dtype = leaves[0].shape, leaves[0].dtype
        stacked = jnp.stack([l.reshape(-1).astype(jnp.float32)
                             for l in leaves])
        if mesh is not None:
            stacked = jax.device_put(stacked,
                                     stacked_shardings(mesh, stacked))
        avg = fedavg_flat(stacked, w, interpret=interpret, mesh=mesh)
        out_leaves.append(avg.reshape(shape).astype(dtype))
    return jax.tree.unflatten(treedef, out_leaves)
