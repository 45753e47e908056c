"""Public agg_fuse ops: jitted kernel/ref dispatch on flat buffers.

Mirrors ``kernels/boundary_fuse/ops.py``: each op takes flat arrays plus
``use_kernel``/``interpret`` statics and routes to the Pallas kernel or
the jnp reference — callers (``fed/aggregate.StreamingAggregator``, the
engine's batched reduce) never touch grids or block specs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.agg_fuse.kernel import (dequant_acc_kernel,
                                           dequant_reduce_kernel,
                                           scatter_acc_kernel)
from repro.kernels.agg_fuse.ref import (dequant_acc_ref, dequant_reduce_ref,
                                        scatter_acc_ref)
from repro.sharding.specs import psum_client_rows


@functools.partial(jax.jit,
                   static_argnames=("use_kernel", "interpret", "mesh"))
def dequant_reduce_flat(wires: jnp.ndarray, scales: jnp.ndarray,
                        weights: jnp.ndarray, *, use_kernel: bool = False,
                        interpret: bool = False, mesh=None) -> jnp.ndarray:
    """Batch reduce: (C, N) wire rows + per-client (C,) scales and fedavg
    weights -> (N,) fp32 weighted MEAN of the dequantized rows (weights
    normalized here, like ``fedavg_flat``).

    ``mesh``: a mesh with a ``clients`` axis the rows are sharded over
    (``sharding.specs.psum_client_rows``)."""
    w = (weights / jnp.sum(weights)).astype(jnp.float32)
    coefs = jnp.stack([w, scales.astype(jnp.float32)], axis=1)
    if not use_kernel:
        return dequant_reduce_ref(wires, coefs)
    kernel = functools.partial(dequant_reduce_kernel, interpret=interpret)
    if mesh is None:
        return kernel(wires, coefs)
    return psum_client_rows(kernel, mesh, wires, coefs)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"),
                   donate_argnames=("acc",))
def dequant_acc_flat(acc: jnp.ndarray, wire: jnp.ndarray, scale, weight, *,
                     use_kernel: bool = False,
                     interpret: bool = False) -> jnp.ndarray:
    """Streaming fold: (N,) fp32 accumulator + one (N,) wire at its wire
    dtype -> ``acc + weight * scale * dequant(wire)``.  UNnormalized —
    the aggregator divides by the weight sum at finalize."""
    if use_kernel:
        scal = jnp.stack([jnp.asarray(weight, jnp.float32).reshape(()),
                          jnp.asarray(scale, jnp.float32).reshape(())]
                         ).reshape(1, 2)
        return dequant_acc_kernel(acc, wire, scal, interpret=interpret)
    return dequant_acc_ref(acc, wire, weight, scale)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"),
                   donate_argnames=("acc",))
def scatter_acc_flat(acc: jnp.ndarray, vals: jnp.ndarray, idx: jnp.ndarray,
                     weight, *, use_kernel: bool = False,
                     interpret: bool = False) -> jnp.ndarray:
    """Sparse streaming fold: weighted top-k (vals, idx) scatter-added
    into the (N,) fp32 accumulator without densifying the wire."""
    if use_kernel:
        scal = jnp.stack([jnp.asarray(weight, jnp.float32).reshape(()),
                          jnp.zeros((), jnp.float32)]).reshape(1, 2)
        return scatter_acc_kernel(acc, vals, idx, scal, interpret=interpret)
    return scatter_acc_ref(acc, vals, idx, weight)
