"""Logical-axis sharding rules.

Parameters and activations are annotated with *logical* axis names; a single
rules table maps them to mesh axes. Mapping is skipped (replicated) whenever
the dimension size does not divide the mesh-axis extent — GSPMD then
propagates a layout instead of failing to shard.

Logical axes
------------
  embed    d_model dim                -> FSDP over ("pod","data") when enabled
  mlp      ffn hidden / fused q_dim   -> tensor-parallel over "model"
  kv       fused kv_dim               -> "model" when divisible
  experts  MoE expert dim             -> expert-parallel over "model"
  vocab    vocabulary dim             -> "model"
  batch    global batch               -> ("pod","data")
  seq      sequence (activations)     -> "model" when sequence_parallel
  layers/stack/conv/...               -> replicated
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

LOGICAL = ("embed", "mlp", "kv", "experts", "vocab", "batch", "seq",
           "heads", "state", "layers", "window", "clients", None)


@dataclass
class AxisRules:
    """Map from logical axis name -> mesh axis (or tuple of axes)."""
    rules: Dict[str, MeshAxes] = field(default_factory=dict)
    fsdp: bool = True
    tensor_parallel: bool = True
    sequence_parallel: bool = True

    def mesh_axes_for(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        return self.rules.get(logical)


def default_rules(mesh: Mesh, parallel=None) -> AxisRules:
    """Production layout: batch/FSDP over (pod,data), TP/EP over model."""
    axes = list(mesh.axis_names)
    data_axes: Tuple[str, ...] = tuple(a for a in ("pod", "data") if a in axes)
    model = "model" if "model" in axes else None
    fsdp = parallel.fsdp if parallel is not None else True
    tp = parallel.tensor_parallel if parallel is not None else True
    sp = parallel.sequence_parallel if parallel is not None else True
    rules: Dict[str, MeshAxes] = {
        "batch": data_axes or None,
        "embed": data_axes if fsdp else None,
        "mlp": model if tp else None,
        "kv": model if tp else None,
        "heads": model if tp else None,
        "experts": model if tp else None,
        "vocab": model if tp else None,
        "seq": model if sp else None,
        "state": None,
        "layers": None,
        "window": None,
    }
    return AxisRules(rules=rules, fsdp=fsdp, tensor_parallel=tp,
                     sequence_parallel=sp)


def mesh_axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def logical_spec(mesh: Mesh, rules: AxisRules, shape: Sequence[int],
                 logical: Sequence[Optional[str]]) -> P:
    """Build a PartitionSpec, dropping any axis that doesn't divide evenly."""
    assert len(shape) == len(logical), (shape, logical)
    out = []
    used: set = set()
    for dim, name in zip(shape, logical):
        ax = rules.mesh_axes_for(name)
        if ax is None:
            out.append(None)
            continue
        ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
        ax_t = tuple(a for a in ax_t if a not in used)
        if not ax_t or dim % mesh_axis_size(mesh, ax_t) != 0:
            out.append(None)
            continue
        used.update(ax_t)
        out.append(ax_t[0] if len(ax_t) == 1 else ax_t)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def spec_for_param(mesh: Mesh, rules: AxisRules, arr_or_shape,
                   logical: Sequence[Optional[str]]) -> NamedSharding:
    shape = getattr(arr_or_shape, "shape", arr_or_shape)
    return NamedSharding(mesh, logical_spec(mesh, rules, shape, logical))


# ---------------------------------------------------------------------------
# Activation-sharding policy hook
#
# Model code is mesh-agnostic; the runtime installs a policy that maps
# logical activation axes to with_sharding_constraint calls. Without a
# policy, constrain() is the identity and GSPMD propagates layouts freely.
# ---------------------------------------------------------------------------

_ACTIVATION_POLICY = None


def set_activation_policy(fn) -> None:
    """fn(x, logical_axes: tuple) -> x, or None to clear."""
    global _ACTIVATION_POLICY
    _ACTIVATION_POLICY = fn


def constrain(x, logical_axes):
    if _ACTIVATION_POLICY is None:
        return x
    return _ACTIVATION_POLICY(x, logical_axes)


def make_activation_policy(mesh: Mesh, rules: "AxisRules"):
    def policy(x, logical_axes):
        spec = logical_spec(mesh, rules, x.shape, logical_axes)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return policy


class Lg(tuple):
    """A tuple of logical axis names used as a *leaf* in spec trees."""
    def __new__(cls, *names):
        return super().__new__(cls, names)


def is_lg(x) -> bool:
    return isinstance(x, Lg)


def tree_shardings(mesh: Mesh, rules: AxisRules, params_tree, logical_tree):
    """Zip a tree of arrays/ShapeDtypeStructs with a matching tree of Lg leaves."""
    flat_p, tdef_p = jax.tree.flatten(params_tree)
    flat_l, tdef_l = jax.tree.flatten(logical_tree, is_leaf=is_lg)
    if tdef_p != jax.tree.structure(jax.tree.unflatten(tdef_l, flat_l)):
        # Structures must match one-to-one; a mismatch is a modelling bug.
        raise ValueError(
            f"param/spec tree mismatch:\n  params: {tdef_p}\n  specs:  {tdef_l}")
    shardings = [spec_for_param(mesh, rules, p, l) for p, l in zip(flat_p, flat_l)]
    return jax.tree.unflatten(tdef_p, shardings)


# ---------------------------------------------------------------------------
# Stacked client-axis sharding (federation runtime)
#
# The vectorized client program (fed/programs.LocalProgram.run_vectorized)
# stacks every per-client tree/batch along a leading `clients` axis.  These
# helpers place those stacked trees on a 1-D `clients` mesh
# (launch/mesh.make_client_mesh): dim 0 is the `clients` logical axis, all
# other dims replicate, and — per logical_spec's policy — a client count
# that does not divide the mesh replicates instead of failing.
# ---------------------------------------------------------------------------

def client_axis_rules(mesh: Mesh, axis: str = "clients") -> AxisRules:
    """Rules mapping the `clients` logical axis onto ``axis`` of ``mesh``
    (replicated when the mesh has no such axis)."""
    ax = axis if axis in mesh.axis_names else None
    return AxisRules(rules={"clients": ax})


def stacked_shardings(mesh: Mesh, tree, *, axis: str = "clients",
                      rules: Optional[AxisRules] = None):
    """NamedShardings for a stacked per-client tree: every leaf's leading
    dim is the `clients` logical axis, the rest replicate.  Works for
    parameter/optimizer stacks and (C, T, B, ...) batch arrays alike."""
    rules = client_axis_rules(mesh, axis) if rules is None else rules
    logical = jax.tree.map(
        lambda l: Lg(*(("clients",) + (None,) * (l.ndim - 1))), tree)
    return tree_shardings(mesh, rules, tree, logical)


def psum_client_rows(kernel, mesh: Mesh, *rows):
    """``kernel(*rows)`` for a reduce over the leading client axis of
    ``rows``, on a mesh with a ``clients`` axis.  XLA cannot partition a
    Mosaic kernel, so each device runs ``kernel`` on its own rows and one
    ``psum`` adds the partial sums.  Rows are first zero-padded to a
    multiple of the axis size: a zero-weight client adds nothing."""
    pad = (-rows[0].shape[0]) % mesh.shape["clients"]
    if pad:
        rows = [jnp.pad(r, [(0, pad)] + [(0, 0)] * (r.ndim - 1))
                for r in rows]
    return jax.shard_map(
        lambda *r: jax.lax.psum(kernel(*r), "clients"), mesh=mesh,
        in_specs=(P("clients"),) * len(rows), out_specs=P(),
        check_vma=False)(*rows)
