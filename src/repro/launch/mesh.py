"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``--xla_force_host_platform_device_count`` *before* first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = 256 v5e chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # jax.make_mesh defaults to Explicit axes; the sharding rules
    # (sharding/specs) place arrays with with_sharding_constraint, which
    # only accepts Auto axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Whatever this host has (1 CPU device in the container) — used by
    smoke tests and examples."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_client_mesh(max_devices: int = 0) -> Mesh:
    """1-D mesh over this host's devices with the single axis ``clients``
    — what the federation runtime shards the vectorized program's stacked
    client axis along (sharding/specs.stacked_shardings).  On CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before first
    jax init to get N > 1.  ``max_devices`` > 0 caps the mesh size."""
    import numpy as np
    devs = jax.devices()
    n = len(devs) if max_devices <= 0 else min(len(devs), int(max_devices))
    return Mesh(np.array(devs[:n]), ("clients",))


def mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
