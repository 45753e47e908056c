"""Every engine Pallas kernel compiles for a TPU v5e at dcgan-mnist widths.

Nothing runs: each kernel is lowered and compiled for a described
``v5e:2x2`` topology with the TPU compiler installed alongside JAX, which
refuses what interpret mode accepts (misaligned blocks, scalar stores to
VMEM, float16 vectors, more VMEM than a kernel may scope).  Widths are
the paper's: the D of ``dcgan-mnist`` (5 clients, batch 256), its conv0
boundary tensor, one D leaf as small as a bias.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.agg_fuse.ops import (dequant_acc_flat, dequant_reduce_flat,
                                        scatter_acc_flat)
from repro.kernels.boundary_fuse.ops import fused_boundary_flat
from repro.kernels.dp_clip.ops import dp_clip_noise_flat
from repro.kernels.fedavg.ops import fedavg_flat
from repro.models.dcgan import disc_init


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def widths():
    cfg = get_config("dcgan-mnist")
    c = cfg.model.dcgan
    d = jax.eval_shape(lambda: disc_init(jax.random.PRNGKey(0), c))
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(d))
    side = -(-c.image_size // 2)
    return {"clients": cfg.fsl.num_clients, "batch": cfg.shape.global_batch,
            "params": n, "boundary": side * side * c.base_filters}


def _compile_native(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _case(name, w):
    """(fn, [(shape, dtype)]) for one kernel op at widths ``w``."""
    c, b, n, f = w["clients"], w["batch"], w["params"], w["boundary"]
    f32, i8, f16 = jnp.float32, jnp.int8, jnp.float16
    k = n // 100                                       # top-k 1%
    return {
        "fedavg": (lambda s, wt: fedavg_flat(s, wt),
                   [((c, n), f32), ((c,), f32)]),
        "dp_clip": (lambda g, nz: dp_clip_noise_flat(
            g, 1.0, 1.0, nz, use_kernel=True),
            [((b, 65536), f32), ((65536,), f32)]),
        "boundary_fuse_int8": (lambda x, nz: fused_boundary_flat(
            x, 1.0, 0.5, nz, codec="int8", use_kernel=True),
            [((b, f), f32), ((b, f), f32)]),
        "boundary_fuse_fp16": (lambda x, nz: fused_boundary_flat(
            x, 1.0, 0.5, nz, codec="fp16", use_kernel=True),
            [((b, f), f32), ((b, f), f32)]),
        # DP-SGD runs the split per example: the kernel under vmap
        "boundary_fuse_int8_per_example": (jax.vmap(
            lambda x, nz: fused_boundary_flat(
                x, 1.0, 0.5, nz, codec="int8", use_kernel=True)),
            [((b, 1, f), f32), ((b, 1, f), f32)]),
        "dequant_reduce_int8": (lambda x, s, wt: dequant_reduce_flat(
            x, s, wt, use_kernel=True),
            [((c, n), i8), ((c,), f32), ((c,), f32)]),
        "dequant_reduce_fp16": (lambda x, s, wt: dequant_reduce_flat(
            x, s, wt, use_kernel=True),
            [((c, n), f16), ((c,), f32), ((c,), f32)]),
        "dequant_acc_int8": (lambda a, x: dequant_acc_flat(
            a, x, 0.01, 0.2, use_kernel=True), [((n,), f32), ((n,), i8)]),
        "dequant_acc_fp16": (lambda a, x: dequant_acc_flat(
            a, x, 1.0, 0.2, use_kernel=True), [((n,), f32), ((n,), f16)]),
        # the stream reduce folds leaf by leaf: a bias is 64 elements
        "dequant_acc_int8_bias_leaf": (lambda a, x: dequant_acc_flat(
            a, x, 0.01, 0.2, use_kernel=True), [((64,), f32), ((64,), i8)]),
        "scatter_acc": (lambda a, v, i: scatter_acc_flat(
            a, v, i, 0.2, use_kernel=True),
            [((n,), f32), ((k,), f32), ((k,), jnp.int32)]),
    }[name]


KERNELS = ["fedavg", "dp_clip", "boundary_fuse_int8", "boundary_fuse_fp16",
           "boundary_fuse_int8_per_example", "dequant_reduce_int8",
           "dequant_reduce_fp16", "dequant_acc_int8", "dequant_acc_fp16",
           "dequant_acc_int8_bias_leaf", "scatter_acc"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip, widths):
    fn, shapes = _case(name, widths)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    _compile_native(fn, *args)


@pytest.mark.parametrize("op", ["fedavg", "dequant_reduce_int8",
                                "dequant_reduce_fp16"])
def test_client_reduce_compiles_on_clients_mesh(op, topo, widths):
    """The server reduces on a 4-chip `clients` mesh: XLA cannot partition
    a Mosaic kernel, so each op reduces each device's rows and adds the
    partial sums with one all-reduce."""
    mesh = Mesh(np.array(topo.devices), ("clients",))
    rows = NamedSharding(mesh, P("clients"))
    rep = NamedSharding(mesh, P())
    c, n = 4, widths["params"]
    vec = jax.ShapeDtypeStruct((c,), jnp.float32, sharding=rep)
    if op == "fedavg":
        stacked = jax.ShapeDtypeStruct((c, n), jnp.float32, sharding=rows)
        compiled = _compile_native(
            lambda x, wt: fedavg_flat(x, wt, mesh=mesh), stacked, vec)
    else:
        dtype = {"int8": jnp.int8, "fp16": jnp.float16}[op.split("_")[-1]]
        wires = jax.ShapeDtypeStruct((c, n), dtype, sharding=rows)
        compiled = _compile_native(
            lambda x, s, wt: dequant_reduce_flat(x, s, wt, use_kernel=True,
                                                 mesh=mesh), wires, vec, vec)
    assert "all-reduce" in compiled.as_text()
