"""Fused dequantize-and-accumulate kernels for compressed-domain FedAvg.

The server-side reduce used to be decode-then-average: every client's
wire payload was dequantized to a full fp32 tree, all of them staged,
then ``kernels/fedavg`` streamed the (C, N) stack.  That pays one full
fp32 materialization + one extra HBM round-trip per client, and server
memory grows linearly in the cohort.  These kernels fold the codec
decode INTO the weighted reduction so the server only ever holds wire
payloads and ONE fp32 accumulator:

  * ``dequant_reduce_kernel`` — batch form.  Grid ``(nb, C)`` with the
    n-block OUTER and the client sweep INNER (the last grid dim iterates
    fastest on TPU), so the fp32 output block stays in VMEM and
    accumulates ``w_c * s_c * x_c`` across all clients of one block.
    Each client reads at its WIRE dtype (int8 / fp16 / fp32) — for int8
    that's 4x less HBM traffic than reducing a dequantized stack.
  * ``dequant_acc_kernel`` — streaming form: one landed uplink folded
    into the running fp32 accumulator (``acc + w * s * x``), the O(1)
    server-memory path the engine uses as uplinks arrive.
  * ``scatter_acc_kernel`` — sparse top-k form: (values, flat indices)
    added into the dense accumulator per n-block via a broadcast-compare
    one-hot sum, which also sums COLLIDING indices correctly — the wire
    is never densified into a per-client tree.

The dense kernels lay a flat buffer out as zero-padded (rows, 128)
tiles: Mosaic takes a block only when its last two dims are multiples
of (8, 128) or the whole array, which a (1, N) row is not.  Per-client
coefficients of the batch form sit in SMEM; fp16 wires are widened from
their bits (``kernels/f16``).  The sparse form keeps (1, bn) rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.f16 import f16_bits_to_f32

LANES = 128


def _row_blocks(n: int, block_n: int):
    """Lay ``n`` elements out as (rows, 128): -> (rows per block, padded
    rows).  A block is either the whole array or ``block_n // 128`` rows,
    a multiple of 32 (the int8 tile height; fp16 bits and fp32 tile at 16
    and 8 rows)."""
    rows = -(-n // LANES)
    block_rows = block_n // LANES
    if block_rows % 32:
        raise ValueError(f"block_n={block_n} must be a multiple of 4096")
    if rows <= block_rows:
        return rows, rows
    return block_rows, rows + (-rows) % block_rows


def _to_rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(..., n) -> (..., rows, 128), zero-padded.  An fp16 wire passes as
    its int16 bit pattern: Mosaic cannot load float16 vectors."""
    if x.dtype == jnp.float16:
        x = jax.lax.bitcast_convert_type(x, jnp.int16)
    pad = rows * LANES - x.shape[-1]
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (rows, LANES))


def _dequant(x: jnp.ndarray, fp16: bool) -> jnp.ndarray:
    return f16_bits_to_f32(x) if fp16 else x.astype(jnp.float32)


def _make_dequant_reduce_kernel(fp16: bool):
    def kernel(x_ref, coef_ref, o_ref):
        i = pl.program_id(1)               # client index — INNER grid dim

        @pl.when(i == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        # the output block stays resident across the client sweep
        o_ref[...] += coef_ref[i] * _dequant(x_ref[...], fp16)

    return kernel


def dequant_reduce_kernel(wires: jnp.ndarray, coefs: jnp.ndarray, *,
                          block_n: int = 4096,
                          interpret: bool = False) -> jnp.ndarray:
    """wires: (C, N) at wire dtype; coefs: (C, 2) fp32 ``[weight, scale]``
    per client (weights already normalized).  -> (N,) fp32 weighted sum
    of the dequantized rows, computed without materializing them."""
    c, n = wires.shape
    rb, rows = _row_blocks(n, block_n)
    coef = (coefs[:, 0] * coefs[:, 1]).astype(jnp.float32)
    out = pl.pallas_call(
        _make_dequant_reduce_kernel(wires.dtype == jnp.float16),
        grid=(rows // rb, c),
        in_specs=[pl.BlockSpec((pl.squeezed, rb, LANES),
                               lambda j, i: (i, j, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((rb, LANES), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(_to_rows(wires, rows), coef)
    return out.reshape(-1)[:n]


def _make_dequant_acc_kernel(fp16: bool):
    def kernel(acc_ref, x_ref, scal_ref, o_ref):
        o_ref[...] = acc_ref[...] + scal_ref[0, 0] * scal_ref[0, 1] \
            * _dequant(x_ref[...], fp16)

    return kernel


def dequant_acc_kernel(acc: jnp.ndarray, wire: jnp.ndarray, scal: jnp.ndarray,
                       *, block_n: int = 4096,
                       interpret: bool = False) -> jnp.ndarray:
    """acc: (N,) fp32 running sum; wire: (N,) at wire dtype; scal: (1, 2)
    fp32 ``[weight, scale]``.  -> (N,) fp32 ``acc + w * s * wire``."""
    n = acc.shape[0]
    rb, rows = _row_blocks(n, block_n)
    spec = pl.BlockSpec((rb, LANES), lambda j: (j, 0))
    out = pl.pallas_call(
        _make_dequant_acc_kernel(wire.dtype == jnp.float16),
        grid=(rows // rb,),
        in_specs=[spec, spec, pl.BlockSpec((1, 2), lambda j: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(_to_rows(acc, rows), _to_rows(wire, rows), scal.astype(jnp.float32))
    return out.reshape(-1)[:n]


def _make_scatter_acc_kernel(k: int, block_n: int):
    def kernel(acc_ref, vals_ref, idx_ref, scal_ref, o_ref):
        j = pl.program_id(0)
        local = idx_ref[...] - j * block_n            # (K, 1)
        inr = jnp.logical_and(local >= 0, local < block_n)
        cols = jax.lax.broadcasted_iota(jnp.int32, (k, block_n), 1)
        sel = jnp.logical_and(local == cols, inr)     # (K, bn) one-hot rows
        # colliding indices each contribute a row, so the column sum adds
        # them — matching .at[idx].add() scatter semantics
        contrib = jnp.sum(jnp.where(sel, vals_ref[...], 0.0),
                          axis=0, keepdims=True)      # (1, bn)
        o_ref[...] = acc_ref[...] + scal_ref[0, 0] * contrib

    return kernel


def scatter_acc_kernel(acc: jnp.ndarray, vals: jnp.ndarray,
                       idx: jnp.ndarray, scal: jnp.ndarray, *,
                       block_n: int = 1024,
                       interpret: bool = False) -> jnp.ndarray:
    """acc: (N,) fp32; vals/idx: (K,) top-k values + flat indices; scal:
    (1, 2) fp32 ``[weight, unused]``.  -> (N,) fp32 with ``w * vals``
    scatter-added at ``idx`` (collisions sum), no densified wire."""
    n = acc.shape[0]
    k = vals.shape[0]
    block_n = min(block_n, max(n, 1))
    pad = (-n) % block_n
    a2 = acc.reshape(1, n)
    if pad:
        a2 = jnp.pad(a2, ((0, 0), (0, pad)))
    n_padded = n + pad
    out = pl.pallas_call(
        _make_scatter_acc_kernel(k, block_n),
        grid=(n_padded // block_n,),
        in_specs=[pl.BlockSpec((1, block_n), lambda j: (0, j)),
                  pl.BlockSpec((k, 1), lambda j: (0, 0)),
                  pl.BlockSpec((k, 1), lambda j: (0, 0)),
                  pl.BlockSpec((1, 2), lambda j: (0, 0))],
        out_specs=pl.BlockSpec((1, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n_padded), jnp.float32),
        interpret=interpret,
    )(a2, vals.astype(jnp.float32).reshape(k, 1),
      idx.astype(jnp.int32).reshape(k, 1), scal.astype(jnp.float32))[0]
    return out[:n] if pad else out
