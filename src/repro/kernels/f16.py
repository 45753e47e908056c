"""IEEE float16 conversions written in 32-bit integer and float ops.

Mosaic on TPU v5e cannot load, store or pack float16 vectors, so the
kernels never hold a float16 array: an fp16 wire enters a kernel as its
int16 bit pattern (a free bitcast outside the kernel) and is widened by
:func:`f16_bits_to_f32`, and the fp16 quantize/dequantize of a boundary
tensor is :func:`round_to_f16`.  Both equal XLA's converts bit for bit,
subnormals, infinities and signed zeros included; NaN stays NaN.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_F32_SIGN = -0x80000000                # 0x80000000 as int32
_F16_EXP_AT_F32 = 0x7C00 << 13         # f16 exponent field, moved to f32
_F16_MIN_NORMAL = 2.0 ** -14
_F16_OVERFLOW = 65536.0                # first value that rounds past max


def _as_f32(bits: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _as_i32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def f16_bits_to_f32(h: jnp.ndarray) -> jnp.ndarray:
    """int16 bit patterns of float16 values -> the float32 values."""
    h = h.astype(jnp.int32) & 0xFFFF
    em = (h & 0x7FFF) << 13
    exp = em & _F16_EXP_AT_F32
    em = em + ((127 - 15) << 23)
    em = jnp.where(exp == _F16_EXP_AT_F32, em + ((128 - 16) << 23), em)
    # zero and subnormals: renormalise through one exact float subtract
    sub = _as_i32(_as_f32(em + (1 << 23)) - _F16_MIN_NORMAL)
    em = jnp.where(exp == 0, sub, em)
    return _as_f32(em | ((h & 0x8000) << 16))


def round_to_f16(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> the float32 value of ``x.astype(float16)``
    (round to nearest even)."""
    bits = _as_i32(x)
    mag = bits & 0x7FFFFFFF
    # normal range: round the 23-bit mantissa to 10 bits
    lsb = jax.lax.shift_right_logical(mag, 13) & 1
    normal = (mag + 0xFFF + lsb) & ~0x1FFF
    normal = jnp.where(_as_f32(normal) >= _F16_OVERFLOW, 0x7F800000, normal)
    # subnormal range: the add rounds to a multiple of 2**-24
    a = _as_f32(mag)
    sub = _as_i32((a + 0.5) - 0.5)
    out = jnp.where(a < _F16_MIN_NORMAL, sub, normal)
    out = jnp.where(mag >= 0x7F800000, mag, out)            # inf, nan
    return _as_f32(out | (bits & _F32_SIGN))
