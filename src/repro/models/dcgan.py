"""DCGAN (Radford et al. 2016): a discriminator of ``conv_blocks`` 5x5
stride-2 conv blocks and a transposed-conv generator, at any image size and
channel count.

The generator has two layouts, picked by ``DCGANConfig.gen_layout``:

  * ``"mnist"``, the FSL-GAN paper's 28x28x1 G: the latent projected to
    (image_size/4)^2 x 4f, two 5x5 stride-2 transposed convs to 2f and f,
    then a 5x5 stride-1 conv to the image's channels;
  * ``"radford"``, DCGAN's published G (arXiv:1511.06434, Fig. 1): the
    latent projected to 4x4 x f*2^(n-1), then n = log2(image_size/4) 5x5
    stride-2 transposed convs halving the channels, the last to the
    image's channels.  At 64x64x3 and f = 128 that is 4x4x1024 -> 8x8x512
    -> 16x16x256 -> 32x32x128 -> 64x64x3.

Batch norm and ReLU follow every G layer but the last, which ends in tanh.

The discriminator is the part the paper federates and splits; it is
deliberately expressed as an ordered list of *named layers* so the FSL split
planner (core/split.py) can cost and partition it exactly the way the paper
partitions "portions" across a client's devices.

Each G stage and each D block runs under a ``jax.named_scope``,
``gen.<layer>`` or ``disc.<layer>`` with ``<layer>`` the key of its
parameters, so the compiled programs' op names say which layer an op
belongs to.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.config import DCGANConfig
from repro.sharding.specs import Lg

DN = ("NHWC", "HWIO", "NHWC")    # conv dimension numbers


def _conv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    fan = kh * kw * cin
    return {"w": (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
                  * (2.0 / fan) ** 0.5 * 0.7).astype(dtype),
            "b": jnp.zeros((cout,), dtype)}


def _bn_init(c, dtype=jnp.float32):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def _scope(model: str, layer: str):
    """The named scope of one layer: ``"<model>.<layer>"``, ``model``
    ``"gen"`` or ``"disc"`` and ``layer`` the key of its parameters."""
    return jax.named_scope(f"{model}.{layer}")


def _bn_apply(p, x, eps=1e-5):
    # batch norm over (N,H,W); GAN training uses per-batch statistics
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y * p["scale"] + p["bias"]


# ---------------------------------------------------------------------------
# Discriminator — an ordered stack of named layers (splittable)
# ---------------------------------------------------------------------------

def disc_layer_names(c: DCGANConfig) -> List[str]:
    names = []
    for i in range(c.conv_blocks):
        names.append(f"conv{i}")
    names.append("classifier")
    return names


def disc_layer_costs(c: DCGANConfig, image_size: int = 0) -> Dict[str, float]:
    """Relative FLOP cost per layer (drives the split planner)."""
    s = image_size or c.image_size
    f = c.base_filters
    costs = {}
    cin, sz = c.channels, s
    for i in range(c.conv_blocks):
        cout = f * (2 ** i)
        costs[f"conv{i}"] = 25.0 * cin * cout * (sz / 2) ** 2
        cin, sz = cout, sz / 2
    costs["classifier"] = cin * sz * sz * 1.0
    return costs


def disc_init(key, c: DCGANConfig, dtype=jnp.float32) -> Dict[str, Any]:
    ks = jax.random.split(key, c.conv_blocks + 1)
    p: Dict[str, Any] = {}
    cin = c.channels
    for i in range(c.conv_blocks):
        cout = c.base_filters * (2 ** i)
        p[f"conv{i}"] = _conv_init(ks[i], 5, 5, cin, cout, dtype)
        if i > 0:
            p[f"conv{i}"]["bn"] = _bn_init(cout, dtype)
        cin = cout
    # pad 28 -> strided convs give ceil: 28->14->7->4
    final_sz = -(-c.image_size // (2 ** c.conv_blocks))
    p["classifier"] = {
        "w": (jax.random.normal(ks[-1], (final_sz * final_sz * cin, 1),
                                jnp.float32)
              * (final_sz * final_sz * cin) ** -0.5).astype(dtype),
        "b": jnp.zeros((1,), dtype)}
    return p


def disc_specs(c: DCGANConfig) -> Dict[str, Any]:
    p: Dict[str, Any] = {}
    for i in range(c.conv_blocks):
        p[f"conv{i}"] = {"w": Lg(None, None, None, "mlp"), "b": Lg("mlp")}
        if i > 0:
            p[f"conv{i}"]["bn"] = {"scale": Lg("mlp"), "bias": Lg("mlp")}
    p["classifier"] = {"w": Lg("mlp", None), "b": Lg(None)}
    return p


def disc_apply_layer(name: str, p, x, c: DCGANConfig) -> jnp.ndarray:
    """Apply one named discriminator layer (the unit of an FSL portion)."""
    with _scope("disc", name):
        return _disc_layer(name, p, x)


def _disc_layer(name: str, p, x) -> jnp.ndarray:
    if name.startswith("conv"):
        lp = p[name]
        y = jax.lax.conv_general_dilated(
            x, lp["w"].astype(x.dtype), window_strides=(2, 2),
            padding="SAME", dimension_numbers=DN)
        y = y + lp["b"].astype(y.dtype)
        if "bn" in lp:
            y = _bn_apply(lp["bn"], y)
        return jax.nn.leaky_relu(y, 0.2)
    if name == "classifier":
        lp = p["classifier"]
        flat = x.reshape(x.shape[0], -1)
        return flat @ lp["w"].astype(flat.dtype) + lp["b"].astype(flat.dtype)
    raise ValueError(name)


def disc_apply(p, images: jnp.ndarray, c: DCGANConfig) -> jnp.ndarray:
    """images: (B, H, W, C) in [-1, 1] -> logits (B, 1)."""
    x = images
    for name in disc_layer_names(c):
        x = disc_apply_layer(name, p, x, c)
    return x


# ---------------------------------------------------------------------------
# Generator — trained by the central server (never sees real data)
# ---------------------------------------------------------------------------

def _deconv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    """Kernel stored (H, W, Cin, Cout) for conv_transpose(transpose_kernel=False)."""
    fan = kh * kw * cin
    return {"w": (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
                  * (2.0 / fan) ** 0.5 * 0.7).astype(dtype),
            "b": jnp.zeros((cout,), dtype)}


def gen_init(key, c: DCGANConfig, dtype=jnp.float32) -> Dict[str, Any]:
    if c.gen_layout == "radford":
        return _radford_init(key, c, dtype)
    ks = jax.random.split(key, 4)
    f = c.base_filters
    s0 = c.image_size // 4            # 7 for 28x28
    return {
        "proj": {"w": (jax.random.normal(ks[0], (c.latent_dim, s0 * s0 * f * 4),
                                         jnp.float32)
                       * c.latent_dim ** -0.5).astype(dtype),
                 "b": jnp.zeros((s0 * s0 * f * 4,), dtype),
                 "bn": _bn_init(f * 4, dtype)},
        "deconv0": {**_deconv_init(ks[1], 5, 5, f * 4, f * 2, dtype),
                    "bn": _bn_init(f * 2, dtype)},
        "deconv1": {**_deconv_init(ks[2], 5, 5, f * 2, f, dtype),
                    "bn": _bn_init(f, dtype)},
        "out": _conv_init(ks[3], 5, 5, f, c.channels, dtype),
    }


def gen_specs(c: DCGANConfig) -> Dict[str, Any]:
    bn = {"scale": Lg(None), "bias": Lg(None)}
    if c.gen_layout == "radford":
        *mid, last = _radford_convs(c)
        return {"proj": {"w": Lg(None, "mlp"), "b": Lg("mlp"), "bn": bn},
                **{name: {"w": Lg(None, None, "mlp", None), "b": Lg(None),
                          "bn": bn} for name, _, _ in mid},
                last[0]: {"w": Lg(None, None, None, None), "b": Lg(None)}}
    return {
        "proj": {"w": Lg(None, "mlp"), "b": Lg("mlp"), "bn": bn},
        "deconv0": {"w": Lg(None, None, "mlp", None), "b": Lg(None), "bn": bn},
        "deconv1": {"w": Lg(None, None, "mlp", None), "b": Lg(None), "bn": bn},
        "out": {"w": Lg(None, None, None, None), "b": Lg(None)},
    }


def _deconv(x, lp, stride=2):
    y = jax.lax.conv_transpose(
        x, lp["w"].astype(x.dtype), strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + lp["b"].astype(y.dtype)


def gen_apply(p, z: jnp.ndarray, c: DCGANConfig) -> jnp.ndarray:
    """z: (B, latent) -> images (B, H, W, C) in (-1, 1)."""
    if c.gen_layout == "radford":
        return _radford_apply(p, z, c)
    f = c.base_filters
    s0 = c.image_size // 4
    b = z.shape[0]
    with _scope("gen", "proj"):
        x = z @ p["proj"]["w"].astype(z.dtype) + p["proj"]["b"].astype(z.dtype)
        x = x.reshape(b, s0, s0, f * 4)
        x = jax.nn.relu(_bn_apply(p["proj"]["bn"], x))
    for name in ("deconv0", "deconv1"):
        with _scope("gen", name):
            x = jax.nn.relu(_bn_apply(p[name]["bn"], _deconv(x, p[name])))
    with _scope("gen", "out"):
        x = jax.lax.conv_general_dilated(x, p["out"]["w"].astype(x.dtype),
                                         (1, 1), "SAME", dimension_numbers=DN)
        x = x + p["out"]["b"].astype(x.dtype)
        return jnp.tanh(x)


# DCGAN's published G: a 4x4 projection, then stride-2 transposed convs
# until the image's size.
RADFORD_SIDE = 4


def _radford_convs(c: DCGANConfig) -> List[Tuple[str, int, int]]:
    """(name, in channels, out channels) of each transposed conv."""
    n = (c.image_size // RADFORD_SIDE).bit_length() - 1
    widths = [c.base_filters * 2 ** (n - 1 - i) for i in range(n)]
    widths.append(c.channels)
    return [(f"deconv{i}", widths[i], widths[i + 1]) for i in range(n)]


def _radford_init(key, c: DCGANConfig, dtype) -> Dict[str, Any]:
    convs = _radford_convs(c)
    ks = jax.random.split(key, len(convs) + 1)
    c0 = convs[0][1]
    width = RADFORD_SIDE * RADFORD_SIDE * c0
    p: Dict[str, Any] = {
        "proj": {"w": (jax.random.normal(ks[0], (c.latent_dim, width),
                                         jnp.float32)
                       * c.latent_dim ** -0.5).astype(dtype),
                 "b": jnp.zeros((width,), dtype),
                 "bn": _bn_init(c0, dtype)}}
    for k, (name, cin, cout) in zip(ks[1:], convs):
        p[name] = _deconv_init(k, 5, 5, cin, cout, dtype)
        if name != convs[-1][0]:
            p[name]["bn"] = _bn_init(cout, dtype)
    return p


def _radford_apply(p, z: jnp.ndarray, c: DCGANConfig) -> jnp.ndarray:
    convs = _radford_convs(c)
    with _scope("gen", "proj"):
        x = z @ p["proj"]["w"].astype(z.dtype) + p["proj"]["b"].astype(z.dtype)
        x = x.reshape(z.shape[0], RADFORD_SIDE, RADFORD_SIDE, convs[0][1])
        x = jax.nn.relu(_bn_apply(p["proj"]["bn"], x))
    for name, _, _ in convs[:-1]:
        with _scope("gen", name):
            x = jax.nn.relu(_bn_apply(p[name]["bn"], _deconv(x, p[name])))
    name = convs[-1][0]
    with _scope("gen", name):
        return jnp.tanh(_deconv(x, p[name]))
