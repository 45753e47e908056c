"""Plain reference of the FSL-GAN round for a DCGAN, in jax.numpy.

It imports nothing of the program.  It follows the round as the paper
and the trainer define it:

  * every client starts the round from the global D and trains it on
    ``T`` batches: batch ``t`` is ``B`` real rows of the client's data
    and ``B`` fakes from the round's G on ``B`` latents; the D loss is
    BCE(D(real), 1) + BCE(D(fake), 0) with batch-norm on the batch;
    Adam(lr, beta1, beta2, eps) with bias correction, one optimizer
    state per client that persists across rounds;
  * the server averages the clients' D, weighted by their example counts;
  * the server then takes ``T`` G steps against the averaged D, each on
    ``B`` latents, loss BCE(D(G(z)), 1), with its own persistent Adam
    state.

The real rows and the latents are given: those the program drew.

Weights are made by :func:`init_params` from a key.  Convolutions and
matmuls run at ``precision`` ("highest" for the reference).  ``dtype``
other than float32 computes everything in that type: the control.
``half_batch`` leaves out the second half of every batch and takes the
mean over the rest: a planted fault.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DN = ("NHWC", "HWIO", "NHWC")


def _normal(key, shape, fan):
    return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan) ** 0.5 \
        * 0.7


def _bn_params(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def init_params(key, d: Dict) -> Dict[str, Any]:
    """{"G": ..., "D": ...} float32, in the trainer's parameter layout:
    normal(0, 0.7 * sqrt(2 / fan_in)) kernels, zero biases, unit
    batch-norm scales; the dense layers at 1 / sqrt(fan_in)."""
    kg, kd = jax.random.split(key)
    f, ch, lat = d["base_filters"], d["channels"], d["latent_dim"]
    s0 = d["image_size"] // 4
    kgs = jax.random.split(kg, 4)

    def conv(k, cin, cout):
        return {"w": _normal(k, (5, 5, cin, cout), 25 * cin),
                "b": jnp.zeros((cout,), jnp.float32)}

    g = {"proj": {"w": jax.random.normal(kgs[0], (lat, s0 * s0 * 4 * f))
                  * lat ** -0.5,
                  "b": jnp.zeros((s0 * s0 * 4 * f,), jnp.float32),
                  "bn": _bn_params(4 * f)},
         "deconv0": {**conv(kgs[1], 4 * f, 2 * f), "bn": _bn_params(2 * f)},
         "deconv1": {**conv(kgs[2], 2 * f, f), "bn": _bn_params(f)},
         "out": conv(kgs[3], f, ch)}
    kds = jax.random.split(kd, d["conv_blocks"] + 1)
    dp, cin = {}, ch
    for i in range(d["conv_blocks"]):
        cout = f * 2 ** i
        dp[f"conv{i}"] = conv(kds[i], cin, cout)
        if i > 0:
            dp[f"conv{i}"]["bn"] = _bn_params(cout)
        cin = cout
    side = -(-d["image_size"] // 2 ** d["conv_blocks"])
    fan = side * side * cin
    dp["classifier"] = {"w": jax.random.normal(kds[-1], (fan, 1)) * fan ** -0.5,
                        "b": jnp.zeros((1,), jnp.float32)}
    return {"G": g, "D": dp}


def same_pads(n: int, k: int, stride: int):
    """SAME padding of one spatial axis: the output is ceil(n / stride)."""
    total = max((-(-n // stride) - 1) * stride + k - n, 0)
    return (total // 2, total - total // 2)


def transpose_pads(k: int, stride: int):
    """Padding of the zero-dilated input that makes a SAME transposed
    convolution's output ``stride`` times its input."""
    total = k + stride - 2
    lo = k - 1 if stride > k - 1 else -(-total // 2)
    return (lo, total - lo)


def _conv(x, w, stride, pads, dilation, prec):
    return lax.conv_general_dilated(
        x, w, (stride, stride), pads, lhs_dilation=(dilation, dilation),
        dimension_numbers=DN, precision=prec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def conv(x, w, stride, pads, dilation, prec):
    """A convolution over the input zero-dilated by ``dilation`` and
    padded by ``pads``, with a stride.  Its weight gradient is the sum,
    for each tap of the kernel, of the input slice under that tap times
    the output's gradient: XLA's own weight gradient of a float32
    convolution at "highest" precision takes the TPU compiler longer
    than a run may last."""
    return _conv(x, w, stride, pads, dilation, prec)


def _conv_fwd(x, w, stride, pads, dilation, prec):
    return _conv(x, w, stride, pads, dilation, prec), (x, w)


def _conv_bwd(stride, pads, dilation, prec, res, dy):
    x, w = res
    dx = jax.vjp(lambda x_: _conv(x_, w, stride, pads, dilation, prec),
                 x)[1](dy)[0]
    xp = lax.pad(x, jnp.zeros((), x.dtype),
                 ((0, 0, 0), (*pads[0], dilation - 1),
                  (*pads[1], dilation - 1), (0, 0, 0)))
    kh, kw = w.shape[:2]
    span = ((dy.shape[1] - 1) * stride + 1, (dy.shape[2] - 1) * stride + 1)

    def tap(t):
        xs = lax.dynamic_slice(xp, (0, t // kw, t % kw, 0),
                               (xp.shape[0], *span, xp.shape[3]))
        return jnp.einsum("bhwc,bhwd->cd", xs[:, ::stride, ::stride],
                          dy, precision=prec)
    dw = lax.map(tap, jnp.arange(kh * kw)).reshape(w.shape)
    return dx, dw.astype(w.dtype)


conv.defvjp(_conv_fwd, _conv_bwd)


def _bn(p, x):
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2), keepdims=True)
    return (x - mu) * lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def disc(p, x, n_blocks: int, prec):
    for i in range(n_blocks):
        lp = p[f"conv{i}"]
        pads = (same_pads(x.shape[1], 5, 2), same_pads(x.shape[2], 5, 2))
        x = conv(x, lp["w"], 2, pads, 1, prec) + lp["b"]
        if "bn" in lp:
            x = _bn(lp["bn"], x)
        x = jnp.where(x >= 0, x, 0.2 * x)
    x = x.reshape(x.shape[0], -1)
    return jnp.dot(x, p["classifier"]["w"], precision=prec) \
        + p["classifier"]["b"]


def gen(p, z, d: Dict, prec):
    f, s0 = d["base_filters"], d["image_size"] // 4
    x = jnp.dot(z, p["proj"]["w"], precision=prec) + p["proj"]["b"]
    x = jax.nn.relu(_bn(p["proj"]["bn"], x.reshape(-1, s0, s0, 4 * f)))
    up = (transpose_pads(5, 2),) * 2
    for name in ("deconv0", "deconv1"):
        lp = p[name]
        x = conv(x, lp["w"], 1, up, 2, prec) + lp["b"]
        x = jax.nn.relu(_bn(lp["bn"], x))
    x = conv(x, p["out"]["w"], 1, ((2, 2), (2, 2)), 1, prec) + p["out"]["b"]
    return jnp.tanh(x)


def bce(logits, target):
    return jnp.mean(jnp.maximum(logits, 0) - logits * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": jnp.zeros((), jnp.int32)}


def adam(params, state, grads, hp):
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    b1, b2 = hp["beta1"], hp["beta2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    c1 = (1 - b1 ** tf).astype(jnp.float32)
    c2 = (1 - b2 ** tf).astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v: (p - hp["lr"] * (m / c1) / (jnp.sqrt(v / c2)
                                                  + hp["eps"])).astype(p.dtype),
        params, m, v)
    return new, {"m": m, "v": v, "t": t}


@functools.lru_cache(maxsize=None)
def _programs(dims: tuple, hp: tuple, dtype: str, half: bool, prec: str):
    d, hp = dict(dims), dict(hp)
    prec = lax.Precision.HIGHEST if prec == "highest" else None
    dt = jnp.dtype(dtype)
    nb = d["conv_blocks"]

    def cut(x):
        return x[: x.shape[0] // 2] if half else x

    def d_loss(dp, real, fake):
        return (bce(disc(dp, real, nb, prec), 1.0)
                + bce(disc(dp, fake, nb, prec), 0.0))

    def g_loss(gp, dp, z):
        return bce(disc(dp, gen(gp, z, d, prec), nb, prec), 1.0)

    @jax.jit
    def client_round(dp, opt, gp, reals, zs):
        def body(carry, xs):
            dp, opt = carry
            real, z = cut(xs[0].astype(dt)), cut(xs[1].astype(dt))
            loss, g = jax.value_and_grad(d_loss)(dp, real, gen(gp, z, d, prec))
            dp, opt = adam(dp, opt, g, hp)
            return (dp, opt), loss
        (dp, opt), losses = lax.scan(body, (dp, opt), (reals, zs))
        return dp, opt, losses

    @jax.jit
    def server_g(gp, opt, dp, zs):
        def body(carry, z):
            gp, opt = carry
            loss, g = jax.value_and_grad(g_loss)(gp, dp, cut(z.astype(dt)))
            gp, opt = adam(gp, opt, g, hp)
            return (gp, opt), loss
        (gp, opt), losses = lax.scan(body, (gp, opt), zs)
        return gp, opt, losses

    @jax.jit
    def average(trees, w):
        return jax.tree.map(
            lambda *ls: sum(wi * l for wi, l in zip(w, ls)).astype(dt), *trees)

    return client_round, server_g, average


def run_rounds(d: Dict, hp: Dict, init: Dict, counts: Dict[str, int],
               draws: List[Dict[str, Any]], *, dtype: str = "float32",
               half_batch: bool = False, precision: str = "highest"
               ) -> Dict[str, Any]:
    """One round from ``init`` for each entry of ``draws``:
    ``draws[r]["clients"][cid] = (reals (T, B, H, W, C), latents (T, B,
    L))`` and ``draws[r]["g"]`` the G steps' latents (T, B, L).  The
    clients' D are averaged in the order of ``counts``, weighted by it.
    Returns on the host: ``losses`` [(mean D loss, mean G loss)] per
    round, ``m1`` the first Adam moments after round 1 (``{"G": tree,
    "D": {cid: tree}}``) and ``params`` after the last round (``{"G":
    tree, "D": tree}``)."""
    dims = tuple(sorted((k, d[k]) for k in ("image_size", "channels",
                                            "latent_dim", "base_filters",
                                            "conv_blocks")))
    hps = tuple(sorted((k, float(hp[k])) for k in ("lr", "beta1", "beta2",
                                                   "eps")))
    client_round, server_g, average = _programs(dims, hps, dtype,
                                                half_batch, precision)
    dt = jnp.dtype(dtype)
    cast = functools.partial(jax.tree.map, lambda x: jnp.asarray(x, dt))
    gp, dp = cast(init["G"]), cast(init["D"])
    g_opt = adam_init(gp)
    d_opt = {cid: adam_init(dp) for cid in counts}
    n = np.array(list(counts.values()), np.float64)
    w = tuple(jnp.asarray(c / n.sum(), dt) for c in n)
    out: Dict[str, Any] = {"losses": []}
    for r, dr in enumerate(draws):
        trees: List[Any] = []
        d_losses = []
        for cid in counts:
            reals, zs = dr["clients"][cid]
            p, d_opt[cid], losses = client_round(
                dp, d_opt[cid], gp, jnp.asarray(reals), jnp.asarray(zs))
            trees.append(p)
            d_losses.append(losses)
        dp = average(trees, w)
        del trees
        gp, g_opt, g_losses = server_g(gp, g_opt, dp, jnp.asarray(dr["g"]))
        out["losses"].append(
            (float(np.mean(np.asarray(jnp.stack(d_losses), np.float64))),
             float(np.mean(np.asarray(g_losses, np.float64)))))
        if r == 0:
            out["m1"] = jax.device_get(
                {"G": g_opt["m"], "D": {c: o["m"] for c, o in d_opt.items()}})
    out["params"] = jax.device_get({"G": gp, "D": dp})
    return out
