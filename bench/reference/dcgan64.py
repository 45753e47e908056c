"""Plain reference of the FSL-GAN round for DCGAN's published G, in
jax.numpy.

The round, the D, the loss, Adam and the convolution with its per-tap
weight gradient are those of ``bench.reference.dcgan``, imported.  This
module writes its own G, as DCGAN's Fig. 1 draws it (arXiv:1511.06434):
the latent projected to 4x4 x f*2^(n-1), batch norm and ReLU, then n =
log2(image_size / 4) 5x5 stride-2 transposed convolutions halving the
channels, the last to the image's channels with tanh and no batch norm.
Each transposed convolution is written as a plain convolution over the
input zero-dilated by 2 and padded so that the output is twice the input,
with the kernel as stored (not flipped).  The D's weights are made as
``bench.reference.dcgan.init_params`` makes them.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.reference import dcgan as base

SIDE = 4          # the projection's side


def widths(d: Dict) -> List[int]:
    """Channels of the projection and of each transposed conv's output."""
    n = (d["image_size"] // SIDE).bit_length() - 1
    return [d["base_filters"] * 2 ** (n - 1 - i) for i in range(n)] \
        + [d["channels"]]


def init_params(key, d: Dict) -> Dict[str, Any]:
    """{"G": ..., "D": ...} float32, in the trainer's parameter layout,
    drawn as ``bench.reference.dcgan.init_params`` draws its layers."""
    kg, _ = jax.random.split(key)
    w = widths(d)
    kgs = jax.random.split(kg, len(w))
    lat, width = d["latent_dim"], SIDE * SIDE * w[0]
    g = {"proj": {"w": jax.random.normal(kgs[0], (lat, width)) * lat ** -0.5,
                  "b": jnp.zeros((width,), jnp.float32),
                  "bn": base._bn_params(w[0])}}
    for i, (cin, cout) in enumerate(zip(w, w[1:])):
        g[f"deconv{i}"] = {
            "w": base._normal(kgs[i + 1], (5, 5, cin, cout), 25 * cin),
            "b": jnp.zeros((cout,), jnp.float32)}
        if i < len(w) - 2:
            g[f"deconv{i}"]["bn"] = base._bn_params(cout)
    return {"G": g, "D": base.init_params(key, d)["D"]}


def gen(p, z, d: Dict, prec):
    w = widths(d)
    x = jnp.dot(z, p["proj"]["w"], precision=prec) + p["proj"]["b"]
    x = jax.nn.relu(base._bn(p["proj"]["bn"],
                             x.reshape(-1, SIDE, SIDE, w[0])))
    up = (base.transpose_pads(5, 2),) * 2
    for i in range(len(w) - 1):
        lp = p[f"deconv{i}"]
        x = base.conv(x, lp["w"], 1, up, 2, prec) + lp["b"]
        x = jax.nn.relu(base._bn(lp["bn"], x)) if "bn" in lp else jnp.tanh(x)
    return x


@functools.lru_cache(maxsize=None)
def _programs(dims: tuple, hp: tuple, dtype: str, half: bool, prec: str):
    d, hp = dict(dims), dict(hp)
    prec = lax.Precision.HIGHEST if prec == "highest" else None
    dt = jnp.dtype(dtype)
    nb = d["conv_blocks"]

    def cut(x):
        return x[: x.shape[0] // 2] if half else x

    def d_loss(dp, real, fake):
        return (base.bce(base.disc(dp, real, nb, prec), 1.0)
                + base.bce(base.disc(dp, fake, nb, prec), 0.0))

    def g_loss(gp, dp, z):
        return base.bce(base.disc(dp, gen(gp, z, d, prec), nb, prec), 1.0)

    @jax.jit
    def client_round(dp, opt, gp, reals, zs):
        def body(carry, xs):
            dp, opt = carry
            real, z = cut(xs[0].astype(dt)), cut(xs[1].astype(dt))
            loss, g = jax.value_and_grad(d_loss)(dp, real, gen(gp, z, d, prec))
            dp, opt = base.adam(dp, opt, g, hp)
            return (dp, opt), loss
        (dp, opt), losses = lax.scan(body, (dp, opt), (reals, zs))
        return dp, opt, losses

    @jax.jit
    def server_g(gp, opt, dp, zs):
        def body(carry, z):
            gp, opt = carry
            loss, g = jax.value_and_grad(g_loss)(gp, dp, cut(z.astype(dt)))
            gp, opt = base.adam(gp, opt, g, hp)
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
    """As ``bench.reference.dcgan.run_rounds``, with this module's G."""
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
    g_opt = base.adam_init(gp)
    d_opt = {cid: base.adam_init(dp) for cid in counts}
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
