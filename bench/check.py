"""The comparison that decides ``correct`` for a training cell.

The program and the plain reference run the same first rounds from the
same weights, on the same draws.  Three numbers are compared, each
against a limit of the cell's own:

  * ``loss_gap``: the larger relative gap of round 1's mean D loss and
    mean G loss.  Later rounds' losses are printed, not compared: two
    sound programs that round differently drift apart there, since GAN
    training amplifies a difference in the last bits round by round
    (on a TPU v5e the gap grows from 1e-3 in round 1 to 5e-2 in round 3
    on some seeds), and the bfloat16 control's does not stand apart from
    that;
  * ``grad_gap``: the first Adam moment after round 1 (the gradient as
    the optimizer got it), of G and of each client's D, by the worst
    leaf: the gap between the program's norm of the leaf and the
    reference's, over the larger of the reference's norm of that leaf
    and of the tree's median leaf;
  * ``change_gap``: the norm of each leaf's change from the initial
    weights after the last compared round, of G and of the global D, by
    the worst leaf in the same way.

Leaves whose gradient is nought to rounding in the reference, such as
the bias of a convolution that feeds a batch norm, move under Adam by
round-off alone.  They are left out by a rule on the reference's first
moment: under a thousandth of the median leaf's.

The reference trains on the real rows and latents that the program drew
in those rounds, recorded as it drew them, so that the order in which
the program draws is not part of the yardstick.  Three more numbers hold
the draws themselves to the round's definition:

  * ``foreign_rows``: real rows that are not rows of their client's data
    (exact: limit 0);
  * ``repeated_batches``: batches of a round that repeat an earlier
    batch's latents, or an earlier batch's rows of the same client
    (exact: limit 0);
  * ``latent_sigmas``: the largest, over the rounds, of the distance of
    a round's latents' mean from 0 and of their standard deviation from
    1, each in its standard error (1 / sqrt(n) and 1 / sqrt(2 n) for n
    standard normal draws), so that it reads alike at any size.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Set, Tuple

import jax
import numpy as np

DEAD = 1e-3      # a leaf whose first moment is under DEAD x the median's


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64).ravel())) for p, x in flat}


def live(ref_norms: Dict[str, float]) -> Set[str]:
    med = float(np.median(list(ref_norms.values())))
    return {k for k, v in ref_norms.items() if v >= DEAD * med}


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               keep: Set[str]) -> Tuple[float, str]:
    med = float(np.median([ref[k] for k in keep]))
    worst, where = 0.0, ""
    for k in sorted(keep):
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not g <= worst:          # NaN wins
            worst, where = g, k
    return worst, where


def change_norms(params, init) -> Dict[str, float]:
    return leaf_norms(jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        params, init))


def compare(prog: Dict[str, Any], ref: Dict[str, Any], init: Dict[str, Any]
            ) -> Dict[str, Tuple[float, str]]:
    """-> {number: (value, where)} for readings shaped as
    ``reference.run_rounds`` returns them; ``prog`` and ``ref`` compare
    the same rounds."""
    (pd, pg), (rd, rg) = prog["losses"][0], ref["losses"][0]
    gaps = [(abs(pd - rd) / abs(rd), "round 1 d_loss"),
            (abs(pg - rg) / abs(rg), "round 1 g_loss")]
    grads = []
    g_ref = leaf_norms(ref["m1"]["G"])
    g_keep = live(g_ref)
    grads.append(_tag(worst_leaf(leaf_norms(prog["m1"]["G"]), g_ref,
                                 g_keep), "G"))
    d_refs = {}
    for cid, m in ref["m1"]["D"].items():
        d_refs[cid] = leaf_norms(m)
        grads.append(_tag(worst_leaf(leaf_norms(prog["m1"]["D"][cid]),
                                     d_refs[cid], live(d_refs[cid])),
                          f"D[{cid}]"))
    # a D leaf is live where the clients' median first moment is
    d_live = live({k: float(np.median([n[k] for n in d_refs.values()]))
                   for k in next(iter(d_refs.values()))})
    change = [
        _tag(worst_leaf(change_norms(prog["params"]["G"], init["G"]),
                        change_norms(ref["params"]["G"], init["G"]),
                        g_keep), "G"),
        _tag(worst_leaf(change_norms(prog["params"]["D"], init["D"]),
                        change_norms(ref["params"]["D"], init["D"]),
                        d_live), "D")]
    return {"loss_gap": _max(gaps), "grad_gap": _max(grads),
            "change_gap": _max(change)}


def draw_numbers(draws, data: Dict[str, np.ndarray]
                 ) -> Dict[str, Tuple[float, str]]:
    """-> {number: (value, where)} for the draws that the reference
    trained on: ``draws[r]["clients"][cid] = (reals, latents)`` with a
    leading batch axis, and ``draws[r]["g"]`` the G steps' latents."""
    rows = {cid: {r.tobytes() for r in x} for cid, x in data.items()}
    foreign, repeated, lat = 0, 0, (0.0, "")
    for r, dr in enumerate(draws):
        seen = set()
        zs = [dr["g"]]
        for cid, (reals, z) in dr["clients"].items():
            zs.append(z)
            for x in reals:
                foreign += sum(v.tobytes() not in rows[cid] for v in x)
                key = (cid, x.tobytes())
                repeated += key in seen
                seen.add(key)
        for z in zs:
            for b in z:
                repeated += b.tobytes() in seen
                seen.add(b.tobytes())
        z = np.concatenate([x.ravel() for x in zs]).astype(np.float64)
        gap = max(abs(z.mean()) * len(z) ** 0.5,
                  abs(z.std() - 1.0) * (2 * len(z)) ** 0.5)
        if not gap <= lat[0]:
            lat = (float(gap), f"round {r + 1}")
    return {"foreign_rows": (float(foreign), "real rows"),
            "repeated_batches": (float(repeated), "batches"),
            "latent_sigmas": lat}


def _tag(vw: Tuple[float, str], tree: str) -> Tuple[float, str]:
    return vw[0], tree + vw[1]


def _max(items):
    for v, w in items:
        if math.isnan(v):
            return v, w
    return max(items, key=lambda x: x[0])


def judge(numbers: Dict[str, Tuple[float, str]], limits: Dict[str, float]
          ) -> bool:
    """Every number at or under its limit; a NaN fails."""
    return all(numbers[k][0] <= limits[k] for k in limits)
