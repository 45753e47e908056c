"""Model FLOPs of one FSL-GAN round of DCGAN's published G against the
DCGAN D, counted from shapes by ``bench.flops.dcgan``'s rules: only
convolutions and matmuls (2 FLOPs per multiply-add), a transposed
convolution counted on its input pixels, and each pass counting the work
it needs and nothing it could skip.  D's layers are
``bench.flops.dcgan.disc_layers``; G's are the 4x4 projection and the
log2(image_size / 4) 5x5 stride-2 transposed convolutions.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.flops.dcgan import disc_layers


def gen_layers(d: Dict) -> List[Tuple[str, float]]:
    """(name, forward FLOPs per latent) of G, input layer first."""
    n = (d["image_size"] // 4).bit_length() - 1
    w = [d["base_filters"] * 2 ** (n - 1 - i) for i in range(n)] \
        + [d["channels"]]
    out = [("proj", 2.0 * d["latent_dim"] * 4 * 4 * w[0])]
    for i in range(n):
        side = 4 * 2 ** i                      # the input's side
        out.append((f"deconv{i}", 2.0 * 25 * w[i] * w[i + 1] * side * side))
    return out


def step_flops(d: Dict, batch: int) -> Dict[str, float]:
    """FLOPs of one D step, one fake batch and one G step."""
    dl, gl = disc_layers(d), gen_layers(d)
    fd, fg = sum(f for _, f in dl), sum(f for _, f in gl)
    return {
        "d_step": 2 * batch * (3 * fd - dl[0][1]),
        "fake_batch": batch * fg,
        "g_step": batch * ((3 * fg - gl[0][1]) + 2 * fd),
    }


def round_flops(d: Dict, batch: int, clients: int, batches: int
                ) -> Dict[str, float]:
    """FLOPs of one round: ``clients`` x ``batches`` D steps and fake
    batches, then ``batches`` G steps."""
    s = step_flops(d, batch)
    out = {"d_steps": clients * batches * s["d_step"],
           "fake_batches": clients * batches * s["fake_batch"],
           "g_steps": batches * s["g_step"]}
    out["total"] = sum(out.values())
    return out
