"""Model FLOPs of one FSL-GAN round of a DCGAN, counted from shapes.

Only convolutions and matmuls count (2 FLOPs per multiply-add); bias,
batch norm and activations are left out.  A transposed convolution is
counted on its input pixels, not on the zero-dilated input.  Each pass
counts the work it needs and nothing it could skip:

  * D step on B real + B fake images: D forward, every weight gradient,
    and every input gradient but the first layer's (nothing consumes the
    gradient of the images);
  * fake batch: G forward on B latents;
  * G step on B latents: G forward, its weight gradients and its input
    gradients but the first layer's, plus D forward and D's input
    gradients (D's weights are not trained there).

A backward pass's weight gradient and its input gradient each cost as
much as the forward pass of that layer.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def disc_layers(d: Dict) -> List[Tuple[str, float]]:
    """(name, forward FLOPs per image) of D, input layer first."""
    out, cin, size = [], d["channels"], d["image_size"]
    for i in range(d["conv_blocks"]):
        cout = d["base_filters"] * 2 ** i
        size = -(-size // 2)                   # stride 2, SAME
        out.append((f"conv{i}", 2.0 * 25 * cin * cout * size * size))
        cin = cout
    out.append(("classifier", 2.0 * size * size * cin))
    return out


def gen_layers(d: Dict) -> List[Tuple[str, float]]:
    """(name, forward FLOPs per latent) of G, input layer first."""
    f, s = d["base_filters"], d["image_size"]
    s0 = s // 4
    return [
        ("proj", 2.0 * d["latent_dim"] * s0 * s0 * 4 * f),
        ("deconv0", 2.0 * 25 * 4 * f * 2 * f * s0 * s0),
        ("deconv1", 2.0 * 25 * 2 * f * f * (2 * s0) ** 2),
        ("out", 2.0 * 25 * f * d["channels"] * s * s),
    ]


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
