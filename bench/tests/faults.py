"""Runs of a cell on the CPU at small sizes with the timed path sound or
broken underneath, for the per-cell correctness tests."""
import time

from bench import check, harness, readings
from bench.tests import small

SEED = 2 ** 32 + 17


def run(cell):
    return harness.run(cell, SEED, 0.2, False, t0=time.perf_counter(),
                       require_tpu=False, compile_cache=False,
                       tweak=small.tweak)


def sound_passes_and_control_fails(cell):
    """The program meets every limit; the reference in bfloat16 in its
    place, and the reference with half of each batch left out, fail."""
    r = readings.readings(cell, SEED, require_tpu=False, tweak=small.tweak)
    limits = harness.load_cell(cell)[3]["limits"]

    def judge(k):
        numbers = {**r["draws"], **r[k]}
        return check.judge({n: (v, "") for n, v in numbers.items()}, limits)
    assert judge("program"), r
    assert not judge("control"), r["control"]
    assert not judge("half_batch"), r["half_batch"]


def unchanged_state_fails(cell, monkeypatch):
    """A round that returns the training state unchanged."""
    from repro.core.gan import FSLGANTrainer
    orig = FSLGANTrainer.train_epoch

    def frozen(self, *a, **kw):
        st = self.state
        keep = (st.g_params, st.g_opt, dict(st.d_params), dict(st.d_opt))
        out = orig(self, *a, **kw)
        st.g_params, st.g_opt, st.d_params, st.d_opt = keep
        return out
    monkeypatch.setattr(FSLGANTrainer, "train_epoch", frozen)
    out = run(cell)
    assert out["correct"] is False
    assert out["check"]["change_gap"]["value"] == 1.0


def half_batch_fails(cell, monkeypatch):
    """Half of every batch left out of the D and G losses, the mean taken
    over the rest."""
    import repro.core.gan as gan
    d_loss, g_loss = gan.d_loss_fn, gan.g_loss_fn

    def d_half(d, real, fake, c):
        n = real.shape[0] // 2
        return d_loss(d, real[:n], fake[:n], c)

    def g_half(g, d, z, c):
        return g_loss(g, d, z[: z.shape[0] // 2], c)
    monkeypatch.setattr(gan, "d_loss_fn", d_half)
    monkeypatch.setattr(gan, "g_loss_fn", g_half)
    out = run(cell)
    assert out["correct"] is False
    assert out["metrics"]["samples_per_s"]["value"] > 0



def foreign_rows_fail(cell, monkeypatch):
    """Real rows altered where they are sampled: the reference follows
    the program's draws, so the draws themselves are held to the data."""
    from repro.core.gan import FSLGANTrainer
    orig = FSLGANTrainer._sample_real
    monkeypatch.setattr(FSLGANTrainer, "_sample_real",
                        lambda self, cid, n: orig(self, cid, n) * 0.5)
    out = run(cell)
    assert out["correct"] is False
    assert out["check"]["foreign_rows"]["value"] > 0


def repeated_latents_fail(cell, monkeypatch):
    """One batch of latents reused for every batch of a round."""
    from repro.core.gan import FSLGANTrainer
    orig, kept = FSLGANTrainer._z, {}

    def z(self, n):
        if n not in kept:
            kept[n] = orig(self, n)
        return kept[n]
    monkeypatch.setattr(FSLGANTrainer, "_z", z)
    out = run(cell)
    assert out["correct"] is False
    assert out["check"]["repeated_batches"]["value"] > 0


def narrow_latents_fail(cell, monkeypatch):
    """Latents drawn with standard deviation 0.9, not 1."""
    from repro.core.gan import FSLGANTrainer
    orig = FSLGANTrainer._z
    monkeypatch.setattr(FSLGANTrainer, "_z",
                        lambda self, n: orig(self, n) * 0.9)
    out = run(cell)
    assert out["correct"] is False
    assert out["check"]["latent_sigmas"]["value"] > 7


def another_draw_order_passes(cell, monkeypatch):
    """The same round with the host RNG drawn in another order (every
    real batch of a client first, then its latents): the reference
    trains on what was drawn, so the run stays correct."""
    import jax.numpy as jnp
    from repro.core.gan import FSLGANTrainer

    def sample(self, cid, steps):
        rs = [self._sample_real(cid, self.batch_size) for _ in range(steps)]
        fs = [self._gen(self.state.g_params, self._z(self.batch_size))
              for _ in range(steps)]
        return jnp.stack(rs), jnp.stack(fs)
    monkeypatch.setattr(FSLGANTrainer, "_sample_round_batches", sample)
    out = run(cell)
    assert out["correct"] is True, out["check"]
