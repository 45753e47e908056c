"""The system under test for the DCGAN family: ``FSLGANTrainer``.

Builds the trainer on the configuration's data from the seed, installs
the weights the benchmark made, and drives rounds through the trainer's
normal entry, ``train_epoch``.  Reads back what the comparison needs:
each round's losses, the first Adam moments after round 1, the
parameters after the last set-up round, and what the program drew in
the set-up rounds.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List

import jax
import numpy as np

from bench import check, data as bench_data
from bench.reference import dcgan as reference

DIMS = ("image_size", "channels", "latent_dim", "base_filters", "conv_blocks")


def program_config(conf: Dict, cell: Dict):
    """The program's ``RunConfig``: the repo's configuration named by the
    configuration file, with the file's and then the cell's overrides.
    Raises where the sizes it would run differ from the file's."""
    from repro.configs.registry import get_config
    cfg = get_config(conf["repo_config"])
    cfg = cfg.override({**conf.get("override", {}),
                        "fsl.num_clients": cell["clients"],
                        **cell.get("override", {})})
    run = {**{k: getattr(cfg.model.dcgan, k) for k in DIMS},
           "global_batch": cfg.shape.global_batch,
           "devices_per_client": cfg.fsl.devices_per_client,
           "lr": cfg.optim.lr, "beta1": cfg.optim.beta1,
           "beta2": cfg.optim.beta2, "eps": cfg.optim.eps,
           "clients": cfg.fsl.num_clients}
    want = {**{k: conf["model"][k] for k in run if k != "clients"},
            "clients": cell["clients"]}
    if run != want:
        raise ValueError(f"program config {run} differs from the "
                         f"configuration file's {want}")
    if cfg.optim.name != "adam" or cfg.optim.grad_clip \
            or cfg.optim.weight_decay or cfg.optim.schedule != "constant":
        raise ValueError(f"the reference runs plain Adam, not {cfg.optim}")
    if cfg.fed.codec != "none" or cfg.privacy.enabled or cfg.split.enabled:
        raise ValueError("the reference has no codec, DP or split stage")
    return cfg


class System:
    """One trainer, built from the seed, driven round by round."""

    def __init__(self, conf: Dict, cell: Dict, seeds: Dict[str, int]):
        from repro.core.gan import FSLGANTrainer
        self.cfg = program_config(conf, cell)
        m = conf["model"]
        self.dims = {k: m[k] for k in DIMS}
        self.hyper = {k: m[k] for k in ("lr", "beta1", "beta2", "eps")}
        self.batch = m["global_batch"]
        self.batches = int(cell["batches_per_client"])
        self.data = bench_data.client_data(
            conf["data"], int(cell["clients"]), seeds["data"],
            m["image_size"], m["channels"])
        self.trainer = FSLGANTrainer(self.cfg, self.data,
                                     seed=seeds["trainer"])
        active = self.trainer._active_clients()
        if active != list(self.data):
            raise ValueError(f"clients {sorted(set(self.data) - set(active))}"
                             " have no split plan; the reference trains all")
        make = jax.jit(lambda k: reference.init_params(k, self.dims))
        self.init = make(jax.random.PRNGKey(seeds["weights"]))
        st, tr = self.trainer.state, self.trainer
        st.g_params = self.init["G"]
        st.g_opt = tr.g_optimizer.init(self.init["G"])
        st.d_params = {cid: self.init["D"] for cid in self.data}
        st.d_opt = {cid: tr.d_optimizer.init(self.init["D"])
                    for cid in self.data}
        self.losses: List[tuple] = []
        self.draws: List[Dict[str, Any]] = []
        self._recording = False

    @contextlib.contextmanager
    def recording(self):
        """Record what the program draws in the rounds run inside: each
        client's real rows and latents, batch by batch, and the G steps'
        latents.  The trainer's sampling calls are wrapped on the
        instance, and unwrapped on leaving, so the window runs them
        bare."""
        tr = self.trainer
        sample, g_updates, draw_z = (tr._sample_round_batches,
                                     tr._g_updates, tr._z)
        latents: List[np.ndarray] = []

        def z(n):
            out = draw_z(n)
            latents.append(np.asarray(out))
            return out

        def sample_round_batches(cid, steps):
            latents.clear()
            reals, fakes = sample(cid, steps)
            clients = self.draws[-1]["clients"]
            if cid in clients:
                raise RuntimeError(f"client {cid} sampled twice in a round")
            clients[cid] = (np.asarray(reals), _batched(latents, steps))
            return reals, fakes

        def g_steps(d_avg, batches):
            latents.clear()
            out = g_updates(d_avg, batches)
            if self.draws[-1]["g"] is not None:
                raise RuntimeError("two G updates in a round")
            self.draws[-1]["g"] = _batched(latents, batches)
            return out

        tr._z, tr._sample_round_batches, tr._g_updates = (
            z, sample_round_batches, g_steps)
        self._recording = True
        try:
            yield
        finally:
            self._recording = False
            for name in ("_z", "_sample_round_batches", "_g_updates"):
                delattr(tr, name)

    def round(self) -> int:
        """One round through ``train_epoch``, ended when the new global D
        and G are on the device.  Returns the real samples the clients'
        local steps trained on."""
        tr = self.trainer
        if self._recording:
            self.draws.append({"clients": {}, "g": None})
        m = tr.train_epoch(self.batches)
        jax.block_until_ready((tr.state.d_params[tr.client_ids[0]],
                               tr.state.g_params))
        self.losses.append((m["d_loss"], m["g_loss"]))
        return int(m["num_clients"]) * self.batches * self.batch

    def first_moments(self) -> Dict[str, Any]:
        st = self.trainer.state
        return jax.device_get({"G": st.g_opt["m"],
                               "D": {c: o["m"] for c, o in st.d_opt.items()}})

    def params(self) -> Dict[str, Any]:
        st = self.trainer.state
        return jax.device_get({"G": st.g_params,
                               "D": st.d_params[self.trainer.client_ids[0]]})

    def spans(self) -> Dict[str, tuple]:
        """Calls into each layer that a traced run wraps in a span:
        span name -> (object, attribute)."""
        tr = self.trainer
        out = {"bench.input": (tr, "_sample_round_batches"),
               "bench.generator": (tr, "_g_updates")}
        if tr.engine is not None:
            out["bench.engine_round"] = (tr.engine, "run_round")
        return out

    def reference(self, **kw) -> Dict[str, Any]:
        """The plain reference over the recorded rounds, from the same
        weights, on the real rows and latents the program drew."""
        counts = {cid: len(x) for cid, x in self.data.items()}
        return reference.run_rounds(self.dims, self.hyper, self.init_host,
                                    counts, self.draws, **kw)

    def draw_numbers(self) -> Dict[str, tuple]:
        return check.draw_numbers(self.draws, self.data)

    def release(self) -> None:
        """Free the program's state; keep what the reference needs."""
        self.init_host = jax.device_get(self.init)
        self.init = None
        self.trainer = None


def _batched(latents: List[np.ndarray], steps: int) -> np.ndarray:
    """The latents drawn in one sampling call, with a leading batch axis."""
    if not latents:
        raise RuntimeError("the program drew no latents through the "
                           "trainer's _z; the reference cannot follow it")
    z = np.concatenate(latents)
    return z.reshape(steps, -1, z.shape[-1])
