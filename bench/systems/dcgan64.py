"""The system under test for the dcgan64 family: ``FSLGANTrainer`` with
DCGAN's published G (``model.dcgan.gen_layout = "radford"``) on seeded
64x64x3 images.

It is ``bench.systems.dcgan.System`` with this family's data
(``bench.data_rgb``) and plain reference (``bench.reference.dcgan64``):
the same rounds through ``train_epoch``, the same ``bench.input`` and
``bench.generator`` spans, and the same recorded draws for
``bench/check.py``.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax

from bench import data_rgb
from bench.reference import dcgan64 as reference
from bench.systems import dcgan

DIMS = dcgan.DIMS


def program_config(conf: Dict, cell: Dict):
    """``bench.systems.dcgan.program_config``, and the G layout the
    configuration file names."""
    cfg = dcgan.program_config(conf, cell)
    layout = cfg.model.dcgan.gen_layout
    if layout != conf["model"]["gen_layout"]:
        raise ValueError(f"program G layout {layout!r} differs from the "
                         f"configuration file's "
                         f"{conf['model']['gen_layout']!r}")
    return cfg


class System(dcgan.System):
    """One trainer, built from the seed, driven round by round."""

    def __init__(self, conf: Dict, cell: Dict, seeds: Dict[str, int]):
        from repro.core.gan import FSLGANTrainer
        self.cfg = program_config(conf, cell)
        m = conf["model"]
        self.dims = {k: m[k] for k in DIMS}
        self.hyper = {k: m[k] for k in ("lr", "beta1", "beta2", "eps")}
        self.batch = m["global_batch"]
        self.batches = int(cell["batches_per_client"])
        self.data = data_rgb.client_data(
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

    def reference(self, **kw) -> Dict[str, Any]:
        """The plain reference over the recorded rounds, from the same
        weights, on the real rows and latents the program drew."""
        counts = {cid: len(x) for cid, x in self.data.items()}
        return reference.run_rounds(self.dims, self.hyper, self.init_host,
                                    counts, self.draws, **kw)
