"""End-to-end behaviour: the whole pipeline wired together at smoke scale,
plus a 1-device mesh integration of the dry-run path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import reduce_for_smoke
from repro.configs.registry import get_config
from repro.data import synthetic_lm_batch
from repro.launch.mesh import make_host_mesh
from repro.models.transformer import lm_init
from repro.optim import make_optimizer
from repro.runtime import make_decode_step, make_prefill_step, make_train_step


def test_lm_trains_on_synthetic_structure():
    """The synthetic token stream is learnable: loss drops toward structure."""
    cfg = reduce_for_smoke(get_config("rwkv6-1.6b", "train_4k"), seq_len=32,
                           batch=8)
    cfg = cfg.override({"optim.schedule": "constant", "optim.lr": 3e-3,
                        "optim.warmup_steps": 0})
    m = cfg.model
    params = lm_init(jax.random.PRNGKey(0), m)
    opt = make_optimizer(cfg.optim)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg))
    first = last = None
    for i in range(40):
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_lm_batch(8, 32, m.vocab_size, seed=i % 4).items()}
        params, opt_state, metrics = step(params, opt_state, batch,
                                          jnp.asarray(i, jnp.int32))
        if first is None:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert last < first - 1.0, (first, last)


def test_serve_path_end_to_end():
    """prefill -> greedy decode through the runtime builders."""
    cfg = reduce_for_smoke(get_config("qwen3-14b", "decode_32k"), seq_len=32,
                           batch=2)
    cfg = cfg.override({"shape.seq_len": 32, "shape.mode": "decode",
                        "parallel.cache_dtype": "float32"})
    m = cfg.model
    params = lm_init(jax.random.PRNGKey(0), m)
    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, m.vocab_size)
    logits, state, idx = prefill(params, {"tokens": toks})
    assert logits.shape == (2, m.vocab_size)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    outs = []
    for t in range(4):
        logits, state = decode(params, nxt, state,
                               jnp.asarray(int(idx) + t, jnp.int32))
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(np.asarray(nxt))
    assert all(o.shape == (2,) for o in outs)
    assert bool(jnp.isfinite(logits).all())


def test_dryrun_path_on_host_mesh():
    """The exact dry-run lowering path, on the host's 1-device mesh with a
    reduced config — catches sharding-spec/tree mismatches cheaply."""
    from repro.launch.dryrun import lower_one
    cfg = reduce_for_smoke(get_config("olmoe-1b-7b", "train_4k"), seq_len=32,
                           batch=4)
    mesh = make_host_mesh()
    lowered, compiled, secs = lower_one(cfg, mesh)
    from repro.roofline.analysis import cost_analysis_dict
    ca = cost_analysis_dict(compiled)
    assert float(ca.get("flops", 0)) > 0
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0


def test_dryrun_decode_path_on_host_mesh():
    from repro.launch.dryrun import lower_one
    cfg = reduce_for_smoke(get_config("rwkv6-1.6b", "decode_32k"), seq_len=64,
                           batch=2)
    cfg = cfg.override({"shape.mode": "decode", "shape.seq_len": 64})
    mesh = make_host_mesh()
    lowered, compiled, _ = lower_one(cfg, mesh)
    assert compiled is not None


# ---------------------------------------------------------------------------
# launch/cache: where the persistent compilation cache lands
# ---------------------------------------------------------------------------

_CACHE_PROBE = """
import json, jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.launch.cache import use_compile_cache
path = use_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(json.dumps({{"path": path,
                  "config": jax.config.jax_compilation_cache_dir}}))
"""


def _cache_probe(env_dir, compile_):
    import json
    import os
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=compile_)],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_uses_the_environment_directory(tmp_path):
    import os
    from repro.launch.cache import CHECKOUT_CACHE
    before = sorted(os.listdir(CHECKOUT_CACHE)) \
        if os.path.isdir(CHECKOUT_CACHE) else None
    got = _cache_probe(str(tmp_path), True)
    assert got == {"path": str(tmp_path), "config": str(tmp_path)}
    assert os.listdir(tmp_path)                  # the entry landed there
    after = sorted(os.listdir(CHECKOUT_CACHE)) \
        if os.path.isdir(CHECKOUT_CACHE) else None
    assert after == before                       # and nowhere else


def test_compile_cache_defaults_to_the_checkout():
    import os
    from repro.launch.cache import CHECKOUT_CACHE
    got = _cache_probe(None, False)
    assert got == {"path": CHECKOUT_CACHE, "config": CHECKOUT_CACHE}
    root = os.path.dirname(CHECKOUT_CACHE)
    assert os.path.isfile(os.path.join(root, "chip_smoke.py"))
