"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fedavg.ops import fedavg_flat, fedavg_trees
from repro.kernels.fedavg.ref import fedavg_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.wkv6.ref import wkv6_ref

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# flash attention: sweep shapes / dtypes / gqa / window / padding
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, sq, sk, h, hkv, d, causal, window)
    (2, 128, 128, 4, 4, 64, True, 0),
    (1, 256, 256, 4, 2, 64, True, 0),      # GQA 2:1
    (2, 200, 200, 4, 1, 128, True, 0),     # MQA + unaligned seq (padding)
    (1, 256, 256, 2, 2, 64, True, 64),     # sliding window
    (1, 384, 384, 8, 8, 32, True, 0),      # small head_dim
    (1, 1, 384, 4, 2, 64, False, 0),       # single-query decode pattern
    (3, 64, 64, 2, 2, 64, True, 0),        # seq < block
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    b, sq, sk, h, hkv, d, causal, win = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), jnp.float32)
    out = flash_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                          jnp.swapaxes(v, 1, 2), causal=causal, window=win,
                          interpret=True)
    ref = jnp.swapaxes(attention_ref(q, k, v, causal=causal, window=win),
                       1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, atol):
    b, s, h, d = 1, 128, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, h, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, h, d)).astype(dtype)
    out = flash_attention(q, k, v, interpret=True)
    ref = jnp.swapaxes(attention_ref(jnp.swapaxes(q, 1, 2),
                                     jnp.swapaxes(k, 1, 2),
                                     jnp.swapaxes(v, 1, 2)), 1, 2)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_flash_attention_block_shape_invariance():
    b, s, h, d = 1, 256, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    o1 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    o2 = flash_attention(q, k, v, block_q=128, block_k=256, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


# ---------------------------------------------------------------------------
# wkv6: sweep shapes / chunk sizes / state carry
# ---------------------------------------------------------------------------

WKV_CASES = [
    # (b, t, h, n, block_t)
    (2, 64, 2, 32, 16),
    (1, 100, 4, 64, 64),    # unaligned t (padding no-op property)
    (2, 17, 1, 16, 8),
    (1, 128, 2, 8, 32),
]


@pytest.mark.parametrize("case", WKV_CASES)
def test_wkv6_matches_ref(case):
    b, t, h, n, bt = case
    ks = jax.random.split(KEY, 5)
    r, k, v = (jax.random.normal(ks[i], (b, t, h, n), jnp.float32)
               for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, t, h, n)) * 0.5))
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    s0 = jax.random.normal(KEY, (b, h, n, n)) * 0.1
    out, sT = wkv6(r, k, v, w, u, s0, block_t=bt, interpret=True)
    ref_out, ref_sT = wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(ref_sT), atol=1e-4)


def test_wkv6_state_chaining_equals_single_pass():
    """Running two halves with carried state == one full pass."""
    b, t, h, n = 1, 64, 2, 16
    ks = jax.random.split(KEY, 5)
    r, k, v = (jax.random.normal(ks[i], (b, t, h, n)) for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, t, h, n)) * 0.5))
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    full, sT = wkv6(r, k, v, w, u, block_t=16, interpret=True)
    h1, s1 = wkv6(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u,
                  block_t=16, interpret=True)
    h2, s2 = wkv6(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, state0=s1,
                  block_t=16, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sT), atol=1e-4)


# ---------------------------------------------------------------------------
# fedavg
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c,n", [(2, 4096), (5, 10000), (3, 100)])
def test_fedavg_kernel_matches_ref(c, n):
    st = jax.random.normal(KEY, (c, n))
    w = jnp.arange(1.0, c + 1)
    out = fedavg_flat(st, w, interpret=True)
    ref = fedavg_ref(st, w / w.sum())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fedavg_trees_matches_host_fedavg():
    from repro.core.fedavg import fedavg as favg
    trees = [{"a": jax.random.normal(jax.random.PRNGKey(i), (7, 13)),
              "b": jnp.full((3,), float(i))} for i in range(3)]
    got = fedavg_trees(trees, [1, 1, 2], interpret=True)
    want = favg(trees, [1, 1, 2])
    for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_), atol=1e-5)


def test_fedavg_identity_single_client():
    t = {"x": jnp.arange(10.0)}
    out = fedavg_trees([t], interpret=True)
    np.testing.assert_allclose(np.asarray(out["x"]), np.asarray(t["x"]),
                               atol=1e-7)


# ---------------------------------------------------------------------------
# kernels/f16: float16 conversions in 32-bit ops, bit-exact with XLA's
# ---------------------------------------------------------------------------

def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    nan = np.isnan(want)
    assert np.isnan(got[nan]).all()
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def test_f16_bits_to_f32_matches_convert_on_every_pattern():
    from repro.kernels.f16 import f16_bits_to_f32
    bits = np.arange(1 << 16, dtype=np.uint16).view(np.int16)
    _same_bits(f16_bits_to_f32(jnp.asarray(bits)),
               bits.view(np.float16).astype(np.float32))


def test_round_to_f16_matches_convert_roundtrip():
    from repro.kernels.f16 import round_to_f16
    f16 = np.arange(1 << 16, dtype=np.uint16).view(np.float16)
    grid = np.sort(f16[np.isfinite(f16)].astype(np.float32))
    # every float16, the ties between neighbours and one ulp either side
    mids = ((grid[1:].astype(np.float64) + grid[:-1]) / 2).astype(np.float32)
    rng = np.random.default_rng(0)
    x = np.concatenate([
        grid, mids, np.nextafter(mids, np.float32(np.inf)),
        np.nextafter(mids, np.float32(-np.inf)),
        np.array([np.inf, -np.inf, np.nan, 65519.996, 65520.0, 1e-45],
                 np.float32),
        rng.integers(0, 1 << 32, 1 << 18, dtype=np.uint64)
        .astype(np.uint32).view(np.float32)])
    _same_bits(round_to_f16(jnp.asarray(x)),
               jnp.asarray(x).astype(jnp.float16).astype(jnp.float32))


# ---------------------------------------------------------------------------
# reduces on a `clients` mesh: per-device kernel + psum == one kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("clients", [4, 5])
def test_fedavg_on_clients_mesh_matches_single_device(clients):
    """Rows split over every host device (5 clients pad with a zero-weight
    row on 4 devices) reduce to the single-device kernel's mean."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()), ("clients",))
    stacked = jax.random.normal(jax.random.PRNGKey(3), (clients, 5000))
    w = jnp.arange(1.0, clients + 1.0)
    want = fedavg_flat(stacked, w, interpret=True)
    got = fedavg_flat(stacked, w, interpret=True, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
