"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.minplus import minplus

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("S", [128, 256])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, hd, heads, dtype):
    Hq, Hkv = heads
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, Hq, S, hd), dtype)
    k = jax.random.normal(ks[1], (1, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (1, Hkv, S, hd), dtype)
    o1 = flash_attention(q, k, v, causal=True, bq=128, bk=128,
                         interpret=True)
    o2 = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 256, 64), jnp.float32)
    o1 = flash_attention(q, k, v, causal=False, interpret=True)
    o2 = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 128, 384),
                                   (128, 256, 128)])
def test_minplus_sweep(shape):
    M, K, N = shape
    a = jax.random.uniform(KEY, (M, K), jnp.float32) * 10
    b = jax.random.uniform(jax.random.PRNGKey(7), (K, N), jnp.float32) * 10
    o1 = minplus(a, b, interpret=True)
    o2 = ref.minplus_ref(a, b)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_apsp_matches_scipy():
    from repro.core import topology as T
    topo = T.pt((4, 4, 8))
    d_kernel, h_kernel = ops.topology_metrics(topo.edges(), topo.n,
                                              interpret=True)
    d_ref, h_ref = T.diameter_avg_hops(topo)
    assert d_kernel == d_ref
    assert abs(h_kernel - h_ref) < 1e-3


def test_minplus_property_random():
    """Property-style: idempotence D = minplus(D, D) at the APSP fixpoint
    and triangle inequality of the closure."""
    rng = np.random.default_rng(0)
    n = 128
    d0 = np.full((n, n), 1e9, np.float32)
    np.fill_diagonal(d0, 0)
    for _ in range(3 * n):
        u, v = rng.integers(0, n, 2)
        if u != v:
            d0[u, v] = d0[v, u] = 1.0
    closure = np.asarray(ref.apsp_ref(jnp.asarray(d0)))
    again = np.asarray(ref.minplus_ref(jnp.asarray(closure),
                                       jnp.asarray(closure)))
    np.testing.assert_allclose(closure, again, atol=1e-5)
