"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Property-style coverage uses a fixed seeded case grid (no ``hypothesis`` in
this environment).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,d,b", [(8, 64, 1), (16, 700, 3), (25, 1024, 4), (12, 513, 0), (9, 31, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_trimmed_mean_kernel(n, d, b, dtype):
    rng = np.random.default_rng(n * d + b)
    v = jnp.asarray(rng.normal(size=(n, d)), dtype)
    mask = jnp.asarray(rng.random(n) < 0.8)
    if int(mask.sum()) < 2 * b + 1:
        mask = jnp.ones((n,), bool)
    sv = jnp.asarray(rng.normal(size=(d,)), dtype)
    out = ops.trimmed_mean(v, mask, sv, b, block_d=256, interpret=True)
    exp = ref.trimmed_mean_ref(v, mask, sv, b)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d", [(5, 100), (16, 512), (23, 777)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_median_kernel(n, d, dtype):
    rng = np.random.default_rng(n + d)
    v = jnp.asarray(rng.normal(size=(n, d)), dtype)
    mask = jnp.asarray(rng.random(n) < 0.7).at[0].set(True)
    out = ops.median(v, mask, block_d=256, interpret=True)
    exp = ref.median_ref(v, mask)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exp, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,d", [(8, 200), (20, 1024), (33, 600)])
def test_krum_dists_kernel(n, d):
    rng = np.random.default_rng(d)
    v = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    out = ops.pairwise_sq_dists(v, block_d=256, interpret=True)
    exp = ref.pairwise_sq_dists_ref(v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("n,d,b,seed", [
    (5, 1, 0, 0), (5, 3, 3, 1), (6, 17, 2, 2), (7, 128, 3, 3), (8, 47, 1, 4),
    (9, 255, 0, 5), (11, 129, 2, 6), (13, 300, 3, 7), (15, 64, 1, 8),
    (16, 200, 0, 9), (17, 5, 3, 10), (18, 257, 2, 11), (19, 96, 1, 12),
    (20, 300, 3, 13), (20, 1, 2, 14),
])
def test_trimmed_mean_property(n, d, b, seed):
    if n < 2 * b + 1:
        b = (n - 1) // 2
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    mask = jnp.ones((n,), bool)
    sv = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    out = ops.trimmed_mean(v, mask, sv, b, block_d=128, interpret=True)
    exp = ref.trimmed_mean_ref(v, mask, sv, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-4, atol=2e-4)
