"""Pallas kernels (interpret mode) and the leafwise fused reductions vs
ref.py oracles: shape x dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat
from repro.kernels import ops, ref
from repro.models import ssm as ssm_mod

SIZES = [1, 1000, 4096, 131072, 300001]
DTYPES = [jnp.float32, jnp.bfloat16]


# The fused (x·y, ||x||², ||y||²) triple and the EF axpy need no kernel:
# they run leafwise in plain XLA (core.flat), and a flat vector is one leaf.


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_cosine(n, dtype):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,), dtype)
    y = jax.random.normal(jax.random.PRNGKey(n + 1), (n,), dtype)
    got = flat.tree_stats(x, y)
    want = ref.fused_cosine(x, y)
    np.testing.assert_allclose(got, want, rtol=5e-3 if dtype == jnp.bfloat16 else 2e-4)


@pytest.mark.parametrize("n", SIZES)
def test_ef_update(n):
    u = jax.random.normal(jax.random.PRNGKey(n), (n,))
    d = jax.random.normal(jax.random.PRNGKey(n + 1), (n,))
    got = flat.tree_ef_update(u, d, jnp.float32(0.37))
    want = ref.ef_update(u, d, jnp.float32(0.37))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert got.shape == u.shape


@pytest.mark.parametrize("n", SIZES)
def test_sign_quant(n):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,))
    signs, scale = ops.sign_quant(x)
    rsigns, rscale = ref.sign_quant(x)
    np.testing.assert_array_equal(np.asarray(signs), np.asarray(rsigns))
    np.testing.assert_allclose(scale, rscale, rtol=1e-5)
    assert signs.dtype == jnp.int8


@pytest.mark.parametrize("n", [1000, 131072, 300001])
@pytest.mark.parametrize("k_frac", [0.001, 0.01, 0.1])
def test_topk_mask_threshold(n, k_frac):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,))
    k = max(1, int(k_frac * n))
    tau = ops.topk_threshold(x, k)
    got, cnt = ops.topk_mask(x, tau)
    want, rcnt = ref.topk_mask(x, tau)
    np.testing.assert_allclose(got, want)
    np.testing.assert_allclose(cnt, rcnt)
    # sampled threshold lands near the requested k (exact when n <= sample)
    if n <= 65536:
        assert abs(int(cnt) - k) <= 1
    else:
        assert 0.3 * k <= int(cnt) <= 3 * k


@pytest.mark.parametrize("shape", [(1, 16, 2, 8, 4), (2, 64, 4, 16, 8),
                                   (1, 128, 8, 32, 16)])
@pytest.mark.parametrize("chunk", [8, 16])
def test_ssd_chunk_vs_scan_oracle(shape, chunk):
    b, s, h, p, n = shape
    if s % chunk:
        pytest.skip("seq must divide chunk")
    k = jax.random.PRNGKey(0)
    xdt = 0.1 * jax.random.normal(k, (b, s, h, p))
    dA = -0.2 * jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (b, s, h)))
    B = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (b, s, n))
    C = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (b, s, n))
    y1, f1 = ssm_mod.ssd_scan(xdt, dA, B, C, chunk)
    y2, f2 = ops.ssd_chunked(xdt, dA, B, C, chunk)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(f1, f2, rtol=1e-4, atol=1e-5)


def test_ssd_single_chunk_kernel_vs_ref():
    """Direct kernel-cell contract vs ref.ssd_chunk (one chunk, one head)."""
    from repro.kernels.ssd_chunk import ssd_chunk_call
    Q, P, N = 16, 8, 4
    k = jax.random.PRNGKey(0)
    x = 0.1 * jax.random.normal(k, (1, 1, 1, Q, P))
    dA = -0.3 * jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, Q)))
    B = jax.random.normal(jax.random.PRNGKey(2), (1, 1, Q, N))
    C = jax.random.normal(jax.random.PRNGKey(3), (1, 1, Q, N))
    y, st, dec = ssd_chunk_call(x, dA, B, C, interpret=ops._interpret())
    ry, rst, rdec = ref.ssd_chunk(x[0, 0, 0], dA[0, 0, 0], B[0, 0], C[0, 0])
    np.testing.assert_allclose(y[0, 0, 0], ry, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(st[0, 0, 0], rst, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dec[0, 0, 0], rdec, rtol=1e-5, atol=1e-6)
