"""Per-kernel validation: Pallas (interpret mode) and the XLA chunked
fallbacks, swept over shapes/dtypes, against the pure-jnp ref oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import (flash_attention as fa, gp_kernel, mamba2_ssd,
                           ref, rwkv6_scan)
from repro.kernels import ops as kops

jax.config.update("jax_enable_x64", False)


# ==========================================================================
# flash attention
# ==========================================================================
ATTN_SHAPES = [
    # (b, sq, skv, h, hkv, dh)
    (2, 128, 128, 4, 2, 64),
    (1, 100, 100, 4, 4, 32),
    (2, 64, 256, 8, 2, 64),      # cross attention window (decode-ish)
    (1, 1, 128, 4, 2, 64),       # single query row
    (1, 257, 257, 2, 1, 128),    # non-multiple of block
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_pallas_vs_oracle(shape, causal, dtype):
    b, sq, skv, h, hkv, dh = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (b, sq, h, dh), dtype)
    k = jax.random.normal(k2, (b, skv, hkv, dh), dtype)
    v = jax.random.normal(k3, (b, skv, hkv, dh), dtype)
    out = fa.flash_attention(q, k, v, causal=causal, block_q=64,
                             block_kv=64, interpret=True)
    want = ref.attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("shape", ATTN_SHAPES[:3])
def test_flash_attention_chunked_fallback(shape):
    b, sq, skv, h, hkv, dh = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (b, sq, h, dh), jnp.float32)
    k = jax.random.normal(k2, (b, skv, hkv, dh), jnp.float32)
    v = jax.random.normal(k3, (b, skv, hkv, dh), jnp.float32)
    out = ref.attention_chunked(q, k, v, causal=True, q_block=32, kv_block=32)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_chunked_vjp():
    """The custom blockwise-recompute VJP must match autodiff-through-
    oracle gradients."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(k1, (1, 96, 2, 32), jnp.float32)
    k = jax.random.normal(k2, (1, 96, 2, 32), jnp.float32)
    v = jax.random.normal(k3, (1, 96, 2, 32), jnp.float32)
    ct = jax.random.normal(k4, (1, 96, 2, 32), jnp.float32)

    def f_ref(q, k, v):
        return jnp.sum(ref.attention(q, k, v, causal=True) * ct)

    def f_chk(q, k, v):
        return jnp.sum(ref.attention_chunked(q, k, v, causal=True,
                                             q_block=32, kv_block=32) * ct)

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_chk = jax.grad(f_chk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_chk):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(sq=st.integers(1, 80), h=st.sampled_from([1, 2, 4]),
       dh=st.sampled_from([16, 32]), seed=st.integers(0, 10_000))
def test_flash_attention_property_rowsum(sq, h, dh, seed):
    """Property: attention output rows are convex combinations of V rows
    -> with V == const c, output == c everywhere (any mask/shape)."""
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (1, sq, h, dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, sq, h, dh))
    v = jnp.full((1, sq, h, dh), 3.5, jnp.float32)
    out = fa.flash_attention(q, k, v, causal=True, block_q=32, block_kv=32,
                             interpret=True)
    np.testing.assert_allclose(out, 3.5, atol=1e-4)


# ==========================================================================
# rwkv6
# ==========================================================================
RWKV_SHAPES = [(2, 130, 3, 16, 16), (1, 64, 2, 32, 32), (1, 33, 1, 8, 8)]


@pytest.mark.parametrize("shape", RWKV_SHAPES)
@pytest.mark.parametrize("with_state", [False, True])
def test_rwkv6_pallas_vs_oracle(shape, with_state):
    b, s, h, kd, vd = shape
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    r = jax.random.normal(ks[0], (b, s, h, kd))
    k = jax.random.normal(ks[1], (b, s, h, kd))
    v = jax.random.normal(ks[2], (b, s, h, vd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, s, h, kd)) * 0.5 - 1.0))
    u = jax.random.normal(ks[4], (h, kd)) * 0.1
    st0 = (jax.random.normal(ks[5], (b, h, kd, vd)) * 0.1
           if with_state else None)
    out, fs = rwkv6_scan.rwkv6_wkv(r, k, v, w, u, st0, chunk=32,
                                   interpret=True)
    want, wfs = ref.rwkv6_wkv(r, k, v, w, u, st0)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(fs, wfs, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("chunk", [16, 64])
def test_rwkv6_chunked_fallback(chunk):
    b, s, h, kd, vd = 2, 100, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r = jax.random.normal(ks[0], (b, s, h, kd))
    k = jax.random.normal(ks[1], (b, s, h, kd))
    v = jax.random.normal(ks[2], (b, s, h, vd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, s, h, kd)) * 0.5 - 1.0))
    u = jax.random.normal(ks[4], (h, kd)) * 0.1
    out, fs = ref.rwkv6_wkv_chunked(r, k, v, w, u, None, chunk=chunk)
    want, wfs = ref.rwkv6_wkv(r, k, v, w, u, None)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(fs, wfs, atol=2e-4, rtol=2e-4)


@settings(max_examples=8, deadline=None)
@given(s=st.integers(2, 70), seed=st.integers(0, 1000))
def test_rwkv6_property_chunk_invariance(s, seed):
    """Chunked evaluation must be invariant to the chunk size."""
    b, h, kd = 1, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r = jax.random.normal(ks[0], (b, s, h, kd))
    k = jax.random.normal(ks[1], (b, s, h, kd))
    v = jax.random.normal(ks[2], (b, s, h, kd))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, s, h, kd)) * 0.3))
    u = jax.random.normal(ks[4], (h, kd)) * 0.1
    o1, s1 = ref.rwkv6_wkv_chunked(r, k, v, w, u, chunk=8)
    o2, s2 = ref.rwkv6_wkv_chunked(r, k, v, w, u, chunk=32)
    np.testing.assert_allclose(o1, o2, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s1, s2, atol=2e-4, rtol=2e-4)


# ==========================================================================
# mamba2 SSD
# ==========================================================================
SSD_SHAPES = [(2, 100, 3, 8, 16), (1, 64, 2, 16, 32), (1, 31, 1, 8, 8)]


@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("with_state", [False, True])
def test_mamba2_pallas_vs_oracle(shape, with_state):
    b, s, h, p, n = shape
    ks = jax.random.split(jax.random.PRNGKey(5), 7)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bi = jax.random.normal(ks[3], (b, s, n))
    ci = jax.random.normal(ks[4], (b, s, n))
    d = jax.random.normal(ks[5], (h,))
    st0 = (jax.random.normal(ks[6], (b, h, p, n)) * 0.1
           if with_state else None)
    y, fs = mamba2_ssd.mamba2_ssd(x, dt, a, bi, ci, d, st0, chunk=32,
                                  interpret=True)
    wy, wfs = ref.mamba2_ssd(x, dt, a, bi, ci, d, st0)
    np.testing.assert_allclose(y, wy, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(fs, wfs, atol=2e-3, rtol=2e-3)


def test_mamba2_chunked_fallback():
    b, s, h, p, n = 2, 77, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bi = jax.random.normal(ks[3], (b, s, n))
    ci = jax.random.normal(ks[4], (b, s, n))
    d = jax.random.normal(ks[5], (h,))
    y, fs = ref.mamba2_ssd_chunked(x, dt, a, bi, ci, d, chunk=16)
    wy, wfs = ref.mamba2_ssd(x, dt, a, bi, ci, d)
    np.testing.assert_allclose(y, wy, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(fs, wfs, atol=2e-3, rtol=2e-3)


def test_mamba2_decay_property():
    """With dt == 0 the state must pass through unchanged and the output
    must be exactly the D-skip."""
    b, s, h, p, n = 1, 16, 2, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jnp.zeros((b, s, h))
    a = -jnp.ones((h,))
    bi = jax.random.normal(ks[1], (b, s, n))
    ci = jax.random.normal(ks[2], (b, s, n))
    d = jax.random.normal(ks[3], (h,))
    st0 = jnp.zeros((b, h, p, n))
    y, fs = ref.mamba2_ssd_chunked(x, dt, a, bi, ci, d, st0, chunk=8)
    np.testing.assert_allclose(y, d[None, None, :, None] * x, atol=1e-5)
    np.testing.assert_allclose(fs, 0.0, atol=1e-6)


# ==========================================================================
# GP covariance kernel
# ==========================================================================
@pytest.mark.parametrize("n,m,d", [(100, 57, 7), (33, 33, 3), (8, 300, 2)])
@pytest.mark.parametrize("kind", ["rbf", "matern52"])
def test_gp_kernel_pallas_vs_oracle(n, m, d, kind):
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    x1 = jax.random.normal(ks[0], (n, d))
    x2 = jax.random.normal(ks[1], (m, d))
    ls = jnp.exp(jax.random.normal(ks[2], (d,)) * 0.2)
    var = jnp.float32(1.7)
    got = gp_kernel.gp_kernel_matrix(x1, x2, ls, var, kind, block_n=32,
                                     block_m=32, interpret=True)
    want = ref.gp_kernel_matrix(x1, x2, ls, var, kind)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def _gp_predict_operands(key, n, s, d, e=None):
    """Training/query points and a real posterior's alpha and L^-1."""
    ks = jax.random.split(key, 4)
    lead = () if e is None else (e,)
    xt = jax.random.normal(ks[0], lead + (n, d))
    xs = jax.random.normal(ks[1], lead + (s, d))
    ls = jnp.exp(jax.random.normal(ks[2], (d,)) * 0.2) * 1.5
    var = jnp.float32(1.7)

    def factor(x):
        kxx = ref.gp_kernel_matrix(x, x, ls, var) + 0.1 * jnp.eye(n)
        chol = jnp.linalg.cholesky(kxx)
        return jax.scipy.linalg.solve_triangular(chol, jnp.eye(n),
                                                 lower=True)

    linv = factor(xt) if e is None else jax.vmap(factor)(xt)
    alpha = jax.random.normal(ks[3], lead + (n, 2))
    return xt, xs, ls, var, alpha, linv


@pytest.mark.parametrize("n,s,block_n", [(100, 57, 512), (40, 20, 16),
                                         (130, 33, 64), (5, 3, 512)])
@pytest.mark.parametrize("kind", ["rbf", "matern52"])
def test_gp_predict_pallas_vs_oracle(n, s, block_n, kind):
    """The training-dimension tiling (several row/column blocks of L^-1,
    a padded last block) reproduces the one-shot reference."""
    xt, xs, ls, var, alpha, linv = _gp_predict_operands(
        jax.random.PRNGKey(10), n, s, 7)
    got_m, got_q = gp_kernel.gp_predict(xt, xs, ls, var, alpha, linv, kind,
                                        block_s=32, block_n=block_n,
                                        interpret=True)
    want_m, want_q = ref.gp_predict(xt, xs, ls, var, alpha, linv, kind)
    np.testing.assert_allclose(got_m, want_m, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got_q, want_q, atol=2e-5, rtol=2e-5)


def test_gp_predict_experts_pallas_vs_oracle():
    xt, xs, ls, var, alpha, linv = _gp_predict_operands(
        jax.random.PRNGKey(11), 48, 20, 3, e=3)
    got_m, got_q = gp_kernel.gp_predict_experts(
        xt, xs, ls, var, alpha, linv, block_s=16, block_n=16,
        interpret=True)
    want_m, want_q = ref.gp_predict_experts(xt, xs, ls, var, alpha, linv)
    np.testing.assert_allclose(got_m, want_m, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got_q, want_q, atol=2e-5, rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 40), d=st.integers(1, 8), seed=st.integers(0, 999))
def test_gp_kernel_properties(n, d, seed):
    """K(X,X) is symmetric PSD with variance on the diagonal (RBF)."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    ls = jnp.ones((d,))
    k = ref.gp_kernel_matrix(x, x, ls, jnp.float32(2.0), "rbf")
    np.testing.assert_allclose(k, k.T, atol=1e-5)
    np.testing.assert_allclose(np.diag(k), 2.0, atol=1e-5)
    eig = np.linalg.eigvalsh(np.asarray(k))
    assert eig.min() > -1e-4


# ==========================================================================
# dispatcher
# ==========================================================================
def test_ops_dispatcher_modes():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    o_xla = kops.flash_attention(q, k, v, impl="xla")
    o_int = kops.flash_attention(q, k, v, impl="interpret")
    np.testing.assert_allclose(o_xla, o_int, atol=2e-5, rtol=2e-5)
