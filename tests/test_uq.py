"""UQ substrate tests: GP vs closed form, GS2 proxy profile, QoI, samplers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.uq import gp as gp_lib
from repro.uq import gs2_proxy, qoi, sampling
from repro.uq.eigen import EigenModel


# --------------------------------------------------------------------------
# samplers
# --------------------------------------------------------------------------
def test_lhs_stratification():
    """LHS: exactly one sample per 1/n stratum in every dimension."""
    n = 50
    x = sampling.latin_hypercube(n, seed=1)
    lo = np.array([r[1] for r in sampling.GS2_PARAM_RANGES])
    hi = np.array([r[2] for r in sampling.GS2_PARAM_RANGES])
    u = (x - lo) / (hi - lo)
    for d in range(u.shape[1]):
        strata = np.floor(u[:, d] * n).astype(int)
        assert len(set(strata.tolist())) == n


def test_lhs_seeded_repeatable():
    a = sampling.latin_hypercube(20, seed=9)
    b = sampling.latin_hypercube(20, seed=9)
    np.testing.assert_array_equal(a, b)
    c = sampling.latin_hypercube(20, seed=10)
    assert not np.array_equal(a, c)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(5, 60))
def test_halton_in_bounds(n):
    x = sampling.halton(n)
    lo = np.array([r[1] for r in sampling.GS2_PARAM_RANGES])
    hi = np.array([r[2] for r in sampling.GS2_PARAM_RANGES])
    assert np.all(x >= lo - 1e-12) and np.all(x <= hi + 1e-12)


# --------------------------------------------------------------------------
# GS2 proxy
# --------------------------------------------------------------------------
def test_gs2_proxy_deterministic():
    theta = sampling.latin_hypercube(1, seed=2)[0]
    assert gs2_proxy.evaluate(theta) == gs2_proxy.evaluate(theta)


def test_gs2_proxy_runtime_spread():
    """The scheduling-relevant property: a wide, unpredictable runtime
    distribution over the LHS inputs (paper: minutes -> hours)."""
    thetas = sampling.latin_hypercube(40, seed=42)
    rts = gs2_proxy.runtime_table(thetas)
    assert rts.min() >= 60.0 and rts.max() <= 10_800.0
    assert rts.max() / rts.min() > 5.0
    its = [gs2_proxy.iteration_count(t) for t in thetas[:20]]
    assert max(its) / max(min(its), 1) > 3.0


def test_gs2_proxy_drive_increases_growth():
    """More temperature-gradient drive -> larger growth rate (physics
    sanity: eta drives micro-instability)."""
    base = np.array([4.0, 1.0, 3.0, 1.0, 0.05, 0.05, 0.4])
    hot = base.copy()
    hot[3] = 6.0
    g_lo, _ = gs2_proxy.evaluate(base)
    g_hi, _ = gs2_proxy.evaluate(hot)
    assert g_hi > g_lo


# --------------------------------------------------------------------------
# GP regression
# --------------------------------------------------------------------------
def test_gp_matches_closed_form():
    """Posterior mean/var must match a direct numpy evaluation of
    eqs. (3)/(4) with the same hyperparameters."""
    rng = np.random.default_rng(3)
    x = rng.random((12, 2))
    y = np.sin(3 * x[:, 0]) + x[:, 1]
    post = gp_lib.fit(x, y, steps=50)
    xs = rng.random((4, 2))
    mean, var = gp_lib.predict(post, xs)

    ls = np.exp(np.asarray(post.params.log_lengthscale))
    sf = np.exp(np.asarray(post.params.log_variance))
    s2 = np.exp(2 * np.asarray(post.params.log_noise))
    ystd = max(float(y.std()), 1e-8)

    def k(a, b):
        d2 = ((a[:, None] / ls - b[None] / ls) ** 2).sum(-1)
        return sf * np.exp(-0.5 * d2)

    kxx = k(x, x) + (s2 + 1e-5 * (sf + 1.0)) * np.eye(len(x))
    kxs = k(x, xs)
    yc = (y - y.mean()) / ystd
    mean_np = y.mean() + (kxs.T @ np.linalg.solve(kxx, yc)) * ystd
    var_np = (sf - np.sum(kxs * np.linalg.solve(kxx, kxs), axis=0)) * ystd ** 2
    np.testing.assert_allclose(np.asarray(mean)[:, 0], mean_np,
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(var)[:, 0], var_np,
                               atol=1e-3, rtol=2e-2)


def test_gp_interpolates_noiselessly():
    rng = np.random.default_rng(4)
    x = rng.random((25, 3))
    y = np.stack([np.cos(2 * x[:, 0]), x[:, 1] * x[:, 2]], 1)
    post = gp_lib.fit(x, y, steps=250)
    mean, var = gp_lib.predict(post, x)
    assert float(jnp.max(jnp.abs(mean - y))) < 0.05
    # posterior variance at training points << each output's prior variance
    prior = jnp.exp(post.params.log_variance) * post.y_std ** 2   # [M]
    assert bool(jnp.all(jnp.max(var, axis=0) < 0.2 * prior))


@pytest.mark.parametrize("kind", ["rbf", "matern52"])
def test_gp_fit_differentiates_through_pallas_kernel(kind):
    """The NLML gradient reaches the Pallas covariance kernel (custom VJP
    through the jnp reference): the interpret-mode fit is finite and
    lands on the XLA fit's hyperparameters."""
    from repro.kernels import ops as kops
    rng = np.random.default_rng(6)
    x = rng.random((24, 3))
    y = np.stack([np.sin(3 * x[:, 0]) + x[:, 1], x[:, 2] ** 2], 1)
    kops.set_default_impl("interpret")
    try:
        post_int = gp_lib.fit(x, y, kind=kind, steps=60)
    finally:
        kops.set_default_impl("auto")
    kops.set_default_impl("xla")
    try:
        post_xla = gp_lib.fit(x, y, kind=kind, steps=60)
    finally:
        kops.set_default_impl("auto")
    for a, b in zip(jax.tree.leaves(post_int.params.tree()),
                    jax.tree.leaves(post_xla.params.tree())):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
    # alpha is as ill-conditioned as K; the posterior mean is not
    x_star = rng.random((16, 3))
    np.testing.assert_allclose(gp_lib.predict(post_int, x_star)[0],
                               gp_lib.predict(post_xla, x_star)[0],
                               atol=1e-3, rtol=1e-3)


def test_gp_condition_shrinks_uncertainty():
    rng = np.random.default_rng(5)
    x = rng.random((10, 2))
    y = x[:, 0] ** 2
    post = gp_lib.fit(x, y, steps=80)
    x_new = np.array([[0.5, 0.5]])
    _, var_before = gp_lib.predict(post, x_new)
    post2 = gp_lib.condition(post, x_new, np.array([0.25]))
    _, var_after = gp_lib.predict(post2, x_new)
    assert float(var_after[0, 0]) < float(var_before[0, 0])


# --------------------------------------------------------------------------
# QoI integral
# --------------------------------------------------------------------------
def _cheap_model(x):
    """Analytic stand-in with the same (growth, freq) signature."""
    g = 0.3 * x[6] * (1.0 - x[6]) + 0.05 * np.sin(x[1])
    return float(g), float(0.1 * x[1])


def test_qoi_quadrature_converges():
    base = sampling.latin_hypercube(1, seed=6)[0]
    coarse = qoi.quadrature(_cheap_model, base, n_ky=4, n_theta0=4)
    fine = qoi.quadrature(_cheap_model, base, n_ky=16, n_theta0=16)
    finer = qoi.quadrature(_cheap_model, base, n_ky=24, n_theta0=24)
    assert abs(fine.value - finer.value) < abs(coarse.value - finer.value) + 1e-9
    assert finer.n_evals == 24 * 24


def test_qoi_bayesian_quadrature_tracks_direct():
    base = sampling.latin_hypercube(1, seed=7)[0]
    direct = qoi.quadrature(_cheap_model, base, n_ky=16, n_theta0=16)
    bq = qoi.bayesian_quadrature(_cheap_model, base, n_init=8,
                                 n_adaptive=10, seed=0)
    assert bq.n_evals == 18                    # 13x fewer than direct 256
    assert abs(bq.value - direct.value) < max(0.25 * abs(direct.value), 0.02)
    assert bq.uncertainty >= 0.0


# --------------------------------------------------------------------------
# eigen model
# --------------------------------------------------------------------------
def test_eigen_model_deterministic_and_sized():
    m = EigenModel(64)
    a = m([[0]])
    b = m([[0]])
    assert a == b
    assert m.get_output_sizes() == [2]
    assert m.cost_hint(None) > 0
