"""Gaussian-process regression in pure JAX (paper §III-B).

Implements eqs. (3)/(4): posterior mean/variance through a Cholesky solve,
ARD RBF / Matérn-5/2 kernels (covariance assembly via the Pallas
`gp_kernel` on TPU, jnp fallback elsewhere), and marginal-likelihood
training with Adam on log-parameters.  Multi-output (the paper's GP emits
growth rate AND mode frequency) is handled as independent GPs sharing the
kernel matrix — one Cholesky, two solves.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

# Bucketed padding sizes for `predict_batch`: every query batch is padded
# up to one of these row counts (large batches are chunked at the biggest
# bucket), so scoring queues of ANY size compiles at most
# len(PREDICT_BUCKETS) distinct shapes per training-set size — instead of
# one fresh XLA compile per queue length.
PREDICT_BUCKETS = (64, 256, 1024)

# (n_train, padded_s) -> number of batched-predict launches.  Tests assert
# the bucket discipline from this counter; it is diagnostic state only.
predict_batch_shapes: collections.Counter = collections.Counter()

# Optional repro.obs.Tracer: when set, `predict_batch` emits one
# `gp.predict_batch` instant per launch (compile-shape visibility in the
# same trace as the scheduling spans).  Module-level because predict is a
# free function — there is no engine object to hang a tracer on.
_obs_tracer = None


def set_obs_tracer(tracer) -> None:
    """Attach (or detach, with None) the module-wide launch tracer."""
    global _obs_tracer
    _obs_tracer = tracer


def bucket_of(n: int) -> int:
    """The padded row count a chunk of `n` queries compiles at.  Raises
    (never a silent StopIteration — this is exported for external shape
    accounting) for chunks beyond the largest bucket: `predict_batch`
    splits those first, and so should any caller."""
    if n > PREDICT_BUCKETS[-1]:
        raise ValueError(f"chunk of {n} rows exceeds the largest predict "
                         f"bucket ({PREDICT_BUCKETS[-1]}); chunk it first")
    return next(b for b in PREDICT_BUCKETS if n <= b)


def bucket_launches(s: int) -> list:
    """The exact padded-launch sizes `predict_batch` issues for a batch
    of `s` queries: full largest-bucket chunks plus one bucketed
    remainder.  The set of distinct values is the compile-shape bill —
    callers (benchmarks, shape-discipline tests) can assert against it
    without replaying the chunk loop."""
    if s <= 0:
        return []
    cap = PREDICT_BUCKETS[-1]
    full, rest = divmod(s, cap)
    out = [cap] * full
    if rest:
        out.append(bucket_of(rest))
    return out


@dataclasses.dataclass
class GPParams:
    log_lengthscale: jax.Array       # [D]
    log_variance: jax.Array          # []
    log_noise: jax.Array             # []

    @staticmethod
    def init(d: int) -> "GPParams":
        return GPParams(jnp.zeros((d,)), jnp.zeros(()), jnp.log(jnp.float32(0.1)))

    def tree(self):
        return {"ls": self.log_lengthscale, "var": self.log_variance,
                "noise": self.log_noise}

    @staticmethod
    def from_tree(t) -> "GPParams":
        return GPParams(t["ls"], t["var"], t["noise"])


@dataclasses.dataclass
class GPPosterior:
    """Trained GP conditioned on (x, y); y may be [N] or [N, M].
    Outputs are standardised internally (per-column mean/std) — predict()
    returns results on the original scale."""
    params: GPParams
    x: jax.Array                     # [N, D]
    y: jax.Array                     # [N, M] raw observations
    y_mean: jax.Array                # [M]
    y_std: jax.Array                 # [M]
    chol: jax.Array                  # [N, N]
    alpha: jax.Array                 # [N, M]  (K + s2 I)^-1 (y - mean)/std
    kind: str = "rbf"
    # cached L^-1 (inverse Cholesky factor) for the batched predict path
    # (built lazily on the first predict_batch call; condition() rebuilds
    # the posterior so the cache naturally resets).  The quadratic form is
    # ||L^-1 ks||^2 — same conditioning as predict()'s triangular solve,
    # unlike an explicit (K + s2 I)^-1 which underestimates tiny variances
    linv: Optional[jax.Array] = None


def _kernel(params: GPParams, x1, x2, kind: str) -> jax.Array:
    # clip log-params: keeps NLML optimisation from walking the noise or
    # lengthscales into Cholesky-breaking territory
    ls = jnp.exp(jnp.clip(params.log_lengthscale, -5.0, 5.0))
    var = jnp.exp(jnp.clip(params.log_variance, -8.0, 8.0))
    return kops.gp_kernel_matrix(x1, x2, ls, var, kind)


def _chol_factor(params: GPParams, x, kind: str) -> jax.Array:
    n = x.shape[0]
    k = _kernel(params, x, x, kind)
    s2 = jnp.exp(2.0 * jnp.clip(params.log_noise, -5.0, 5.0))
    # jitter scales with the signal variance: keeps the f32 Cholesky
    # conditioned even in the noiseless-interpolation regime the NLML
    # optimum sometimes reaches (large var, lengthscale >> data range)
    var = jnp.exp(jnp.clip(params.log_variance, -8.0, 8.0))
    return jnp.linalg.cholesky(k + (s2 + 1e-5 * (var + 1.0)) * jnp.eye(n))


# Public aliases for the surrogate engines (`repro.uq.engine`): the
# incremental and partitioned backends assemble cross-covariances and
# cap-bounded factors out of the SAME primitives the exact path uses, so
# their results can be pinned to `recondition` at tight tolerance.
kernel_matrix = _kernel
chol_factor = _chol_factor


def nlml(tree, x, y, kind: str = "rbf") -> jax.Array:
    """Negative log marginal likelihood, summed over output columns."""
    params = GPParams.from_tree(tree)
    y2 = y if y.ndim == 2 else y[:, None]
    yc = y2 - jnp.mean(y2, axis=0, keepdims=True)
    n, m = yc.shape
    # K comes from `kops.gp_kernel_matrix` like every other covariance: on
    # TPU the Pallas kernel runs forward and its custom VJP differentiates
    # the jnp reference expression (`kernels/gp_kernel.py`)
    chol = _chol_factor(params, x, kind)
    alpha = jax.scipy.linalg.cho_solve((chol, True), yc)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
    quad = jnp.sum(yc * alpha)
    return 0.5 * (quad + m * logdet + m * n * jnp.log(2.0 * jnp.pi))


@functools.partial(jax.jit, static_argnames=("kind", "steps", "lr"))
def _fit(x, y, kind: str, steps: int, lr: float):
    tree0 = GPParams.init(x.shape[1]).tree()
    grad_fn = jax.value_and_grad(lambda t: nlml(t, x, y, kind))

    clip_lo = {"ls": -5.0, "var": -8.0, "noise": -5.0}
    clip_hi = {"ls": 5.0, "var": 8.0, "noise": 2.0}

    def adam_step(state, _):
        tree, m, v, t = state
        loss, g = grad_fn(tree)
        # a NaN gradient (transient Cholesky breakdown) must not poison
        # the parameters: zero it and let the next step recover
        g = jax.tree.map(lambda a: jnp.nan_to_num(a), g)
        t = t + 1
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        mh = jax.tree.map(lambda a: a / (1 - 0.9 ** t), m)
        vh = jax.tree.map(lambda a: a / (1 - 0.999 ** t), v)
        tree = jax.tree.map(lambda p, a, b: p - lr * a / (jnp.sqrt(b) + 1e-8),
                            tree, mh, vh)
        tree = {k: jnp.clip(x, clip_lo[k], clip_hi[k])
                for k, x in tree.items()}
        return (tree, m, v, t), loss

    zeros = jax.tree.map(jnp.zeros_like, tree0)
    (tree, _, _, _), losses = jax.lax.scan(
        adam_step, (tree0, zeros, zeros, jnp.float32(0)), None, length=steps)
    return tree, losses


def fit(x: jax.Array, y: jax.Array, kind: str = "rbf", steps: int = 200,
        lr: float = 5e-2) -> GPPosterior:
    """Type-II MLE: optimise (lengthscales, variance, noise) by Adam."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    y2 = y if y.ndim == 2 else y[:, None]
    mean = jnp.mean(y2, axis=0)
    std = jnp.maximum(jnp.std(y2, axis=0), 1e-8)
    yn = (y2 - mean) / std
    tree, _ = _fit(x, yn, kind, steps, lr)
    params = GPParams.from_tree(tree)
    chol = _chol_factor(params, x, kind)
    alpha = jax.scipy.linalg.cho_solve((chol, True), yn)
    return GPPosterior(params=params, x=x, y=y2, y_mean=mean, y_std=std,
                       chol=chol, alpha=alpha, kind=kind)


@functools.partial(jax.jit, static_argnames=("kind",))
def _predict(params_tree, x_train, y_mean, y_std, chol, alpha, x_star, kind):
    params = GPParams.from_tree(params_tree)
    ks = _kernel(params, x_train, x_star, kind)                 # [N, S]
    mean = y_mean[None] + jnp.matmul(
        ks.T, alpha, precision=jax.lax.Precision.HIGHEST) * y_std[None]
    v = jax.scipy.linalg.solve_triangular(chol, ks, lower=True)  # [N, S]
    prior = jnp.exp(params.log_variance)
    var = jnp.maximum(prior - jnp.sum(v * v, axis=0), 1e-12)    # [S]
    # original scale PER OUTPUT: the outputs were standardised per column,
    # so the latent variance maps back through each column's own y_std^2 —
    # pooling the scale (mean(y_std)^2) is wrong for every column whenever
    # the outputs differ in magnitude (growth rate vs mode frequency)
    var = var[:, None] * (y_std ** 2)[None, :]                  # [S, M]
    return mean, var


def predict(post: GPPosterior, x_star: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """Posterior mean [S, M] and per-output variance [S, M] at x_star
    (eqs. 3-4); the latent variance is shared across outputs (one kernel),
    scaled back by each column's standardisation std."""
    x_star = jnp.asarray(x_star, jnp.float32)
    if x_star.ndim == 1:
        x_star = x_star[None]
    return _predict(post.params.tree(), post.x, post.y_mean, post.y_std,
                    post.chol, post.alpha, x_star, post.kind)


def _ensure_linv(post: GPPosterior) -> jax.Array:
    """Cache L^-1 on the posterior: the batched predict path trades one
    triangular inversion at first use for a predict that is a single
    fused launch (no per-call triangular solve).

    Staleness contract: `linv` is valid iff it matches `chol`.  Every
    update path constructs a NEW GPPosterior (`recondition`, `fit`, the
    engine block-update), so a cached inverse can never outlive its
    factor on an aliased posterior — `invalidate_linv` exists for code
    that mutates a posterior's factor in place (none in-tree; the
    regression test in test_surrogate_engine.py holds the line)."""
    if post.linv is None:
        n = post.x.shape[0]
        post.linv = jax.scipy.linalg.solve_triangular(
            post.chol, jnp.eye(n, dtype=jnp.float32), lower=True)
    return post.linv


# public alias: the engines maintain / rebuild this cache explicitly
ensure_linv = _ensure_linv


def invalidate_linv(post: GPPosterior) -> None:
    """Drop the cached L^-1 so the next `predict_batch` rebuilds it.
    Required after any in-place change to `post.chol` — serving a stale
    inverse silently corrupts every batched variance."""
    post.linv = None


@functools.partial(jax.jit, static_argnames=("kind",))
def _predict_batch(params_tree, x_train, y_mean, y_std, linv, alpha,
                   x_star, kind):
    params = GPParams.from_tree(params_tree)
    ls = jnp.exp(jnp.clip(params.log_lengthscale, -5.0, 5.0))
    var = jnp.exp(jnp.clip(params.log_variance, -8.0, 8.0))
    mean_n, qf = kops.gp_predict(x_train, x_star, ls, var, alpha, linv, kind)
    mean = y_mean[None] + mean_n * y_std[None]                  # [S, M]
    lat = jnp.maximum(var - qf, 1e-12)                          # [S]
    return mean, lat[:, None] * (y_std ** 2)[None, :]           # [S, M]


def predict_batch(post: GPPosterior, x_star: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Bucket-padded batched posterior predict: mean [S, M], variance
    [S, M].

    Same contract as `predict`, but the query batch is padded up to a
    fixed bucket size (`PREDICT_BUCKETS`; oversize batches are chunked at
    the largest bucket) and evaluated through the one-launch
    `kops.gp_predict` path (Pallas on TPU, fused XLA elsewhere).  Scoring
    a whole dispatch queue therefore hits at most len(PREDICT_BUCKETS)
    distinct compile shapes per training-set size, instead of one fresh
    XLA compile per queue length — the per-task `predict` calls the
    offload router would otherwise issue.
    """
    x_star = jnp.asarray(x_star, jnp.float32)
    if x_star.ndim == 1:
        x_star = x_star[None]
    s = x_star.shape[0]
    if s == 0:
        m = post.y.shape[1]
        return (jnp.zeros((0, m), jnp.float32),
                jnp.zeros((0, m), jnp.float32))
    linv = _ensure_linv(post)
    tree = post.params.tree()
    cap = PREDICT_BUCKETS[-1]
    means, variances = [], []
    for lo in range(0, s, cap):
        chunk = x_star[lo:lo + cap]
        bucket = bucket_of(chunk.shape[0])
        pad = bucket - chunk.shape[0]
        if pad:
            chunk = jnp.pad(chunk, ((0, pad), (0, 0)))
        predict_batch_shapes[(int(post.x.shape[0]), bucket)] += 1
        if _obs_tracer is not None:
            _obs_tracer.instant(
                "gp.predict_batch",
                args={"n": int(chunk.shape[0]) - pad, "bucket": bucket,
                      "train_n": int(post.x.shape[0])})
        mean, var = _predict_batch(tree, post.x, post.y_mean, post.y_std,
                                   linv, post.alpha, chunk, post.kind)
        means.append(mean[:bucket - pad])
        variances.append(var[:bucket - pad])
    if len(means) == 1:
        return means[0], variances[0]
    return jnp.concatenate(means), jnp.concatenate(variances)


def recondition(post: GPPosterior, x: jax.Array, y: jax.Array
                ) -> GPPosterior:
    """Posterior with the SAME hyperparameters on a replacement dataset
    (recency-capped surrogates, sliding windows): one Cholesky rebuild,
    no re-training."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    y2 = y if y.ndim == 2 else y[:, None]
    mean = jnp.mean(y2, axis=0)
    std = jnp.maximum(jnp.std(y2, axis=0), 1e-8)
    chol = _chol_factor(post.params, x, post.kind)
    alpha = jax.scipy.linalg.cho_solve((chol, True), (y2 - mean) / std)
    return GPPosterior(params=post.params, x=x, y=y2, y_mean=mean,
                       y_std=std, chol=chol, alpha=alpha, kind=post.kind)


def coerce_new_data(x_new: jax.Array, y_new: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Normalise a conditioning batch to (x [K, D], y [K, M]): a 1-D y is
    a column when x carries several rows, and a single multi-output row
    otherwise.  Shared by `condition` and every engine backend so all
    conditioning paths accept identical shapes."""
    x_new = jnp.atleast_2d(jnp.asarray(x_new, jnp.float32))
    y_new2 = jnp.asarray(y_new, jnp.float32)
    if y_new2.ndim == 1:
        y_new2 = y_new2[:, None] if x_new.shape[0] > 1 else y_new2[None, :]
    return x_new, y_new2


def condition(post: GPPosterior, x_new: jax.Array, y_new: jax.Array
              ) -> GPPosterior:
    """Add observations and re-condition (adaptive/Bayesian-quadrature use);
    hyperparameters are kept — only the Cholesky is rebuilt."""
    x_new, y_new2 = coerce_new_data(x_new, y_new)
    return recondition(post, jnp.concatenate([post.x, x_new]),
                       jnp.concatenate([post.y, y_new2]))
