"""Pluggable surrogate engines: exact, incremental, and partitioned GPs.

`repro.uq.gp` is the scheduler's brain — runtime prediction
(`sched.predictor`), offload trust gates (`sched.offload`), adaptive
delegation and Bayesian quadrature (`uq.adaptive` / `uq.qoi`) all
condition one posterior online.  Every one of those consumers used to
pay an exact Cholesky refit — O(n³) per update — so at the 10⁵–10⁶
completions the paper's UQ workloads produce, the surrogate becomes the
bottleneck PR 5 removed from the queues.  This module makes the
conditioning path pluggable behind one `SurrogateEngine` interface:

  * ``exact`` — the reference: every `condition` is a full
    re-factorisation (`gp.recondition`).  O(n³) per update, bitwise the
    pre-refactor behaviour; the default everywhere.
  * ``incremental`` — rank-k block Cholesky *updates*: conditioning on
    a batch of k new points extends the existing factor L (and its
    cached inverse, so `predict_batch` never re-inverts) in O(n²k)
    instead of refactoring in O(n³).  Periodic full re-factorisation
    (``refactor_every``) plus a finite-ness check keep f32 drift and
    near-singular blocks from accumulating — the same hygiene HPC
    always-on services apply to refit-from-scratch state (Balsam,
    PAPERS.md).
  * ``partitioned`` — a local-GP ensemble routed by input region:
    recursive median splits bound every expert at ``expert_cap``
    points, so conditioning is O(cap³) *per affected expert* no matter
    how large the training set grows, and predict fans out through ONE
    fused multi-expert launch (`kops.gp_predict_experts`, Pallas on
    TPU) with optional multi-device sharding over the expert axis.
    Predictions are approximate (each query answered by its region's
    expert); the differential suite bounds the error.

Engines are *persistent* (functional): `condition` / `recondition`
return a NEW engine sharing hyperparameters, so the thread-safety
patterns the consumers already use (install-if-not-raced under a lock,
expensive math outside it) carry over unchanged.  Every engine keeps
the `gp.predict_batch` bucket discipline — scoring any queue costs a
bounded set of compile shapes.

Backend choice in one line: ``exact`` until conditioning shows up in a
profile; ``incremental`` when one posterior must absorb an unbounded
completion stream; ``partitioned`` when the training set itself must
scale past what one Cholesky can hold.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, List, Optional, Protocol, Sequence, Tuple, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.uq import gp as gp_lib

BACKENDS = ("exact", "incremental", "partitioned")


@runtime_checkable
class SurrogateEngine(Protocol):
    """What every consumer of the posterior needs from a backend."""

    backend: str

    def n_train(self) -> int: ...
    def dim(self) -> int: ...
    def n_outputs(self) -> int: ...
    def condition(self, x_new, y_new) -> "SurrogateEngine": ...
    def recondition(self, x, y) -> "SurrogateEngine": ...
    def predict(self, x_star) -> Tuple[jax.Array, jax.Array]: ...
    def predict_batch(self, x_star) -> Tuple[jax.Array, jax.Array]: ...
    def latent_sd(self, thetas) -> np.ndarray: ...


class _EngineBase:
    """Shared surface: data views and the latent-sd trust metric."""

    backend = "base"

    # subclasses define .x / .y / .y_std / .kind / .params
    def n_train(self) -> int:
        return int(self.x.shape[0])

    def dim(self) -> int:
        return int(self.x.shape[1])

    def n_outputs(self) -> int:
        return int(self.y.shape[1])

    def latent_sd(self, thetas) -> np.ndarray:
        """Standardised (latent) posterior sd at each theta: the
        dimensionless trust metric the offload gate thresholds — one
        bucket-padded `predict_batch` pass for the whole batch."""
        _, var = self.predict_batch(np.asarray(thetas, np.float32))
        return (np.sqrt(np.asarray(var)[:, 0])
                / max(float(self.y_std[0]), 1e-12))

    def warm(self) -> None:
        """Pre-compile the single-row predict bucket (push-time trust
        checks run under dispatch locks — never stall them on XLA).  A
        compile error raises here, where it is cheapest to see."""
        self.predict_batch(np.asarray(self.x[:1], np.float32))


# ===========================================================================
# exact — the O(n³) reference path
# ===========================================================================
class ExactEngine(_EngineBase):
    """The pre-refactor behaviour behind the engine interface: every
    `condition` re-factorises from scratch (`gp.recondition`, one fresh
    O(n³) Cholesky), with the same most-recent-``max_points`` window the
    consumers applied by hand.  Kept as the differential reference the
    other backends are pinned against."""

    backend = "exact"

    def __init__(self, post: gp_lib.GPPosterior, *,
                 max_points: Optional[int] = None):
        self.post = post
        self.max_points = max_points

    # -- views -----------------------------------------------------------
    @property
    def x(self):
        return self.post.x

    @property
    def y(self):
        return self.post.y

    @property
    def y_std(self):
        return self.post.y_std

    @property
    def params(self):
        return self.post.params

    @property
    def kind(self):
        return self.post.kind

    # -- predict ---------------------------------------------------------
    def predict(self, x_star):
        return gp_lib.predict(self.post, x_star)

    def predict_batch(self, x_star):
        return gp_lib.predict_batch(self.post, x_star)

    # -- conditioning ----------------------------------------------------
    def _merged(self, x_new, y_new):
        x_new, y_new2 = gp_lib.coerce_new_data(x_new, y_new)
        x_all = jnp.concatenate([self.post.x, x_new])
        y_all = jnp.concatenate([self.post.y, y_new2])
        if self.max_points and x_all.shape[0] > self.max_points:
            x_all = x_all[-self.max_points:]   # keep the most recent
            y_all = y_all[-self.max_points:]
        return x_all, y_all

    def condition(self, x_new, y_new) -> "ExactEngine":
        x_all, y_all = self._merged(x_new, y_new)
        return type(self)(gp_lib.recondition(self.post, x_all, y_all),
                          max_points=self.max_points)

    def recondition(self, x, y) -> "ExactEngine":
        return type(self)(gp_lib.recondition(self.post, x, y),
                          max_points=self.max_points)


# ===========================================================================
# incremental — rank-k block Cholesky updates
# ===========================================================================
def _np_params(params: gp_lib.GPParams) -> Tuple[np.ndarray, float, float]:
    """(lengthscale, variance, jitter) with the SAME clips and diagonal
    load as `gp._chol_factor` — the block update must extend the factor
    the exact path would have built."""
    ls = np.exp(np.clip(np.asarray(params.log_lengthscale, np.float32),
                        -5.0, 5.0))
    var = float(np.exp(np.clip(float(params.log_variance), -8.0, 8.0)))
    s2 = float(np.exp(2.0 * np.clip(float(params.log_noise), -5.0, 5.0)))
    return ls, var, s2 + 1e-5 * (var + 1.0)


def _np_kernel(params: gp_lib.GPParams, x1: np.ndarray, x2: np.ndarray,
               kind: str) -> np.ndarray:
    """`kernels.ref.gp_kernel_matrix` in numpy (f32, same formulas) —
    the update path stays off the XLA eager dispatcher entirely."""
    import math
    ls, var, _ = _np_params(params)
    x1s = (x1 / ls).astype(np.float32)
    x2s = (x2 / ls).astype(np.float32)
    d2 = ((x1s ** 2).sum(-1)[:, None] + (x2s ** 2).sum(-1)[None, :]
          - 2.0 * x1s @ x2s.T)
    d2 = np.maximum(d2, 0.0)
    if kind == "rbf":
        k = np.exp(-0.5 * d2)
    elif kind == "matern52":
        r = np.sqrt(d2 + 1e-12)
        k = (1.0 + math.sqrt(5.0) * r + 5.0 / 3.0 * d2) \
            * np.exp(-math.sqrt(5.0) * r)
    else:
        raise ValueError(kind)
    return (var * k).astype(np.float32)


def _np_solve_tri(a: np.ndarray, b: np.ndarray,
                  trans: str = "N") -> np.ndarray:
    import scipy.linalg
    return scipy.linalg.solve_triangular(a, b, lower=True, trans=trans,
                                         check_finite=False)


def _np_alpha(chol: np.ndarray, yn: np.ndarray) -> np.ndarray:
    """K⁻¹yn by two backward-stable triangular solves (LAPACK) — the
    explicit-inverse product (linvᵀ(linv·yn)) loses ~cond(K)·eps of
    accuracy, which is exactly the drift the differential suite pins."""
    return _np_solve_tri(chol, _np_solve_tri(chol, yn), trans="T")


class _IncrementalState:
    """Growable append-only numpy storage for one factor lineage.

    The Cholesky factor, its inverse, and the training window live in
    capacity-padded buffers; each engine generation pins its own fill
    level `n` and reads the [:n] views, which are frozen the moment they
    are written — appending rows [n, n+k) never touches them, so every
    generation's view stays valid forever (persistence without copying
    O(n²) state per update).  Appends go through `append` under the
    lock: only the lineage tip may extend in place; a raced or forked
    append — or one past capacity — copies the prefix into fresh
    buffers (amortised by 1.25x capacity slack) and extends there."""

    def __init__(self, n: int, cap: int, d: int, m: int):
        self.lock = threading.Lock()
        self.n = n
        self.chol = np.zeros((cap, cap), np.float32)
        self.linv = np.zeros((cap, cap), np.float32)
        self.x = np.zeros((cap, d), np.float32)
        self.y = np.zeros((cap, m), np.float32)

    @classmethod
    def from_arrays(cls, chol, linv, x, y) -> "_IncrementalState":
        n = chol.shape[0]
        st = cls(n, n, x.shape[1], y.shape[1])
        st.chol[:n, :n] = chol
        st.linv[:n, :n] = linv
        st.x[:n] = x
        st.y[:n] = y
        return st

    def _fork(self, n: int, need: int) -> "_IncrementalState":
        cap = max(need, (need * 5) // 4 + 16)
        st = _IncrementalState(n, cap, self.x.shape[1], self.y.shape[1])
        st.chol[:n, :n] = self.chol[:n, :n]
        st.linv[:n, :n] = self.linv[:n, :n]
        st.x[:n] = self.x[:n]
        st.y[:n] = self.y[:n]
        return st

    def append(self, n: int, x_new, y_new, s12, s22, li21, li22
               ) -> Tuple["_IncrementalState", bool]:
        """Write the new factor block after row n; returns the state
        holding the result and whether a fork (copy) was needed."""
        k = x_new.shape[0]
        with self.lock:
            forked = self.n != n or self.chol.shape[0] < n + k
            st = self._fork(n, n + k) if forked else self
            st.chol[n:n + k, :n] = s12.T
            st.chol[n:n + k, n:n + k] = s22
            st.linv[n:n + k, :n] = li21
            st.linv[n:n + k, n:n + k] = li22
            st.x[n:n + k] = x_new
            st.y[n:n + k] = y_new
            st.n = n + k
        return st, forked


class IncrementalEngine(_EngineBase):
    """O(n²k) conditioning by extending the Cholesky factor in place of
    rebuilding it.

    For new points X_k against the factored K_n = L Lᵀ:

        L' = [[L,    0  ],          S21 = (L⁻¹ K(X_n, X_k))ᵀ
              [S21,  S22]],         S22 S22ᵀ = K_kk − S21 S21ᵀ

    and the cached inverse factor extends the same way
    (L'⁻¹ = [[L⁻¹, 0], [−S22⁻¹ S21 L⁻¹, S22⁻¹]]), so the fused
    `predict_batch` path never pays the O(n³) triangular inversion the
    exact engine re-runs after every update.  The observation
    standardisation and alpha are recomputed over the full window —
    two O(n²m) BLAS products against the maintained inverse, not a
    refactor.

    The factor lineage lives in `_IncrementalState`'s growable numpy
    buffers: an update computes three O(n²k) BLAS products and WRITES
    only the O(nk) new block (old generations keep reading their frozen
    prefix views), so per-batch cost is two orders of magnitude under
    an O(n³) refactorisation — and entirely off the XLA eager
    dispatcher, whose CPU triangular solves and whole-matrix rebuilds
    were costing nearly as much as the refactor they replaced.  The
    predict paths still run through `gp.predict_batch` (bucketed fused
    launches) against a per-generation lazily materialised
    `GPPosterior`.

    Numerical hygiene: every ``refactor_every`` updates — and whenever
    the update block comes out non-positive-definite (near-singular
    S22) or the recency window slides (`max_points`) — the engine falls
    back to one exact re-factorisation, bounding f32 drift.
    """

    backend = "incremental"

    def __init__(self, post: Optional[gp_lib.GPPosterior] = None, *,
                 max_points: Optional[int] = None,
                 refactor_every: int = 64,
                 _internal: Optional[tuple] = None):
        self.max_points = max_points
        self.refactor_every = refactor_every
        self._post_cache: Optional[gp_lib.GPPosterior] = None
        if _internal is not None:
            (self.params, self.kind, self.y_mean, self.y_std,
             self._state, self._n, self._alpha, self._updates,
             self.stats) = _internal
            return
        self.params = post.params
        self.kind = post.kind
        self.y_mean = np.asarray(post.y_mean, np.float32)
        self.y_std = np.asarray(post.y_std, np.float32)
        chol = np.asarray(post.chol, np.float32)
        linv = post.linv
        linv = np.asarray(linv, np.float32) if linv is not None else \
            _np_solve_tri(chol, np.eye(chol.shape[0], dtype=np.float32))
        self._state = _IncrementalState.from_arrays(
            chol, linv, np.asarray(post.x, np.float32),
            np.asarray(post.y, np.float32))
        self._n = chol.shape[0]
        self._alpha = np.asarray(post.alpha, np.float32)
        self._updates = 0                      # block updates since refactor
        # carried across persistent copies: diagnostics for tests/benchmarks
        self.stats = {"block_updates": 0, "refactors": 0, "forks": 0}

    def _successor(self, state, n, alpha, y_mean, y_std, *,
                   updates) -> "IncrementalEngine":
        return IncrementalEngine(
            max_points=self.max_points, refactor_every=self.refactor_every,
            _internal=(self.params, self.kind, y_mean, y_std,
                       state, n, alpha, updates, self.stats))

    # -- views -----------------------------------------------------------
    @property
    def x(self) -> np.ndarray:
        return self._state.x[:self._n]

    @property
    def y(self) -> np.ndarray:
        return self._state.y[:self._n]

    @property
    def post(self) -> gp_lib.GPPosterior:
        """This generation's `GPPosterior`, materialised to jax arrays
        on first use (one device copy per conditioning generation, paid
        off the conditioning path) — the predict-side consumers and the
        `.posterior` introspection surface read this."""
        if self._post_cache is None:
            n = self._n
            self._post_cache = gp_lib.GPPosterior(
                params=self.params,
                x=jnp.asarray(self._state.x[:n]),
                y=jnp.asarray(self._state.y[:n]),
                y_mean=jnp.asarray(self.y_mean),
                y_std=jnp.asarray(self.y_std),
                chol=jnp.asarray(self._state.chol[:n, :n]),
                alpha=jnp.asarray(self._alpha), kind=self.kind,
                linv=jnp.asarray(self._state.linv[:n, :n]))
        return self._post_cache

    # -- predict ---------------------------------------------------------
    def predict(self, x_star):
        return gp_lib.predict(self.post, x_star)

    def predict_batch(self, x_star):
        return gp_lib.predict_batch(self.post, x_star)

    # -- conditioning ----------------------------------------------------
    def condition(self, x_new, y_new) -> "IncrementalEngine":
        x_new, y_new2 = gp_lib.coerce_new_data(x_new, y_new)
        x_new = np.asarray(x_new, np.float32)
        y_new2 = np.asarray(y_new2, np.float32)
        n, k = self._n, x_new.shape[0]
        slides = self.max_points and n + k > self.max_points
        if slides or self._updates + 1 >= self.refactor_every:
            x_all = np.concatenate([self.x, x_new])
            y_all = np.concatenate([self.y, y_new2])
            if slides:
                x_all = x_all[-self.max_points:]
                y_all = y_all[-self.max_points:]
            return self._refactor(x_all, y_all)
        st = self._state
        linv_v = st.linv[:n, :n]
        b = _np_kernel(self.params, self.x, x_new, self.kind)  # [n, k]
        _, _, jitter = _np_params(self.params)
        c = _np_kernel(self.params, x_new, x_new, self.kind) \
            + jitter * np.eye(k, dtype=np.float32)
        # L⁻¹b via the maintained inverse factor: a strided BLAS gemm.
        # (solve_triangular on the [n, n] buffer view forces an O(n²)
        # F-contiguous copy per call — the copy, not the math, dominated
        # the conditioning latency at n=5k.)  Drift from the explicit
        # inverse is bounded by the periodic refactor and the Cholesky
        # breakdown fallback below.
        s12 = linv_v @ b                                       # [n, k]
        try:
            s22 = np.linalg.cholesky(c - s12.T @ s12)          # [k, k]
        except np.linalg.LinAlgError:                          # breakdown
            return self._refactor(np.concatenate([self.x, x_new]),
                                  np.concatenate([self.y, y_new2]))
        li22 = _np_solve_tri(s22, np.eye(k, dtype=np.float32))
        li21 = -(li22 @ (s12.T @ linv_v))                      # [k, n]
        state, forked = st.append(n, x_new, y_new2, s12, s22, li21, li22)
        # alpha over the full window: the standardisation tracks the
        # stream (same as exact).  Two strided gemv against the
        # maintained L⁻¹ instead of triangular solves — same copy
        # avoidance as s12 above; the refactor recomputes alpha with
        # backward-stable solves and resets any accumulated drift.
        y_all = state.y[:n + k]
        mean = y_all.mean(axis=0, dtype=np.float32)
        std = np.maximum(y_all.std(axis=0, dtype=np.float32), 1e-8)
        linv2 = state.linv[:n + k, :n + k]
        alpha = linv2.T @ (linv2 @ ((y_all - mean) / std))
        self.stats["block_updates"] += 1
        if forked:
            self.stats["forks"] += 1
        return self._successor(state, n + k, alpha, mean, std,
                               updates=self._updates + 1)

    def recondition(self, x, y) -> "IncrementalEngine":
        y = np.asarray(y, np.float32)
        return self._refactor(np.asarray(x, np.float32),
                              y if y.ndim == 2 else y[:, None])

    def _refactor(self, x_all: np.ndarray, y_all: np.ndarray
                  ) -> "IncrementalEngine":
        n = x_all.shape[0]
        _, _, jitter = _np_params(self.params)
        kmat = _np_kernel(self.params, x_all, x_all, self.kind) \
            + jitter * np.eye(n, dtype=np.float32)
        chol = np.linalg.cholesky(kmat)
        linv = _np_solve_tri(chol, np.eye(n, dtype=np.float32))
        mean = y_all.mean(axis=0, dtype=np.float32)
        std = np.maximum(y_all.std(axis=0, dtype=np.float32), 1e-8)
        alpha = _np_alpha(chol, (y_all - mean) / std)
        state = _IncrementalState.from_arrays(chol, linv, x_all, y_all)
        self.stats["refactors"] += 1
        return self._successor(state, n, alpha, mean, std, updates=0)


# ===========================================================================
# partitioned — region-routed local-GP ensemble
# ===========================================================================
@dataclasses.dataclass(frozen=True)
class _Expert:
    """One local GP: immutable once factored (persistent engines share
    untouched experts across conditioning generations)."""
    x: jax.Array                     # [n, d]
    y: jax.Array                     # [n, m] raw
    chol: jax.Array                  # [n, n]
    alpha: jax.Array                 # [n, m]
    linv: jax.Array                  # [n, n]
    centroid: np.ndarray             # [d] routing key


def _factor_expert(params: gp_lib.GPParams, kind: str, x, y,
                   y_mean, y_std) -> _Expert:
    """Exact factorisation of one cap-bounded expert (O(cap³) — the
    bounded cost the partitioning exists to guarantee) under the SHARED
    standardisation, so expert predictions live on one scale."""
    chol = gp_lib.chol_factor(params, x, kind)
    alpha = jax.scipy.linalg.cho_solve((chol, True), (y - y_mean) / y_std)
    linv = jax.scipy.linalg.solve_triangular(
        chol, jnp.eye(int(x.shape[0]), dtype=jnp.float32), lower=True)
    return _Expert(x=x, y=y, chol=chol, alpha=alpha, linv=linv,
                   centroid=np.asarray(x, np.float64).mean(axis=0))


def _median_parts(x_np: np.ndarray, idx: np.ndarray,
                  cap: int) -> List[np.ndarray]:
    """Recursive median split along the widest dimension until every
    part holds at most `cap` points — deterministic, no RNG."""
    if len(idx) <= cap:
        return [idx]
    sub = x_np[idx]
    dim = int(np.argmax(sub.max(axis=0) - sub.min(axis=0)))
    order = np.argsort(sub[:, dim], kind="stable")
    half = len(idx) // 2
    return (_median_parts(x_np, idx[order[:half]], cap)
            + _median_parts(x_np, idx[order[half:]], cap))


class PartitionedEngine(_EngineBase):
    """Local-GP ensemble routed by nearest expert centroid.

    Every expert holds at most ``expert_cap`` training points, so
    conditioning re-factors only the experts that received new points —
    O(cap³) each, independent of the total training-set size — and an
    expert that outgrows the cap splits at the median of its widest
    dimension.  Predict routes each query to its nearest centroid and
    answers ALL experts' routed queries in one fused stacked launch
    (`kops.gp_predict_experts`: Pallas on TPU, vmapped XLA elsewhere),
    optionally sharded over the expert axis across devices
    (``shard=True``; effective on the XLA path when the expert count
    divides the device count).

    The standardisation (y_mean / y_std) is FROZEN at fit time — experts
    must share one output scale — so unlike exact/incremental the
    normalisation does not track the conditioned stream; the
    differential suite bounds the resulting predictive error.
    ``max_points`` is accepted for interface parity and ignored: memory
    is already bounded per expert, and evicting old regions would
    silently forget calibrated parts of the input space.
    """

    backend = "partitioned"

    def __init__(self, params: gp_lib.GPParams, kind: str, y_mean, y_std,
                 experts: Sequence[_Expert], *, expert_cap: int = 128,
                 shard: bool = False, _stats: Optional[dict] = None):
        self.params = params
        self.kind = kind
        self.y_mean = y_mean
        self.y_std = y_std
        self.experts = list(experts)
        self.expert_cap = int(expert_cap)
        self.shard = shard
        self.stats = _stats if _stats is not None else \
            {"splits": 0, "expert_refactors": 0}
        self._stack = None                     # cached fused-predict operands
        self._centroids = None                 # cached [E, d] routing matrix

    # -- construction ----------------------------------------------------
    @classmethod
    def fit(cls, x, y, *, expert_cap: int = 128, kind: str = "rbf",
            steps: int = 200, lr: float = 5e-2, fit_subsample: int = 512,
            shard: bool = False, **_ignored) -> "PartitionedEngine":
        """Train hyperparameters on a bounded subsample (type-II MLE is
        itself O(steps·n³) — the wall this backend removes), standardise
        over the FULL data, then partition and factor the experts."""
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        y2 = y if y.ndim == 2 else y[:, None]
        n = int(x.shape[0])
        stride = max(1, -(-n // max(int(fit_subsample), 1)))
        base = gp_lib.fit(x[::stride], y2[::stride], kind=kind,
                          steps=steps, lr=lr)
        y_mean = jnp.mean(y2, axis=0)
        y_std = jnp.maximum(jnp.std(y2, axis=0), 1e-8)
        return cls._build(base.params, kind, y_mean, y_std, x, y2,
                          expert_cap=expert_cap, shard=shard)

    @classmethod
    def from_posterior(cls, post: gp_lib.GPPosterior, *,
                       expert_cap: int = 128, shard: bool = False,
                       **_ignored) -> "PartitionedEngine":
        """Re-partition an already-trained posterior's data under its
        hyperparameters and standardisation."""
        return cls._build(post.params, post.kind, post.y_mean, post.y_std,
                          post.x, post.y, expert_cap=expert_cap,
                          shard=shard)

    @classmethod
    def _build(cls, params, kind, y_mean, y_std, x, y2, *,
               expert_cap: int, shard: bool,
               _stats: Optional[dict] = None) -> "PartitionedEngine":
        x_np = np.asarray(x, np.float64)
        parts = _median_parts(x_np, np.arange(len(x_np)), expert_cap)
        experts = [_factor_expert(params, kind, x[ids], y2[ids],
                                  y_mean, y_std) for ids in parts]
        return cls(params, kind, y_mean, y_std, experts,
                   expert_cap=expert_cap, shard=shard, _stats=_stats)

    # -- views -----------------------------------------------------------
    @property
    def x(self):
        return jnp.concatenate([e.x for e in self.experts])

    @property
    def y(self):
        return jnp.concatenate([e.y for e in self.experts])

    def n_train(self) -> int:
        return sum(int(e.x.shape[0]) for e in self.experts)

    # -- routing ---------------------------------------------------------
    def _route(self, x_star: np.ndarray) -> np.ndarray:
        """Nearest-centroid expert index per query row."""
        if self._centroids is None:
            self._centroids = np.stack([e.centroid for e in self.experts])
        d2 = ((x_star[:, None, :].astype(np.float64)
               - self._centroids[None, :, :]) ** 2).sum(axis=2)
        return np.argmin(d2, axis=1)

    # -- conditioning ----------------------------------------------------
    def condition(self, x_new, y_new) -> "PartitionedEngine":
        x_new, y_new2 = gp_lib.coerce_new_data(x_new, y_new)
        x_np = np.asarray(x_new, np.float64)
        routed = self._route(x_np)
        experts = list(self.experts)
        for eidx in np.unique(routed):
            rows = np.nonzero(routed == eidx)[0]
            e = experts[eidx]
            x_e = jnp.concatenate([e.x, x_new[rows]])
            y_e = jnp.concatenate([e.y, y_new2[rows]])
            if int(x_e.shape[0]) > self.expert_cap:
                # split at the median of the widest dimension: two
                # cap-bounded experts replace the overgrown one
                parts = _median_parts(np.asarray(x_e, np.float64),
                                      np.arange(int(x_e.shape[0])),
                                      self.expert_cap)
                halves = [_factor_expert(self.params, self.kind, x_e[ids],
                                         y_e[ids], self.y_mean, self.y_std)
                          for ids in parts]
                experts[eidx] = halves[0]
                experts.extend(halves[1:])
                self.stats["splits"] += 1
            else:
                experts[eidx] = _factor_expert(self.params, self.kind,
                                               x_e, y_e, self.y_mean,
                                               self.y_std)
            self.stats["expert_refactors"] += 1
        return PartitionedEngine(self.params, self.kind, self.y_mean,
                                 self.y_std, experts,
                                 expert_cap=self.expert_cap,
                                 shard=self.shard, _stats=self.stats)

    def recondition(self, x, y) -> "PartitionedEngine":
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        y2 = y if y.ndim == 2 else y[:, None]
        return self._build(self.params, self.kind, self.y_mean, self.y_std,
                           x, y2, expert_cap=self.expert_cap,
                           shard=self.shard, _stats=self.stats)

    # -- fused predict ---------------------------------------------------
    def _stacked(self):
        """Stacked fused-predict operands [E, n_max, ...], zero-padded
        (padded training rows are exact: alpha and linv are zero there).
        Cached per engine generation — conditioning returns a NEW engine,
        so a stale stack can never serve post-condition predictions."""
        if self._stack is None:
            n_max = max(int(e.x.shape[0]) for e in self.experts)
            d = self.dim()
            m = self.n_outputs()

            def padded(a, rows, *cols):
                pad = [(0, rows - a.shape[0])] + \
                    [(0, c - s) for c, s in zip(cols, a.shape[1:])]
                return jnp.pad(a, pad)

            xt = jnp.stack([padded(e.x, n_max, d) for e in self.experts])
            al = jnp.stack([padded(e.alpha, n_max, m)
                            for e in self.experts])
            li = jnp.stack([padded(e.linv, n_max, n_max)
                            for e in self.experts])
            self._stack = self._maybe_shard((xt, al, li))
        return self._stack

    def _maybe_shard(self, arrs):
        """Expert-axis sharding across devices; unsharded on one device or
        when the expert count does not divide the device count.  Any other
        sharding error raises."""
        if not self.shard:
            return arrs
        devs = jax.devices()
        if len(devs) < 2 or len(self.experts) % len(devs):
            return arrs
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        mesh = Mesh(np.array(devs), ("expert",))
        sharding = NamedSharding(mesh, P("expert"))
        return tuple(jax.device_put(a, sharding) for a in arrs)

    def predict_batch(self, x_star) -> Tuple[jax.Array, jax.Array]:
        """Route, group by expert, answer every group in fused stacked
        launches.  Each launch carries ALL experts at a bucket-padded
        per-expert query width, so the compile-shape bill is bounded by
        len(PREDICT_BUCKETS) per (expert count, expert size) — the same
        discipline as `gp.predict_batch`."""
        x_star = np.atleast_2d(np.asarray(x_star, np.float32))
        s = x_star.shape[0]
        m = self.n_outputs()
        if s == 0:
            return (jnp.zeros((0, m), jnp.float32),
                    jnp.zeros((0, m), jnp.float32))
        routed = self._route(x_star.astype(np.float64))
        cap = gp_lib.PREDICT_BUCKETS[-1]
        # per-expert query chunks of <= cap rows, answered in rounds of
        # one chunk per expert
        chunks: List[List[np.ndarray]] = []
        for eidx in range(len(self.experts)):
            rows = np.nonzero(routed == eidx)[0]
            chunks.append([rows[lo:lo + cap]
                           for lo in range(0, len(rows), cap)] or [rows])
        xt, al, li = self._stacked()
        ls = jnp.exp(jnp.clip(self.params.log_lengthscale, -5.0, 5.0))
        var = jnp.exp(jnp.clip(self.params.log_variance, -8.0, 8.0))
        mean_out = np.zeros((s, m), np.float32)
        var_out = np.zeros((s, m), np.float32)
        n_rounds = max(len(c) for c in chunks)
        for rnd in range(n_rounds):
            groups = [c[rnd] if rnd < len(c) else c[0][:0] for c in chunks]
            width = max(len(g) for g in groups)
            if width == 0:
                continue
            bucket = gp_lib.bucket_of(width)
            xq = np.zeros((len(groups), bucket, self.dim()), np.float32)
            for e, g in enumerate(groups):
                if len(g):
                    xq[e, :len(g)] = x_star[g]
            key = ("part", len(self.experts), int(xt.shape[1]), bucket)
            gp_lib.predict_batch_shapes[key] += 1
            mean_n, qf = kops.gp_predict_experts(
                xt, jnp.asarray(xq), ls, var, al, li, self.kind)
            mean_n = np.asarray(mean_n)
            lat = np.maximum(np.asarray(qf), 0.0)
            lat = np.maximum(float(var) - lat, 1e-12)
            y_mean = np.asarray(self.y_mean, np.float32)
            y_std = np.asarray(self.y_std, np.float32)
            for e, g in enumerate(groups):
                if not len(g):
                    continue
                mean_out[g] = y_mean[None] + mean_n[e, :len(g)] * y_std[None]
                var_out[g] = lat[e, :len(g), None] * (y_std ** 2)[None, :]
        return jnp.asarray(mean_out), jnp.asarray(var_out)

    def predict(self, x_star) -> Tuple[jax.Array, jax.Array]:
        """Same routed path as `predict_batch` (one code path, one
        numerical behaviour)."""
        return self.predict_batch(x_star)


# ===========================================================================
# factories
# ===========================================================================
def wrap_posterior(post: gp_lib.GPPosterior, backend: str = "exact", *,
                   max_points: Optional[int] = None,
                   **backend_kw) -> SurrogateEngine:
    """Lift an already-trained `GPPosterior` into a backend engine."""
    if backend == "exact":
        return ExactEngine(post, max_points=max_points)
    if backend == "incremental":
        return IncrementalEngine(post, max_points=max_points, **backend_kw)
    if backend == "partitioned":
        return PartitionedEngine.from_posterior(post, **backend_kw)
    raise ValueError(f"unknown surrogate backend {backend!r}; "
                     f"expected one of {BACKENDS}")


def as_engine(obj: Any, backend: str = "exact", *,
              max_points: Optional[int] = None,
              **backend_kw) -> Optional[SurrogateEngine]:
    """Posterior -> engine (via `wrap_posterior`); engines and None pass
    through — the consumers' one-line compatibility shim."""
    if obj is None or isinstance(obj, _EngineBase):
        return obj
    return wrap_posterior(obj, backend, max_points=max_points, **backend_kw)


def fit_engine(x, y, backend: str = "exact", *, kind: str = "rbf",
               steps: int = 200, lr: float = 5e-2,
               max_points: Optional[int] = None,
               **backend_kw) -> SurrogateEngine:
    """Train hyperparameters and return a conditioned engine."""
    if backend == "partitioned":
        return PartitionedEngine.fit(x, y, kind=kind, steps=steps, lr=lr,
                                     **backend_kw)
    post = gp_lib.fit(x, y, kind=kind, steps=steps, lr=lr)
    return wrap_posterior(post, backend, max_points=max_points,
                          **backend_kw)
