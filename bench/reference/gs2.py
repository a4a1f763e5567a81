"""Plain reference of the GS2 proxy: the ballooning-mode operator and its
initial-value power iteration, in numpy, vectorised over a batch of inputs.

Same semantics as the program's proxy (an m x m operator built from the
seven Table II inputs, a second-order propagator with the explicit
stability step, power iteration until the growth-rate estimate moves by
less than `TOL`, at most `MAX_ITERS` steps), written independently of it.

`growth_rate(thetas)` is the reference: the growth rate of the dominant
mode, log|mu_1| / dt for the largest eigenvalue mu_1 of the float64
propagator, which is the value the power iteration converges to.  LAPACK
has no bfloat16, so the control (``precision="bfloat16"``) computes the
same quantity the only way bfloat16 arithmetic can: by the power iteration
of `solve`, with every intermediate rounded to bfloat16.
"""
from __future__ import annotations

import numpy as np

M = 96
MAX_ITERS = 20_000
TOL = 1e-9


def _round(dtype):
    if dtype == "bfloat16":
        import ml_dtypes

        def rnd(a):
            return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16) \
                .astype(np.float32)
        return rnd, np.float32
    return (lambda a: a), np.dtype(dtype).type


def operators(thetas: np.ndarray, m: int = M, dtype="float64") -> np.ndarray:
    """[B, 7] inputs -> [B, m, m] operators A(theta)."""
    rnd, ft = _round(dtype)
    th = np.atleast_2d(np.asarray(thetas, np.float64)).astype(ft)
    q, shear, dens, temp, beta, nu, ky = (th[:, i:i + 1] for i in range(7))
    ky = ky + ft(0.05)
    grid = np.linspace(-np.pi, np.pi, m).astype(ft)[None, :]
    h = grid[0, 1] - grid[0, 0]
    metric = rnd(shear * grid - beta * q * np.sin(grid))
    bend = rnd((1.0 + metric ** 2) / (q * q))                     # [B, m]
    eye = np.eye(m, dtype=ft)
    lap = (np.eye(m, k=1, dtype=ft) + np.eye(m, k=-1, dtype=ft)
           - 2.0 * eye) / (h * h)
    drive = rnd(ky * (temp + 0.4 * dens)
                * (np.cos(grid) + metric * np.sin(grid)))         # [B, m]
    damp = nu[:, 0] * 12.0 + 0.15 * ky[:, 0] ** 2                 # [B]
    a = (bend[:, :, None] * lap[None] * 0.05
         + drive[:, :, None] * eye[None] * 0.5
         - damp[:, None, None] * eye[None])
    c = np.cos(grid[0, :-1])
    couple = (np.diag(c, 1) - np.diag(c, -1)).astype(ft)
    a = a + 0.08 * shear[:, :, None] * couple[None]
    return rnd(a).astype(ft)


def solve(thetas: np.ndarray, m: int = M, dtype="float64",
          max_iters: int = MAX_ITERS):
    """Batch of inputs -> (growth rate [B], mode frequency [B], iterations
    [B]).  Each input iterates until its own stopping rule holds."""
    rnd, ft = _round(dtype)
    a = operators(thetas, m, dtype)
    b = a.shape[0]
    gersh = np.max(np.sum(np.abs(a), axis=2), axis=1)
    dt = np.minimum(0.02, 0.5 / np.maximum(gersh, 1e-6)).astype(ft)
    eye = np.eye(m, dtype=ft)[None]
    prop = rnd(eye + dt[:, None, None] * a
               + 0.5 * (dt * dt)[:, None, None] * rnd(a @ a))
    v = np.full((b, m), 1.0 / np.sqrt(m), ft)
    lam = np.zeros(b, ft)
    lam_prev = np.full(b, np.inf, ft)
    iters = np.zeros(b, np.int64)
    active = np.ones(b, bool)
    while active.any():
        idx = np.nonzero(active)[0]
        w = rnd(np.einsum("bij,bj->bi", prop[idx], v[idx]))
        nrm = rnd(np.sqrt(np.sum(w * w, axis=1)))
        v[idx] = rnd(w / np.maximum(nrm, 1e-30)[:, None])
        lam_prev[idx] = lam[idx]
        lam[idx] = rnd(np.log(np.maximum(nrm, 1e-30)) / dt[idx])
        iters[idx] += 1
        active[idx] = (np.abs(lam[idx] - lam_prev[idx]) > TOL) \
            & (iters[idx] < max_iters)
    asym = 0.5 * (a - np.swapaxes(a, 1, 2))
    freq = rnd(np.einsum("bi,bij,bj->b", v, asym, v))
    return (lam.astype(np.float64), freq.astype(np.float64), iters)


def growth_rate(thetas: np.ndarray, m: int = M,
                precision: str = "float64") -> np.ndarray:
    """[B] growth rate of the dominant mode at each input."""
    if precision == "bfloat16":
        return solve(thetas, m, "bfloat16")[0]
    a = operators(thetas, m)
    gersh = np.max(np.sum(np.abs(a), axis=2), axis=1)
    dt = np.minimum(0.02, 0.5 / np.maximum(gersh, 1e-6))
    prop = (np.eye(m)[None] + dt[:, None, None] * a
            + 0.5 * (dt * dt)[:, None, None] * (a @ a))
    mu = np.linalg.eigvals(prop)
    return np.log(np.max(np.abs(mu), axis=1)) / dt
