"""Training labels for the GP surrogates, made on the device from the
seed in one jitted call: the GS2 proxy's (growth rate, mode frequency) at
each input, by the power iteration that `bench/reference/gs2.py` states,
in float32 with every product at full float32 accuracy.

The benchmark makes these itself, as a model benchmark makes its weights,
so that the plain reference can take the same labels without taking
anything the program has made."""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

M = 96
MAX_ITERS = 20_000
TOL = 1e-9
_HI = jax.lax.Precision.HIGHEST


def _operator(theta):
    q, shear, dens, temp, beta, nu, ky = (theta[i] for i in range(7))
    ky = ky + 0.05
    grid = jnp.linspace(-jnp.pi, jnp.pi, M)
    h = grid[1] - grid[0]
    metric = shear * grid - beta * q * jnp.sin(grid)
    bend = (1.0 + metric ** 2) / (q * q)
    eye = jnp.eye(M)
    lap = (jnp.eye(M, k=1) + jnp.eye(M, k=-1) - 2.0 * eye) / (h * h)
    drive = ky * (temp + 0.4 * dens) * (jnp.cos(grid) + metric * jnp.sin(grid))
    damp = nu * 12.0 + 0.15 * ky * ky
    c = jnp.cos(grid[:-1])
    couple = jnp.diag(c, 1) - jnp.diag(c, -1)
    return (bend[:, None] * lap * 0.05 + jnp.diag(drive) * 0.5 - damp * eye
            + 0.08 * shear * couple)


def _label(theta):
    a = _operator(theta)
    dt = jnp.minimum(0.02, 0.5 / jnp.maximum(jnp.max(jnp.sum(jnp.abs(a), 1)),
                                            1e-6))
    prop = jnp.eye(M) + dt * a + 0.5 * dt * dt * jnp.matmul(a, a, precision=_HI)

    def cond(s):
        return (jnp.abs(s[1] - s[2]) > TOL) & (s[3] < MAX_ITERS)

    def body(s):
        v, lam, _, it = s
        w = jnp.matmul(prop, v, precision=_HI)
        nrm = jnp.maximum(jnp.sqrt(jnp.sum(w * w)), 1e-30)
        return w / nrm, jnp.log(nrm) / dt, lam, it + 1

    v0 = jnp.full((M,), 1.0 / np.sqrt(M), jnp.float32)
    v, lam, _, _ = jax.lax.while_loop(
        cond, body, (v0, jnp.float32(0.0), jnp.float32(jnp.inf), 0))
    freq = jnp.dot(v, jnp.matmul(0.5 * (a - a.T), v, precision=_HI),
                   precision=_HI)
    return jnp.stack([lam, freq])


@jax.jit
def _labels(x):
    return jax.vmap(_label)(x)


def labels(x: np.ndarray) -> np.ndarray:
    """[n, 7] inputs -> [n, 2] float32 labels, computed on the device."""
    return np.asarray(_labels(jnp.asarray(x, jnp.float32)))
