"""Plain reference of the GP surrogate's posterior (paper eqs. 3 and 4):
ARD RBF covariance with signal variance and a noise-plus-jitter diagonal,
outputs standardised per column, one Cholesky factor shared by the
outputs.  Hyperparameters are given, never fitted here.

`Posterior(x, y, hyper, precision)` builds the posterior in float64 for
the reference.  With ``precision="bfloat16"`` it is the control: arrays
in float32 and every matrix product taking bfloat16 operands, as one
MXU pass computes it.
"""
from __future__ import annotations

import numpy as np
import scipy.linalg

# clips and jitter of the covariance the surrogate is defined with
LOG_LS_CLIP = (-5.0, 5.0)
LOG_VAR_CLIP = (-8.0, 8.0)
LOG_NOISE_CLIP = (-5.0, 5.0)
JITTER = 1e-5
STD_FLOOR = 1e-8


def _bf16(a):
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


class Posterior:
    def __init__(self, x, y, hyper, precision: str = "float64"):
        self.precision = precision
        self.ft = np.float64 if precision == "float64" else np.float32
        ft = self.ft
        self.ls = np.exp(np.clip(np.asarray(hyper["log_lengthscale"],
                                            np.float64), *LOG_LS_CLIP))
        self.var = float(np.exp(np.clip(hyper["log_variance"],
                                        *LOG_VAR_CLIP)))
        s2 = float(np.exp(2.0 * np.clip(hyper["log_noise"],
                                        *LOG_NOISE_CLIP)))
        self.x = np.asarray(x, np.float64).astype(ft)
        y = np.asarray(y, np.float64)
        y = y[:, None] if y.ndim == 1 else y
        self.y_mean = y.mean(axis=0)
        self.y_std = np.maximum(y.std(axis=0), STD_FLOOR)
        yn = ((y - self.y_mean) / self.y_std).astype(ft)
        n = self.x.shape[0]
        k = self.cov(self.x, self.x) + (s2 + JITTER * (self.var + 1.0)) \
            * np.eye(n, dtype=ft)
        self.chol = np.linalg.cholesky(k).astype(ft)
        self.alpha = scipy.linalg.cho_solve((self.chol, True), yn) \
            .astype(ft)

    def _mm(self, a, b):
        if self.precision == "bfloat16":
            return _bf16(a) @ _bf16(b)
        return a @ b

    def cov(self, x1, x2):
        ft = self.ft
        a = (np.asarray(x1, np.float64) / self.ls).astype(ft)
        b = (np.asarray(x2, np.float64) / self.ls).astype(ft)
        d2 = (np.sum(a * a, 1)[:, None] + np.sum(b * b, 1)[None, :]
              - 2.0 * self._mm(a, b.T))
        return (self.var * np.exp(-0.5 * np.maximum(d2, 0.0))).astype(ft)

    def predict(self, xq):
        """(mean [S, M] in original units, latent sd [S]): the sd of the
        standardised outputs, which the outputs share."""
        ks = self.cov(self.x, xq)                                 # [N, S]
        mean = self.y_mean + self._mm(ks.T, self.alpha) * self.y_std
        v = scipy.linalg.solve_triangular(self.chol, ks, lower=True)
        lat = np.maximum(self.var - np.sum(v * v, axis=0), 1e-12)
        return np.asarray(mean, np.float64), np.sqrt(np.asarray(lat,
                                                                np.float64))
