"""Dispatching wrappers around the Pallas kernels.

Every op has three implementations selected by `impl` (or the module default
set via `set_default_impl`):
  * "pallas"    — the TPU kernel (compiled; requires a TPU backend),
  * "interpret" — the same Pallas kernel run in interpret mode (CPU-correct,
                  used by the test suite to validate the kernel body),
  * "xla"       — the pure-jnp chunked fallback from `ref.py` (used on CPU and
                  for the 512-device dry-run lowering).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

_DEFAULT_IMPL = "auto"


def set_default_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    assert impl in ("auto", "pallas", "interpret", "xla")
    _DEFAULT_IMPL = impl


def _resolve(impl: Optional[str]) -> str:
    impl = impl or _DEFAULT_IMPL
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


# --------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool = True,
                    impl: Optional[str] = None):
    """q: [B,S,H,D]; k/v: [B,Skv,Hkv,D] (GQA expanded inside)."""
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal,
                                  interpret=(mode == "interpret"))
    if q.shape[1] <= 1024 and k.shape[1] <= 1024:
        return ref.attention(q, k, v, causal=causal)
    return ref.attention_chunked(q, k, v, causal=causal)


def rwkv6_wkv(r, k, v, w, u, state=None, *, impl: Optional[str] = None,
              chunk: int = 64):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import rwkv6_scan
        return rwkv6_scan.rwkv6_wkv(r, k, v, w, u, state, chunk=chunk,
                                    interpret=(mode == "interpret"))
    return ref.rwkv6_wkv_chunked(r, k, v, w, u, state, chunk=chunk)


def mamba2_ssd(x, dt, a, b, c, d, state=None, *, impl: Optional[str] = None,
               chunk: int = 128):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import mamba2_ssd as ssd
        return ssd.mamba2_ssd(x, dt, a, b, c, d, state, chunk=chunk,
                              interpret=(mode == "interpret"))
    return ref.mamba2_ssd_chunked(x, dt, a, b, c, d, state, chunk=chunk)


def gp_kernel_matrix(x1, x2, lengthscale, variance, kind: str = "rbf", *,
                     impl: Optional[str] = None):
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import gp_kernel
        return gp_kernel.gp_kernel_matrix(x1, x2, lengthscale, variance, kind,
                                          interpret=(mode == "interpret"))
    return ref.gp_kernel_matrix(x1, x2, lengthscale, variance, kind)


def gp_predict(x_train, x_star, lengthscale, variance, alpha, linv,
               kind: str = "rbf", *, impl: Optional[str] = None):
    """Batched GP posterior predict: (normalised mean [S, M], quadratic
    form ||L^-1 ks||^2 [S]) in one launch — covariance assembly, alpha
    product and the variance quadratic form fused so queue scoring never
    materialises Ks in HBM per task."""
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import gp_kernel
        return gp_kernel.gp_predict(x_train, x_star, lengthscale, variance,
                                    alpha, linv, kind,
                                    interpret=(mode == "interpret"))
    return ref.gp_predict(x_train, x_star, lengthscale, variance, alpha,
                          linv, kind)


def gp_predict_experts(x_train, x_star, lengthscale, variance, alpha, linv,
                       kind: str = "rbf", *, impl: Optional[str] = None):
    """Stacked local-GP ensemble predict: every expert answers its routed
    query tile in ONE launch (grid over experts × query tiles on TPU,
    vmapped XLA elsewhere).  x_train: [E, N, D]; x_star: [E, S, D];
    alpha: [E, N, M]; linv: [E, N, N] -> (mean [E, S, M], qf [E, S])."""
    mode = _resolve(impl)
    if mode in ("pallas", "interpret"):
        from repro.kernels import gp_kernel
        return gp_kernel.gp_predict_experts(
            x_train, x_star, lengthscale, variance, alpha, linv, kind,
            interpret=(mode == "interpret"))
    return ref.gp_predict_experts(x_train, x_star, lengthscale, variance,
                                  alpha, linv, kind)
