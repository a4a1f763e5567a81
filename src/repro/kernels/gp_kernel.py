"""GP covariance assembly and the fused batched posterior predict in Pallas.

The paper's GP surrogate spends its dense-algebra time in covariance
assembly (O(N^2 d)), the Cholesky solve, and — once it re-costs queues —
the batched posterior predict.  Both kernels here build covariance tiles
with ONE body (`_cov_tile`): squared distances from the MXU cross term
(-2 xa xb^T) plus row norms, then the RBF / Matérn-5/2 nonlinearity.  ARD
lengthscale scaling is folded into the inputs by the wrappers.

Every array in a kernel body is 2-D (Mosaic refuses 1-D vectors reshaped
into columns), and every matmul runs at f32 precision: the posterior
variance is var - ||L^-1 ks||^2, a difference that one bf16 pass would
swamp.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

DEFAULT_BLOCK = 256
# training-dimension tile of the predict: one (block, block) slice of L^-1
# is resident per grid step, so VMEM use is flat in the training-set size
DEFAULT_TRAIN_BLOCK = 512
_HI = jax.lax.Precision.HIGHEST


def _nt_dot(a, b):
    """a [p, d] x b [q, d] -> a b^T [p, q] in f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _cov_tile(xa, xb, kind):
    """Unit-variance covariance tile k(xa, xb) [p, q] of pre-scaled inputs
    xa [p, d], xb [q, d].  The row norms of xb come out of the MXU as a
    [1, q] row (a lane-major reduction would need a 1-D transpose)."""
    cross = _nt_dot(xa, xb)
    na = jnp.sum(xa * xa, axis=1, keepdims=True)                # [p, 1]
    nb = _nt_dot(jnp.ones((1, xb.shape[1]), jnp.float32), xb * xb)  # [1, q]
    d2 = jnp.maximum(na + nb - 2.0 * cross, 0.0)
    if kind == "rbf":
        return jnp.exp(-0.5 * d2)
    r = jnp.sqrt(d2 + 1e-12)                                    # matern52
    return (1.0 + math.sqrt(5.0) * r + 5.0 / 3.0 * d2) * jnp.exp(
        -math.sqrt(5.0) * r)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _tile(n: int, block: int, align: int) -> "tuple[int, int]":
    """(tile, padded extent) for an axis of `n`: one whole `align`-rounded
    tile when that fits in `block`, else `block`-sized tiles."""
    whole = _round_up(max(n, 1), align)
    if whole <= block:
        return whole, whole
    return block, _round_up(n, block)


def _pad_rows(x, rows: int, axis: int = 0):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rows - x.shape[axis])
    return jnp.pad(x, pad) if rows != x.shape[axis] else x


# ==========================================================================
# covariance matrix
# ==========================================================================
def _gp_kernel(x1_ref, x2_ref, o_ref, *, kind, n, m):
    i = pl.program_id(0)
    j = pl.program_id(1)
    bn, bm = o_ref.shape
    k = _cov_tile(x1_ref[...], x2_ref[...], kind)
    # zero padded rows/cols so downstream reductions stay exact
    rows = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 0)
    cols = j * bm + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1)
    o_ref[...] = jnp.where((rows < n) & (cols < m), k, 0.0)


def _kernel_matrix_fwd_impl(x1, x2, lengthscale, variance, kind, block_n,
                            block_m, interpret):
    n, d = x1.shape
    m = x2.shape[0]
    ls = lengthscale.astype(jnp.float32)
    bn, n_pad = _tile(n, block_n, 8)
    bm, m_pad = _tile(m, block_m, 128)
    x1s = _pad_rows(x1.astype(jnp.float32) / ls, n_pad)
    x2s = _pad_rows(x2.astype(jnp.float32) / ls, m_pad)
    out = pl.pallas_call(
        functools.partial(_gp_kernel, kind=kind, n=n, m=m),
        grid=(n_pad // bn, m_pad // bm),
        in_specs=[pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((bm, d), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, m_pad), jnp.float32),
        interpret=interpret,
    )(x1s, x2s)
    return variance.astype(jnp.float32) * out[:n, :m]


# The forward pass is the Pallas kernel; the backward pass is the VJP of
# the jnp reference (`ref.gp_kernel_matrix`, the one covariance definition
# both paths implement).  `pallas_call` has no JVP rule, and the NLML fit
# differentiates through K — this keeps the fit on the kernel's forward.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _kernel_matrix(x1, x2, lengthscale, variance, kind, block_n, block_m,
                   interpret):
    return _kernel_matrix_fwd_impl(x1, x2, lengthscale, variance, kind,
                                   block_n, block_m, interpret)


def _kernel_matrix_fwd(x1, x2, lengthscale, variance, kind, block_n,
                       block_m, interpret):
    out = _kernel_matrix_fwd_impl(x1, x2, lengthscale, variance, kind,
                                  block_n, block_m, interpret)
    return out, (x1, x2, lengthscale, variance)


def _kernel_matrix_bwd(kind, block_n, block_m, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(ref.gp_kernel_matrix, kind=kind),
                     *res)
    return vjp(g)


_kernel_matrix.defvjp(_kernel_matrix_fwd, _kernel_matrix_bwd)


@functools.partial(jax.jit, static_argnames=("kind", "block_n", "block_m",
                                             "interpret"))
def gp_kernel_matrix(x1, x2, lengthscale, variance, kind: str = "rbf", *,
                     block_n: int = DEFAULT_BLOCK, block_m: int = DEFAULT_BLOCK,
                     interpret: bool = False) -> jax.Array:
    """x1: [N,D]; x2: [M,D]; ARD lengthscale: [D] -> K [N,M] f32.
    Differentiable (custom VJP through the jnp reference)."""
    assert kind in ("rbf", "matern52"), kind
    return _kernel_matrix(x1, x2, lengthscale, variance, kind, block_n,
                          block_m, interpret)


# ==========================================================================
# fused batched posterior predict
# ==========================================================================
def _gp_predict_kernel(x1_ref, x2_ref, alpha_ref, linv_ref, mean_ref,
                       qf_ref, w_ref, *, kind):
    """Grid step (expert e, query tile s, train row-block i, train
    col-block j).  Over the (i, j) sweep of one query tile:

      mean = sum_j k_j alpha_j                (on the last row-block)
      qf   = sum_i || sum_{j<=i} k_j L^-1_ij^T ||^2   (row-wise)

    with k_j the [bs, bn] cross-covariance of the query tile against
    training block j, rebuilt per step (d is small; the L^-1 tile is the
    bandwidth).  L^-1 is lower triangular: blocks j > i are skipped, and
    their index maps clamp to (i, i) so they are never fetched.  Padded
    TRAINING rows are exact because alpha and L^-1 are zero there."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    last = pl.num_programs(2) - 1

    @pl.when((i == 0) & (j == 0))
    def _():
        mean_ref[...] = jnp.zeros_like(mean_ref)
        qf_ref[...] = jnp.zeros_like(qf_ref)

    @pl.when(j == 0)
    def _():
        w_ref[...] = jnp.zeros_like(w_ref)

    @pl.when(j <= i)
    def _():
        k = _cov_tile(x2_ref[0], x1_ref[0], kind)               # [bs, bn]
        w_ref[...] += _nt_dot(k, linv_ref[0])                   # k L_ij^T

        @pl.when(i == last)
        def _():
            mean_ref[0] += jax.lax.dot_general(
                k, alpha_ref[0], (((1,), (0,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)             # [bs, m]

        @pl.when(j == i)
        def _():
            w = w_ref[...]
            qf_ref[0] += jnp.sum(w * w, axis=1, keepdims=True)  # [bs, 1]


@functools.partial(jax.jit, static_argnames=("kind", "block_s", "block_n",
                                             "interpret"))
def gp_predict_experts(x_train, x_star, lengthscale, variance, alpha, linv,
                       kind: str = "rbf", *, block_s: int = DEFAULT_BLOCK,
                       block_n: int = DEFAULT_TRAIN_BLOCK,
                       interpret: bool = False):
    """Stacked GP posterior predict in ONE kernel launch.

    x_train: [E, N, D]; x_star: [E, S, D] (each expert's queries,
    zero-padded to a common width); alpha: [E, N, M]; linv: [E, N, N]
    (inverse Cholesky factors of K + s2 I); shared hyperparameters
    -> (normalised mean [E, S, M], quadratic form ||L^-1 ks||^2 [E, S]).

    The covariance nonlinearity commutes with the signal variance, so the
    kernel works on the unit-variance correlation and the wrapper applies
    `variance` (mean) and `variance^2` (quadratic form) afterwards.  The
    grid is (E, S/bs, N/bn, N/bn): expert e never reads expert e2's
    operands, so the launch shards over the expert axis; the training
    axis is tiled, so VMEM holds one (bn, bn) tile of L^-1 at any N.
    Padded query rows produce garbage that is sliced off.
    """
    assert kind in ("rbf", "matern52"), kind
    e, n, d = x_train.shape
    s = x_star.shape[1]
    m_out = alpha.shape[2]
    ls = lengthscale.astype(jnp.float32)
    bn, n_pad = _tile(n, block_n, 8)
    bs, s_pad = _tile(s, block_s, 8)
    x1s = _pad_rows(x_train.astype(jnp.float32) / ls, n_pad, axis=1)
    x2s = _pad_rows(x_star.astype(jnp.float32) / ls, s_pad, axis=1)
    alpha = _pad_rows(alpha.astype(jnp.float32), n_pad, axis=1)
    linv = _pad_rows(_pad_rows(linv.astype(jnp.float32), n_pad, axis=1),
                     n_pad, axis=2)

    def train_block(ei, si, i, j):
        return (ei, jnp.minimum(j, i), 0)

    mean0, qf0 = pl.pallas_call(
        functools.partial(_gp_predict_kernel, kind=kind),
        grid=(e, s_pad // bs, n_pad // bn, n_pad // bn),
        in_specs=[pl.BlockSpec((1, bn, d), train_block),
                  pl.BlockSpec((1, bs, d), lambda ei, si, i, j: (ei, si, 0)),
                  pl.BlockSpec((1, bn, m_out), train_block),
                  pl.BlockSpec((1, bn, bn), lambda ei, si, i, j:
                               (ei, i, jnp.minimum(j, i)))],
        out_specs=(pl.BlockSpec((1, bs, m_out),
                                lambda ei, si, i, j: (ei, si, 0)),
                   pl.BlockSpec((1, bs, 1),
                                lambda ei, si, i, j: (ei, si, 0))),
        out_shape=(jax.ShapeDtypeStruct((e, s_pad, m_out), jnp.float32),
                   jax.ShapeDtypeStruct((e, s_pad, 1), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bs, bn), jnp.float32)],
        interpret=interpret,
    )(x1s, x2s, alpha, linv)
    var_f = variance.astype(jnp.float32)
    return var_f * mean0[:, :s], (var_f * var_f) * qf0[:, :s, 0]


def gp_predict(x_train, x_star, lengthscale, variance, alpha, linv,
               kind: str = "rbf", *, block_s: int = DEFAULT_BLOCK,
               block_n: int = DEFAULT_TRAIN_BLOCK, interpret: bool = False):
    """Batched GP posterior predict in ONE kernel launch: the one-expert
    case of `gp_predict_experts`.

    x_train: [N, D]; x_star: [S, D]; alpha: [N, M]; linv: [N, N]
    -> (normalised mean [S, M], quadratic form ||L^-1 ks||^2 [S])."""
    mean, qf = gp_predict_experts(
        x_train[None], x_star[None], lengthscale, variance, alpha[None],
        linv[None], kind, block_s=block_s, block_n=block_n,
        interpret=interpret)
    return mean[0], qf[0]
