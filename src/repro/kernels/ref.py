"""Pure-jnp oracles for every kernel, plus XLA-efficient chunked fallbacks.

The *simple* functions are the correctness oracles (O(S^2) memory where
applicable — test-sized inputs only).  The *chunked* functions are the
XLA fallbacks actually used by the model code off-TPU: same math, online
softmax / chunked-scan structure, bounded memory.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ==========================================================================
# Attention
# ==========================================================================
def _expand_kv(q, k, v):
    h, hkv = q.shape[2], k.shape[2]
    if h != hkv:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True) -> jax.Array:
    """Oracle. q:[B,S,H,D] k/v:[B,S,Hkv,D] -> [B,S,H,Dv]."""
    k, v = _expand_kv(q, k, v)
    sq, sk = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(q.shape[-1])
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, q_block: int = 512,
                      kv_block: int = 512) -> jax.Array:
    """Flash attention in pure JAX: online-softmax blocked forward and a
    custom blockwise-recompute VJP (memory O(S*block) in both directions —
    differentiating a naive scan would otherwise save O(S^2) residuals)."""
    k, v = _expand_kv(q, k, v)
    q_block = min(q_block, q.shape[1])
    kv_block = min(kv_block, k.shape[1])
    return _flash(q, k, v, causal, q_block, kv_block)


def _pad_blocks(x, blk):
    s = x.shape[1]
    pad = (-s) % blk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n = x.shape[1] // blk
    b, _, h, d = x.shape
    # [B, n, blk, H, D] -> f32 blocks
    return x.reshape(b, n, blk, h, d).astype(jnp.float32), n


def _block_mask(qi, ki, q_block, kv_block, sq, sk, causal, q_off):
    qpos = qi * q_block + jnp.arange(q_block) + q_off
    kpos = ki * kv_block + jnp.arange(kv_block)
    valid = (kpos[None, :] < sk) & (qpos[:, None] < sq + q_off)
    if causal:
        valid = valid & (kpos[None, :] <= qpos[:, None])
    return valid


def attention_chunked_fwd(q, k, v, *, causal: bool = True,
                          q_offset=None, q_block: int = 512,
                          kv_block: int = 512):
    """Forward-only chunked attention with an explicit (traceable) global
    row offset for the Q block — the building block for context-parallel
    prefill, where each model-rank owns rows [off, off + sq) of a longer
    sequence."""
    k2, v2 = _expand_kv(q, k, v)
    q_block = min(q_block, q.shape[1])
    kv_block = min(kv_block, k2.shape[1])
    out, _ = _flash_fwd_impl(q, k2, v2, causal, q_block, kv_block,
                             q_off=q_offset)
    return out


def _flash_fwd_impl(q, k, v, causal, q_block, kv_block, q_off=None):
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    qb, nq = _pad_blocks(q, q_block)
    kb, nk = _pad_blocks(k, kv_block)
    vb, _ = _pad_blocks(v, kv_block)
    scale = 1.0 / math.sqrt(d)
    if q_off is None:
        q_off = sk - sq

    def per_qblock(_, qi):
        qblk = qb[:, qi]

        def per_kvblock(state, ki):
            m, l, acc = state
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kb[:, ki]) * scale
            valid = _block_mask(qi, ki, q_block, kv_block, sq, sk, causal, q_off)
            s = jnp.where(valid[None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new[..., None]) * valid[None, None]
            l_new = l * corr + jnp.sum(e, axis=-1)
            acc_new = (acc * corr[..., None]
                       + jnp.einsum("bhqk,bkhd->bhqd", e, vb[:, ki]))
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, h, q_block), -1e30, jnp.float32),
                jnp.zeros((b, h, q_block), jnp.float32),
                jnp.zeros((b, h, q_block, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(per_kvblock, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]            # [B,H,Q,Dv]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))                # [B,H,Q]
        return None, (out.transpose(0, 2, 1, 3), lse)

    _, (blocks, lses) = jax.lax.scan(per_qblock, None, jnp.arange(nq))
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(b, nq * q_block, h, dv)
    lse = lses.transpose(1, 2, 0, 3).reshape(b, h, nq * q_block)
    return out[:, :sq].astype(q.dtype), lse[..., :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, q_block, kv_block):
    return _flash_fwd_impl(q, k, v, causal, q_block, kv_block)[0]


def _flash_fwd(q, k, v, causal, q_block, kv_block):
    out, lse = _flash_fwd_impl(q, k, v, causal, q_block, kv_block)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_block, kv_block, res, dout):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    sk, dvd = k.shape[1], v.shape[-1]
    scale = 1.0 / math.sqrt(d)
    q_off = sk - sq
    qb, nq = _pad_blocks(q, q_block)
    kb, nk = _pad_blocks(k, kv_block)
    vb, _ = _pad_blocks(v, kv_block)
    dob, _ = _pad_blocks(dout.astype(jnp.float32), q_block)
    pad_q = nq * q_block - sq
    lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)))
    lse_b = lse_p.reshape(b, h, nq, q_block)                    # [B,H,nq,Q]
    # D_i = rowsum(dO * O)
    dd = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dd_b = jnp.pad(dd, ((0, 0), (0, pad_q), (0, 0))
                   ).reshape(b, nq, q_block, h)                 # [B,nq,Q,H]

    def _p_and_ds(qi, ki):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb[:, qi], kb[:, ki]) * scale
        valid = _block_mask(qi, ki, q_block, kv_block, sq, sk, causal, q_off)
        s = jnp.where(valid[None, None], s, -1e30)
        p = jnp.exp(s - lse_b[:, :, qi][..., None]) * valid[None, None]
        dp = jnp.einsum("bqhd,bkhd->bhqk", dob[:, qi], vb[:, ki])
        ds = p * (dp - dd_b[:, qi].transpose(0, 2, 1)[..., None])
        return p, ds

    # pass 1: dq (scan q blocks; inner kv)
    def dq_block(_, qi):
        def inner(acc, ki):
            _, ds = _p_and_ds(qi, ki)
            return acc + jnp.einsum("bhqk,bkhd->bqhd", ds, kb[:, ki]) * scale, None
        acc0 = jnp.zeros((b, q_block, h, d), jnp.float32)
        dq, _ = jax.lax.scan(inner, acc0, jnp.arange(nk))
        return None, dq

    _, dqb = jax.lax.scan(dq_block, None, jnp.arange(nq))
    dq = dqb.transpose(1, 0, 2, 3, 4).reshape(b, nq * q_block, h, d)[:, :sq]

    # pass 2: dk, dv (scan kv blocks; inner q)
    def dkv_block(_, ki):
        def inner(carry, qi):
            dk_acc, dv_acc = carry
            p, ds = _p_and_ds(qi, ki)
            dk_acc += jnp.einsum("bhqk,bqhd->bkhd", ds, qb[:, qi]) * scale
            dv_acc += jnp.einsum("bhqk,bqhd->bkhd", p, dob[:, qi])
            return (dk_acc, dv_acc), None
        init = (jnp.zeros((b, kv_block, h, d), jnp.float32),
                jnp.zeros((b, kv_block, h, dvd), jnp.float32))
        (dk_b, dv_b), _ = jax.lax.scan(inner, init, jnp.arange(nq))
        return None, (dk_b, dv_b)

    _, (dkb, dvb) = jax.lax.scan(dkv_block, None, jnp.arange(nk))
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(b, nk * kv_block, h, d)[:, :sk]
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(b, nk * kv_block, h, dvd)[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ==========================================================================
# RWKV6 (Finch) WKV recurrence — data-dependent per-channel decay.
#   state_t = diag(w_t) state_{t-1} + k_t v_t^T
#   out_t   = r_t^T (state_{t-1} + diag(u * k_t) v_t^T)
# ==========================================================================
def rwkv6_wkv(r, k, v, w, u, state: Optional[jax.Array] = None):
    """r,k,w: [B,S,H,K]; v: [B,S,H,V]; u: [H,K]; state: [B,H,K,V].
    Returns (out [B,S,H,V], final_state)."""
    b, s, h, kd = r.shape
    vd = v.shape[-1]
    if state is None:
        state = jnp.zeros((b, h, kd, vd), jnp.float32)
    state = state.astype(jnp.float32)
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(st, t):
        rt, kt, vt, wt = rf[:, t], kf[:, t], vf[:, t], wf[:, t]
        kv = kt[..., :, None] * vt[..., None, :]               # [B,H,K,V]
        out = jnp.einsum("bhk,bhkv->bhv", rt, st + uf[..., :, None] * kv)
        st = wt[..., :, None] * st + kv
        return st, out

    state, outs = jax.lax.scan(step, state, jnp.arange(s))
    return outs.transpose(1, 0, 2, 3).astype(r.dtype), state


def rwkv6_wkv_chunked(r, k, v, w, u, state: Optional[jax.Array] = None,
                      chunk: int = 64):
    """Chunked gated-linear-attention form of the WKV6 recurrence."""
    b, s, h, kd = r.shape
    vd = v.shape[-1]
    if state is None:
        state = jnp.zeros((b, h, kd, vd), jnp.float32)
    state = state.astype(jnp.float32)
    pad = (-s) % chunk
    if pad:
        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    n = (s + pad) // chunk
    rf = r.reshape(b, n, chunk, h, kd).astype(jnp.float32)
    kf = k.reshape(b, n, chunk, h, kd).astype(jnp.float32)
    vf = v.reshape(b, n, chunk, h, vd).astype(jnp.float32)
    wf = w.reshape(b, n, chunk, h, kd).astype(jnp.float32)
    uf = u.astype(jnp.float32)

    def per_chunk(st, ci):
        rc, kc, vc, wc = rf[:, ci], kf[:, ci], vf[:, ci], wf[:, ci]
        logw = jnp.log(jnp.maximum(wc, 1e-30))                 # [B,C,H,K]
        cum = jnp.cumsum(logw, axis=1)                          # prod w_1..w_t
        # inter-chunk: r_t . (prod_{j<=t-1} w_j) state   (decays up to t-1)
        dec_in = jnp.exp(cum - logw)                            # prod w_1..w_{t-1}
        out_inter = jnp.einsum("bthk,bhkv->bthv", rc * dec_in, st)
        # intra-chunk: pairs j < t:  r_t (prod_{j<u<t} w ... ) using ratios
        # A[t,j] = sum_k r_t[k] k_j[k] * exp(cum[t-1,k] - cum[j,k])
        r_dec = rc * dec_in                                     # r_t * prod_{<=t-1}
        k_dec = kc * jnp.exp(-cum)                              # k_j / prod_{<=j}
        a = jnp.einsum("bthk,bjhk->bhtj", r_dec, k_dec)
        tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), k=-1)
        a = a * tri[None, None]
        out_intra = jnp.einsum("bhtj,bjhv->bthv", a, vc)
        # diagonal bonus term: r_t . (u * k_t) v_t
        diag = jnp.einsum("bthk,bthk->bth", rc, uf[None, None] * kc)
        out_diag = diag[..., None] * vc
        # state update: st' = diag(prod_all w) st + sum_j (prod_{j<u<=C} w) k_j v_j
        dec_all = jnp.exp(cum[:, -1])                           # [B,H,K]
        k_out = kc * jnp.exp(cum[:, -1][:, None] - cum)         # prod_{j<u<=C}
        st = dec_all[..., None] * st + jnp.einsum("bjhk,bjhv->bhkv", k_out, vc)
        return st, out_inter + out_intra + out_diag

    state, outs = jax.lax.scan(per_chunk, state, jnp.arange(n))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n * chunk, h, vd)
    return out[:, :s].astype(r.dtype), state


# ==========================================================================
# Mamba2 SSD — scalar per-head decay.
#   state_t = exp(dt_t * A_h) state_{t-1} + dt_t * B_t x_t^T
#   y_t     = C_t . state_t + D_h * x_t
# ==========================================================================
def mamba2_ssd(x, dt, a, b_in, c_in, d, state: Optional[jax.Array] = None):
    """x: [B,S,H,P]; dt: [B,S,H]; a: [H] (negative); b,c: [B,S,N]; d: [H];
    state: [B,H,P,N].  Returns (y [B,S,H,P], final_state)."""
    bb, s, h, p = x.shape
    n = b_in.shape[-1]
    if state is None:
        state = jnp.zeros((bb, h, p, n), jnp.float32)
    state = state.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af, bf, cf, df = (t.astype(jnp.float32) for t in (a, b_in, c_in, d))

    def step(st, t):
        dtt = dtf[:, t]                                        # [B,H]
        dec = jnp.exp(dtt * af[None])                          # [B,H]
        dbx = jnp.einsum("bh,bhp,bn->bhpn", dtt, xf[:, t], bf[:, t])
        st = dec[..., None, None] * st + dbx
        y = jnp.einsum("bhpn,bn->bhp", st, cf[:, t]) + df[None, :, None] * xf[:, t]
        return st, y

    state, ys = jax.lax.scan(step, state, jnp.arange(s))
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), state


def mamba2_ssd_chunked(x, dt, a, b_in, c_in, d,
                       state: Optional[jax.Array] = None, chunk: int = 128):
    """Chunked SSD (the Mamba2 'state-space dual' algorithm)."""
    bb, s, h, p = x.shape
    n = b_in.shape[-1]
    if state is None:
        state = jnp.zeros((bb, h, p, n), jnp.float32)
    state = state.astype(jnp.float32)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk
    xf = x.reshape(bb, nc, chunk, h, p).astype(jnp.float32)
    dtf = dt.reshape(bb, nc, chunk, h).astype(jnp.float32)
    bf = b_in.reshape(bb, nc, chunk, n).astype(jnp.float32)
    cf = c_in.reshape(bb, nc, chunk, n).astype(jnp.float32)
    af = a.astype(jnp.float32)
    df = d.astype(jnp.float32)

    def per_chunk(st, ci):
        xc, dtc, bc, cc = xf[:, ci], dtf[:, ci], bf[:, ci], cf[:, ci]
        la = dtc * af[None, None]                              # [B,C,H] log-decay
        cum = jnp.cumsum(la, axis=1)                           # sum_{u<=t}
        # inter: y_t += exp(cum_t) * (C_t . st)
        dec_t = jnp.exp(cum)                                   # [B,C,H]
        y_in = jnp.einsum("btn,bhpn->bthp", cc, st) * dec_t[..., None]
        # intra: L[t,j] = exp(cum_t - cum_j) for j<=t ; y_t += sum_j L C_t.B_j dt_j x_j
        g = jnp.einsum("btn,bjn->btj", cc, bc)                 # [B,C,C]
        ratio = cum[:, :, None, :] - cum[:, None, :, :]        # [B,C,C,H]
        tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
        l_mat = jnp.exp(ratio) * tri[None, :, :, None]
        y_intra = jnp.einsum("btj,btjh,bjh,bjhp->bthp", g, l_mat, dtc, xc)
        # state update
        dec_all = jnp.exp(cum[:, -1])                          # [B,H]
        k_dec = jnp.exp(cum[:, -1][:, None] - cum)             # [B,C,H]
        st = (dec_all[..., None, None] * st
              + jnp.einsum("bjh,bjh,bjhp,bjn->bhpn", k_dec, dtc, xc, bc))
        y = y_in + y_intra + df[None, None, :, None] * xc
        return st, y

    state, ys = jax.lax.scan(per_chunk, state, jnp.arange(nc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bb, nc * chunk, h, p)
    return y[:, :s].astype(x.dtype), state


# ==========================================================================
# GP kernel matrix (RBF / Matern-5/2)
# ==========================================================================
# f32 matmuls: TPU's default one-pass bf16 would swamp the posterior
# variance (a difference var - ||L^-1 ks||^2); CPU results are unchanged
_HI = jax.lax.Precision.HIGHEST


def gp_kernel_matrix(x1: jax.Array, x2: jax.Array, lengthscale: jax.Array,
                     variance: jax.Array, kind: str = "rbf") -> jax.Array:
    """x1: [N,D]; x2: [M,D]; ARD lengthscale: [D] -> [N,M] (f32)."""
    x1s = x1.astype(jnp.float32) / lengthscale.astype(jnp.float32)
    x2s = x2.astype(jnp.float32) / lengthscale.astype(jnp.float32)
    d2 = (jnp.sum(x1s ** 2, -1)[:, None] + jnp.sum(x2s ** 2, -1)[None, :]
          - 2.0 * jnp.matmul(x1s, x2s.T, precision=_HI))
    d2 = jnp.maximum(d2, 0.0)
    if kind == "rbf":
        k = jnp.exp(-0.5 * d2)
    elif kind == "matern52":
        r = jnp.sqrt(d2 + 1e-12)
        k = (1.0 + math.sqrt(5.0) * r + 5.0 / 3.0 * d2) * jnp.exp(-math.sqrt(5.0) * r)
    else:
        raise ValueError(kind)
    return variance.astype(jnp.float32) * k


def gp_predict(x_train: jax.Array, x_star: jax.Array, lengthscale: jax.Array,
               variance: jax.Array, alpha: jax.Array, linv: jax.Array,
               kind: str = "rbf") -> "tuple[jax.Array, jax.Array]":
    """Batched GP posterior predict (XLA fallback for the Pallas kernel).

    Returns (normalised mean [S, M], quadratic form [S]) where
    mean = Ks^T alpha and qf[s] = ||L^-1 ks||^2 (nonnegative by
    construction — the same conditioning as a triangular solve against
    the Cholesky factor); the caller maps both back to the original
    output scale.
    """
    ks = gp_kernel_matrix(x_train, x_star, lengthscale, variance, kind)
    mean = jnp.matmul(ks.T, alpha, precision=_HI)
    v = jnp.matmul(linv, ks, precision=_HI)
    qf = jnp.sum(v * v, axis=0)
    return mean, qf


def gp_predict_experts(x_train: jax.Array, x_star: jax.Array,
                       lengthscale: jax.Array, variance: jax.Array,
                       alpha: jax.Array, linv: jax.Array,
                       kind: str = "rbf") -> "tuple[jax.Array, jax.Array]":
    """Stacked local-GP ensemble predict (XLA fallback): vmap of
    `gp_predict` over the expert axis.

    x_train: [E, N, D]; x_star: [E, S, D]; alpha: [E, N, M];
    linv: [E, N, N]; shared hyperparameters
    -> (normalised mean [E, S, M], quadratic form [E, S]).  Zero-padded
    training rows contribute nothing (alpha/linv zero there), matching
    the Pallas kernel exactly.
    """
    return jax.vmap(
        lambda xt, xs, al, li: gp_predict(xt, xs, lengthscale, variance,
                                          al, li, kind)
    )(x_train, x_star, alpha, linv)
