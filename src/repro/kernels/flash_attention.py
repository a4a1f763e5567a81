"""Causal GQA flash attention as a Pallas TPU kernel.

TPU-native adaptation (not a CUDA port): the online-softmax blocking is
expressed as a 2-D grid over (batch*heads, q_blocks) with an inner
fori_loop over KV blocks; BlockSpecs stage q/k/v tiles HBM->VMEM sized to
MXU-aligned (block_q x head_dim) / (block_kv x head_dim) tiles, so the
working set is O(block^2) VMEM and matmul dims are multiples of 128 for
head_dim>=128 (dh 64 still maps onto half-lane tiles).  GQA is handled by
indexing the kv head map in the BlockSpec index fn — no jnp.repeat
materialisation of K/V.

Causal skipping: KV blocks strictly above the diagonal are never read
(the fori_loop upper bound is derived from the q block index), which
halves both FLOPs and HBM traffic for causal prefill.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_KV = 256
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, sq, skv, block_q, block_kv,
                 causal, scale):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                 # [bq, dh]
    bq = q.shape[0]
    dv = v_ref.shape[-1]

    # global q rows, kept 2-D: Mosaic lays out no 1-D index vectors
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_kv), 0)
    nkv = pl.cdiv(skv, block_kv)
    if causal:
        # highest kv block this q block can see (diag offset skv - sq)
        q_off = skv - sq
        last = (qi * block_q + block_q - 1 + q_off) // block_kv
        nkv_used = jnp.minimum(nkv, last + 1)
    else:
        nkv_used = nkv

    def body(ki, carry):
        m, l, acc = carry
        start = pl.multiple_of(ki * block_kv, block_kv)
        k = k_ref[0, pl.ds(start, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(start, block_kv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_kv), 1)
        valid = k_pos < skv
        if causal:
            valid = valid & (k_pos <= q_pos + (skv - sq))
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    init = (jnp.full((bq, 1), NEG_INF, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, dv), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, nkv_used, body, init)
    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_kv: int = DEFAULT_BLOCK_KV,
                    interpret: bool = False):
    """q: [B,Sq,H,Dh]; k/v: [B,Skv,Hkv,Dh(v)] -> [B,Sq,H,Dv].

    Forward-only kernel (decode/prefill serving path); the training path
    uses the custom-VJP chunked fallback in ref.py.
    """
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    scale = 1.0 / math.sqrt(dh)

    block_q = min(block_q, max(sq, 16))
    block_kv = min(block_kv, max(skv, 16))
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_kv)
    vp = _pad_to(v, 1, block_kv)
    sq_p, skv_p = qp.shape[1], kp.shape[1]

    # layout: fold heads into the grid; kernel sees [1, S, Dh] tiles
    qt = qp.transpose(0, 2, 1, 3).reshape(b * h, sq_p, dh)
    kt = kp.transpose(0, 2, 1, 3).reshape(b * hkv, skv_p, dh)
    vt = vp.transpose(0, 2, 1, 3).reshape(b * hkv, skv_p, dv)

    grid = (b * h, sq_p // block_q)

    def q_index(bh, qi):
        return (bh, qi, 0)

    def kv_index(bh, qi):
        return (bh // group, 0, 0)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, sq=sq, skv=skv, block_q=block_q,
                          block_kv=block_kv, causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), q_index),
            pl.BlockSpec((1, skv_p, dh), kv_index),
            pl.BlockSpec((1, skv_p, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), q_index),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, dv), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)

    out = out.reshape(b, h, sq_p, dv).transpose(0, 2, 1, 3)
    return out[:, :sq]
