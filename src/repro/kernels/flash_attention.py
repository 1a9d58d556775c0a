"""Pallas TPU flash attention (blocked online softmax).

Grid: (batch*heads, Tq/block_q, Tk/block_k) — the k dimension is the
innermost ("arbitrary") grid axis, so the (m, l, acc) running statistics
live in VMEM scratch across k iterations.  Block shapes are MXU-aligned
(block_q × d and block_k × d tiles, multiples of (8, 128) for fp32).

Supports causal masking, sliding windows (gemma2/starcoder2 local layers)
and gemma2's logit softcap.  Validated in interpret mode against
``ref.flash_attention_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: Optional[int],
                 logit_softcap: Optional[float], block_q: int, block_k: int,
                 n_k: int, tq: int, tk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (bk, d)
    v = v_ref[0].astype(jnp.float32)                    # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0) \
        + (tk - tq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    valid = k_pos < tk
    if causal:
        valid &= k_pos <= q_pos
    if window is not None:
        valid &= k_pos > q_pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_blk = jnp.max(s, axis=1, keepdims=True)           # (bq, 1)
    m_new = jnp.maximum(m_prev, m_blk)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    logit_softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q/k/v: (B, H, T, D) — MHA layout (GQA callers pre-broadcast KV heads).
    """
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = D ** -0.5 if scale is None else scale
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)

    # pad sequence dims to block multiples
    def pad_to(x, blk, axis):
        t = x.shape[axis]
        rem = (-t) % blk
        if rem == 0:
            return x
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, rem)
        return jnp.pad(x, cfg)

    qp = pad_to(q, block_q, 2).reshape(B * H, -1, D)
    kp = pad_to(k, block_k, 2).reshape(B * H, -1, D)
    vp = pad_to(v, block_k, 2).reshape(B * H, -1, D)
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        logit_softcap=logit_softcap, block_q=block_q, block_k=block_k,
        n_k=nk, tq=Tq, tk=Tk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out.reshape(B, H, -1, D)[:, :, :Tq]
