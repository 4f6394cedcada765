"""Pallas TPU flash attention (forward): blocked online softmax.

TPU-native layout: heads-major (B, H, S, D), so every block's last two dims
are (block, D) with D whole.  Grid = (batch, q_head, q_block, kv_block) with
the kv_block axis innermost (sequential on TPU), carrying the softmax state
(m, l, acc) in 2-D VMEM scratch across kv blocks.  Fully-masked (causal /
out-of-window) kv blocks skip their compute via ``pl.when``.  GQA is expressed
in the k/v index_maps (query head h reads kv head h // group_size), so no kv
replication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale, causal, window, kv_len, q_offset,
                 block_q, block_k, nk, precision):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Block-level visibility: does any (query, key) pair in this tile pass the
    # causal / sliding-window masks?  If not, skip the whole tile.
    q_first = q_offset + iq * block_q
    q_last = q_first + block_q - 1
    k_first = ik * block_k
    k_last = k_first + block_k - 1
    run = k_first < kv_len
    if causal:
        run = jnp.logical_and(run, k_first <= q_last)
    if window is not None:
        run = jnp.logical_and(run, k_last > q_first - window)

    @pl.when(run)
    def _compute():
        q = q_ref[...]                      # (bq, d)
        k = k_ref[...]                      # (bk, d)
        v = v_ref[...]                      # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                 # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        out = jnp.where(l > 0.0, acc_ref[...] / safe, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal=True, window=None, q_offset=0,
                           kv_len=None, block_q=128, block_k=128,
                           interpret=False):
    """q: (B, H, Sq, D); k, v: (B, KH, Sk, D).  Sq % block_q == Sk % block_k == 0.

    ``kv_len`` masks trailing (padded) keys.  Returns (B, H, Sq, Dv).
    f32 inputs contract at full f32 precision; bf16 inputs at the MXU's
    native bf16 x bf16 -> f32.
    """
    B, H, Sq, D = q.shape
    _, KH, Sk, _ = k.shape
    Dv = v.shape[3]
    assert H % KH == 0, (H, KH)
    group = H // KH
    nq, nk = Sq // block_q, Sk // block_k
    kv_len = Sk if kv_len is None else kv_len
    scale = 1.0 / (D ** 0.5)
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else None)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        kv_len=kv_len, q_offset=q_offset,
        block_q=block_q, block_k=block_k, nk=nk, precision=precision)

    sq = pl.squeezed
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((sq, sq, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((sq, sq, block_k, D),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((sq, sq, block_k, Dv),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((sq, sq, block_q, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),  # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running denom)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
