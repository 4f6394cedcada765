"""Pallas TPU flash attention (forward): blocked online softmax.

TPU-native layout: heads-major (B, H, S, D), so every block's last two dims
are (block, D) with D whole.  GQA is expressed in the k/v index_maps (query
head h reads kv head h // group_size), so no kv replication in HBM.

Tile schedule.  The static mask parameters (``causal``, ``window``,
``q_offset``, ``kv_len`` and the block sizes) decide at trace time which
(q block, kv block) tiles hold a visible (query, key) pair
(``tile_schedule``).  The grid is (batch, q_head, tile) over those tiles
only, the schedule passed by scalar prefetch: the q, k, v and output
index maps read each step's q block ``iq`` and kv block ``ik`` from it, so
a tile with nothing visible is no grid step and fetches nothing.  Tiles run
by ``iq``, then by ascending ``ik``, carrying the softmax state (m, l, acc)
in 2-D VMEM scratch across one q block's tiles; the table flags the first
tile of each q block (reset the state) and its last (write the output).
Every visited tile builds the element mask.  A q block that sees no key
gets one fully masked step, so its output is written (zeros).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# One tile of the schedule in one int32:  iq << 16 | ik << 2 | last << 1 | first
_IK_BITS = 14
_IQ_BITS = 15


def unpack_tile(word):
    """(iq, ik, last, first) of packed schedule words; works on numpy arrays
    and on traced int32 scalars alike."""
    return (word >> 16, (word >> 2) & ((1 << _IK_BITS) - 1),
            (word >> 1) & 1, word & 1)


@functools.lru_cache(maxsize=64)
def tile_schedule(Sq, Sk, *, block_q, block_k, causal=True, window=None,
                  q_offset=0, kv_len=None):
    """Packed int32 tiles that hold a visible (query, key) pair, by ``iq``
    then ascending ``ik``.  Key j is visible to query i (absolute position
    ``q_offset + i``) iff j < kv_len, and j <= i if ``causal``, and
    j > i - window if ``window``."""
    nq, nk = Sq // block_q, Sk // block_k
    if nq >= 1 << _IQ_BITS or nk >= 1 << _IK_BITS:
        raise ValueError(f"{nq} q blocks or {nk} kv blocks overflow a "
                         "packed tile; use larger blocks")
    kv_len = Sk if kv_len is None else kv_len
    # first/last query position of each q block as a column, first/last key
    # of each kv block as a row: (nq, nk) tables of the tile's extremes
    q_first = q_offset + np.arange(nq)[:, None] * block_q
    q_last = q_first + block_q - 1
    k_first = np.arange(nk)[None, :] * block_k
    k_last = k_first + block_k - 1
    # any visible: some key below kv_len, some key at or before the last
    # query (causal), some key after the first query's window start
    k_seen = np.minimum(k_last, kv_len - 1)
    visible = np.broadcast_to(k_first <= k_seen, (nq, nk))
    if causal:
        visible = visible & (k_first <= q_last)
    if window is not None:
        visible = visible & (k_seen > q_first - window)
    words = []
    for iq in range(nq):
        iks = np.flatnonzero(visible[iq]).tolist() or [0]
        for n, ik in enumerate(iks):
            words.append(iq << 16 | ik << 2 | (n == len(iks) - 1) << 1
                         | (n == 0))
    table = np.asarray(words, np.int32)
    table.flags.writeable = False
    return table


class TileCounts(NamedTuple):
    visited: int    # grid steps per (batch, head)
    rectangle: int  # nq * nk: the grid steps of a full rectangle


def tile_counts(Sq, Sk, *, block_q, block_k, causal=True, window=None,
                q_offset=0, kv_len=None):
    """Counts of the schedule the kernel runs for these static parameters."""
    table = tile_schedule(Sq, Sk, block_q=block_q, block_k=block_k,
                          causal=causal, window=window, q_offset=q_offset,
                          kv_len=kv_len)
    return TileCounts(len(table), (Sq // block_q) * (Sk // block_k))


def _attn_kernel(sched_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                 l_ref, *, scale, causal, window, kv_len, q_offset,
                 block_q, block_k, precision):
    iq, ik, last, first = unpack_tile(sched_ref[pl.program_id(2)])

    @pl.when(first == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]                      # (bq, d)
    k = k_ref[...]                      # (bk, d)
    v = v_ref[...]                      # (bk, dv)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    qpos = (q_offset + iq * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
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

    @pl.when(last == 1)
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
    kv_len = Sk if kv_len is None else kv_len
    sched = tile_schedule(Sq, Sk, block_q=block_q, block_k=block_k,
                          causal=causal, window=window, q_offset=q_offset,
                          kv_len=kv_len)
    scale = 1.0 / (D ** 0.5)
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else None)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        kv_len=kv_len, q_offset=q_offset,
        block_q=block_q, block_k=block_k, precision=precision)

    def q_map(b, h, t, sched):
        return b, h, unpack_tile(sched[t])[0], 0

    def kv_map(b, h, t, sched):
        return b, h // group, unpack_tile(sched[t])[1], 0

    sq = pl.squeezed
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, len(sched)),
            in_specs=[
                pl.BlockSpec((sq, sq, block_q, D), q_map),
                pl.BlockSpec((sq, sq, block_k, D), kv_map),
                pl.BlockSpec((sq, sq, block_k, Dv), kv_map),
            ],
            out_specs=pl.BlockSpec((sq, sq, block_q, Dv), q_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, Dv), jnp.float32),  # acc
                pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
                pltpu.VMEM((block_q, 1), jnp.float32),   # l (running denom)
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(jnp.asarray(sched), q, k, v)
