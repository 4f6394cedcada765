"""Pallas TPU flash attention: blocked online softmax, and its backward.

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

Backward (FlashAttention-2).  The forward, asked for it, also writes each
row's log-sum-exp ``m + log l`` as a (1, block_q) row of a (B, H, 1, Sq)
f32 array.  Two kernels then recompute ``p = exp(s * scale - lse)`` under
the same element mask, with ``delta = rowsum(o * do)``:

- ``flash_attention_bwd_dkv`` walks the same visible tiles in kv order (by
  ``ik``, then ascending ``iq``), and for each tile the group's query heads
  (grid (batch, kv_head, tile, group)).  It works on the transposed tile
  (keys on sublanes, queries on lanes), so lse and delta broadcast as rows
  and every product is a plain or rhs-transposed matmul; dk and dv
  accumulate in f32 VMEM and are written once per kv block, per kv head.
- ``flash_attention_bwd_dq`` runs the forward's grid and order, and
  accumulates dq in f32 VMEM, written once per q block.
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
_LANES = 128


def unpack_tile(word):
    """(iq, ik, last, first) of packed schedule words; works on numpy arrays
    and on traced int32 scalars alike."""
    return (word >> 16, (word >> 2) & ((1 << _IK_BITS) - 1),
            (word >> 1) & 1, word & 1)


class TileSchedule(NamedTuple):
    """Packed tiles of one visibility rule in two orders; ``last``/``first``
    flag the ends of each q block in ``by_q``, of each kv block in
    ``by_kv``."""
    by_q: np.ndarray   # by iq, then ascending ik: the forward and dq
    by_kv: np.ndarray  # by ik, then ascending iq: dk and dv


def _packed(pairs_by_block, major_is_q):
    words = []
    for major, minors in enumerate(pairs_by_block):
        minors = minors or [0]
        for n, minor in enumerate(minors):
            iq, ik = (major, minor) if major_is_q else (minor, major)
            words.append(iq << 16 | ik << 2 | (n == len(minors) - 1) << 1
                         | (n == 0))
    table = np.asarray(words, np.int32)
    table.flags.writeable = False
    return table


@functools.lru_cache(maxsize=64)
def tile_schedule(Sq, Sk, *, block_q, block_k, causal=True, window=None,
                  q_offset=0, kv_len=None):
    """The tiles that hold a visible (query, key) pair, in q order and in kv
    order.  Key j is visible to query i (absolute position ``q_offset + i``)
    iff j < kv_len, and j <= i if ``causal``, and j > i - window if
    ``window``.  A block that sees nothing gets one fully masked tile
    (against block 0 of the other side), so its output is written."""
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
    return TileSchedule(
        _packed([np.flatnonzero(row).tolist() for row in visible], True),
        _packed([np.flatnonzero(col).tolist() for col in visible.T], False))


class TileCounts(NamedTuple):
    by_q: int       # grid steps per (batch, head): the forward and dq
    by_kv: int      # grid steps per (batch, head): dk and dv
    rectangle: int  # nq * nk: the grid steps of a full rectangle


def tile_counts(Sq, Sk, *, block_q, block_k, causal=True, window=None,
                q_offset=0, kv_len=None):
    """Counts of the schedules the kernels run for these static parameters."""
    sched = tile_schedule(Sq, Sk, block_q=block_q, block_k=block_k,
                          causal=causal, window=window, q_offset=q_offset,
                          kv_len=kv_len)
    return TileCounts(len(sched.by_q), len(sched.by_kv),
                      (Sq // block_q) * (Sk // block_k))


def _element_mask(iq, ik, shape, q_axis, *, causal, window, kv_len,
                  q_offset, block_q, block_k):
    """Visible (query, key) pairs of tile (iq, ik), queries along
    ``q_axis`` of ``shape``, keys along the other."""
    qpos = (q_offset + iq * block_q
            + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis))
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                   1 - q_axis)
    mask = kpos < kv_len
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    return mask


def _row(col):
    """(n, 1) -> (1, n), through a lane-aligned transpose."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[:1]


def _col(row):
    """(1, n) -> (n, 1), through a lane-aligned transpose."""
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, n)))[:, :1]


def _attn_kernel(sched_ref, q_ref, k_ref, v_ref, *refs, scale, causal,
                 window, kv_len, q_offset, block_q, block_k, precision,
                 with_lse):
    if with_lse:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
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

    mask = _element_mask(iq, ik, (block_q, block_k), 0, causal=causal,
                         window=window, kv_len=kv_len, q_offset=q_offset,
                         block_q=block_q, block_k=block_k)
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
        if with_lse:
            # a row that sees no key reads -inf; every use of it is masked
            lse_ref[...] = _row(m_ref[...] + jnp.log(l))


def _precision(dtype):
    """f32 inputs contract at full f32 precision; bf16 at the MXU's native
    bf16 x bf16 -> f32."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _by_q_maps(group):
    """Index maps of a (batch, q_head, tile) grid over the q-ordered
    schedule: q-shaped blocks, kv blocks (query head h reads kv head
    h // group), and (1, block_q) rows of lse and delta."""
    def q_map(b, h, t, sched):
        return b, h, unpack_tile(sched[t])[0], 0

    def kv_map(b, h, t, sched):
        return b, h // group, unpack_tile(sched[t])[1], 0

    def row_map(b, h, t, sched):
        return b, h, 0, unpack_tile(sched[t])[0]

    return q_map, kv_map, row_map


def flash_attention_kernel(q, k, v, *, causal=True, window=None, q_offset=0,
                           kv_len=None, block_q=128, block_k=128,
                           interpret=False, with_lse=False):
    """q: (B, H, Sq, D); k, v: (B, KH, Sk, D).  Sq % block_q == Sk % block_k == 0.

    ``kv_len`` masks trailing (padded) keys.  Returns (B, H, Sq, Dv), and
    with ``with_lse`` also each row's log-sum-exp, (B, H, 1, Sq) f32, for
    ``flash_attention_bwd_kernel``.
    """
    B, H, Sq, D = q.shape
    _, KH, Sk, _ = k.shape
    Dv = v.shape[3]
    assert H % KH == 0, (H, KH)
    group = H // KH
    kv_len = Sk if kv_len is None else kv_len
    sched = tile_schedule(Sq, Sk, block_q=block_q, block_k=block_k,
                          causal=causal, window=window, q_offset=q_offset,
                          kv_len=kv_len).by_q
    scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        kv_len=kv_len, q_offset=q_offset, block_q=block_q, block_k=block_k,
        precision=_precision(q.dtype), with_lse=with_lse)

    q_map, kv_map, row_map = _by_q_maps(group)
    sq = pl.squeezed
    out_specs = pl.BlockSpec((sq, sq, block_q, Dv), q_map)
    out_shape = jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype)
    if with_lse:
        out_specs = [out_specs, pl.BlockSpec((sq, sq, 1, block_q), row_map)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32)]
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
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block_q, Dv), jnp.float32),  # acc
                pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
                pltpu.VMEM((block_q, 1), jnp.float32),   # l (running denom)
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(jnp.asarray(sched), q, k, v)


def _dkv_kernel(sched_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, group, precision,
                **mask_kw):
    iq, ik, last, first = unpack_tile(sched_ref[pl.program_id(2)])
    g = pl.program_id(3)

    @pl.when(jnp.logical_and(first == 1, g == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[...]                      # (bq, d)
    k = k_ref[...]                      # (bk, d)
    v = v_ref[...]                      # (bk, dv)
    do = do_ref[...]                    # (bq, dv)
    nt = (((1,), (1,)), ((), ()))
    nn = (((1,), (0,)), ((), ()))
    s_t = jax.lax.dot_general(k, q, nt, precision=precision,
                              preferred_element_type=jnp.float32) * scale
    mask = _element_mask(iq, ik, s_t.shape, 1, **mask_kw)   # (bk, bq)
    p_t = jnp.where(mask, jnp.exp(s_t - lse_ref[...]), 0.0)
    dv_acc[...] += jax.lax.dot_general(
        p_t.astype(do.dtype), do, nn, precision=precision,
        preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v, do, nt, precision=precision,
                               preferred_element_type=jnp.float32)
    ds_t = p_t * (dp_t - delta_ref[...])
    dk_acc[...] += jax.lax.dot_general(
        ds_t.astype(q.dtype), q, nn, precision=precision,
        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(last == 1, g == group - 1))
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(sched_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, lse_col, delta_col, *, scale, precision,
               **mask_kw):
    iq, ik, last, first = unpack_tile(sched_ref[pl.program_id(2)])

    @pl.when(first == 1)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        lse_col[...] = _col(lse_ref[...])
        delta_col[...] = _col(delta_ref[...])

    q = q_ref[...]                      # (bq, d)
    k = k_ref[...]                      # (bk, d)
    v = v_ref[...]                      # (bk, dv)
    do = do_ref[...]                    # (bq, dv)
    nt = (((1,), (1,)), ((), ()))
    s = jax.lax.dot_general(q, k, nt, precision=precision,
                            preferred_element_type=jnp.float32) * scale
    mask = _element_mask(iq, ik, s.shape, 0, **mask_kw)     # (bq, bk)
    p = jnp.where(mask, jnp.exp(s - lse_col[...]), 0.0)
    dp = jax.lax.dot_general(do, v, nt, precision=precision,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_col[...])
    dq_acc[...] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)

    @pl.when(last == 1)
    def _finalize():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd_kernel(q, k, v, o, lse, do, *, causal=True,
                               window=None, q_offset=0, kv_len=None,
                               block_q=128, block_k=128, interpret=False):
    """Gradients (dq, dk, dv) of ``flash_attention_kernel`` at cotangent
    ``do``, from its inputs, its output ``o`` and its ``lse``; shapes and
    static arguments as the forward's, dk and dv per kv head."""
    B, H, Sq, D = q.shape
    _, KH, Sk, _ = k.shape
    Dv = v.shape[3]
    group = H // KH
    kv_len = Sk if kv_len is None else kv_len
    sched = tile_schedule(Sq, Sk, block_q=block_q, block_k=block_k,
                          causal=causal, window=window, q_offset=q_offset,
                          kv_len=kv_len)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=3)[:, :, None, :]                  # (B, H, 1, Sq)
    kw = dict(scale=1.0 / (D ** 0.5), precision=_precision(q.dtype),
              causal=causal, window=window, kv_len=kv_len, q_offset=q_offset,
              block_q=block_q, block_k=block_k)
    sq = pl.squeezed

    # dk, dv: kv blocks in order, the group's query heads innermost
    def q_map(b, h, t, g, sched):
        return b, h * group + g, unpack_tile(sched[t])[0], 0

    def row_map(b, h, t, g, sched):
        return b, h * group + g, 0, unpack_tile(sched[t])[0]

    def kv_map(b, h, t, g, sched):
        return b, h, unpack_tile(sched[t])[1], 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, group=group, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KH, len(sched.by_kv), group),
            in_specs=[
                pl.BlockSpec((sq, sq, block_q, D), q_map),
                pl.BlockSpec((sq, sq, block_k, D), kv_map),
                pl.BlockSpec((sq, sq, block_k, Dv), kv_map),
                pl.BlockSpec((sq, sq, block_q, Dv), q_map),
                pl.BlockSpec((sq, sq, 1, block_q), row_map),
                pl.BlockSpec((sq, sq, 1, block_q), row_map),
            ],
            out_specs=[pl.BlockSpec((sq, sq, block_k, D), kv_map),
                       pl.BlockSpec((sq, sq, block_k, Dv), kv_map)],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, Dv), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(jnp.asarray(sched.by_kv), q, k, v, do, lse, delta)

    # dq: the forward's grid and order
    dq_q_map, dq_kv_map, dq_row_map = _by_q_maps(group)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, len(sched.by_q)),
            in_specs=[
                pl.BlockSpec((sq, sq, block_q, D), dq_q_map),
                pl.BlockSpec((sq, sq, block_k, D), dq_kv_map),
                pl.BlockSpec((sq, sq, block_k, Dv), dq_kv_map),
                pl.BlockSpec((sq, sq, block_q, Dv), dq_q_map),
                pl.BlockSpec((sq, sq, 1, block_q), dq_row_map),
                pl.BlockSpec((sq, sq, 1, block_q), dq_row_map),
            ],
            out_specs=pl.BlockSpec((sq, sq, block_q, D), dq_q_map),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),   # lse
                            pltpu.VMEM((block_q, 1), jnp.float32)],  # delta
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(jnp.asarray(sched.by_q), q, k, v, do, lse, delta)
    return dq, dk, dv
