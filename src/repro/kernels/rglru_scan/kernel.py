"""Pallas TPU kernel for the RG-LRU linear recurrence.

TPU adaptation of the GPU scan: no warp shuffles exist on TPU, so the
recurrence is blocked over (time, channels).  Grid = (B, channel_block,
time_block) with the time axis innermost (sequential on TPU); the hidden
state is carried across time blocks in a (1, block_w) VMEM scratch row, and
the within-block recurrence runs as an unrolled elementwise (VPU) loop over
the time tile.  Channels shard freely (diagonal recurrence), which is also
what lets the "model" mesh axis split the LRU width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, h_ref, hlast_ref, state_ref, *,
                  block_t, nt):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)   # (bt, bw)
    b = b_ref[...].astype(jnp.float32)   # (bt, bw)
    h = state_ref[...]                   # (1, bw)
    for t in range(block_t):
        h = a[t:t + 1] * h + b[t:t + 1]
        h_ref[t:t + 1, :] = h.astype(h_ref.dtype)
    state_ref[...] = h

    @pl.when(it == nt - 1)
    def _final():
        hlast_ref[...] = h.astype(hlast_ref.dtype)


def rglru_scan_kernel(a, b, h0, *, block_t=128, block_w=256, interpret=False):
    """a, b: (B, T, W); h0: (B, 1, W).  T % block_t == 0, W % block_w == 0.

    Returns (h: (B, T, W), h_last: (B, 1, W) f32).
    """
    B, T, W = a.shape
    nt, nw = T // block_t, W // block_w
    kernel = functools.partial(_rglru_kernel, block_t=block_t, nt=nt)
    sq = pl.squeezed
    seq_spec = pl.BlockSpec((sq, block_t, block_w), lambda b_, iw, it: (b_, it, iw))
    row_spec = pl.BlockSpec((sq, 1, block_w), lambda b_, iw, it: (b_, 0, iw))
    h, h_last = pl.pallas_call(
        kernel,
        grid=(B, nw, nt),  # time innermost: sequential carry in scratch
        in_specs=[seq_spec, seq_spec, row_spec],
        out_specs=[seq_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, W), a.dtype),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="rglru_scan_fwd",
        interpret=interpret,
    )(a, b, h0)
    return h, h_last
