"""Pallas TPU kernel for the RWKV6 WKV state recurrence.

Heads-major layout (B, H, T, D) so every block's last two dims are
(block_t, D) with D whole.  Grid = (B, H, time_block) with time innermost
(sequential); the per-head (D x D) state is carried in VMEM scratch across
time blocks.  Within a block the recurrence unrolls over the time tile: each
step is an outer product + mat-vec — small MXU/VPU work on resident VMEM
tiles, the TPU-native analogue of the CUDA per-warp state registers used by
the reference GPU kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, slast_ref,
                state_ref, *, block_t, nt):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)   # (bt, D)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)   # (1, D)
    # transposed copies: column t of each is step t's vector along the
    # state's row (key) axis
    rT, kT, wT = r.T, k.T, w.T           # (D, bt)
    S = state_ref[...]                   # (D, D)
    for t in range(block_t):
        vt = v[t:t + 1]                                  # (1, D)
        kv = kT[:, t:t + 1] * vt                         # (D, D)
        bonus = jnp.sum(r[t:t + 1] * u * k[t:t + 1], axis=1, keepdims=True)
        y = jnp.sum(rT[:, t:t + 1] * S, axis=0, keepdims=True) + bonus * vt
        y_ref[t:t + 1, :] = y.astype(y_ref.dtype)
        S = wT[:, t:t + 1] * S + kv
    state_ref[...] = S

    @pl.when(it == nt - 1)
    def _final():
        slast_ref[...] = S.astype(slast_ref.dtype)


def rwkv6_wkv_kernel(r, k, v, w, u, s0, *, block_t=64, interpret=False):
    """r/k/v/w: (B, H, T, D); u: (H, 1, D); s0: (B, H, D, D).  T % block_t == 0.

    Returns (y: (B, H, T, D), s_last: (B, H, D, D) f32).
    """
    B, H, T, D = r.shape
    nt = T // block_t
    kernel = functools.partial(_wkv_kernel, block_t=block_t, nt=nt)
    sq = pl.squeezed
    seq_spec = pl.BlockSpec((sq, sq, block_t, D), lambda b, h, it: (b, h, it, 0))
    state_spec = pl.BlockSpec((sq, sq, D, D), lambda b, h, it: (b, h, 0, 0))
    y, s_last = pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((sq, 1, D), lambda b, h, it: (h, 0, 0)),
            state_spec,
        ],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), r.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="rwkv6_wkv_fwd",
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return y, s_last
