"""Wrapper for the RWKV6 WKV recurrence with backend dispatch.

The Pallas path pads time to the block (padded steps have k = 0 and w = 1,
so they leave the state unchanged) and differentiates through the VJP of
the jnp reference (``rwkv6_wkv_bwd_ref_jnp``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import rwkv6_wkv_kernel
from .ref import rwkv6_reference


def _pad_time(x, t_pad, value):
    return jnp.pad(x, ((0, 0), (0, 0), (0, t_pad), (0, 0)),
                   constant_values=value)


def _wkv_forward(r, k, v, w, u, s0, block_t, interpret):
    B, T, H, D = r.shape
    bt = min(block_t, -(-T // 8) * 8)
    t_pad = (-T) % bt
    heads_major = [x.transpose(0, 2, 1, 3) for x in (r, k, v, w)]
    rh, kh, vh, wh = [_pad_time(x, t_pad, fill) for x, fill
                      in zip(heads_major, (0.0, 0.0, 0.0, 1.0))]
    y, s_last = rwkv6_wkv_kernel(rh, kh, vh, wh, u.reshape(H, 1, D), s0,
                                 block_t=bt, interpret=interpret)
    return y[:, :, :T].transpose(0, 2, 1, 3), s_last


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _wkv_pallas(r, k, v, w, u, s0, block_t, interpret):
    return _wkv_forward(r, k, v, w, u, s0, block_t, interpret)


def _wkv_pallas_fwd(r, k, v, w, u, s0, block_t, interpret):
    out = _wkv_forward(r, k, v, w, u, s0, block_t, interpret)
    return out, (r, k, v, w, u, s0)


def _wkv_ref_jnp_bwd(block_t, interpret, res, g):
    """Backward of the Pallas forward, taken as the VJP of the jnp
    reference scan (recomputes the forward in jnp)."""
    with jax.named_scope("rwkv6_wkv_bwd_ref_jnp"):
        _, vjp = jax.vjp(rwkv6_reference, *res)
        return vjp(g)


_wkv_pallas.defvjp(_wkv_pallas_fwd, _wkv_ref_jnp_bwd)


def rwkv6_wkv(r, k, v, w, u, s0=None, *, backend=None, interpret=False,
              block_t=64):
    """RWKV6 recurrence.  r/k/v/w: (B,T,H,D); u: (H,D).  Returns (y, s_last)."""
    B, T, H, D = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, D, D), jnp.float32)
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "pallas":
        return _wkv_pallas(r, k, v, w, u, s0, block_t, interpret)
    return rwkv6_reference(r, k, v, w, u, s0)
