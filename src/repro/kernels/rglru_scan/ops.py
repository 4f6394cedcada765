"""Wrapper for the RG-LRU recurrence with backend dispatch.

The Pallas path pads time and width to the block (padded steps have a = 1
and b = 0, so they leave the state unchanged) and differentiates through the
VJP of the jnp reference (``rglru_scan_bwd_ref_jnp``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import rglru_scan_kernel
from .ref import rglru_reference


def _rglru_forward(a, b, h0, block_t, block_w, interpret):
    B, T, W = a.shape
    bt = min(block_t, -(-T // 8) * 8)
    bw = min(block_w, -(-W // 128) * 128)
    widths = ((0, 0), (0, (-T) % bt), (0, (-W) % bw))
    ap = jnp.pad(a, widths, constant_values=1.0)
    bp = jnp.pad(b, widths)
    h0p = jnp.pad(h0, ((0, 0), (0, (-W) % bw)))[:, None, :]
    h, h_last = rglru_scan_kernel(ap, bp, h0p, block_t=bt, block_w=bw,
                                  interpret=interpret)
    return h[:, :T, :W], h_last[:, 0, :W]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rglru_pallas(a, b, h0, block_t, block_w, interpret):
    return _rglru_forward(a, b, h0, block_t, block_w, interpret)


def _rglru_pallas_fwd(a, b, h0, block_t, block_w, interpret):
    return _rglru_forward(a, b, h0, block_t, block_w, interpret), (a, b, h0)


def _rglru_ref_jnp_bwd(block_t, block_w, interpret, res, g):
    """Backward of the Pallas forward, taken as the VJP of the jnp
    reference scan (recomputes the forward in jnp)."""
    with jax.named_scope("rglru_scan_bwd_ref_jnp"):
        _, vjp = jax.vjp(rglru_reference, *res)
        return vjp(g)


_rglru_pallas.defvjp(_rglru_pallas_fwd, _rglru_ref_jnp_bwd)


def rglru_scan(a, b, h0=None, *, backend=None, interpret=False,
               block_t=128, block_w=256):
    """Run h_t = a_t*h_{t-1} + b_t.  a, b: (B, T, W).  Returns (h, h_last)."""
    B, T, W = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, W), jnp.float32)
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "pallas":
        return _rglru_pallas(a, b, h0, block_t, block_w, interpret)
    return rglru_reference(a, b, h0)
