"""Pieces the plain references share: contractions at full float32
precision, the control's lower precision, and the norm.

``quant=None`` is the reference: every contraction in float32 with
``Precision.HIGHEST`` (on a TPU a float32 product otherwise runs one bf16
pass).  ``quant="fp8"`` is the control: both operands of every contraction
are rounded to float8 e4m3 with one scale per tensor (largest magnitude to
448), the path a lower-precision serving or training change would take.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def fp8(x):
    """Round to float8 e4m3 with a per-tensor scale; back in float32.
    The scale carries no gradient (straight-through rounding)."""
    x = x.astype(jnp.float32)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(eq, a, b, quant=None):
    """einsum in float32 at full precision, or on fp8-rounded operands."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = fp8(a), fp8(b)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps=1e-6):
    """RMSNorm with the gain stored as an offset from 1 (weight = 1 + g)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + g.astype(jnp.float32))


def stacked_blocks(w):
    return {k.split("/", 1)[1]: v for k, v in w.items()
            if k.startswith("blocks/")}

