"""RecurrentGemma recurrent block: gated branch + causal conv1d + RG-LRU.

The RG-LRU recurrence runs through kernels.rglru_scan (Pallas on TPU, jnp
scan elsewhere).  Gate projections are block-diagonal with 16 TP-aligned
blocks so the recurrence channels shard cleanly over the "model" axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.rglru_scan import rglru_scan
from repro.models.schema import RGLRU_BLOCKS
from repro.sharding import constrain

from .layers import rms_norm

RGLRU_C = 8.0  # recurrence sharpness constant (RG-LRU paper value)


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv, width 4.  x: (B,S,W); w: (4,W); state: (B,3,W)."""
    if state is None:
        xp = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(4)) + b
    new_state = xp[:, -3:, :] if x.shape[1] >= 1 else state
    return out.astype(x.dtype), new_state


def _gates(xb, p, B, S, w_total):
    g = RGLRU_BLOCKS
    wb = w_total // g
    xg = xb.reshape(B, S, g, wb)
    xg = constrain(xg, "batch", "seq", "lru_blocks", "lru_width")
    r = jax.nn.sigmoid(
        jnp.einsum("bsgw,gwv->bsgv", xg, p["gate_r"],
                   preferred_element_type=jnp.float32)
        + p["bias_r"].astype(jnp.float32).reshape(g, wb))
    i = jax.nn.sigmoid(
        jnp.einsum("bsgw,gwv->bsgv", xg, p["gate_i"],
                   preferred_element_type=jnp.float32)
        + p["bias_i"].astype(jnp.float32).reshape(g, wb))
    return r.reshape(B, S, w_total), i.reshape(B, S, w_total)


def _lru_coeffs(p, r, i, xb):
    """a_t = exp(-c*softplus(lam)*r_t); b_t = sqrt(1-a^2) * (i_t * x_t)."""
    log_a = -RGLRU_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(-jnp.expm1(2.0 * log_a), 1e-12))
    b = mult * (i * xb.astype(jnp.float32))
    return a, b


def rglru_block(p, x, *, cfg, mode, cache):
    """Recurrent residual branch.  x: (B,S,D).  Returns (out, new_cache)."""
    with obs.scope(obs.RGLRU):
        return _rglru_branch(p, x, cfg=cfg, mode=mode, cache=cache)


def _rglru_branch(p, x, *, cfg, mode, cache):
    B, S, D = x.shape
    W = cfg.lru_width or D
    y = rms_norm(x, p["ln1"])
    xz = jnp.einsum("bsd,dcw->bscw", y, p["w_in"])
    xb, gate = xz[:, :, 0, :], xz[:, :, 1, :]
    xb = constrain(xb, "batch", "seq", "lru_blocks")

    new_cache = None
    if mode == "decode":
        xb, conv_state = _causal_conv(xb, p["conv_w"], p["conv_b"],
                                      cache["conv"])
        r, i = _gates(xb, p, B, S, W)
        a, b = _lru_coeffs(p, r[:, 0], i[:, 0], xb[:, 0])
        h = a * cache["h"] + b                       # single step (B, W)
        new_cache = {"h": h, "conv": conv_state}
        h = h[:, None, :]
    else:
        xb, conv_state = _causal_conv(xb, p["conv_w"], p["conv_b"])
        r, i = _gates(xb, p, B, S, W)
        a, b = _lru_coeffs(p, r, i, xb)
        h, h_last = rglru_scan(a, b)
        if mode == "prefill":
            new_cache = {"h": h_last, "conv": conv_state.astype(jnp.float32)}
    h = constrain(h.astype(x.dtype), "batch", "seq", "lru_blocks")
    out = jnp.einsum("bsw,wd->bsd", jax.nn.gelu(gate) * h, p["w_out"])
    return x + out, new_cache
