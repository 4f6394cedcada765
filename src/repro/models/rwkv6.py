"""RWKV6 (Finch) block: data-dependent token-shift time-mix + channel-mix.

The WKV state recurrence runs through kernels.rwkv6_wkv (Pallas on TPU, jnp
scan elsewhere).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.rwkv6_wkv import rwkv6_wkv
from repro.sharding import constrain

from .layers import rms_norm


def _shift(x, prev):
    """Token shift: x_{t-1} with x_{-1} = prev (or zeros).  x: (B,S,D)."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    else:
        prev = prev[:, None, :].astype(x.dtype)
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _group_norm(x, scale, eps=1e-5):
    """Per-head layer norm.  x: (B,S,H,D); scale: (H,D)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)
            * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _time_mix(p, x, x_prev, *, cfg, state):
    B, S, D = x.shape
    hd = cfg.rwkv_head_dim
    H = D // hd
    dx = x_prev - x
    xxx = x + dx * p["tm_mu_x"].astype(x.dtype)
    z = jnp.tanh(jnp.einsum("bsd,dk->bsk", xxx, p["tm_w1"]))
    z = z.reshape(B, S, 5, 32)
    adj = jnp.einsum("bsfk,fkd->bsfd", z, p["tm_w2"])
    mixed = (x[:, :, None, :]
             + dx[:, :, None, :] * (p["tm_mus"].astype(x.dtype) + adj))
    xw, xk, xv, xr, xg = [mixed[:, :, j, :] for j in range(5)]

    r = jnp.einsum("bsd,dhk->bshk", xr, p["wr"])
    k = jnp.einsum("bsd,dhk->bshk", xk, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xv, p["wv"])
    g = jax.nn.silu(jnp.einsum("bsd,dhk->bshk", xg, p["wg"]))
    r = constrain(r, "batch", "seq", "heads", "head_dim")

    dz = jnp.tanh(jnp.einsum("bsd,dk->bsk", xw, p["decay_w1"]))
    decay = (p["decay_base"].astype(jnp.float32)
             + jnp.einsum("bsk,kd->bsd", dz, p["decay_w2"]).astype(jnp.float32))
    w = jnp.exp(-jnp.exp(decay.astype(jnp.float32)))   # (B,S,D) in (0,1)
    w = w.reshape(B, S, H, hd)

    y, s_last = rwkv6_wkv(r, k, v, w.astype(r.dtype), p["u"], state)
    y = _group_norm(y, p["ln_x"]) * g
    y = constrain(y, "batch", "seq", "heads", "head_dim")
    out = jnp.einsum("bshk,hkd->bsd", y, p["wo"])
    return out, s_last


def rwkv_block(p, x, *, cfg, mode, cache):
    """Full RWKV6 layer (time-mix + channel-mix residual branches)."""
    B, S, D = x.shape
    new_cache = None

    with obs.scope(obs.RWKV_TIME_MIX):
        y = rms_norm(x, p["ln1"])
        if mode == "decode":
            x_prev = cache["x_tm"][:, None, :].astype(y.dtype)
            state = cache["s"]
        else:
            x_prev = _shift(y, None)
            state = None
        tm_out, s_last = _time_mix(p, y, x_prev, cfg=cfg, state=state)
        x = x + tm_out

    with obs.scope(obs.RWKV_CHANNEL_MIX):
        y2 = rms_norm(x, p["ln2"])
        if mode == "decode":
            y2_prev = cache["x_cm"][:, None, :].astype(y2.dtype)
        else:
            y2_prev = _shift(y2, None)
        dk = y2 + (y2_prev - y2) * p["cm_mu_k"].astype(y2.dtype)
        dr = y2 + (y2_prev - y2) * p["cm_mu_r"].astype(y2.dtype)
        kk = jax.nn.relu(jnp.einsum("bsd,df->bsf", dk, p["cm_k"]))
        kk = constrain(kk * kk, "batch", "seq", "ffn")
        cm = jnp.einsum("bsf,fd->bsd", kk, p["cm_v"])
        rr = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", dr, p["cm_r"]))
        x = x + rr.astype(cm.dtype) * cm

    if mode in ("prefill", "decode"):
        new_cache = {"s": s_last,
                     "x_tm": y[:, -1, :].astype(jnp.float32),
                     "x_cm": y2[:, -1, :].astype(jnp.float32)}
    return x, new_cache
