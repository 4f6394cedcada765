"""Attention blocks: GQA (global / sliding-window) and MLA, with train /
prefill / decode modes.  Decode uses the two-pass SPMD-friendly formulation
(kernels.flash_attention.decode_attention) so a sequence-sharded KV cache
lowers to two small all-reduces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.flash_attention import decode_attention, flash_attention
from repro.sharding import constrain

from .layers import rms_norm, rope

NEG_INF = -1e30


def _write_cache(cache, new, slot, seq_axis, layer=None):
    """Write one step's entries ``new`` (size 1 on ``seq_axis``) at ``slot``
    of a layer's cache, or, given ``layer``, of that layer of a stacked
    cache: one dynamic_update_slice, in place."""
    start = [0] * new.ndim
    start[seq_axis] = slot
    if layer is not None:
        new, start = new[None], [layer] + start
    return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), start)


def _layer(cache, layer):
    return cache if layer is None else cache[layer]


def gqa_block(p, x, *, cfg, positions, mode, cache, pos=None, window=None,
              layer=None):
    """Pre-norm GQA attention residual branch.

    x: (B, S, D); positions: (B, S) absolute positions; ``pos``: scalar
    absolute position of the current token (decode only); ``layer``: this
    layer's index where ``cache`` is the stacked cache of every layer.
    Returns (residual_out, new_cache).
    """
    with obs.scope(obs.ATTN_QKV):
        y = rms_norm(x, p["ln1"])
        q = jnp.einsum("bsd,dhk->bshk", y, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", y, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", y, p["wv"])
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"])
            k = rms_norm(k, p["k_norm"])
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        q = constrain(q, "batch", "seq", "heads", "head_dim")
        k = constrain(k, "batch", "seq", "kv_heads", "head_dim")

    new_cache = None
    if mode == "decode":
        with obs.scope(obs.ATTN_CACHE_WRITE):
            # the cache is heads-major: (B, KH, S, D)
            slot = pos if window is None else pos % window
            lead = () if layer is None else ("layers",)
            axes = (*lead, "batch", "kv_heads", "kv_seq", "head_dim")
            kc = constrain(_write_cache(cache["k"], k.transpose(0, 2, 1, 3),
                                        slot, 2, layer), *axes)
            vc = constrain(_write_cache(cache["v"], v.transpose(0, 2, 1, 3),
                                        slot, 2, layer), *axes)
        with obs.scope(obs.ATTN_CORE):
            length = jnp.minimum(pos + 1, window) if window else pos + 1
            out = decode_attention(
                q, _layer(kc, layer), _layer(vc, layer), length,
                logits_constraint=lambda s: constrain(
                    s, "batch", None, "kv_heads", None, "kv_seq"))
        new_cache = {"k": kc, "v": vc}
    else:
        with obs.scope(obs.ATTN_CORE):
            out = flash_attention(q, k, v, causal=cfg.causal, window=window)
        if mode == "prefill":
            with obs.scope(obs.ATTN_CACHE_WRITE):
                new_cache = _prefill_cache(k, v, cache, x.shape[1], window)
    with obs.scope(obs.ATTN_OUT):
        out = constrain(out, "batch", "seq", "heads", "head_dim")
        if cfg.padded_heads != cfg.n_heads:
            # zero the padded heads so the padded model == the assigned model
            hmask = (jnp.arange(cfg.padded_heads) < cfg.n_heads).astype(out.dtype)
            out = out * hmask[None, None, :, None]
        o = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return x + o, new_cache


def _prefill_cache(k, v, cache, S, window):
    """The heads-major cache a prefill of S tokens leaves: k/v padded to the
    cache's length, or their trailing window in ring order (slot = pos %
    window)."""
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    if window is not None and window < S:
        shift = S % window
        kc = jnp.roll(k[:, :, S - window:], shift, axis=2)
        vc = jnp.roll(v[:, :, S - window:], shift, axis=2)
    else:
        pad = ((0, 0), (0, 0), (0, cache["k"].shape[2] - S), (0, 0))
        kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)
    return {"k": kc.astype(cache["k"].dtype), "v": vc.astype(cache["v"].dtype)}


def _mla_two_pass(q_abs, q_rope, ckv, krope, length, scale, constraint=None):
    """Absorbed-MLA decode attention: logits from compressed cache.

    q_abs: (B,1,H,R); q_rope: (B,1,H,P); ckv: (B,S,R); krope: (B,S,P).
    Values are the compressed ckv themselves -> (B,1,H,R).
    """
    s = (jnp.einsum("bqhr,bsr->bqhs", q_abs, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhp,bsp->bqhs", q_rope, krope,
                      preferred_element_type=jnp.float32)) * scale
    if constraint is not None:
        s = constraint(s)
    S = ckv.shape[1]
    mask = jnp.arange(S)[None, None, None, :] < length
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p_ = jnp.where(mask, jnp.exp(s - m), 0.0)
    num = jnp.einsum("bqhs,bsr->bqhr", p_, ckv,
                     preferred_element_type=jnp.float32)
    den = jnp.sum(p_, axis=-1, keepdims=True)
    return num / jnp.maximum(den, 1e-30)


def mla_block(p, x, *, cfg, positions, mode, cache, pos=None, window=None,
              layer=None):
    """Multi-head Latent Attention (DeepSeek-V2/MiniCPM3) residual branch."""
    m = cfg.mla
    with obs.scope(obs.ATTN_QKV):
        y = rms_norm(x, p["ln1"])
        cq = rms_norm(jnp.einsum("bsd,dr->bsr", y, p["wq_a"]), p["q_a_norm"])
        q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])  # (B,S,H,nope+rope)
        q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
        q_rope = rope(q_rope, positions, cfg.rope_theta)

        ckv_full = jnp.einsum("bsd,dr->bsr", y, p["wkv_a"])
        ckv, krope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
        ckv = rms_norm(ckv, p["kv_a_norm"])
        krope = rope(krope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

        wkv_b_k = p["wkv_b"][:, :, : m.qk_nope_dim]      # (R, H, nope)
        wkv_b_v = p["wkv_b"][:, :, m.qk_nope_dim:]       # (R, H, v)
    scale = 1.0 / ((m.qk_nope_dim + m.qk_rope_dim) ** 0.5)

    new_cache = None
    if mode == "decode":
        with obs.scope(obs.ATTN_CACHE_WRITE):
            lead = () if layer is None else ("layers",)
            ckv_c = constrain(_write_cache(cache["ckv"], ckv, pos, 1, layer),
                              *lead, "batch", "kv_seq", "lora")
            kr_c = constrain(_write_cache(cache["krope"], krope, pos, 1, layer),
                             *lead, "batch", "kv_seq", "qk_dim")
        with obs.scope(obs.ATTN_CORE):
            q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, wkv_b_k)
            ctx = _mla_two_pass(
                q_abs, q_rope, _layer(ckv_c, layer), _layer(kr_c, layer),
                pos + 1, scale,
                constraint=lambda s: constrain(s, "batch", None, "heads", "kv_seq"))
            out = jnp.einsum("bshr,rhv->bshv", ctx.astype(x.dtype), wkv_b_v)
        new_cache = {"ckv": ckv_c, "krope": kr_c}
    else:
        with obs.scope(obs.ATTN_QKV):
            k_nope = jnp.einsum("bsr,rhn->bshn", ckv, wkv_b_k)
            v = jnp.einsum("bsr,rhv->bshv", ckv, wkv_b_v)
            H = k_nope.shape[2]  # padded head count
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(krope[:, :, None, :],
                                          k_nope.shape[:2] + (H, m.qk_rope_dim))],
                axis=-1)
            qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        with obs.scope(obs.ATTN_CORE):
            out = flash_attention(qq, k, v, causal=cfg.causal, window=window)
        if mode == "prefill":
            with obs.scope(obs.ATTN_CACHE_WRITE):
                Smax = cache["ckv"].shape[1]
                pad = Smax - ckv.shape[1]
                new_cache = {
                    "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))).astype(
                        cache["ckv"].dtype),
                    "krope": jnp.pad(krope, ((0, 0), (0, pad), (0, 0))).astype(
                        cache["krope"].dtype),
                }
    with obs.scope(obs.ATTN_OUT):
        if cfg.padded_heads != cfg.n_heads:
            hmask = (jnp.arange(cfg.padded_heads) < cfg.n_heads).astype(out.dtype)
            out = out * hmask[None, None, :, None]
        o = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
        return x + o, new_cache
