"""Unified LM assembly for all assigned architectures.

Modes:
  train   — full-sequence forward + chunked CE loss (no cache)
  prefill — full-sequence forward producing a populated decode cache
  decode  — single-token step against the cache

Uniform-block archs run layers through ``lax.scan`` over stacked params
(remat per layer); the hybrid recurrentgemma runs an unrolled loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.sharding import constrain, spec_for
from repro.types import ArchConfig

from .attention import gqa_block, mla_block
from .layers import chunked_ce_loss, mlp_apply, rms_norm
from .moe import moe_block
from .rglru import rglru_block
from .rwkv6 import rwkv_block
from .schema import (  # noqa: F401  (re-exported)
    Param,
    abstract_params,
    init_params,
    model_schema,
    param_specs,
)

def _maybe_remat(fn, remat):
    """remat: 'none' | 'full' (save nothing) | 'dots' (save contractions)."""
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(remat)


# ---------------------------------------------------------------------------
# Cache schema (same Param machinery as model params)
# ---------------------------------------------------------------------------

def cache_schema(cfg: ArchConfig, batch: int, max_len: int):
    kinds = cfg.layer_kinds()

    def layer(kind):
        if kind in ("attn", "attn_local"):
            S = min(cfg.local_window, max_len) if kind == "attn_local" else max_len
            if cfg.attn_kind == "mla":
                m = cfg.mla
                return {
                    "ckv": Param((batch, S, m.kv_lora_rank),
                                 ("batch", "kv_seq", "lora"), "zeros"),
                    "krope": Param((batch, S, m.qk_rope_dim),
                                   ("batch", "kv_seq", "qk_dim"), "zeros"),
                }
            kh, hd = cfg.n_kv_heads, cfg.head_dim
            return {
                "k": Param((batch, S, kh, hd),
                           ("batch", "kv_seq", "kv_heads", "head_dim"), "zeros"),
                "v": Param((batch, S, kh, hd),
                           ("batch", "kv_seq", "kv_heads", "head_dim"), "zeros"),
            }
        if kind == "rglru":
            W = cfg.lru_width or cfg.d_model
            return {
                "h": Param((batch, W), ("batch", "lru_blocks"), "zeros",
                           dtype="float32"),
                "conv": Param((batch, 3, W), ("batch", None, "lru_blocks"),
                              "zeros", dtype="float32"),
            }
        if kind == "rwkv":
            hd = cfg.rwkv_head_dim
            h = cfg.d_model // hd
            return {
                "s": Param((batch, h, hd, hd),
                           ("batch", "heads", "head_dim", None), "zeros",
                           dtype="float32"),
                "x_tm": Param((batch, cfg.d_model), ("batch", "embed"),
                              "zeros", dtype="float32"),
                "x_cm": Param((batch, cfg.d_model), ("batch", "embed"),
                              "zeros", dtype="float32"),
            }
        raise ValueError(kind)

    if cfg.uniform_blocks:
        one = layer(kinds[0])
        layers = jax.tree.map(
            lambda p: Param((cfg.n_layers,) + p.shape, ("layers",) + p.axes,
                            p.init, p.scale, p.dtype),
            one, is_leaf=lambda x: isinstance(x, Param))
    else:
        layers = [layer(k) for k in kinds]
    return {"pos": Param((), (), "zeros", dtype="int32"), "layers": layers}


def _materialize(schema, dtype, abstract: bool):
    def mk(p: Param):
        dt = jnp.dtype(p.dtype) if p.dtype else dtype
        if abstract:
            return jax.ShapeDtypeStruct(p.shape, dt)
        return jnp.zeros(p.shape, dt)
    return jax.tree.map(mk, schema, is_leaf=lambda x: isinstance(x, Param))


def init_cache(cfg, batch, max_len, dtype=jnp.bfloat16):
    return _materialize(cache_schema(cfg, batch, max_len), dtype, False)


def abstract_cache(cfg, batch, max_len, dtype=jnp.bfloat16):
    return _materialize(cache_schema(cfg, batch, max_len), dtype, True)


def cache_specs(cfg, batch, max_len, rules):
    return jax.tree.map(lambda p: spec_for(p.axes, rules),
                        cache_schema(cfg, batch, max_len),
                        is_leaf=lambda x: isinstance(x, Param))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _block_apply(kind, p, x, *, cfg, positions, mode, cache, pos):
    if kind == "rwkv":
        return rwkv_block(p, x, cfg=cfg, mode=mode, cache=cache)
    if kind in ("attn", "attn_local"):
        window = cfg.local_window if kind == "attn_local" else None
        fn = mla_block if cfg.attn_kind == "mla" else gqa_block
        x, new_cache = fn(p, x, cfg=cfg, positions=positions, mode=mode,
                          cache=cache, pos=pos, window=window)
    elif kind == "rglru":
        x, new_cache = rglru_block(p, x, cfg=cfg, mode=mode, cache=cache)
    else:
        raise ValueError(kind)
    if cfg.moe is not None:
        with obs.scope(obs.MOE):
            x = moe_block(p, x, cfg=cfg)
    else:
        with obs.scope(obs.MLP):
            mlp_p = {k[4:]: p[k] for k in ("mlp_wg", "mlp_wu", "mlp_wo") if k in p}
            x = x + mlp_apply(mlp_p, rms_norm(x, p["ln2"]), cfg.mlp_kind)
    x = constrain(x, "batch", "seq", "embed")
    return x, new_cache


def _run_stack(params, cfg, x, positions, mode, cache, remat="full",
               remat_group=8):
    with obs.scope(obs.LAYERS):
        return _run_layers(params, cfg, x, positions, mode, cache, remat,
                           remat_group)


def _run_layers(params, cfg, x, positions, mode, cache, remat, remat_group):
    kinds = cfg.layer_kinds()
    pos = None if cache is None else cache["pos"]
    layer_caches = None if cache is None else cache["layers"]

    if cfg.uniform_blocks:
        kind = kinds[0]

        def body(h, xs):
            lp, lc = xs
            h, c = _block_apply(kind, lp, h, cfg=cfg, positions=positions,
                                mode=mode, cache=lc, pos=pos)
            return h, c

        if mode == "train" and remat != "none":
            # Checkpoint *groups* of k layers: the saved residual stack is
            # (L/k, B, S, D) instead of (L, B, S, D) — 4x less live memory for
            # one extra in-group forward during backprop (already paid by
            # remat).  k = largest of {8,4,2,1} dividing L.
            L = cfg.n_layers
            k = next(g for g in (remat_group, 4, 2, 1) if L % g == 0)

            def group(h, lps):
                # hierarchical remat: per-layer checkpoints inside the
                # checkpointed group, so the group's backward recompute keeps
                # only per-layer inputs live (not layer internals)
                def inner(h2, lp):
                    h2, _ = _maybe_remat(body, remat)(h2, (lp, None))
                    return h2, None
                h, _ = jax.lax.scan(inner, h, lps)
                return h, None

            grouped = jax.tree.map(
                lambda a: a.reshape((L // k, k) + a.shape[1:]),
                params["blocks"])
            x, _ = jax.lax.scan(_maybe_remat(group, remat), x, grouped)
            return x, None
        xs = (params["blocks"], layer_caches)
        x, new_layer_caches = jax.lax.scan(body, x, xs)
    else:
        new_layer_caches = []
        for i, kind in enumerate(kinds):
            lc = None if layer_caches is None else layer_caches[i]

            def one(h, lp, kind=kind, lc=lc):
                return _block_apply(kind, lp, h, cfg=cfg, positions=positions,
                                    mode=mode, cache=lc, pos=pos)

            if mode == "train":
                one = _maybe_remat(one, remat)
            x, c = one(x, params["blocks"][i])
            new_layer_caches.append(c)
    if mode == "train":
        return x, None
    return x, new_layer_caches


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _embed_tokens(params, cfg, tokens):
    with obs.scope(obs.EMBED):
        return jnp.take(params["embed"], tokens, axis=0)


def _head_weight(params, cfg):
    if not cfg.has_decoder:
        return params["cls_head"]
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def forward(params, cfg: ArchConfig, *, tokens=None, embeds=None,
            mode="train", cache=None, remat="full", remat_group=8):
    """Returns (final_hidden, new_cache)."""
    if embeds is not None:
        x = embeds
    else:
        x = _embed_tokens(params, cfg, tokens)
    x = constrain(x, "batch", "seq", "embed")
    B, S = x.shape[0], x.shape[1]
    if mode == "decode":
        positions = jnp.broadcast_to(cache["pos"], (B, 1))
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, new_layer_caches = _run_stack(params, cfg, x, positions, mode, cache,
                                     remat=remat, remat_group=remat_group)
    with obs.scope(obs.HEAD):
        x = rms_norm(x, params["final_norm"])
    new_cache = None
    if mode in ("prefill", "decode"):
        base = S if mode == "prefill" else 1
        new_cache = {"pos": (cache["pos"] + base).astype(jnp.int32),
                     "layers": new_layer_caches}
    return x, new_cache


def loss_fn(params, cfg: ArchConfig, batch, *, remat="full", ce_chunk=512,
            remat_group=8):
    """batch: {"tokens" | "embeds", "labels"}.  Returns (loss, aux)."""
    x, _ = forward(params, cfg, tokens=batch.get("tokens"),
                   embeds=batch.get("embeds"), mode="train", remat=remat,
                   remat_group=remat_group)
    with obs.scope(obs.LOSS):
        head_w = _head_weight(params, cfg)
        loss, count = chunked_ce_loss(x, head_w, batch["labels"], chunk=ce_chunk)
    return loss, {"tokens": count}


def prefill(params, cfg: ArchConfig, cache, *, tokens=None, embeds=None):
    """Populate the cache from a prompt; returns (last_logits, cache)."""
    if not cfg.has_decoder:
        # encoder-only: plain forward + frame-level logits over the small vocab
        x, _ = forward(params, cfg, tokens=tokens, embeds=embeds,
                       mode="train", remat="none")
        logits = jnp.einsum("bsd,dv->bsv", x, _head_weight(params, cfg),
                            preferred_element_type=jnp.float32)
        return logits, None
    x, new_cache = forward(params, cfg, tokens=tokens, embeds=embeds,
                           mode="prefill", cache=cache)
    with obs.scope(obs.HEAD):
        head_w = _head_weight(params, cfg)
        last = x[:, -1:, :]
        logits = jnp.einsum("bsd,dv->bsv", last, head_w,
                            preferred_element_type=jnp.float32)
        return logits[:, 0], new_cache


def decode_step(params, cfg: ArchConfig, cache, tokens):
    """One decode step.  tokens: (B, 1) int32.  Returns (logits, cache)."""
    x, new_cache = forward(params, cfg, tokens=tokens, mode="decode",
                           cache=cache)
    with obs.scope(obs.HEAD):
        head_w = _head_weight(params, cfg)
        logits = jnp.einsum("bsd,dv->bsv", x, head_w,
                            preferred_element_type=jnp.float32)
        return logits[:, 0], new_cache
