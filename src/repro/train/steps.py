"""Step builders (train / prefill / decode) + abstract input specs.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every model
input of an (arch × shape) cell — weak-type-correct, shardable, and never
allocating device memory — the dry-run lowers against these.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models import lm
from repro.optim import adamw_update, cosine_schedule
from repro.sharding import make_rules, use_mesh_rules
from repro.types import ArchConfig, ShapeConfig


def make_train_step(cfg: ArchConfig, *, lr=3e-4, warmup=100, total=10_000,
                    remat="full", ce_chunk=512, clip=1.0, weight_decay=0.1,
                    remat_group=8, microbatch=1):
    """microbatch > 1: split the global batch into that many sequential
    micro-batches with f32 gradient accumulation — activation memory scales
    1/microbatch at (nearly) constant FLOPs."""
    schedule = cosine_schedule(lr, warmup, total)

    def loss_of(params, batch):
        return lm.loss_fn(params, cfg, batch, remat=remat,
                          ce_chunk=ce_chunk, remat_group=remat_group)

    def train_step(state, batch):
        if microbatch == 1:
            (loss, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                state["params"], batch)
            tokens = aux["tokens"]
        else:
            mbs = jax.tree.map(
                lambda a: a.reshape((microbatch, a.shape[0] // microbatch)
                                    + a.shape[1:]), batch)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              state["params"])

            def body(carry, mb):
                acc, lsum, tsum = carry
                (l, aux), g = jax.value_and_grad(loss_of, has_aux=True)(
                    state["params"], mb)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), acc, g)
                return (acc, lsum + l, tsum + aux["tokens"]), None

            (grads, lsum, tokens), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.int32)), mbs)
            grads = jax.tree.map(lambda g: g / microbatch, grads)
            loss = lsum / microbatch
        new_state, opt_aux = adamw_update(state, grads, lr=schedule,
                                          clip=clip,
                                          weight_decay=weight_decay)
        metrics = {"loss": loss, "tokens": tokens, **opt_aux}
        return new_state, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig):
    def prefill_step(params, cache, batch):
        return lm.prefill(params, cfg, cache, tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"))
    return prefill_step


def make_decode_step(cfg: ArchConfig):
    def decode_step(params, cache, batch):
        return lm.decode_step(params, cfg, cache, batch["tokens"])
    return decode_step


@dataclasses.dataclass(frozen=True)
class ShardedServe:
    """Serving steps of one architecture over a ("data", "model") mesh, as
    ``make_sharded_serve`` builds them.  ``params``, ``cache`` and
    ``tokens`` are the ``NamedSharding``s the steps' arguments carry (a
    pytree each, like the parameters and the batch cache, and one for a
    (batch, length) token array)."""
    mesh: Any
    rules: dict
    params: Any
    cache: Any
    tokens: NamedSharding
    prefill: Callable   # (params, tokens) -> (logits, cache of those rows)
    insert: Callable    # (cache, part, row) -> cache, donated
    decode: Callable    # (params, cache, tokens, *extra) -> (out, cache)


def make_sharded_serve(cfg: ArchConfig, mesh, batch: int, max_len: int, *,
                       sample=None):
    """Prefill, batch-row insert and decode of ``cfg`` served over ``mesh``
    (``launch.mesh.make_chip_mesh``: tensor parallelism over one host's
    chips), each jitted with its shardings and traced under the mesh's
    ``make_rules`` rules.

    ``prefill`` fills a cache of ``max_len`` positions for the sequences it
    is given (any number of rows), in the parameters' dtype; ``insert``
    writes such a cache into the batch cache at a row, in place; ``decode``
    is one step of the whole batch, in place.  ``sample(logits, *extra)``
    makes what ``decode`` returns beside the cache from its (batch, vocab)
    logits (default: the logits); it comes back whole on every device, as
    do prefill's logits.  The steps take their arguments' shardings:
    parameters placed by ``params``, caches by ``cache`` (prefill's output
    carries it too), tokens by ``tokens``."""
    rules = make_rules(cfg, mesh, global_batch=batch)
    shard = lambda specs: jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    whole = NamedSharding(mesh, P())
    cache_sh = shard(lm.cache_specs(cfg, batch, max_len, rules))
    prefill_step, decode_step = make_prefill_step(cfg), make_decode_step(cfg)
    sample = sample or (lambda logits: logits)

    def under_rules(fn):
        @functools.wraps(fn)
        def traced(*args):
            with use_mesh_rules(mesh, rules):
                return fn(*args)
        return traced

    def prefill(params, tokens):
        # a zero cache built inside the program only gives shapes
        cache = lm.init_cache(cfg, tokens.shape[0], max_len,
                              params["embed"].dtype)
        return prefill_step(params, cache, {"tokens": tokens})

    def decode(params, cache, tokens, *extra):
        logits, cache = decode_step(params, cache, {"tokens": tokens})
        return sample(logits, *extra), cache

    return ShardedServe(
        mesh=mesh, rules=rules, params=shard(lm.param_specs(cfg, rules)),
        cache=cache_sh, tokens=NamedSharding(mesh, P(rules["batch"], None)),
        prefill=jax.jit(under_rules(prefill), out_shardings=(whole, cache_sh)),
        insert=jax.jit(under_rules(functools.partial(lm.insert_rows, cfg)),
                       out_shardings=cache_sh, donate_argnums=0),
        decode=jax.jit(under_rules(decode), out_shardings=(whole, cache_sh),
                       donate_argnums=1))


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig, *,
                act_dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-ins for the batch of one (arch, shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct
    if shape.kind == "train":
        if cfg.frontend:
            batch = {"embeds": tok((B, S, cfg.d_model), act_dtype),
                     "labels": tok((B, S), jnp.int32)}
        else:
            batch = {"tokens": tok((B, S), jnp.int32),
                     "labels": tok((B, S), jnp.int32)}
        return batch
    if shape.kind == "prefill":
        if cfg.frontend:
            return {"embeds": tok((B, S, cfg.d_model), act_dtype)}
        return {"tokens": tok((B, S), jnp.int32)}
    # decode: one new token against a seq_len-deep cache
    return {"tokens": tok((B, 1), jnp.int32)}


def batch_specs(cfg: ArchConfig, shape: ShapeConfig, rules):
    """PartitionSpecs matching input_specs."""
    dp = rules.get("batch")
    if shape.kind == "train":
        if cfg.frontend:
            return {"embeds": P(dp, None, None), "labels": P(dp, None)}
        return {"tokens": P(dp, None), "labels": P(dp, None)}
    if shape.kind == "prefill":
        if cfg.frontend:
            return {"embeds": P(dp, None, None)}
        return {"tokens": P(dp, None)}
    return {"tokens": P(dp, None)}


def ideal_bytes(cfg: ArchConfig, shape: ShapeConfig, *, n_chips: int,
                tp: int) -> float:
    """Analytic lower bound on per-device HBM traffic for one step.

    Brackets the HLO-derived byte count (which inherits the CPU backend's
    shallower fusion granularity and is therefore an upper bound).
    params: read once per pass; train = 3 forwards (primal + 2-level remat)
    + 1 backward + optimizer read/write.  Activations: ~8 residual-stream
    values per layer per pass.  Decode: the KV cache/state read dominates.
    """
    B, S = shape.global_batch, shape.seq_len
    dp = max(n_chips // tp, 1)
    p_bytes = cfg.n_params() * 2 / tp            # bf16, model-sharded
    d = cfg.d_model
    L = cfg.n_layers

    if shape.kind == "train":
        passes = 4.0
        opt = cfg.n_params() * 12.0 / n_chips * 2.0   # ZeRO-1 f32 m/v/master
        act = 8.0 * L * (B / dp) * S * d * 2.0 * 4.0
        grads = p_bytes * 2.0
        return passes * p_bytes + opt + act + grads
    if shape.kind == "prefill":
        act = 8.0 * L * (B / dp) * S * d * 2.0
        return p_bytes + act
    # decode: params once + full cache/state read (+ tiny activations)
    cache = 0.0
    for kind in cfg.layer_kinds():
        if kind in ("attn", "attn_local"):
            Sc = min(cfg.local_window, S) if kind == "attn_local" else S
            if cfg.attn_kind == "mla":
                per_tok = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
            else:
                per_tok = 2 * cfg.n_kv_heads * cfg.head_dim
            cache += (B / dp) * (Sc / max(tp, 1)) * per_tok * 2.0  # seq sharded over model
        elif kind == "rglru":
            cache += (B / dp) * 2 * (cfg.lru_width or d) * 4.0
        elif kind == "rwkv":
            cache += (B / dp) * d * cfg.rwkv_head_dim * 4.0
    return p_bytes + cache


# ---------------------------------------------------------------------------
# Useful-FLOPs model (roofline numerator)
# ---------------------------------------------------------------------------

def useful_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS for one step of this cell, whole cluster (all devices).

    6*N*T for train / 2*N*T for inference (N = active non-embedding params +
    head), plus the attention score/value matmuls (not captured by 6ND):
    fwd 4*B*H*hd*Sq*Skv_eff, x3 for train (bwd = 2x fwd).
    """
    # parameter-matmul term
    n = cfg.n_active_params()
    n -= cfg.padded_vocab * cfg.d_model  # embedding lookup is not a matmul
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens, mult = B * S, 6.0
    elif shape.kind == "prefill":
        tokens, mult = B * S, 2.0
    else:
        tokens, mult = B * 1, 2.0
    total = mult * n * tokens

    # attention term
    attn_mult = 3.0 if shape.kind == "train" else 1.0
    for kind in cfg.layer_kinds():
        if kind not in ("attn", "attn_local"):
            continue
        if cfg.attn_kind == "mla":
            h = cfg.n_heads
            hd_qk = cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim
            hd_v = cfg.mla.v_head_dim
        else:
            h, hd_qk = cfg.n_heads, cfg.head_dim
            hd_v = cfg.head_dim
        window = cfg.local_window if kind == "attn_local" else None
        if shape.kind == "decode":
            sq, skv = 1, (min(S, window) if window else S)
        else:
            sq = S
            if window and window < S:
                skv = window  # each query sees ~window keys
            else:
                skv = (S + 1) / 2 if cfg.causal else S
        total += attn_mult * 2.0 * B * h * sq * skv * (hd_qk + hd_v)
    return total
