"""The model stack's layer names, as ``jax.named_scope``s.

Every op of a compiled step carries the name scopes it was traced in: the
optimized HLO holds them as ``metadata={op_name=...}`` and a TPU profiler
trace as each op's ``tf_op``.  So a trace's device time splits by layer
(``chipbench/scopes.py``).  The scopes are metadata only: with or without
them the compiled program is the same, instruction for instruction.

Where the ops of a scope land in a trace:

- ``layers``: the whole layer stack (the ``lax.scan`` over stacked layers or
  the unrolled loop).  Its ops under no block scope are the scan's own
  slicing of stacked params and caches and its stacking of outputs.
- ``attn/qkv``, ``attn/cache_write``, ``attn/core``, ``attn/out``: an
  attention block's norm and projections, its cache update, the attention
  itself (kernel, decode attention, and the jnp backward's
  ``flash_attention_bwd_chunked_jnp``), and its output projection.
- ``mlp``, ``moe``, ``rwkv/time_mix``, ``rwkv/channel_mix``, ``rglru``: the
  other blocks.
- ``embed``, ``head`` (final norm and logits), ``loss``, ``optimizer``
  (all of the AdamW update with its global norm and clip).
"""
from __future__ import annotations

import jax

EMBED = "embed"
LAYERS = "layers"
ATTN_QKV = "attn/qkv"
ATTN_CACHE_WRITE = "attn/cache_write"
ATTN_CORE = "attn/core"
ATTN_OUT = "attn/out"
MLP = "mlp"
MOE = "moe"
RWKV_TIME_MIX = "rwkv/time_mix"
RWKV_CHANNEL_MIX = "rwkv/channel_mix"
RGLRU = "rglru"
HEAD = "head"
LOSS = "loss"
OPTIMIZER = "optimizer"

SCOPES = (EMBED, LAYERS, ATTN_QKV, ATTN_CACHE_WRITE, ATTN_CORE, ATTN_OUT, MLP,
          MOE, RWKV_TIME_MIX, RWKV_CHANNEL_MIX, RGLRU, HEAD, LOSS, OPTIMIZER)


def scope(name):
    """``jax.named_scope`` for one of the layer names above."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a layer scope: {SCOPES}")
    return jax.named_scope(name)
