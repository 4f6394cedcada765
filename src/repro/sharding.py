"""Logical-axis sharding rules (MaxText-style) for a ("data", "model") mesh.

Models annotate params/activations with *logical* axis names; a per-arch rule
table maps those to mesh axes.  An FFN width or vocabulary that does not
divide the "model" axis is replicated; kv heads that do not divide it leave
the decode cache sharded on its sequence instead; query heads and experts
shard anyway, and GSPMD pads them.

``use_mesh_rules`` installs a (mesh, rules) context so deep model code can call
``constrain(x, *logical)`` without threading the mesh everywhere; outside the
context the call is a no-op (CPU smoke tests run unsharded).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_CTX = threading.local()

Rules = Dict[str, Optional[object]]  # logical name -> mesh axis (str|tuple|None)


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def make_rules(cfg, mesh, *, global_batch: Optional[int] = None) -> Rules:
    """Build the logical->mesh mapping for one architecture on one mesh.

    global_batch: if given and not divisible by the DP world size, the batch
    axis is replicated (e.g. a batch of one).
    """
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp = dp if dp else None
    if dp is not None and global_batch is not None:
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if global_batch % dp_size != 0:
            # shed outer axes until the batch divides (pod first, then data)
            while dp and global_batch % _prod(mesh, dp) != 0:
                dp = dp[1:]
            dp = dp if dp else None
    tp = "model" if "model" in names else None
    tp_size = mesh.shape["model"] if tp else 1

    def div(n):  # shard over model only if divisible
        return tp if tp and n % tp_size == 0 else None

    kv_heads_shardable = tp is not None and cfg.n_kv_heads % tp_size == 0 \
        and cfg.attn_kind == "gqa"
    rules: Rules = {
        "batch": dp,
        "seq": None,
        # decode cache: shard kv heads when they divide the model axis,
        # otherwise shard the cache *sequence* dim (flash-decoding in SPMD)
        "kv_seq": None if kv_heads_shardable else tp,
        # q heads shard over "model" even when the count does not divide 16:
        # GSPMD pads the dim (e.g. 40 MLA heads -> 48, 24 -> 32).  Padded
        # head-sharding wastes <= (pad/heads) compute but replication would
        # waste (tp-1)/tp compute AND blow up per-device attention buffers
        # (measured: minicpm3 train went 234 GB -> fits after this change).
        "heads": tp if cfg.n_heads > 1 else None,
        "kv_heads": tp if kv_heads_shardable else None,
        "head_dim": None,
        "qk_dim": None,
        "v_dim": None,
        "embed": None,
        "ffn": div(cfg.d_ff),
        "expert_ffn": None,  # EP consumes "model" on the expert dim
        "shared_ffn": div(cfg.moe.d_shared) if (cfg.moe and cfg.moe.n_shared) else None,
        "vocab": div(cfg.padded_vocab),
        "experts": tp,  # uneven expert sharding (60 -> pad 64) beats replication
        "capacity": None,
        "layers": None,
        "lru_blocks": div(16) if tp_size in (1, 2, 4, 8, 16) else None,
        "lru_width": None,
        "lora": None,
        "stats": None,
    }
    return rules


def spec_for(axes: Tuple[Optional[str], ...], rules: Rules) -> P:
    parts = []
    for a in axes:
        parts.append(None if a is None else rules.get(a))
    # trim trailing Nones for tidiness
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


@contextlib.contextmanager
def use_mesh_rules(mesh, rules: Rules):
    stack = getattr(_CTX, "stack", None)
    if stack is None:
        stack = _CTX.stack = []
    stack.append((mesh, rules))
    try:
        yield
    finally:
        stack.pop()


def current() -> Optional[Tuple[object, Rules]]:
    stack = getattr(_CTX, "stack", None)
    return stack[-1] if stack else None


def constrain(x, *logical: Optional[str]):
    """Pin x's sharding by logical axis names; no-op outside a mesh context."""
    ctx = current()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = spec_for(tuple(logical), rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh, rules: Rules, axes: Tuple[Optional[str], ...]):
    return NamedSharding(mesh, spec_for(axes, rules))
