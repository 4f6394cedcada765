"""Parameter schema: single source of truth for shapes, logical sharding axes
and initializers.  From one schema tree we derive (a) real initialized params,
(b) ShapeDtypeStruct abstract params for compiling without weights, (c)
PartitionSpecs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import Rules, spec_for
from repro.types import ArchConfig

RGLRU_BLOCKS = 16  # TP-aligned block-diagonal gate projections (see DESIGN.md)


@dataclass(frozen=True)
class Param:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "fan_in"       # fan_in | normal | zeros | ones | lru_lambda
    scale: float = 1.0
    dtype: Optional[str] = None  # override (e.g. f32 gate params)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _attn_schema(cfg: ArchConfig):
    d, h, kh, hd = cfg.d_model, cfg.padded_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "ln1": Param((d,), ("embed",), "zeros"),
        "wq": Param((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Param((d, kh, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Param((d, kh, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Param((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = Param((hd,), ("head_dim",), "zeros")
        s["k_norm"] = Param((hd,), ("head_dim",), "zeros")
    return s


def _mla_schema(cfg: ArchConfig):
    d, h, m = cfg.d_model, cfg.padded_heads, cfg.mla
    qk = m.qk_nope_dim + m.qk_rope_dim
    return {
        "ln1": Param((d,), ("embed",), "zeros"),
        "wq_a": Param((d, m.q_lora_rank), ("embed", "lora")),
        "q_a_norm": Param((m.q_lora_rank,), ("lora",), "zeros"),
        "wq_b": Param((m.q_lora_rank, h, qk), ("lora", "heads", "qk_dim")),
        "wkv_a": Param((d, m.kv_lora_rank + m.qk_rope_dim), ("embed", "lora")),
        "kv_a_norm": Param((m.kv_lora_rank,), ("lora",), "zeros"),
        "wkv_b": Param((m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim),
                       ("lora", "heads", "qk_dim")),
        "wo": Param((h, m.v_head_dim, d), ("heads", "v_dim", "embed")),
    }


def _mlp_schema(cfg: ArchConfig, d_ff=None, prefix="mlp_", ffn_axis="ffn"):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    s = {}
    if cfg._gated:
        s[prefix + "wg"] = Param((d, f), ("embed", ffn_axis))
    s[prefix + "wu"] = Param((d, f), ("embed", ffn_axis))
    s[prefix + "wo"] = Param((f, d), (ffn_axis, "embed"))
    return s


def _moe_schema(cfg: ArchConfig):
    d, m = cfg.d_model, cfg.moe
    ep = cfg.padded_experts
    s = {
        "ln2": Param((d,), ("embed",), "zeros"),
        "router": Param((d, ep), ("embed", "experts"), dtype="float32"),
    }
    # expert weights consume the "model" axis on the expert dim (EP); the
    # per-expert ffn dim must stay unsharded (one mesh axis, one dim)
    if cfg._gated:
        s["we_g"] = Param((ep, d, m.d_expert),
                          ("experts", "embed", "expert_ffn"))
    s["we_u"] = Param((ep, d, m.d_expert),
                      ("experts", "embed", "expert_ffn"))
    s["we_o"] = Param((ep, m.d_expert, d),
                      ("experts", "expert_ffn", "embed"))
    if m.n_shared:
        s.update(_mlp_schema(cfg, d_ff=m.d_shared, prefix="sh_",
                             ffn_axis="shared_ffn"))
        s["sh_gate"] = Param((d,), ("embed",))
    return s


def _rglru_schema(cfg: ArchConfig):
    d = cfg.d_model
    w = cfg.lru_width or d
    g = RGLRU_BLOCKS
    wb = w // g
    return {
        "ln1": Param((d,), ("embed",), "zeros"),
        "w_in": Param((d, 2, w), ("embed", None, "lru_blocks")),
        "conv_w": Param((4, w), (None, "lru_blocks"), scale=0.5),
        "conv_b": Param((w,), ("lru_blocks",), "zeros"),
        "gate_r": Param((g, wb, wb), ("lru_blocks", "lru_width", "lru_width")),
        "gate_i": Param((g, wb, wb), ("lru_blocks", "lru_width", "lru_width")),
        "bias_r": Param((w,), ("lru_blocks",), "zeros"),
        "bias_i": Param((w,), ("lru_blocks",), "zeros"),
        "lam": Param((w,), ("lru_blocks",), "lru_lambda", dtype="float32"),
        "w_out": Param((w, d), ("lru_blocks", "embed")),
    }


def _rwkv_schema(cfg: ArchConfig):
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    h = d // hd
    return {
        "ln1": Param((d,), ("embed",), "zeros"),
        "tm_mu_x": Param((d,), ("embed",), "zeros"),
        "tm_mus": Param((5, d), (None, "embed"), "zeros"),
        "tm_w1": Param((d, 5 * 32), ("embed", "lora")),
        "tm_w2": Param((5, 32, d), (None, "lora", "embed"), scale=0.1),
        "decay_base": Param((d,), ("embed",), "normal", dtype="float32"),
        "decay_w1": Param((d, 64), ("embed", "lora")),
        "decay_w2": Param((64, d), ("lora", "embed"), scale=0.1),
        "u": Param((h, hd), ("heads", "head_dim"), "normal", dtype="float32"),
        "wr": Param((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Param((d, h, hd), ("embed", "heads", "head_dim")),
        "wv": Param((d, h, hd), ("embed", "heads", "head_dim")),
        "wg": Param((d, h, hd), ("embed", "heads", "head_dim")),
        "wo": Param((h, hd, d), ("heads", "head_dim", "embed")),
        "ln_x": Param((h, hd), ("heads", "head_dim"), "zeros"),
        "ln2": Param((d,), ("embed",), "zeros"),
        "cm_mu_k": Param((d,), ("embed",), "zeros"),
        "cm_mu_r": Param((d,), ("embed",), "zeros"),
        "cm_k": Param((d, cfg.d_ff), ("embed", "ffn")),
        "cm_v": Param((cfg.d_ff, d), ("ffn", "embed")),
        "cm_r": Param((d, d), ("embed", None)),
    }


def block_schema(cfg: ArchConfig, kind: str):
    if kind == "rwkv":
        return _rwkv_schema(cfg)
    s = {}
    if kind in ("attn", "attn_local"):
        s.update(_mla_schema(cfg) if cfg.attn_kind == "mla" else _attn_schema(cfg))
    elif kind == "rglru":
        s.update(_rglru_schema(cfg))
    else:
        raise ValueError(kind)
    if cfg.moe is not None:
        s.update(_moe_schema(cfg))
    else:
        s["ln2"] = Param((cfg.d_model,), ("embed",), "zeros")
        s.update(_mlp_schema(cfg))
    return s


def _stack(schema, n):
    return jax.tree.map(
        lambda p: Param((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale,
                        p.dtype),
        schema, is_leaf=lambda x: isinstance(x, Param))


def model_schema(cfg: ArchConfig):
    """Full parameter schema for one architecture."""
    d, v = cfg.d_model, cfg.padded_vocab
    tree = {"embed": Param((v, d), ("vocab", "embed"), "normal"),
            "final_norm": Param((d,), ("embed",), "zeros")}
    if cfg.has_decoder and not cfg.tie_embeddings:
        tree["lm_head"] = Param((d, v), ("embed", "vocab"))
    if not cfg.has_decoder:
        tree["cls_head"] = Param((d, v), ("embed", "vocab"))
    kinds = cfg.layer_kinds()
    if cfg.uniform_blocks:
        tree["blocks"] = _stack(block_schema(cfg, kinds[0]), cfg.n_layers)
    else:
        tree["blocks"] = [block_schema(cfg, k) for k in kinds]
    return tree


# ---------------------------------------------------------------------------
# Materialization
# ---------------------------------------------------------------------------

def _is_param(x):
    return isinstance(x, Param)


def _leaf_dtype(p: Param, default):
    return jnp.dtype(p.dtype) if p.dtype else default


def init_params(cfg: ArchConfig, key, dtype=jnp.float32):
    schema = model_schema(cfg)
    leaves, treedef = jax.tree.flatten(schema, is_leaf=_is_param)
    keys = jax.random.split(key, len(leaves))

    def mk(p: Param, k):
        dt = _leaf_dtype(p, dtype)
        if p.init == "zeros":
            return jnp.zeros(p.shape, dt)
        if p.init == "ones":
            return jnp.ones(p.shape, dt)
        if p.init == "lru_lambda":
            # a = sigmoid(lam) ** 8 in (0.9, 0.999): standard LRU init
            u = jax.random.uniform(k, p.shape, jnp.float32, 0.9, 0.999)
            a8 = u ** (1.0 / 8.0)
            return jnp.log(a8 / (1 - a8)).astype(dt)
        if p.init == "normal":
            return (p.scale * jax.random.normal(k, p.shape, jnp.float32)).astype(dt)
        # fan_in
        std = p.scale / (_fan_in(p) ** 0.5)
        return (std * jax.random.normal(k, p.shape, jnp.float32)).astype(dt)

    return jax.tree.unflatten(treedef, [mk(p, k) for p, k in zip(leaves, keys)])


def _fan_in(p: Param) -> int:
    # contraction dims = all but the trailing "output" dims; heuristic: for
    # matrices (a, b) fan_in = a; for (a, h, d) projections fan_in = a; for
    # (h, d, a) output projections fan_in = h*d; for (g, w, v) block-diag = w.
    sh, ax = p.shape, p.axes
    if ax and ax[0] == "layers":  # stacked: strip the leading layer dim
        sh, ax = sh[1:], ax[1:]
    if len(sh) == 1:
        return sh[0]
    if len(sh) == 2:
        return sh[0]
    if len(sh) == 3:
        if ax[-1] == "embed":               # (h, d, D) / (E, f, D) out-proj
            return sh[0] * sh[1] if ax[0] in ("heads",) else sh[1]
        if ax[0] == "experts":              # (E, D, f)
            return sh[1]
        if ax[0] == "lru_blocks":           # (g, w, v)
            return sh[1]
        return sh[0]                        # (D, h, d) in-proj
    return sh[0]


def abstract_params(cfg: ArchConfig, dtype=jnp.bfloat16):
    schema = model_schema(cfg)
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, _leaf_dtype(p, dtype)),
        schema, is_leaf=_is_param)


def param_specs(cfg: ArchConfig, rules: Rules):
    schema = model_schema(cfg)
    return jax.tree.map(lambda p: spec_for(p.axes, rules), schema,
                        is_leaf=_is_param)


def param_logical_axes(cfg: ArchConfig):
    schema = model_schema(cfg)
    return jax.tree.map(lambda p: p.axes, schema, is_leaf=_is_param)
