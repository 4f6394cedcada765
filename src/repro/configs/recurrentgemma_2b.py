"""recurrentgemma-2b — RG-LRU + local attention hybrid, 1 attn : 2 recurrent.

[arXiv:2402.19427; hf]  26L d_model=2560 10H (GQA kv=1) d_ff=7680 vocab=256000.
"""
from repro.types import ArchConfig

CONFIG = ArchConfig(
    name="recurrentgemma-2b",
    family="hybrid",
    n_layers=26,
    d_model=2560,
    n_heads=10,
    n_kv_heads=1,
    head_dim=256,
    d_ff=7680,
    vocab=256_000,
    block_pattern=("rglru", "rglru", "attn_local"),
    attn_kind="gqa",
    mlp_kind="geglu",
    local_window=2048,
    lru_width=2560,
    rope_theta=10_000.0,
    tie_embeddings=True,
    source="[arXiv:2402.19427; hf]",
)
