"""rwkv6-7b (Finch) — attention-free, data-dependent decay.

[arXiv:2404.05892; hf]  32L d_model=4096 d_ff=14336 vocab=65536.
64 WKV heads of dim 64; constant-size recurrent state => sub-quadratic.
"""
from repro.types import ArchConfig

CONFIG = ArchConfig(
    name="rwkv6-7b",
    family="ssm",
    n_layers=32,
    d_model=4096,
    n_heads=64,
    n_kv_heads=64,
    head_dim=64,
    d_ff=14336,
    vocab=65_536,
    block_pattern=("rwkv",),
    attn_kind="none",
    mlp_kind="relu2",
    rwkv_head_dim=64,
    source="[arXiv:2404.05892; hf]",
)
