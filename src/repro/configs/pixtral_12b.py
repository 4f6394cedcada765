"""pixtral-12b — pixtral-ViT frontend (stub) + mistral-nemo decoder backbone.

[hf:mistralai/Pixtral-12B-2409; unverified]  40L d_model=5120 32H (GQA kv=8)
d_ff=14336 vocab=131072.  The vision frontend is a stub: the batch carries
precomputed patch embeddings (B, n_patches, d_model) to the backbone.
"""
from repro.types import ArchConfig

CONFIG = ArchConfig(
    name="pixtral-12b",
    family="vlm",
    n_layers=40,
    d_model=5120,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,
    vocab=131_072,
    rope_theta=1_000_000.0,
    frontend="vision",
    source="[hf:mistralai/Pixtral-12B-2409; unverified]",
)
