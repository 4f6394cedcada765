"""Registry of the assigned architectures.

Every architecture is selectable via ``--arch <id>`` in the launchers; ids use
dashes exactly as assigned.
"""
from repro.types import ArchConfig

from . import (
    hubert_xlarge,
    minicpm3_4b,
    minitron_4b,
    pixtral_12b,
    qwen2_moe_a2_7b,
    qwen3_1_7b,
    qwen3_moe_30b_a3b,
    recurrentgemma_2b,
    rwkv6_7b,
    yi_9b,
)

ARCHS = {
    cfg.name: cfg
    for cfg in (
        recurrentgemma_2b.CONFIG,
        qwen2_moe_a2_7b.CONFIG,
        qwen3_moe_30b_a3b.CONFIG,
        yi_9b.CONFIG,
        qwen3_1_7b.CONFIG,
        minicpm3_4b.CONFIG,
        minitron_4b.CONFIG,
        pixtral_12b.CONFIG,
        hubert_xlarge.CONFIG,
        rwkv6_7b.CONFIG,
    )
}


def get_config(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]
