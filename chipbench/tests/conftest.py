"""Tests of the benchmark that need no chip: tiny widths on the CPU.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import dataclasses
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

TINY_YI = {
    "name": "tiny-yi", "family": "llama", "program_arch": "yi-9b", "reference": "yi",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 16,
    "num_key_value_heads": 4, "head_dim": 8, "intermediate_size": 96,
    "vocab_size": 250, "padded_vocab_size": 256, "rope_theta": 5000000.0,
    "param_dtype": "float32",
}
TINY_RWKV = {
    "name": "tiny-rwkv", "family": "rwkv6", "program_arch": "rwkv6-7b",
    "reference": "rwkv6", "num_hidden_layers": 2, "hidden_size": 64,
    "head_size": 16, "intermediate_size": 96, "vocab_size": 256,
    "padded_vocab_size": 256, "param_dtype": "float32",
    "float32_params": ["blocks/decay_base", "blocks/u"],
}


def tiny_arch(c, get_config):
    """The program's record for a tiny configuration: the named
    architecture with the tiny file's widths."""
    a = get_config(c["program_arch"])
    if c["family"] == "llama":
        return dataclasses.replace(
            a, d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"])
    return dataclasses.replace(a, d_model=c["hidden_size"], d_ff=c["intermediate_size"],
                               rwkv_head_dim=c["head_size"], n_heads=c["hidden_size"]
                               // c["head_size"], n_kv_heads=c["hidden_size"]
                               // c["head_size"], head_dim=c["head_size"])


@pytest.fixture
def tiny(monkeypatch):
    """Let the harness build tiny configurations of the program."""
    import repro.configs as configs
    real = configs.get_config
    by_arch = {TINY_YI["program_arch"]: TINY_YI, TINY_RWKV["program_arch"]: TINY_RWKV}

    def get_config(name):
        if name in by_arch:
            return tiny_arch(by_arch[name], real)
        return real(name)

    monkeypatch.setattr(configs, "get_config", get_config)
    return {"yi": TINY_YI, "rwkv6": TINY_RWKV}
