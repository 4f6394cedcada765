"""FLOP and byte counts against numbers worked out by hand from the
configurations' widths."""
from __future__ import annotations

import pytest

from chipbench import counts, spec

SERVE = spec.config("yi9b-serve-8l")
TRAIN = spec.config("yi9b-train-4l")


def test_yi_layer_weights():
    # q and o 4096 x 4096, k and v 4096 x 512, three 4096 x 11008 MLP matrices
    assert counts.layer_matmul_params(SERVE) == 2 * 4096 ** 2 + 2 * 4096 * 512 + 3 * 4096 * 11008


def test_prefill_32k_flops():
    # 2 x 1.384e9 x 32768 (matmuls) + 7.04e13 (causal attention, 8 layers)
    # + 2 x 4096 x 64000 (the head, over the last token only)
    f = counts.prefill_flops(SERVE, 1, 32768)
    assert f == pytest.approx(1.61e14, rel=2e-3)
    attn = 8 * counts.attention_fwd_flops(SERVE, 1, 32768)
    assert attn == pytest.approx(7.04e13, rel=2e-3)
    assert attn / f == pytest.approx(0.44, abs=0.01)


def test_train_4k_flops():
    # 6 x 7.25e8 x 8192 + 3 x attention (4 layers, 2 rows)
    assert counts.train_flops(TRAIN, 2, 4096) == pytest.approx(3.89e13, rel=2e-3)


def test_decode_bytes():
    # 8 layers of weights (2.77e9) + the head (0.52e9) + 16 x 32768 cached
    # tokens x 8 layers x (k + v) 4 x 128 x 2 bytes (8.59e9)
    assert counts.decode_step_bytes(SERVE, 16, 32768) == pytest.approx(11.9e9, rel=3e-3)


def test_kernel_work_is_causal_half():
    flops, nbytes = counts.kernel_work("flash_attention_fwd", SERVE, 1, 32768)
    full = 8 * 4 * 32 * 128 * 32768 ** 2
    assert flops / full == pytest.approx(0.5, rel=1e-4)
    assert nbytes == 8 * 2 * 32768 * 128 * (2 * 32 + 2 * 4)


def test_rwkv_counts():
    c = spec.config("rwkv6-serve-4l")
    flops, nbytes = counts.kernel_work("rwkv6_wkv_fwd", c, 1, 16384)
    assert flops == 4 * 5 * 16384 * 64 * 64 * 64
    assert nbytes == 4 * (5 * 2 * 16384 * 4096 + 4 * 4096 * 64)
