"""Operations and bytes that a cell's work needs, computed from shapes.

These count the work the model requires, never what a kernel happens to do:
no padded or causally masked blocks, no recomputation under remat.  A
later kernel that does less work therefore cannot push a share past 100%.
``c`` is a configuration file's dict; a multiply-add is two operations.
"""
from __future__ import annotations

BF16 = 2


def _attn_layer_weights(c):
    D, H, KH, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return D * H * hd + 2 * D * KH * hd + H * hd * D


def layer_matmul_params(c):
    """Weights of one layer that enter a matrix product with each token."""
    D, F = c["hidden_size"], c["intermediate_size"]
    if c["family"] == "llama":
        return _attn_layer_weights(c) + 3 * D * F
    if c["family"] == "rwkv6":
        return (D * 5 * 32 + 5 * 32 * D + D * 64 + 64 * D   # low-rank mixes
                + 5 * D * D                                  # r, k, v, g, out
                + D * F + F * D + D * D)                     # channel mix
    raise ValueError(c["family"])


def causal_pairs(S):
    """(query, key) pairs a causal sequence of S tokens attends over."""
    return S * (S + 1) // 2


def attention_fwd_flops(c, batch, S):
    """QK^T and PV of causal attention, one layer (llama family)."""
    H, hd = c["num_attention_heads"], c["head_dim"]
    return 4 * batch * H * hd * causal_pairs(S)


def attention_fwd_bytes(c, batch, S):
    """Least HBM traffic of one causal attention layer: read q, k, v once,
    write the output, all bf16."""
    H, KH, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return BF16 * batch * S * hd * (2 * H + 2 * KH)


def wkv_fwd_flops(c, batch, S):
    """The RWKV-6 state recurrence, one layer: per token and head, the
    outer product k^T v (hd^2), the decayed state update (2 hd^2) and the
    read-out r S (2 hd^2); the O(hd) bonus term is left out."""
    D, hd = c["hidden_size"], c["head_size"]
    return 5 * batch * S * (D // hd) * hd * hd


def wkv_fwd_bytes(c, batch, S):
    """Read r, k, v, w (bf16) and write y (bf16) and the final state (f32)."""
    D, hd = c["hidden_size"], c["head_size"]
    return 5 * BF16 * batch * S * D + 4 * batch * D * hd


def head_params(c):
    return c["hidden_size"] * c["padded_vocab_size"]


def mixer_fwd_flops(c, batch, S):
    if c["family"] == "llama":
        return attention_fwd_flops(c, batch, S)
    return wkv_fwd_flops(c, batch, S)


def prefill_flops(c, batch, S):
    """One prefill call: every layer over every token, the head over the
    last token of each sequence only (that is all the program projects)."""
    L = c["num_hidden_layers"]
    return (2 * batch * S * L * layer_matmul_params(c)
            + L * mixer_fwd_flops(c, batch, S)
            + 2 * batch * head_params(c))


def train_flops(c, batch, S):
    """One training step: forward and backward (twice the forward) of every
    layer and of the head over every token.  Recomputation is not work."""
    L = c["num_hidden_layers"]
    fwd = (2 * batch * S * (L * layer_matmul_params(c) + head_params(c))
           + L * mixer_fwd_flops(c, batch, S))
    return 3 * fwd


def decode_step_bytes(c, batch, context):
    """Least HBM bytes of one decode step: every layer weight and the head
    once (bf16), and the keys and values of ``context`` cached tokens of
    every sequence in every layer.  The embedding is a gather of one row per
    sequence and is left out."""
    L, KH, hd = c["num_hidden_layers"], c["num_key_value_heads"], c["head_dim"]
    weights = BF16 * (L * layer_matmul_params(c) + head_params(c))
    cache = BF16 * batch * context * L * 2 * KH * hd
    return weights + cache


MIXER_KERNEL = {"llama": "flash_attention_fwd", "rwkv6": "rwkv6_wkv_fwd"}

KERNELS = {
    # kernel name in the trace -> (flops, bytes) of one call of every layer
    "flash_attention_fwd": (attention_fwd_flops, attention_fwd_bytes),
    "rwkv6_wkv_fwd": (wkv_fwd_flops, wkv_fwd_bytes),
}


def kernel_work(kernel, c, batch, S):
    """(flops, bytes) of one forward call of ``kernel`` over every layer."""
    flops, nbytes = KERNELS[kernel]
    L = c["num_hidden_layers"]
    return L * flops(c, batch, S), L * nbytes(c, batch, S)
