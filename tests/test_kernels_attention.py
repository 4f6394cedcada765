"""Pallas flash-attention + chunked jnp path vs the naive oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import (attention_reference,
                                           chunked_attention,
                                           decode_attention, flash_attention)

CASES = [
    # B, Sq, Sk, H, KH, D, causal, window, q_offset
    (2, 64, 64, 4, 2, 16, True, None, 0),
    (1, 128, 128, 8, 8, 32, True, None, 0),
    (1, 128, 128, 4, 1, 32, True, 48, 0),      # GQA + sliding window
    (2, 37, 93, 6, 3, 16, True, None, 56),     # ragged continuation
    (1, 50, 50, 4, 4, 16, False, None, 0),     # bidirectional (encoder)
    (1, 96, 96, 2, 2, 64, True, 32, 0),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_kernel_matches_reference(case, dtype):
    B, Sq, Sk, H, KH, D, causal, window, qoff = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KH, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KH, D), dtype)
    ref = attention_reference(q, k, v, causal=causal, window=window,
                              q_offset=qoff)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=qoff, backend="pallas", interpret=True,
                          block_q=32, block_k=32)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("case", CASES)
def test_chunked_matches_reference(case):
    B, Sq, Sk, H, KH, D, causal, window, qoff = case
    ks = jax.random.split(jax.random.PRNGKey(1 + hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, KH, D), jnp.float32)
    ref = attention_reference(q, k, v, causal=causal, window=window,
                              q_offset=qoff)
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            q_offset=qoff, q_chunk=32, k_chunk=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_mla_shapes_dk_ne_dv():
    """k-dim 96 vs v-dim 64 (MLA) supported by every path."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 32, 4, 96), jnp.float32)
    k = jax.random.normal(ks[1], (2, 32, 4, 96), jnp.float32)
    v = jax.random.normal(ks[2], (2, 32, 4, 64), jnp.float32)
    ref = attention_reference(q, k, v, causal=True)
    chk = chunked_attention(q, k, v, causal=True, q_chunk=16, k_chunk=16)
    pal = flash_attention(q, k, v, causal=True, backend="pallas",
                          interpret=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(ref), atol=2e-6)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=2e-6)


def test_decode_attention_matches_full():
    """Two-pass decode == full attention at the last position."""
    B, S, H, KH, D = 2, 40, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q_all = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32)
    full = attention_reference(q_all, k, v, causal=True)
    # heads-major cache, padded beyond the valid length
    pad = ((0, 0), (0, 0), (0, 24), (0, 0))
    kc = jnp.pad(k.transpose(0, 2, 1, 3), pad)
    vc = jnp.pad(v.transpose(0, 2, 1, 3), pad)
    out = decode_attention(q_all[:, -1:], kc, vc, length=S)
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-6)


@settings(max_examples=20, deadline=None)
@given(
    b=st.integers(1, 2), s=st.integers(4, 48),
    h=st.sampled_from([1, 2, 4]), g=st.sampled_from([1, 2]),
    d=st.sampled_from([8, 16]),
    causal=st.booleans(),
)
def test_chunked_property(b, s, h, g, d, causal):
    H, KH = h * g, h
    ks = jax.random.split(jax.random.PRNGKey(b * 1000 + s), 3)
    q = jax.random.normal(ks[0], (b, s, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, KH, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, KH, d), jnp.float32)
    ref = attention_reference(q, k, v, causal=causal)
    out = chunked_attention(q, k, v, causal=causal, q_chunk=16, k_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


GRAD_CASES = [
    # B, S, H, KH, D, window
    (1, 64, 4, 1, 16, None),    # GQA, group 4
    (2, 48, 4, 2, 16, 20),      # GQA + sliding window, S not a block multiple
    (1, 40, 2, 2, 32, None),    # MHA
]


@pytest.mark.parametrize("case", GRAD_CASES)
def test_pallas_grad_matches_reference_grad(case):
    """jax.grad through the Pallas forward (custom VJP) == grad of the naive
    oracle.  f32 throughout; the two differ only in summation order over at
    most 64 keys of O(1) terms, so 2e-5 absolute/relative is ~100 ulp."""
    B, S, H, KH, D, window = case
    ks = jax.random.split(jax.random.PRNGKey(7 + S), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32)
    ct = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)

    def loss_pallas(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              backend="pallas", interpret=True,
                              block_q=16, block_k=16)
        return jnp.sum(out * ct)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True,
                                           window=window) * ct)

    assert "pallas_call" in str(jax.make_jaxpr(
        jax.grad(loss_pallas, argnums=(0, 1, 2)))(q, k, v))
    got = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=2e-5)
