"""Pallas flash-attention + chunked jnp path vs the naive oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import (attention_reference,
                                           chunked_attention,
                                           decode_attention, flash_attention,
                                           tile_counts)
from repro.kernels.flash_attention.kernel import tile_schedule, unpack_tile

CASES = [
    # B, Sq, Sk, H, KH, D, causal, window, q_offset, kv_len, block_q, block_k
    (2, 64, 64, 4, 2, 16, True, None, 0, None, 32, 32),
    (1, 128, 128, 8, 8, 32, True, None, 0, None, 32, 32),
    (1, 128, 128, 4, 1, 32, True, 48, 0, None, 32, 32),   # GQA + window
    (2, 37, 93, 6, 3, 16, True, None, 56, None, 32, 32),  # ragged continuation
    (1, 50, 50, 4, 4, 16, False, None, 0, None, 32, 32),  # bidirectional
    (1, 96, 96, 2, 2, 64, True, 32, 0, None, 32, 32),
    # the diagonal crosses tiles off their corners
    (1, 128, 128, 4, 2, 32, True, None, 0, None, 32, 64),
    (1, 96, 160, 2, 1, 16, True, None, 40, None, 64, 32),
    (2, 64, 96, 4, 2, 16, True, None, 32, 80, 32, 32),    # kv_len tail
    (1, 128, 128, 2, 1, 32, True, 20, 0, None, 32, 32),   # window < block
    (1, 192, 192, 4, 2, 16, True, 80, 0, None, 32, 32),   # window > 2 blocks
    (1, 40, 72, 2, 1, 16, False, None, 0, 60, 32, 32),    # bidirectional tail
    # q blocks 2-3 see no key: each is one fully masked step, output zeros
    (1, 128, 128, 2, 2, 16, True, 16, 0, 40, 32, 32),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_kernel_matches_reference(case, dtype):
    B, Sq, Sk, H, KH, D, causal, window, qoff, kv_len, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KH, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KH, D), dtype)
    ref = attention_reference(q, k, v, causal=causal, window=window,
                              q_offset=qoff, kv_len=kv_len)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=qoff, kv_len=kv_len, backend="pallas",
                          interpret=True, block_q=bq, block_k=bk)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def _kernel_args(case):
    """The kernel's static arguments for a ``CASES`` entry, padded and
    blocked as ``flash_attention`` pads and blocks them."""
    _, Sq, Sk, _, _, _, causal, window, qoff, kv_len, bq, bk = case
    bq, bk = min(bq, -(-Sq // 128) * 128), min(bk, -(-Sk // 128) * 128)
    return (-(-Sq // bq) * bq, -(-Sk // bk) * bk, bq, bk, causal, window,
            qoff, Sk if kv_len is None else kv_len)


SCHEDULE_SHAPES = [_kernel_args(c) for c in CASES] + [
    # Sq, Sk, block_q, block_k, causal, window, q_offset, kv_len
    (32768, 32768, 512, 512, True, None, 0, None),   # yi-9b prefill
    (4096, 4096, 512, 512, True, None, 0, None),     # yi-9b training
    (1024, 1024, 128, 256, True, None, 0, None),     # block_q != block_k
    (512, 1024, 128, 128, True, None, 300, None),    # q_offset off the block
    (256, 384, 128, 128, True, None, 100, 350),      # Sk off the block
    (8192, 8192, 512, 512, True, 2048, 0, None),     # recurrentgemma window
    (256, 512, 128, 128, False, None, 0, 450),       # bidirectional tail
    (256, 256, 64, 64, True, 32, 0, 100),            # q blocks seeing nothing
]

# per head: (visited, rectangle)
SCHEDULE_COUNTS = {SCHEDULE_SHAPES[len(CASES)]: (2080, 4096),
                   SCHEDULE_SHAPES[len(CASES) + 1]: (36, 64)}


@pytest.mark.parametrize("shape", SCHEDULE_SHAPES)
def test_tile_schedule_matches_brute_force(shape):
    """The schedule against the per-element mask: visited tiles hold a
    visible (query, key) pair, each q block's tiles in ascending ``ik``; a
    q block that sees no key is one fully masked step at ``ik`` 0."""
    Sq, Sk, bq, bk, causal, window, qoff, kv_len = shape
    kw = dict(block_q=bq, block_k=bk, causal=causal, window=window,
              q_offset=qoff, kv_len=kv_len)
    kv_len = Sk if kv_len is None else kv_len
    nq, nk = Sq // bq, Sk // bk
    kpos = np.arange(Sk)[None, :]
    want = []
    for iq in range(nq):
        qpos = qoff + iq * bq + np.arange(bq)[:, None]
        mask = np.broadcast_to(kpos < kv_len, (bq, Sk))
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        tiles = mask.reshape(bq, nk, bk)
        seen = tiles.any(axis=(0, 2))
        want += [(iq, ik) for ik in np.flatnonzero(seen)] or [(iq, 0)]

    iq, ik, last, first = unpack_tile(tile_schedule(Sq, Sk, **kw))
    assert list(zip(iq.tolist(), ik.tolist())) == want
    starts = np.r_[True, iq[1:] != iq[:-1]]
    assert first.tolist() == starts.astype(int).tolist()
    assert last.tolist() == np.r_[starts[1:], True].astype(int).tolist()
    counts = tile_counts(Sq, Sk, **kw)
    assert counts == (len(want), nq * nk)
    if shape in SCHEDULE_COUNTS:
        assert counts == SCHEDULE_COUNTS[shape]


def test_tile_schedule_rejects_block_counts_it_cannot_pack():
    with pytest.raises(ValueError, match="overflow"):
        tile_schedule(128, (1 << 14) * 128, block_q=128, block_k=128)


@pytest.mark.parametrize("case", CASES)
def test_chunked_matches_reference(case):
    B, Sq, Sk, H, KH, D, causal, window, qoff, kv_len, _, _ = case
    ks = jax.random.split(jax.random.PRNGKey(1 + hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, KH, D), jnp.float32)
    ref = attention_reference(q, k, v, causal=causal, window=window,
                              q_offset=qoff, kv_len=kv_len)
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            q_offset=qoff, kv_len=kv_len, q_chunk=32,
                            k_chunk=48)
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
