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

# per head: (visited in q order, visited in kv order, rectangle)
SCHEDULE_COUNTS = {SCHEDULE_SHAPES[len(CASES)]: (2080, 2080, 4096),
                   SCHEDULE_SHAPES[len(CASES) + 1]: (36, 36, 64)}


@pytest.mark.parametrize("shape,order", [
    pytest.param(shape, order, id=f"shape{i}" + ("" if order == "by_q"
                                                else "-by_kv"))
    for order in ("by_q", "by_kv") for i, shape in enumerate(SCHEDULE_SHAPES)])
def test_tile_schedule_matches_brute_force(shape, order):
    """Each order of the schedule against the per-element mask: visited
    tiles hold a visible (query, key) pair, each q block's tiles in
    ascending ``ik`` (``by_q``), or each kv block's in ascending ``iq``
    (``by_kv``); a block that sees nothing is one fully masked step against
    block 0 of the other side.  Both orders visit the same visible tiles."""
    Sq, Sk, bq, bk, causal, window, qoff, kv_len = shape
    kw = dict(block_q=bq, block_k=bk, causal=causal, window=window,
              q_offset=qoff, kv_len=kv_len)
    kv_len = Sk if kv_len is None else kv_len
    nq, nk = Sq // bq, Sk // bk
    kpos = np.arange(Sk)[None, :]
    seen = np.zeros((nq, nk), bool)       # tiles holding a visible pair
    for iq in range(nq):
        qpos = qoff + iq * bq + np.arange(bq)[:, None]
        mask = np.broadcast_to(kpos < kv_len, (bq, Sk))
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (kpos > qpos - window)
        seen[iq] = mask.reshape(bq, nk, bk).any(axis=(0, 2))
    if order == "by_q":
        want = [t for iq in range(nq) for t in
                [(iq, ik) for ik in np.flatnonzero(seen[iq])] or [(iq, 0)]]
        major = 0
    else:
        want = [t for ik in range(nk) for t in
                [(iq, ik) for iq in np.flatnonzero(seen[:, ik])] or [(0, ik)]]
        major = 1

    sched = tile_schedule(Sq, Sk, **kw)
    iq, ik, last, first = unpack_tile(getattr(sched, order))
    assert list(zip(iq.tolist(), ik.tolist())) == want
    blocks = (iq, ik)[major]
    starts = np.r_[True, blocks[1:] != blocks[:-1]]
    assert first.tolist() == starts.astype(int).tolist()
    assert last.tolist() == np.r_[starts[1:], True].astype(int).tolist()

    def visible(table):
        tq, tk, _, _ = unpack_tile(table)
        return {(a, b) for a, b in zip(tq.tolist(), tk.tolist()) if seen[a, b]}
    assert visible(sched.by_q) == visible(sched.by_kv) == set(
        zip(*map(np.ndarray.tolist, np.nonzero(seen))))
    counts = tile_counts(Sq, Sk, **kw)
    assert counts == (len(sched.by_q), len(sched.by_kv), nq * nk)
    assert getattr(counts, order) == len(want)
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
    # B, S, H, KH, D, Dv, causal, window, dtype
    (1, 64, 4, 1, 16, 16, True, None, jnp.float32),   # GQA, group 4
    # GQA + sliding window, S not a block multiple
    (2, 48, 4, 2, 16, 16, True, 20, jnp.float32),
    (1, 40, 2, 2, 32, 32, True, None, jnp.float32),   # MHA
    (2, 32, 4, 2, 24, 16, True, None, jnp.float32),   # MLA: Dv != D
    (1, 48, 4, 2, 16, 16, False, None, jnp.float32),  # bidirectional
    # 50 pads to 64: kv_len masks the padded keys, padded queries drop out
    (2, 50, 4, 2, 16, 16, True, None, jnp.float32),
    (1, 64, 4, 2, 32, 32, True, None, jnp.bfloat16),  # bf16 vs f32 oracle
]


@pytest.mark.parametrize("case", GRAD_CASES)
def test_pallas_grad_matches_reference_grad(case):
    """jax.grad through the Pallas kernels (forward and the dkv/dq backward)
    == grad of the naive oracle, run in f32 on the same input values.

    f32: the two differ only in summation order over at most 64 keys of
    O(1) terms, so 2e-5 absolute/relative is ~100 ulp.  bf16: the kernels
    round to bf16 (unit roundoff u = 2**-8) the operands of each backward
    matmul (p, ds; do as given), the output o that delta is summed from,
    and each gradient; every term of a gradient then carries at most ~3u
    of its size and the gradient at most ~4u of the largest gradient
    (readings over six seeds: at most 1.9u)."""
    B, S, H, KH, D, Dv, causal, window, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(7 + S), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KH, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KH, Dv), dtype)
    ct = jax.random.normal(ks[3], (B, S, H, Dv), jnp.float32)

    def loss_pallas(q, k, v):
        out = flash_attention(q, k, v, causal=causal, window=window,
                              backend="pallas", interpret=True,
                              block_q=16, block_k=16)
        return jnp.sum(out.astype(jnp.float32) * ct)

    def loss_ref(q, k, v):
        f32 = lambda x: x.astype(jnp.float32)
        return jnp.sum(attention_reference(f32(q), f32(k), f32(v),
                                           causal=causal, window=window) * ct)

    jaxpr = str(jax.make_jaxpr(jax.grad(loss_pallas, argnums=(0, 1, 2)))(
        q, k, v))
    assert "flash_attention_bwd_dkv" in jaxpr
    assert "flash_attention_bwd_dq" in jaxpr
    assert "scan" not in jaxpr     # no chunked_attention in the gradient
    got = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.dtype == dtype
        g, w = np.asarray(g, np.float32), np.asarray(w)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-5)
        else:
            assert np.max(np.abs(g - w)) <= 4 * 2**-8 * np.max(np.abs(w))
