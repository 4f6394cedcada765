"""RG-LRU and RWKV6 Pallas kernels vs jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.rglru_scan import rglru_reference, rglru_scan
from repro.kernels.rwkv6_wkv import rwkv6_reference, rwkv6_wkv


@pytest.mark.parametrize("B,T,W,bt,bw", [
    (1, 32, 32, 8, 16), (2, 128, 64, 32, 32), (3, 64, 96, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_kernel(B, T, W, bt, bw, dtype):
    ks = jax.random.split(jax.random.PRNGKey(T * W), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, W))).astype(dtype)
    b = (jax.random.normal(ks[1], (B, T, W)) * 0.1).astype(dtype)
    h0 = jax.random.normal(ks[2], (B, W), jnp.float32)
    ref_h, ref_l = rglru_reference(a, b, h0)
    pal_h, pal_l = rglru_scan(a, b, h0, backend="pallas", interpret=True,
                              block_t=bt, block_w=bw)
    tol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(pal_h, np.float32),
                               np.asarray(ref_h, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(pal_l), np.asarray(ref_l), atol=tol)


@pytest.mark.parametrize("B,T,H,D,bt", [
    (1, 16, 2, 8, 8), (2, 64, 3, 16, 16), (1, 48, 4, 32, 16),
])
def test_rwkv6_kernel(B, T, H, D, bt):
    ks = jax.random.split(jax.random.PRNGKey(B * T * H), 6)
    r = jax.random.normal(ks[0], (B, T, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, D)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, D)))
    u = jax.random.normal(ks[4], (H, D)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, D, D)) * 0.1
    ry, rs = rwkv6_reference(r, k, v, w, u, s0)
    py, ps = rwkv6_wkv(r, k, v, w, u, s0, backend="pallas", interpret=True,
                       block_t=bt)
    np.testing.assert_allclose(np.asarray(py), np.asarray(ry), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(rs), atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(b=st.integers(1, 2), t=st.sampled_from([16, 32, 64]),
       w=st.sampled_from([16, 32]))
def test_rglru_decay_bounds_property(b, t, w):
    """With |a|<1 and bounded b, the state stays bounded (stability)."""
    ks = jax.random.split(jax.random.PRNGKey(b * t + w), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, t, w)))
    bb = jnp.clip(jax.random.normal(ks[1], (b, t, w)), -1, 1)
    h, h_last = rglru_reference(a, bb)
    bound = t + 1.0
    assert bool(jnp.all(jnp.abs(h) <= bound))
    assert bool(jnp.all(jnp.isfinite(h_last)))


def test_rglru_state_continuation():
    """Scanning [x1;x2] == scanning x1 then x2 from its final state."""
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 64, 16)))
    b = jax.random.normal(ks[1], (2, 64, 16)) * 0.2
    h_full, last_full = rglru_reference(a, b)
    h1, l1 = rglru_reference(a[:, :32], b[:, :32])
    h2, l2 = rglru_reference(a[:, 32:], b[:, 32:], l1)
    np.testing.assert_allclose(np.asarray(h_full[:, 32:]), np.asarray(h2),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(last_full), np.asarray(l2),
                               atol=1e-6)


def _rwkv_inputs(B, T, H, D, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r = jax.random.normal(ks[0], (B, T, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, D)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, D)))
    u = jax.random.normal(ks[4], (H, D)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, D, D)) * 0.1
    return r, k, v, w, u, s0


def test_rwkv6_kernel_pads_ragged_length():
    """T = 37 does not divide block_t = 16: the wrapper pads (k = 0, w = 1
    steps leave the state unchanged) instead of leaving the kernel."""
    r, k, v, w, u, s0 = _rwkv_inputs(2, 37, 2, 16, 11)
    ry, rs = rwkv6_reference(r, k, v, w, u, s0)
    jaxpr = str(jax.make_jaxpr(lambda *a: rwkv6_wkv(
        *a, backend="pallas", interpret=True, block_t=16))(r, k, v, w, u, s0))
    assert "pallas_call" in jaxpr
    py, ps = rwkv6_wkv(r, k, v, w, u, s0, backend="pallas", interpret=True,
                       block_t=16)
    assert py.shape == ry.shape
    np.testing.assert_allclose(np.asarray(py), np.asarray(ry), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ps), np.asarray(rs), atol=2e-5)


def test_rglru_kernel_pads_ragged_length_and_width():
    """T = 50, W = 200 divide neither block: padded steps (a = 1, b = 0)
    and padded channels are sliced off; h_last is the state at step T."""
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 50, 200)))
    b = jax.random.normal(ks[1], (2, 50, 200)) * 0.1
    h0 = jax.random.normal(ks[2], (2, 200), jnp.float32)
    ref_h, ref_l = rglru_reference(a, b, h0)
    jaxpr = str(jax.make_jaxpr(lambda *x: rglru_scan(
        *x, backend="pallas", interpret=True, block_t=16,
        block_w=128))(a, b, h0))
    assert "pallas_call" in jaxpr
    h, h_last = rglru_scan(a, b, h0, backend="pallas", interpret=True,
                           block_t=16, block_w=128)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref_h), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(ref_l),
                               atol=1e-6)


def test_rwkv6_grad_matches_reference_grad():
    """jax.grad through the Pallas forward == grad of the jnp reference, for
    all six inputs.  The backward is the reference's own VJP and the
    cotangents do not depend on the kernel's outputs, so the only
    difference is f32 rounding: 1e-5 is tens of ulp at these O(1) values."""
    args = _rwkv_inputs(1, 24, 2, 8, 13)
    cts = jax.random.split(jax.random.PRNGKey(14), 2)
    cy = jax.random.normal(cts[0], (1, 24, 2, 8))
    cs = jax.random.normal(cts[1], (1, 2, 8, 8))

    def loss(fn, *a):
        y, s = fn(*a)
        return jnp.sum(y * cy) + jnp.sum(s * cs)

    pallas = lambda *a: rwkv6_wkv(*a, backend="pallas", interpret=True,
                                  block_t=16)
    got = jax.grad(lambda *a: loss(pallas, *a), argnums=range(6))(*args)
    want = jax.grad(lambda *a: loss(rwkv6_reference, *a),
                    argnums=range(6))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_rglru_grad_matches_reference_grad():
    """jax.grad through the Pallas forward == grad of the jnp reference for
    a, b and h0.  Same argument as for RWKV6: 1e-5 absolute is f32
    rounding at O(1) values."""
    ks = jax.random.split(jax.random.PRNGKey(15), 5)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 40, 24)))
    b = jax.random.normal(ks[1], (2, 40, 24)) * 0.1
    h0 = jax.random.normal(ks[2], (2, 24))
    ch = jax.random.normal(ks[3], (2, 40, 24))
    cl = jax.random.normal(ks[4], (2, 24))

    def loss(fn, *x):
        h, hl = fn(*x)
        return jnp.sum(h * ch) + jnp.sum(hl * cl)

    pallas = lambda *x: rglru_scan(*x, backend="pallas", interpret=True,
                                   block_t=16, block_w=128)
    got = jax.grad(lambda *x: loss(pallas, *x), argnums=(0, 1, 2))(a, b, h0)
    want = jax.grad(lambda *x: loss(rglru_reference, *x),
                    argnums=(0, 1, 2))(a, b, h0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)
