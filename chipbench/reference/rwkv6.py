"""Plain float32 reference of the RWKV-6 (Finch) language model.

Written from the published description (Peng et al., "Eagle and Finch: RWKV
with Matrix-Valued States and Dynamic Recurrence", arXiv:2404.05892): per
layer a time-mix and a channel-mix branch, both residual and pre-norm.

Time mix: token shift ``dx = x[t-1] - x[t]``; the data-dependent lerp
("ddlerp") ``x + dx * (mu_j + tanh((x + dx * mu_x) W1) W2_j)`` for the five
inputs w, k, v, r, g (low rank 32); the decay
``w_t = exp(-exp(d + tanh(x_w A) B))`` (low rank 64); per head of 64 the
matrix-valued state ``y_t = r_t (S_t + diag(u) k_t^T v_t)``,
``S_{t+1} = diag(w_t) S_t + k_t^T v_t``; a per-head norm of y, gated by
``silu(x_g W_g)``, and the output projection.  Channel mix:
``sigmoid(x_r W_r) * (relu(x_k W_k)^2 W_v)`` with its own token shift.

Departures, as the configuration is run: RMSNorm (gain stored as an offset
from 1) in place of LayerNorm before each branch and at the output, no norm
after the embedding, and the per-head norm's gain also as an offset from 1
(eps 1e-5).  The recurrence runs step by step in float32, with nothing
shared with the code under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import mm, rms_norm, stacked_blocks

MIX_RANK, DECAY_RANK = 32, 64
TOKEN_BLOCK = 4096


def weight_table(c):
    D, F, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    hd = c["head_size"]
    H, V = D // hd, c["padded_vocab_size"]
    n = lambda shape, std: (tuple(shape), "normal", 0.0, std)
    u = lambda shape, lo, hi: (tuple(shape), "uniform", lo, hi)
    return {
        "embed": n((V, D), 1.0),
        "final_norm": n((D,), 0.1),
        "lm_head": n((D, V), D ** -0.5),
        "blocks/ln1": n((L, D), 0.1),
        "blocks/tm_mu_x": u((L, D), 0.0, 1.0),
        "blocks/tm_mus": u((L, 5, D), 0.0, 1.0),
        "blocks/tm_w1": n((L, D, 5 * MIX_RANK), D ** -0.5),
        "blocks/tm_w2": n((L, 5, MIX_RANK, D), 0.1 * MIX_RANK ** -0.5),
        # the published initialisation spreads the decay base over [-6, -1]
        "blocks/decay_base": u((L, D), -6.0, -1.0),
        "blocks/decay_w1": n((L, D, DECAY_RANK), D ** -0.5),
        "blocks/decay_w2": n((L, DECAY_RANK, D), 0.1 * DECAY_RANK ** -0.5),
        "blocks/u": n((L, H, hd), 0.5),
        "blocks/wr": n((L, D, H, hd), D ** -0.5),
        "blocks/wk": n((L, D, H, hd), D ** -0.5),
        "blocks/wv": n((L, D, H, hd), D ** -0.5),
        "blocks/wg": n((L, D, H, hd), D ** -0.5),
        "blocks/wo": n((L, H, hd, D), D ** -0.5),
        "blocks/ln_x": n((L, H, hd), 0.1),
        "blocks/ln2": n((L, D), 0.1),
        "blocks/cm_mu_k": u((L, D), 0.0, 1.0),
        "blocks/cm_mu_r": u((L, D), 0.0, 1.0),
        "blocks/cm_k": n((L, D, F), D ** -0.5),
        "blocks/cm_v": n((L, F, D), F ** -0.5),
        "blocks/cm_r": n((L, D, D), D ** -0.5),
    }


def shift(x):
    """x[t-1], zero before the first token.  x (B, S, D)."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def wkv(r, k, v, w, u):
    """The state recurrence, one step at a time.  r/k/v/w (B,S,H,hd)."""
    B, S, H, hd = r.shape

    def step(state, xs):
        rt, kt, vt, wt = xs                                   # (B, H, hd)
        kv = kt[..., :, None] * vt[..., None, :]              # (B,H,hd,hd)
        y = jnp.einsum("bhi,bhij->bhj", rt, state + u[None, :, :, None] * kv,
                       precision=jax.lax.Precision.HIGHEST)
        return wt[..., :, None] * state + kv, y

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, jnp.zeros((B, H, hd, hd), jnp.float32), xs)
    return ys.transpose(1, 0, 2, 3)


def head_norm(y, g, eps=1e-5):
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.var(y, -1, keepdims=True)
    return (y - mu) * jax.lax.rsqrt(var + eps) * (1.0 + g)


def tokenwise(fn, *xs):
    """fn over blocks of tokens, so float32 intermediates stay small."""
    B, S = xs[0].shape[:2]
    t = min(S, TOKEN_BLOCK)
    split = lambda a: a.reshape((B, S // t, t) + a.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(lambda a: fn(*a)), tuple(map(split, xs)))
    return jax.tree.map(lambda o: o.swapaxes(0, 1).reshape((B, S) + o.shape[3:]), out)


def layer(c, x, lw, quant):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    B, S, D = x.shape
    hd = c["head_size"]
    H = D // hd

    y = rms_norm(x, lw["ln1"])
    dx = shift(y) - y

    def mix(y, dx):
        z = jnp.tanh(mm("bsd,dk->bsk", y + dx * lw["tm_mu_x"], lw["tm_w1"], quant))
        adj = mm("bsfk,fkd->bsfd", z.reshape(z.shape[:2] + (5, MIX_RANK)),
                 lw["tm_w2"], quant)
        m = y[:, :, None] + dx[:, :, None] * (lw["tm_mus"] + adj)
        xw, xk, xv, xr, xg = (m[:, :, j] for j in range(5))
        proj = lambda a, n: mm("bsd,dhk->bshk", a, lw[n], quant)
        decay = lw["decay_base"] + mm(
            "bsk,kd->bsd", jnp.tanh(mm("bsd,dk->bsk", xw, lw["decay_w1"], quant)),
            lw["decay_w2"], quant)
        w = jnp.exp(-jnp.exp(decay)).reshape(decay.shape[:2] + (H, hd))
        return (proj(xr, "wr"), proj(xk, "wk"), proj(xv, "wv"),
                jax.nn.silu(proj(xg, "wg")), w)

    r, k, v, g, w = tokenwise(mix, y, dx)
    att = wkv(r, k, v, w, lw["u"])
    x = x + tokenwise(
        lambda a, gg: mm("bshk,hkd->bsd", head_norm(a, lw["ln_x"]) * gg,
                         lw["wo"], quant), att, g)

    y2 = rms_norm(x, lw["ln2"])
    dx2 = shift(y2) - y2

    def channel(y2, dx2):
        kk = jax.nn.relu(mm("bsd,df->bsf", y2 + dx2 * lw["cm_mu_k"], lw["cm_k"], quant))
        rr = jax.nn.sigmoid(mm("bsd,de->bse", y2 + dx2 * lw["cm_mu_r"],
                               lw["cm_r"], quant))
        return rr * mm("bsf,fd->bsd", kk * kk, lw["cm_v"], quant)

    return x + tokenwise(channel, y2, dx2)


def hidden(w, c, tokens, quant=None):
    """Final-normed hidden states (B, S, D) float32 of ``tokens`` (B, S)."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def body(x, lw):
        return layer(c, x, lw, quant), None

    x, _ = jax.lax.scan(body, x, stacked_blocks(w))
    return rms_norm(x, w["final_norm"])


def logits(w, h, quant=None):
    """Output-head logits of hidden states h (..., D)."""
    return mm("...d,dv->...v", h, w["lm_head"], quant)
