"""Plain float32 reference of the Yi decoder (a Llama-style block).

Written from the published description (Young et al., "Yi: Open Foundation
Models by 01.AI", arXiv:2403.04652, and the Llama block it follows): token
embedding; per layer a pre-norm grouped-query attention with rotary
positions (rotate-half convention, base ``rope_theta``) and a pre-norm
SwiGLU MLP, both residual; a final RMSNorm and an untied output head.

Departures, each a parametrisation and not a change of the function: the
norm gain is stored as an offset from 1; the vocabulary is padded to
``padded_vocab_size`` rows, which take part in the softmax like the others.
Nothing here is shared with the code under test.  Sequences are processed
whole, attention in blocks of query rows and the MLP in blocks of tokens,
only so that the float32 intermediates fit on one chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import mm, rms_norm, stacked_blocks

NEG_INF = -1e30
# float32 elements of one block of attention scores (256 MiB)
SCORE_BLOCK = 1 << 26
MLP_BLOCK_TOKENS = 2048


def weight_table(c):
    D, H, KH = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, F, L = c["head_dim"], c["intermediate_size"], c["num_hidden_layers"]
    V = c["padded_vocab_size"]
    n = lambda shape, std: (tuple(shape), "normal", 0.0, std)
    return {
        "embed": n((V, D), 1.0),
        "final_norm": n((D,), 0.1),
        "lm_head": n((D, V), D ** -0.5),
        "blocks/ln1": n((L, D), 0.1),
        "blocks/wq": n((L, D, H, hd), D ** -0.5),
        "blocks/wk": n((L, D, KH, hd), D ** -0.5),
        "blocks/wv": n((L, D, KH, hd), D ** -0.5),
        "blocks/wo": n((L, H, hd, D), (H * hd) ** -0.5),
        "blocks/ln2": n((L, D), 0.1),
        "blocks/mlp_wg": n((L, D, F), D ** -0.5),
        "blocks/mlp_wu": n((L, D, F), D ** -0.5),
        "blocks/mlp_wo": n((L, F, D), F ** -0.5),
    }


def rope(x, theta):
    """x: (B, S, H, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant):
    """Causal grouped-query attention.  q (B,S,H,hd); k, v (B,S,KH,hd)."""
    B, S, H, hd = q.shape
    KH = k.shape[2]
    G = H // KH
    chunk = max(8, min(S, SCORE_BLOCK // (B * H * S)))
    while S % chunk:
        chunk //= 2
    n = S // chunk
    qs = q.reshape(B, n, chunk, KH, G, hd).transpose(1, 0, 2, 3, 4, 5)

    @jax.checkpoint
    def block(args):
        qb, i = args
        s = mm("bqkgd,bskd->bkgqs", qb, k, quant) * hd ** -0.5
        qpos = i * chunk + jnp.arange(chunk)
        s = jnp.where(jnp.arange(S)[None, :] <= qpos[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bkgqs,bskd->bqkgd", p, v, quant)

    out = jax.lax.map(block, (qs, jnp.arange(n)))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)


def mlp(y, lw, quant):
    """SwiGLU over blocks of tokens.  y (B, S, D)."""
    B, S, D = y.shape
    t = min(S, MLP_BLOCK_TOKENS)
    ys = y.reshape(B, S // t, t, D).transpose(1, 0, 2, 3)

    @jax.checkpoint
    def block(yb):
        g = mm("bsd,df->bsf", yb, lw["mlp_wg"], quant)
        u = mm("bsd,df->bsf", yb, lw["mlp_wu"], quant)
        return mm("bsf,fd->bsd", jax.nn.silu(g) * u, lw["mlp_wo"], quant)

    out = jax.lax.map(block, ys)
    return out.transpose(1, 0, 2, 3).reshape(B, S, D)


def layer(c, x, lw, quant):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    y = rms_norm(x, lw["ln1"])
    q = rope(mm("bsd,dhk->bshk", y, lw["wq"], quant), c["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", y, lw["wk"], quant), c["rope_theta"])
    v = mm("bsd,dhk->bshk", y, lw["wv"], quant)
    x = x + mm("bshk,hkd->bsd", attention(q, k, v, quant), lw["wo"], quant)
    return x + mlp(rms_norm(x, lw["ln2"]), lw, quant)


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


def loss(w, c, tokens, labels, quant=None):
    """Mean next-token cross-entropy over every position of the batch."""
    h = hidden(w, c, tokens, quant)
    B, S, D = h.shape
    t = min(S, 512)
    hs = h.reshape(B, S // t, t, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, S // t, t).transpose(1, 0, 2)

    @jax.checkpoint
    def block(args):
        hb, lb = args
        lg = logits(w, hb, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, lb[..., None], -1)[..., 0])

    return jnp.sum(jax.lax.map(block, (hs, ls))) / (B * S)
