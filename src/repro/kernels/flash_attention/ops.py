"""jit'd attention wrappers.

* ``flash_attention`` — public entry point; dispatches to the Pallas TPU kernel
  on TPU backends and to ``chunked_attention`` (pure jnp, memory-bounded,
  GSPMD-friendly) elsewhere (the CPU tests).
  The Pallas path differentiates through the Pallas backward kernels
  (``flash_attention_bwd_dkv``, ``flash_attention_bwd_dq``) and, under a
  mesh that shards heads, runs per head shard in a ``shard_map``.
* ``chunked_attention`` — scan-of-scans online softmax, O(seq * chunk) memory.
* ``decode_attention`` — single-token two-pass softmax written so that a KV
  cache whose *sequence* dim is sharded over the "model" mesh axis lowers to
  two tiny all-reduces (flash-decoding expressed in SPMD).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import current

from .kernel import flash_attention_bwd_kernel, flash_attention_kernel

NEG_INF = -1e30


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "kv_len", "q_chunk",
                     "k_chunk"))
def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      kv_len=None, q_chunk=512, k_chunk=512):
    """Online-softmax attention via lax.scan over (q chunks × kv chunks).

    q: (B, Sq, H, D); k, v: (B, Sk, KH, D).  Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    Dv = v.shape[3]
    group = H // KH
    kv_len = Sk if kv_len is None else kv_len
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    scale = 1.0 / (D ** 0.5)

    # GQA: expand kv to H heads so every einsum keeps the *head* dim intact —
    # reshaping a head dim that is sharded over the "model" mesh axis would
    # force GSPMD resharding collectives inside the scan.  (The Pallas kernel
    # instead expresses GQA in its k/v index_maps: no expansion in HBM.)
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)

    qp = _pad_to(q, 1, q_chunk)
    kp = _pad_to(k, 1, k_chunk)
    vp = _pad_to(v, 1, k_chunk)
    nq = qp.shape[1] // q_chunk
    nk = kp.shape[1] // k_chunk

    # (nq, B, qc, H, D) / (nk, B, kc, H, D)
    qs = qp.reshape(B, nq, q_chunk, H, D).transpose(1, 0, 2, 3, 4)
    ks = kp.reshape(B, nk, k_chunk, H, D).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(B, nk, k_chunk, H, Dv).transpose(1, 0, 2, 3, 4)

    def q_block(carry, xs):
        del carry
        qb, iq = xs  # (B, qc, H, D), scalar
        qpos = q_offset + iq * q_chunk + jnp.arange(q_chunk)

        def kv_block(state, kxs):
            m, l, acc = state
            kb, vb, ik = kxs
            s = jnp.einsum("bqhd,bkhd->bqhk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            kpos = ik * k_chunk + jnp.arange(k_chunk)
            mask = (kpos < kv_len)[None, :]
            if causal:
                mask = jnp.logical_and(mask, kpos[None, :] <= qpos[:, None])
            if window is not None:
                mask = jnp.logical_and(mask, kpos[None, :] > qpos[:, None] - window)
            mask = mask[None, :, None, :]  # (1, qc, 1, kc)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhk,bkhd->bqhd", p, vb, preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, q_chunk, H), NEG_INF, jnp.float32),
            jnp.zeros((B, q_chunk, H), jnp.float32),
            jnp.zeros((B, q_chunk, H, Dv), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_block, init, (ks, vs, jnp.arange(nk)))
        safe = jnp.where(l > 0.0, l, 1.0)
        out = jnp.where((l > 0.0)[..., None], acc / safe[..., None], 0.0)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_chunk, H, Dv)
    return out[:, :Sq]


def decode_attention(q, k_cache, v_cache, length, *, logits_constraint=None):
    """Single-step attention over a (possibly sequence-sharded) KV cache.

    q: (B, 1, H, D); caches: (B, KH, S, D), heads-major; ``length``: number
    of valid cache entries (scalar int32).  Two-pass (global max, then
    weighted sum) so GSPMD turns a sequence-sharded cache into two small
    all-reduces instead of an all-gather of the cache.
    ``logits_constraint``: optional fn applied to the (B, 1, KH, G, S)
    logits to pin their sharding.
    """
    B, _, H, D = q.shape
    _, KH, S, _ = k_cache.shape
    group = H // KH
    scale = 1.0 / (D ** 0.5)
    qf = q.reshape(B, 1, KH, group, D)
    s = jnp.einsum("bqhgd,bhsd->bqhgs", qf, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if logits_constraint is not None:
        s = logits_constraint(s)
    mask = jnp.arange(S)[None, None, None, None, :] < length
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)          # all-reduce(max) when sharded
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    num = jnp.einsum("bqhgs,bhsd->bqhgd", p, v_cache,
                     preferred_element_type=jnp.float32)  # all-reduce(sum)
    den = jnp.sum(p, axis=-1, keepdims=False)
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)


class _FlashOpts(NamedTuple):
    causal: bool
    window: Optional[int]
    q_offset: int
    kv_len: Optional[int]
    block_q: int
    block_k: int
    interpret: bool


def _round_up(n, m):
    return -(-n // m) * m


def _blocked(q, k, v, o: _FlashOpts):
    """The kernels' heads-major layout of the models' (B, S, H, D) inputs,
    sequences padded to the block, and the kernels' static arguments (the
    padded keys masked through ``kv_len``)."""
    Sq, Sk = q.shape[1], k.shape[1]
    bq = min(o.block_q, _round_up(Sq, 128))
    bk = min(o.block_k, _round_up(Sk, 128))
    qh = _pad_to(q.transpose(0, 2, 1, 3), 2, bq)
    kh = _pad_to(k.transpose(0, 2, 1, 3), 2, bk)
    vh = _pad_to(v.transpose(0, 2, 1, 3), 2, bk)
    kw = dict(causal=o.causal, window=o.window, q_offset=o.q_offset,
              kv_len=Sk if o.kv_len is None else o.kv_len, block_q=bq,
              block_k=bk, interpret=o.interpret)
    return qh, kh, vh, kw


def _models_layout(x, S):
    """A heads-major kernel result back in the models' (B, S, H, D)."""
    return x[:, :, :S].transpose(0, 2, 1, 3)


def _flash_forward(q, k, v, o: _FlashOpts):
    """Pallas forward in the models' (B, S, H, D) layout."""
    qh, kh, vh, kw = _blocked(q, k, v, o)
    return _models_layout(flash_attention_kernel(qh, kh, vh, **kw),
                          q.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_pallas(q, k, v, o: _FlashOpts):
    return _flash_forward(q, k, v, o)


def _flash_pallas_fwd(q, k, v, o):
    qh, kh, vh, kw = _blocked(q, k, v, o)
    out, lse = flash_attention_kernel(qh, kh, vh, with_lse=True, **kw)
    return _models_layout(out, q.shape[1]), (q, k, v, out, lse)


def _flash_pallas_bwd(o, res, g):
    """The Pallas backward: dk/dv and dq kernels on the forward's tiles."""
    q, k, v, out, lse = res
    qh, kh, vh, kw = _blocked(q, k, v, o)
    do = _pad_to(g.transpose(0, 2, 1, 3), 2, kw["block_q"])
    dq, dk, dv = flash_attention_bwd_kernel(qh, kh, vh, out, lse, do, **kw)
    return (_models_layout(dq, q.shape[1]), _models_layout(dk, k.shape[1]),
            _models_layout(dv, k.shape[1]))


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def _over_head_shards(fn, q, k, v):
    """Run ``fn`` per shard of the head axis when a mesh context shards
    heads, so each device runs the kernel on its own heads (GSPMD cannot
    partition a ``pallas_call``).  kv heads that do not divide the axis are
    expanded to one per query head first."""
    ctx = current()
    heads = None if ctx is None else ctx[1].get("heads")
    if heads is None:
        return fn(q, k, v)
    mesh, rules = ctx
    tp = mesh.shape[heads]
    H, KH = q.shape[2], k.shape[2]
    if H % tp:
        return fn(q, k, v)
    if KH % tp:
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
    spec = P(rules.get("batch"), None, heads, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    kv_len=None, backend=None, interpret=False,
                    block_q=512, block_k=512, q_chunk=512, k_chunk=512):
    """Dispatching attention entry point used by the models.

    ``backend="pallas"`` (the default on TPU) runs the Pallas kernels, the
    forward and its backward.  ``backend="chunked"`` (the default
    elsewhere) runs the jnp path both ways.
    """
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "chunked"
    if backend == "pallas":
        o = _FlashOpts(causal, window, q_offset, kv_len, block_q, block_k,
                       interpret)
        return _over_head_shards(
            lambda q_, k_, v_: _flash_pallas(q_, k_, v_, o), q, k, v)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, kv_len=kv_len,
                             q_chunk=q_chunk, k_chunk=k_chunk)
