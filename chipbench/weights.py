"""Seeded weights, made on the device in one jitted call.

A weight table maps a leaf name ("blocks/wq") to ``(shape, init, a, b)``:
``normal`` draws mean ``a`` and standard deviation ``b``; ``uniform`` draws
from ``[a, b)``.  Each leaf's key is the seed's key folded with a hash of
its name, so one leaf can be made alone and equals the same leaf made with
all the others.  The program and the plain references both take their
weights from here; neither makes its own.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative seed that fits in 64 bits."""
    seed = int(seed)
    lo, hi = np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)
    return jnp.stack([jnp.asarray(hi), jnp.asarray(lo)]).astype(jnp.uint32)


def _leaf_key(key, name: str):
    return jax.random.fold_in(jax.random.wrap_key_data(key),
                              zlib.crc32(name.encode()) & 0x7FFFFFFF)


def make_leaf(key, name, entry, dtype):
    shape, init, a, b = entry
    k = _leaf_key(key, name)
    if init == "normal":
        x = a + b * jax.random.normal(k, shape, jnp.float32)
    elif init == "uniform":
        x = jax.random.uniform(k, shape, jnp.float32, a, b)
    else:
        raise ValueError(f"unknown init {init!r} for {name}")
    return x.astype(dtype)


def make(key, table, dtypes):
    """All leaves of ``table``; ``dtypes`` maps a name to the dtype it is
    served in.  Call under ``jax.jit`` (``table`` and ``dtypes`` static)."""
    return {name: make_leaf(key, name, entry, dtypes[name])
            for name, entry in table.items()}


def make_jit(table, dtypes):
    """One jitted call from a seed key to every leaf."""
    frozen = tuple(sorted(table.items()))
    dts = tuple(sorted((n, jnp.dtype(d).name) for n, d in dtypes.items()))

    @jax.jit
    def fn(key):
        return make(key, dict(frozen), dict(dts))
    return fn


def served_dtypes(table, cfg):
    """Each leaf's served dtype, as the configuration states it."""
    base = jnp.dtype(cfg["param_dtype"])
    f32 = set(cfg.get("float32_params", ()))
    return {n: (jnp.float32 if n in f32 else base) for n in table}


def nest(flat):
    """{"blocks/wq": x} -> {"blocks": {"wq": x}}."""
    out = {}
    for name, x in flat.items():
        node = out
        *parents, leaf = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out
