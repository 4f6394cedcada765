"""The system under test, as the benchmark reaches it: the program's
architecture record for a configuration file, and its weights made here.

Only this module and the drivers import the program (``repro``).  A width
in the configuration file that differs from the program's record is an
error, so the cell runs the model its file states.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench import weights

# configuration key -> the program's ArchConfig field (per family)
WIDTHS = {
    "llama": {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff", "rope_theta": "rope_theta"},
    "rwkv6": {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "head_size": "rwkv_head_dim"},
}


def arch(c):
    """The program's ArchConfig for configuration ``c``, cut as ``c`` says."""
    from repro.configs import get_config
    a = dataclasses.replace(get_config(c["program_arch"]),
                            n_layers=c["num_hidden_layers"], vocab=c["vocab_size"])
    for key, field_ in WIDTHS[c["family"]].items():
        if getattr(a, field_) != c[key]:
            raise ValueError(f"{c['name']}: {key}={c[key]} but the program's "
                             f"{c['program_arch']} has {field_}={getattr(a, field_)}")
    if a.padded_vocab != c["padded_vocab_size"]:
        raise ValueError(f"{c['name']}: padded vocabulary {a.padded_vocab} != "
                         f"{c['padded_vocab_size']}")
    return a


def weight_fn(c, ref):
    """A jitted function from a seed key to the program's parameter tree,
    in the dtypes the configuration serves, checked against the program's
    own parameter shapes."""
    from repro.models import lm
    table = ref.weight_table(c)
    dtypes = weights.served_dtypes(table, c)
    want = lm.abstract_params(arch(c), jnp.dtype(c["param_dtype"]))
    got = weights.nest({n: jax.ShapeDtypeStruct(e[0], dtypes[n])
                        for n, e in table.items()})
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{c['name']}: weight table does not match the "
                         f"program's parameters:\n{want}\nvs\n{got}")
    make = weights.make_jit(table, dtypes)
    return lambda key: weights.nest(make(key))


def reference_weights(c, ref, key):
    """The reference's weights: the same seeded values, as served."""
    table = ref.weight_table(c)
    return weights.make_jit(table, weights.served_dtypes(table, c))(key)
