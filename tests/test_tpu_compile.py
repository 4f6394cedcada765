"""Compile the Pallas kernels at real widths for a described TPU v5e.

Nothing runs: the TPU compiler is asked for each program against a
``v5e:2x2`` topology description, which refuses what the chip would refuse
(block shapes off the (8, 128) tiling, too much VMEM, a program that does not
fit).  Interpret-mode tests cannot see those faults.

The topology and everything built from it are made inside module-scoped
fixtures, never at import: only one process at a time may load the TPU
library, and every test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_wkv import rwkv6_wkv
from repro.sharding import use_mesh_rules


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


# yi-9b attention widths: 32 query heads, 4 kv heads, head_dim 128
def _yi_qkv(sharding, S):
    q = jax.ShapeDtypeStruct((1, S, 32, 128), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, S, 4, 128), jnp.bfloat16, sharding=sharding)
    return q, kv, kv


# at 131072 the kernel's tile schedule, 32896 words (one per visited causal
# tile at 512 blocks), must still fit the chip's scalar memory
@pytest.mark.parametrize("S", [32768, 131072])
def test_flash_attention_forward_compiles(one_chip, no_compile_cache, S):
    _compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, backend="pallas"),
        *_yi_qkv(one_chip, S))


def test_flash_attention_grad_compiles(one_chip, no_compile_cache):
    # a loss whose gradient needs the forward output, so the kernel stays in
    def loss(q, k, v):
        out = flash_attention(q, k, v, backend="pallas")
        return jnp.sum(jnp.square(out.astype(jnp.float32)))

    _compile_for_chip(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                      *_yi_qkv(one_chip, 32768))


def test_flash_attention_head_sharded_compiles(topo, no_compile_cache):
    """Four chips, heads over "model": the kernel runs per head shard."""
    mesh = jax.sharding.Mesh(
        [[d for d in topo.devices]], ("data", "model"))
    rules = {"batch": "data", "heads": "model"}
    spec = NamedSharding(mesh, P("data", None, "model", None))

    def fwd(q, k, v):
        with use_mesh_rules(mesh, rules):
            return flash_attention(q, k, v, backend="pallas")

    hlo = _compile_for_chip(fwd, *_yi_qkv(spec, 8192))
    assert "all-gather" not in hlo


def test_rwkv6_wkv_compiles(one_chip, no_compile_cache):
    # rwkv6-7b: 64 heads of 64
    x = jax.ShapeDtypeStruct((1, 4096, 64, 64), jnp.bfloat16, sharding=one_chip)
    u = jax.ShapeDtypeStruct((64, 64), jnp.float32, sharding=one_chip)
    s0 = jax.ShapeDtypeStruct((1, 64, 64, 64), jnp.float32, sharding=one_chip)
    _compile_for_chip(
        lambda r, k, v, w, u, s0: rwkv6_wkv(r, k, v, w, u, s0,
                                            backend="pallas"),
        x, x, x, x, u, s0)


def test_rglru_scan_compiles(one_chip, no_compile_cache):
    # recurrentgemma-2b: lru width 2560, batch 2
    a = jax.ShapeDtypeStruct((2, 4096, 2560), jnp.bfloat16, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((2, 2560), jnp.float32, sharding=one_chip)
    _compile_for_chip(
        lambda a, b, h0: rglru_scan(a, b, h0, backend="pallas"), a, a, h0)


def test_decode_step_writes_the_cache_in_place(one_chip, no_compile_cache):
    """yi-9b decode at the widths and cache of the benchmark's decode cell
    (2 of its 8 layers): with the cache donated, the chip's program holds no
    temporary as large as one layer's keys and values, so no layer of the
    stacked cache is sliced out, relaid out or stacked again."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import lm

    cfg = dataclasses.replace(get_config("yi-9b"), n_layers=2)
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    params = on_chip(lm.abstract_params(cfg, jnp.bfloat16))
    cache = on_chip(lm.abstract_cache(cfg, 8, 32768))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    mem = jax.jit(lambda p, c, t: lm.decode_step(p, cfg, c, t),
                  donate_argnums=1).lower(params, cache, tokens).compile(
                  ).memory_analysis()
    stack = sum(a.size * a.dtype.itemsize
                for a in jax.tree.leaves(cache["layers"]))
    assert mem.alias_size_in_bytes >= stack
    assert mem.temp_size_in_bytes < stack / cfg.n_layers, (
        mem.temp_size_in_bytes, stack)


def _collective_bytes(hlo, op):
    """Bytes of each ``op`` instruction's result in an HLO text."""
    import re
    sizes = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1}
    out = []
    for dtype, dims in re.findall(
            rf"= \(?(\w+)\[([\d,]*)\][^=]*? {op}(?:-start)?\(", hlo):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append(n * sizes[dtype])
    return out


def test_tp4_decode_step_keeps_the_sharded_cache_in_place(topo, no_compile_cache):
    """yi-9b decode tensor-parallel over the four chips of a v5e 2x2, as the
    benchmark's four-chip cell serves it (2 of its 48 layers, batch 8, a
    32768-position cache, donated): each chip holds no temporary as large as
    its share of one layer's cache, and no collective gathers a cache."""
    import dataclasses

    import numpy as np

    from repro.configs import get_config
    from repro.models import lm
    from repro.train.steps import make_sharded_serve

    cfg = dataclasses.replace(get_config("yi-9b"), n_layers=2)
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    serve = make_sharded_serve(cfg, mesh, 8, 32768)
    placed = lambda t, sh: jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), t, sh)
    params = placed(lm.abstract_params(cfg, jnp.bfloat16), serve.params)
    cache = placed(lm.abstract_cache(cfg, 8, 32768), serve.cache)
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=serve.tokens)
    compiled = serve.decode.lower(params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    chip_stack = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(cache["layers"])) // 4
    chip_layer = chip_stack // cfg.n_layers
    assert mem.alias_size_in_bytes >= chip_stack
    assert mem.temp_size_in_bytes < chip_layer, (mem.temp_size_in_bytes, chip_layer)
    hlo = compiled.as_text()
    assert len(_collective_bytes(hlo, "all-reduce")) > 0
    gathers = _collective_bytes(hlo, "all-gather")
    assert all(n < chip_layer for n in gathers), gathers
