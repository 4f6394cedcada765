"""Step builders: train, prefill and decode, and the sharded serving steps."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models import lm
from repro.optim import adamw_update, cosine_schedule
from repro.sharding import make_rules, use_mesh_rules
from repro.types import ArchConfig


def make_train_step(cfg: ArchConfig, *, lr=3e-4, warmup=100, total=10_000,
                    remat="full", ce_chunk=512, clip=1.0, weight_decay=0.1,
                    remat_group=8):
    schedule = cosine_schedule(lr, warmup, total)

    def loss_of(params, batch):
        return lm.loss_fn(params, cfg, batch, remat=remat,
                          ce_chunk=ce_chunk, remat_group=remat_group)

    def train_step(state, batch):
        (loss, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
            state["params"], batch)
        new_state, opt_aux = adamw_update(state, grads, lr=schedule,
                                          clip=clip,
                                          weight_decay=weight_decay)
        metrics = {"loss": loss, "tokens": aux["tokens"], **opt_aux}
        return new_state, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig):
    def prefill_step(params, cache, batch):
        return lm.prefill(params, cfg, cache, tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"))
    return prefill_step


def make_decode_step(cfg: ArchConfig):
    def decode_step(params, cache, batch):
        return lm.decode_step(params, cfg, cache, batch["tokens"])
    return decode_step


@dataclasses.dataclass(frozen=True)
class ShardedServe:
    """Serving steps of one architecture over a ("data", "model") mesh, as
    ``make_sharded_serve`` builds them.  ``params``, ``cache`` and
    ``tokens`` are the ``NamedSharding``s the steps' arguments carry (a
    pytree each, like the parameters and the batch cache, and one for a
    (batch, length) token array)."""
    mesh: Any
    rules: dict
    params: Any
    cache: Any
    tokens: NamedSharding
    prefill: Callable   # (params, tokens) -> (logits, cache of those rows)
    insert: Callable    # (cache, part, row) -> cache, donated
    decode: Callable    # (params, cache, tokens, *extra) -> (out, cache)


def make_sharded_serve(cfg: ArchConfig, mesh, batch: int, max_len: int, *,
                       sample=None):
    """Prefill, batch-row insert and decode of ``cfg`` served over ``mesh``
    (``launch.mesh.make_chip_mesh``: tensor parallelism over one host's
    chips), each jitted with its shardings and traced under the mesh's
    ``make_rules`` rules.

    ``prefill`` fills a cache of ``max_len`` positions for the sequences it
    is given (any number of rows), in the parameters' dtype; ``insert``
    writes such a cache into the batch cache at a row, in place; ``decode``
    is one step of the whole batch, in place.  ``sample(logits, *extra)``
    makes what ``decode`` returns beside the cache from its (batch, vocab)
    logits (default: the logits); it comes back whole on every device, as
    do prefill's logits.  The steps take their arguments' shardings:
    parameters placed by ``params``, caches by ``cache`` (prefill's output
    carries it too), tokens by ``tokens``."""
    rules = make_rules(cfg, mesh, global_batch=batch)
    shard = lambda specs: jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    whole = NamedSharding(mesh, P())
    cache_sh = shard(lm.cache_specs(cfg, batch, max_len, rules))
    prefill_step, decode_step = make_prefill_step(cfg), make_decode_step(cfg)
    sample = sample or (lambda logits: logits)

    def under_rules(fn):
        @functools.wraps(fn)
        def traced(*args):
            with use_mesh_rules(mesh, rules):
                return fn(*args)
        return traced

    def prefill(params, tokens):
        # a zero cache built inside the program only gives shapes
        cache = lm.init_cache(cfg, tokens.shape[0], max_len,
                              params["embed"].dtype)
        return prefill_step(params, cache, {"tokens": tokens})

    def decode(params, cache, tokens, *extra):
        logits, cache = decode_step(params, cache, {"tokens": tokens})
        return sample(logits, *extra), cache

    return ShardedServe(
        mesh=mesh, rules=rules, params=shard(lm.param_specs(cfg, rules)),
        cache=cache_sh, tokens=NamedSharding(mesh, P(rules["batch"], None)),
        prefill=jax.jit(under_rules(prefill), out_shardings=(whole, cache_sh)),
        insert=jax.jit(under_rules(functools.partial(lm.insert_rows, cfg)),
                       out_shardings=cache_sh, donate_argnums=0),
        decode=jax.jit(under_rules(decode), out_shardings=(whole, cache_sh),
                       donate_argnums=1))
