"""Decode traffic over a tensor-parallel mesh: the ``decode`` driver's
batch of long contexts and greedy steps, with the model and its cache split
over the ``tensor_parallel`` chips of one host.

Traffic keys are ``decode``'s.  The program's sharded serving entry
(``repro.train.steps.make_sharded_serve`` on ``make_chip_mesh``) gives the
steps and the shardings: each chip holds its share of every layer's heads,
MLP width and vocabulary, and of the cache's kv heads.  The weights are the
seeded values of the one-chip cells, made in place on the chips; the plain
reference is given the same values, placed on the same chips, and is left
to the compiler to split.  The window, the check and the counts are the
``decode`` driver's.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, program, weights
from chipbench.drivers import decode
from chipbench.drivers.prefill import make_prompts


def flat_names(tree, prefix=""):
    """{"blocks": {"wq": x}} -> {"blocks/wq": x}: the weight table's names."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        out.update(flat_names(v, name + "/") if isinstance(v, dict) else {name: v})
    return out


class Driver(decode.Driver):
    def setup(self):
        from repro.launch.mesh import make_chip_mesh
        from repro.models import lm
        from repro.train.steps import make_sharded_serve

        c, t = self.c, self.t
        a = program.arch(c)
        B, P, M, G = t["batch"], t["prompt_len"], t["max_len"], t["fill_group"]
        self.key = weights.seed_key(self.seed)
        self.watch = int(np.random.default_rng(self.seed).integers(B))

        def sample(logits, watch):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return nxt, logits[watch]

        serve = self.serve = make_sharded_serve(
            a, make_chip_mesh(c["tensor_parallel"]), B, M, sample=sample)
        program.weight_fn(c, self.ref)  # checks the table against the program
        self.params = jax.block_until_ready(self.placed_weights(serve.params))
        self.prompts = make_prompts(self.key, 1, B, P, c["vocab_size"])[0]
        prompts = jax.device_put(self.prompts, serve.tokens)
        cache = jax.jit(lambda: lm.init_cache(a, B, M), out_shardings=serve.cache)()
        first = []
        for g in range(0, B, G):
            logits, part = serve.prefill(self.params, prompts[g:g + G])
            cache = serve.insert(cache, part, g)
            first.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
            del part
        tok = jax.device_put(jnp.concatenate(first)[:, None], serve.tokens)
        step = serve.decode.lower(self.params, cache, tok, self.watch).compile()
        self.footprint = harness.program_bytes(step)
        gathers = step.as_text().count(" all-gather")
        print(f"decode_mesh: the step per chip: {step.memory_analysis()}; "
              f"all-gather instructions: {gathers}", file=sys.stderr)

        def fn(*args):
            (tok, lw), cache = step(*args)
            return tok, lw, cache

        self.fn = fn
        # one step outside the window; its token is served like the rest
        self.tokens, self.watched = [np.asarray(tok)], []
        tok, lw, cache = self.fn(self.params, cache, tok, self.watch)
        self.tokens.append(np.asarray(tok))
        self.watched.append(np.asarray(lw))
        self.cache, self.tok = cache, tok

    def placed_weights(self, shardings):
        """The seeded weights (``weights.make``: the values the one-chip
        cells make), made in place on the chips: ``shardings`` is a tree
        like the program's parameters."""
        table = self.ref.weight_table(self.c)
        dtypes = weights.served_dtypes(table, self.c)
        flat = flat_names(shardings)
        make = jax.jit(lambda key: weights.make(key, table, dtypes),
                       out_shardings={n: flat[n] for n in table})
        return weights.nest(make(self.key))

    def reference_logits(self, quant=None):
        """``decode.Driver``'s, with the reference's weights placed as the
        program's are, so that they fit."""
        c, t, ref = self.c, self.t, self.ref
        P, M = t["prompt_len"], t["max_len"]
        b = self.watch
        served = self.served[b]
        seq = np.zeros((1, M), np.int32)
        seq[0, :P] = np.asarray(self.prompts[b])
        seq[0, P:P + len(served)] = served
        w = flat_names(self.placed_weights(self.serve.params))
        fn = jax.jit(lambda w, s: ref.logits(
            w, jax.lax.dynamic_slice_in_dim(ref.hidden(w, c, s, quant)[0],
                                            P, M - P), quant))
        n = len(self.watched)
        return np.asarray(fn(w, jnp.asarray(seq)))[:n]
