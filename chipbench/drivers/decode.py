"""Decode traffic: a batch of long contexts, decoded greedily step by step.

Traffic keys: ``batch`` sequences whose ``prompt_len``-token prompts fill
the cache in set-up through the program's prefill, ``fill_group`` sequences
at a time; a cache of ``max_len`` positions, so that the window's steps
need no new shapes.  One sequence, drawn from the seed, is compared with
the reference over every step it was served.

Each step runs the program's decode step (``repro.train.steps``) against the
cache, takes the greedy token on the device and reads it back to the host,
as a server that streams tokens does.  The step time, from dispatch to the
tokens on the host, is the inter-token latency every sequence of the batch
sees; its 95th percentile is taken over every step of the window.  The
window never prefills.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, program, weights
from chipbench.drivers.prefill import make_prompts


class Driver:
    def __init__(self, c, t, seed, ref):
        self.c, self.t, self.seed, self.ref = c, t, seed, ref
        self.spans = harness.Spans()

    def setup(self):
        from repro.models import lm
        from repro.train.steps import make_decode_step, make_prefill_step

        c, t = self.c, self.t
        a = program.arch(c)
        B, P, M, G = t["batch"], t["prompt_len"], t["max_len"], t["fill_group"]
        self.key = weights.seed_key(self.seed)
        self.params = jax.block_until_ready(program.weight_fn(c, self.ref)(self.key))
        self.prompts = make_prompts(self.key, 1, B, P, c["vocab_size"])[0]
        self.watch = int(np.random.default_rng(self.seed).integers(B))
        prefill_step, decode_step = make_prefill_step(a), make_decode_step(a)

        def prefill(params, tokens):
            return prefill_step(params, lm.init_cache(a, G, M), {"tokens": tokens})

        def insert(cache, part, row):
            layers = jax.tree.map(
                lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                    big, small, row, axis=1), cache["layers"], part["layers"])
            return {"pos": part["pos"], "layers": layers}

        def step(params, cache, tokens, watch):
            logits, cache = decode_step(params, cache, {"tokens": tokens})
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return nxt, logits[watch], cache

        fill = jax.jit(prefill)
        put = jax.jit(insert, donate_argnums=(0,))
        cache = jax.jit(lambda: lm.init_cache(a, B, M))()
        first = []
        for g in range(0, B, G):
            logits, part = fill(self.params, self.prompts[g:g + G])
            cache = put(cache, part, g)
            first.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
            del part
        tok = jnp.concatenate(first)[:, None]
        self.fn = jax.jit(step, donate_argnums=(1,)).lower(
            self.params, cache, tok, self.watch).compile()
        self.footprint = harness.program_bytes(self.fn)
        # one step outside the window; its token is served like the rest
        self.tokens, self.watched = [np.asarray(tok)], []
        tok, lw, cache = self.fn(self.params, cache, tok, self.watch)
        self.tokens.append(np.asarray(tok))
        self.watched.append(np.asarray(lw))
        self.cache, self.tok = cache, tok

    def window(self, seconds):
        span, t = self.spans, self.t
        B, M, P = t["batch"], t["max_len"], t["prompt_len"]
        cache, tok = self.cache, self.tok
        times, marks = [], []
        t0 = time.perf_counter()
        while True:
            if P + len(self.tokens) >= M:
                print(f"decode: cache room of {M - P} positions used up after "
                      f"{time.perf_counter() - t0:.3f} s; the window ends early",
                      file=sys.stderr)
                break
            ts = time.perf_counter()
            marks.append(ts - t0)
            with span("dispatch"):
                tok, lw, cache = self.fn(self.params, cache, tok, self.watch)
            with span("readback"):
                # the tokens come back to the host, as a streaming server's
                # do; the watched sequence's logits come with them, so that
                # nothing of a step stays on the device
                self.tokens.append(np.asarray(tok))
                self.watched.append(np.asarray(lw))
            times.append(time.perf_counter() - ts)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.cache, self.tok = cache, tok
        n = len(times)
        slow = sorted(range(n), key=times.__getitem__)[-5:][::-1]
        print("slowest steps (ms at s into the window): " + " ".join(
            f"{times[i] * 1e3:.3f}@{marks[i]:.3f}" for i in slow), file=sys.stderr)
        self.steps = n
        return {"attempted": n * B, "failed": 0, "elapsed_s": elapsed, "units": n,
                "metrics": {"decode_tokens_per_s": n * B / elapsed,
                            "decode_step_ms_p95": float(np.quantile(times, 0.95)) * 1e3}}

    def release(self):
        self.served = np.concatenate(self.tokens, axis=1)
        self.watched = np.stack(self.watched)
        del self.params, self.fn, self.cache, self.tok

    def reference_logits(self, quant=None):
        """Reference logits at the positions that served the watched
        sequence's tokens after its first: prompt + served tokens, padded to
        the cache length so the program has one shape for every run."""
        c, t, ref = self.c, self.t, self.ref
        P, M = t["prompt_len"], t["max_len"]
        b = self.watch
        served = self.served[b]
        seq = np.zeros((1, M), np.int32)
        seq[0, :P] = np.asarray(self.prompts[b])
        seq[0, P:P + len(served)] = served
        w = program.reference_weights(c, ref, self.key)
        fn = jax.jit(lambda w, s: ref.logits(
            w, jax.lax.dynamic_slice_in_dim(ref.hidden(w, c, s, quant)[0],
                                            P, M - P), quant))
        n = len(self.watched)
        return np.asarray(fn(w, jnp.asarray(seq)))[:n]

    def check(self):
        self.ref_logits = self.reference_logits()
        return compare(self.watched, self.served[self.watch][1:], self.ref_logits)

    def control(self):
        """The fp8 reference in the program's place, over the same prompt
        and served tokens: the token it would put first at each position."""
        low = self.reference_logits("fp8")
        return compare(low, np.argmax(low, axis=-1), self.ref_logits)

    def counts(self):
        from chipbench import counts
        B, P = self.t["batch"], self.t["prompt_len"]
        steps = getattr(self, "steps", 0)
        # context of window step i is P + 1 + i + 1 (one step ran in set-up)
        total = sum(counts.decode_step_bytes(self.c, B, P + 2 + i) for i in range(steps))
        return {"unit_bytes": total / steps if steps else 0.0}


def compare(watched, served, ref):
    """Numbers compared over the watched sequence's decode steps: the widest
    gap by which a served token's logit lies below the reference's best,
    and the widest relative L2 gap of one step's logits."""
    gaps = harness.token_gaps(ref, served[:len(ref)])
    rel = max(harness.rel_l2(watched[i], ref[i]) for i in range(len(ref)))
    return {"token_gap": float(np.max(gaps)), "logit_rel_l2": rel}
