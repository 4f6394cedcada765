"""Prefill traffic: a closed loop of prompts, one call after another.

Traffic keys: ``batch`` sequences of ``prompt_len`` tokens per call, drawn
from ``prompts`` distinct prompts made from the seed and sent in turn;
``check_sample`` calls of the window are compared with the reference.

Each call runs the program's prefill step (``repro.train.steps``) as one
compiled program: the whole stack, its attention or recurrence kernel, the
cache it fills, and the output head over the last token.  The served answer
is that last token's logits, from which a greedy server takes its token.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, program, weights


class Driver:
    def __init__(self, c, t, seed, ref):
        self.c, self.t, self.seed, self.ref = c, t, seed, ref
        self.spans = harness.Spans()

    def setup(self):
        from repro.models import lm
        from repro.train.steps import make_prefill_step

        c, t = self.c, self.t
        a = program.arch(c)
        B, S = t["batch"], t["prompt_len"]
        self.key = weights.seed_key(self.seed)
        self.params = jax.block_until_ready(program.weight_fn(c, self.ref)(self.key))
        self.prompts = make_prompts(self.key, t["prompts"], B, S, c["vocab_size"])
        step = make_prefill_step(a)

        def prefill(params, tokens):
            # the zero cache only gives shapes; made inside the program it
            # takes no device memory of its own
            return step(params, lm.init_cache(a, B, S), {"tokens": tokens})

        self.fn = jax.jit(prefill).lower(self.params, self.prompts[0]).compile()
        self.footprint = harness.program_bytes(self.fn)
        jax.block_until_ready(self.fn(self.params, self.prompts[0]))

    def window(self, seconds):
        span, B, S = self.spans, self.t["batch"], self.t["prompt_len"]
        self.served, marks = [], []
        t0 = time.perf_counter()
        while True:
            marks.append(time.perf_counter() - t0)
            with span("dispatch"):
                logits, cache = self.fn(self.params, self.prompts[len(self.served)
                                                                 % len(self.prompts)])
            del cache
            with span("wait"):
                # the served logits come back to the host, as a server's
                # answer does; nothing of a call stays on the device, so
                # every call finds device memory laid out alike
                self.served.append(np.asarray(logits))
            del logits
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        n = len(self.served)
        print("call starts (s): " + " ".join(f"{m:.3f}" for m in marks), file=sys.stderr)
        return {"attempted": n, "failed": 0, "elapsed_s": elapsed, "units": n,
                "metrics": {"prefill_tokens_per_s": n * B * S / elapsed}}

    def release(self):
        del self.params, self.fn

    def sample(self):
        """Calls to compare, drawn from the seed (all are equally long)."""
        n, k = len(self.served), min(self.t["check_sample"], len(self.served))
        return sorted(np.random.default_rng(self.seed).choice(
            n, size=k, replace=False).tolist())

    def reference_logits(self, quant=None):
        """{call index: reference last-token logits (B, V)} for the sample."""
        c, ref = self.c, self.ref
        w = program.reference_weights(c, ref, self.key)
        fn = jax.jit(lambda w, toks: ref.logits(
            w, ref.hidden(w, c, toks, quant)[:, -1], quant))
        return {i: np.asarray(fn(w, self.prompts[i % len(self.prompts)]))
                for i in self.sample()}

    def check(self):
        self.ref_logits = self.reference_logits()
        return compare({i: self.served[i] for i in self.ref_logits}, self.ref_logits)

    def control(self):
        """The fp8 reference in the program's place, on the same sample."""
        return compare(self.reference_logits("fp8"), self.ref_logits)

    def counts(self):
        from chipbench import counts
        B, S = self.t["batch"], self.t["prompt_len"]
        kernel = counts.MIXER_KERNEL[self.c["family"]]
        return {"unit_flops": counts.prefill_flops(self.c, B, S),
                "kernel_work": {kernel: counts.kernel_work(kernel, self.c, B, S)}}


def make_prompts(key, n, batch, length, vocab):
    """``n`` prompts of (batch, length) token ids, made on the device."""
    fn = jax.jit(lambda k: jax.random.randint(
        jax.random.fold_in(jax.random.wrap_key_data(k), 1),
        (n, batch, length), 0, vocab, jnp.int32))
    return list(fn(key))


def compare(served, ref):
    """The numbers compared for served last-token logits: the widest
    relative L2 gap of a call's logits, and the widest gap by which a
    greedy served token's logit lies below the reference's best."""
    rel = max(harness.rel_l2(served[i], ref[i]) for i in ref)
    gap = max(float(np.max(harness.token_gaps(
        ref[i], np.argmax(np.asarray(served[i]), axis=-1)))) for i in ref)
    return {"logit_rel_l2": rel, "token_gap": gap}
