"""Training traffic: the program's training loop, step after step.

Traffic keys: ``batch`` rows of ``seq_len`` tokens per step from the
program's data pipeline; the step's settings (``remat``, ``lr``, ``warmup``,
``total_steps``, ``clip``, ``weight_decay``, ``ce_chunk``); and
``checked_steps``, the first steps, run in set-up, that the reference
follows.

Each step is what ``repro.launch.train.run`` does per step: the pipeline's
``batch`` on the host, ``jnp.asarray``, the jitted and donated train step,
and the loss read back.  Set-up builds that one step and its state and
drives it through the checked steps; the window goes on from there with the
same object.  Readings taken in set-up: each checked step's loss, each
leaf's gradient as the optimizer got it on step 1 (its first moment over
1 - beta1), and each leaf's change after the checked steps.  Every batch fed
to the step, in set-up and in the window, is kept on the host and compared
with the benchmark's own copy of the pipeline once the window has closed.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, program, synthetic_lm, weights

B1, B2, EPS = 0.9, 0.95, 1e-8   # AdamW as the configuration trains


def leaf(tree, name):
    for part in name.split("/"):
        tree = tree[part]
    return tree


class Driver:
    def __init__(self, c, t, seed, ref):
        self.c, self.t, self.seed, self.ref = c, t, seed, ref
        self.spans = harness.Spans()

    def setup(self):
        from repro.data import SyntheticLMDataset
        from repro.optim import init_train_state
        from repro.train import make_train_step

        c, t = self.c, self.t
        a = program.arch(c)
        self.key = weights.seed_key(self.seed)
        # as the program's trainer does: the served weights as arrays of
        # their own, then the state beside them outside any jit.  Inside
        # one jit, XLA may skip the weights' rounding to bf16 before their
        # f32 copy (excess precision): the master weights would not start
        # from the served ones.
        state = init_train_state(program.weight_fn(c, self.ref)(self.key))
        self.data = SyntheticLMDataset(c["vocab_size"], t["seq_len"], seed=self.seed)
        step = jax.jit(make_train_step(
            a, lr=t["lr"], warmup=t["warmup"], total=t["total_steps"],
            remat=t["remat"], ce_chunk=t["ce_chunk"], clip=t["clip"],
            weight_decay=t["weight_decay"]), donate_argnums=(0,))
        self.fed = {}
        batch = self._batch(0)
        self.fn = step.lower(state, batch).compile()
        self.footprint = harness.program_bytes(self.fn)

        self.names = sorted(self.ref.weight_table(c))
        self.losses, self.step = [], 0
        for _ in range(t["checked_steps"]):
            state = self._step(state, self._batch(self.step))
            if self.step == 1:
                self.grad_norms = [
                    n / (1 - B1) for n in harness.leaf_norms(
                        [leaf(state["mu"], k) for k in self.names])]
        self.change_norms = self._change_norms(state["master"])
        self.state = state

    def _batch(self, step):
        b = self.data.batch(step, self.t["batch"])
        self.fed[step] = b
        return {k: jnp.asarray(v) for k, v in b.items()}

    def _step(self, state, batch):
        state, m = self.fn(state, batch)
        self.losses.append(float(m["loss"]))
        self.step += 1
        return state

    def _change_norms(self, master):
        """Per-leaf norm of (weights now - the seed's initial weights)."""
        table = self.ref.weight_table(self.c)
        dtypes = weights.served_dtypes(table, self.c)
        norm = jax.jit(lambda x, x0: jnp.sqrt(jnp.sum(jnp.square(x - x0.astype(jnp.float32)))))
        out = []
        for name in self.names:
            # made as an array of its own, so it holds the served rounding
            x0 = jax.jit(lambda key, name=name: weights.make_leaf(
                key, name, table[name], dtypes[name]))(self.key)
            out.append(float(norm(leaf(master, name), x0)))
            del x0
        return out

    def window(self, seconds):
        span, t = self.spans, self.t
        state, first, marks = self.state, self.step, []
        t0 = time.perf_counter()
        while True:
            marks.append(time.perf_counter() - t0)
            with span("data"):
                b = self.fed[self.step] = self.data.batch(self.step, t["batch"])
            with span("transfer"):
                b = {k: jnp.asarray(v) for k, v in b.items()}
            with span("dispatch"):
                state, m = self.fn(state, b)
            with span("wait"):
                loss = float(m["loss"])
            self.step += 1
            if not np.isfinite(loss):
                raise FloatingPointError(f"loss {loss} at step {self.step}")
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        print("step starts (s): " + " ".join(f"{m:.3f}" for m in marks), file=sys.stderr)
        self.state = state
        n = self.step - first
        tokens = n * t["batch"] * t["seq_len"]
        return {"attempted": n, "failed": 0, "elapsed_s": elapsed, "units": n,
                "metrics": {"train_tokens_per_s": tokens / elapsed}}

    def release(self):
        del self.state, self.fn

    def reference_readings(self, quant=None, *, fault=None):
        """The reference's losses, step-1 gradient norms and change norms
        over the checked steps, from the seed's weights and batches.
        ``fault``: "half_batch" or "token" plants that fault in it."""
        c, t = self.c, self.t
        ref = self.ref
        w = {k: v.astype(jnp.float32) for k, v in
             program.reference_weights(c, ref, self.key).items()}
        # the moments wait on the host while the gradients are taken: the
        # float32 weights, gradients and both moments do not fit beside the
        # backward pass's temporaries on one chip
        mu = nu = {k: np.zeros(v.shape, np.float32) for k, v in w.items()}

        def lr_at(step):
            if step < t["warmup"]:
                return t["lr"] * step / t["warmup"]
            frac = min(max((step - t["warmup"]) / max(t["total_steps"] - t["warmup"], 1),
                           0.0), 1.0)
            return 0.5 * t["lr"] * (1.0 + np.cos(np.pi * frac))

        @jax.jit
        def grads_of(w, tokens, labels):
            loss, g = jax.value_and_grad(ref.loss)(w, c, tokens, labels, quant)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
            scale = jnp.minimum(1.0, t["clip"] / jnp.maximum(gnorm, 1e-12))
            return loss, {k: x * scale for k, x in g.items()}

        def adamw(w, mu, nu, g, step, lr):
            out = {}
            for k in w:
                m = B1 * mu[k] + (1 - B1) * g[k]
                v = B2 * nu[k] + (1 - B2) * g[k] * g[k]
                upd = (m / (1 - B1 ** step)) / (jnp.sqrt(v / (1 - B2 ** step)) + EPS)
                out[k] = (w[k] - lr * (upd + t["weight_decay"] * w[k]), m, v)
            return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
                    {k: o[2] for k, o in out.items()})

        adamw = jax.jit(adamw, donate_argnums=(0, 1, 2, 3))
        losses, grad_norms = [], None
        for s in range(t["checked_steps"]):
            tokens, labels = synthetic_lm.batch(c["vocab_size"], t["seq_len"],
                                                self.seed, s, t["batch"])
            if fault == "half_batch":
                tokens, labels = tokens[: t["batch"] // 2], labels[: t["batch"] // 2]
            elif fault == "token":
                tokens = tokens.copy()
                tokens[0, 0] = (tokens[0, 0] + 1) % c["vocab_size"]
            loss, g = grads_of(w, jnp.asarray(tokens), jnp.asarray(labels))
            losses.append(float(loss))
            if s == 0:
                grad_norms = harness.leaf_norms([g[k] for k in self.names])
            w, mu, nu = adamw(w, jax.device_put(mu), jax.device_put(nu), g,
                              jnp.float32(s + 1), jnp.float32(lr_at(s + 1)))
            del g
            mu, nu = jax.device_get(mu), jax.device_get(nu)
        change = self._change_norms(weights.nest(w))
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}

    def program_readings(self):
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}

    def batch_mismatch(self):
        """Token ids, over every step fed to the program (the checked steps
        and the window's), that differ from the benchmark's own copy of the
        pipeline: what the timed path trained on must be the traffic."""
        c, t = self.c, self.t
        n = 0
        for step, b in self.fed.items():
            tokens, labels = synthetic_lm.batch(c["vocab_size"], t["seq_len"],
                                                self.seed, step, t["batch"])
            n += int(np.sum(b["tokens"] != tokens) + np.sum(b["labels"] != labels))
        return n

    def check(self):
        self.ref_readings = self.reference_readings()
        prog, ref = self.program_readings(), self.ref_readings
        for i, name in enumerate(self.names):
            print(f"leaf {name}: grad {prog['grad_norms'][i]!r} ref {ref['grad_norms'][i]!r}"
                  f" change {prog['change_norms'][i]!r} ref {ref['change_norms'][i]!r}",
                  file=sys.stderr)
        print(f"losses {prog['losses']} ref {ref['losses']}", file=sys.stderr)
        return {**compare(prog, ref), "batch_mismatch": self.batch_mismatch()}

    def control(self):
        """The fp8 reference in the program's place, and the faults planted
        in the reference, each against the float32 reference."""
        out = {"control": compare(self.reference_readings("fp8"), self.ref_readings)}
        for fault in ("half_batch", "token"):
            out[fault] = compare(self.reference_readings(fault=fault), self.ref_readings)
        return out

    def counts(self):
        from chipbench import counts
        return {"unit_flops": counts.train_flops(self.c, self.t["batch"],
                                                 self.t["seq_len"])}


def compare(prog, ref):
    """Numbers compared for the checked steps: the widest relative gap of a
    step's loss; the worst leaf's gap of step-1 gradient norms; the worst
    leaf's gap of change norms, leaving out leaves whose reference gradient
    is under a thousandth of the median leaf's (they move by round-off)."""
    rg = np.asarray(ref["grad_norms"])
    moving = rg >= 1e-3 * np.median(rg)
    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"])),
        "grad_gap": harness.worst_leaf_gap(prog["grad_norms"], rg),
        "change_gap": harness.worst_leaf_gap(prog["change_norms"], ref["change_norms"],
                                             keep=moving),
    }
