"""The plain references agree with the program at tiny widths in float32,
so a faulty reference is caught before any chip time is spent on it."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program, spec, weights


@pytest.mark.parametrize("family", ["yi", "rwkv6"])
def test_reference_matches_program_float32(tiny, family):
    from repro.models import lm

    c = tiny[family]
    a = program.arch(c)
    ref = spec.reference(c)
    key = weights.seed_key(2**31 + 12345)
    params = program.weight_fn(c, ref)(key)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, c["vocab_size"])

    with jax.default_matmul_precision("highest"):
        prog_logits, _ = lm.prefill(params, a, lm.init_cache(a, 2, 64, jnp.float32),
                                    tokens=tokens)
        x, _ = lm.forward(params, a, tokens=tokens, mode="train", remat="none")
    w = program.reference_weights(c, ref, key)
    h = ref.hidden(w, c, tokens)
    ref_logits = ref.logits(w, h[:, -1])

    np.testing.assert_allclose(np.asarray(h), np.asarray(x), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(prog_logits),
                               rtol=2e-4, atol=2e-4)


def test_reference_loss_matches_program(tiny):
    from repro.models import lm

    c = tiny["yi"]
    a = program.arch(c)
    ref = spec.reference(c)
    key = weights.seed_key(7)
    params = program.weight_fn(c, ref)(key)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, c["vocab_size"])
    labels = jnp.roll(tokens, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        prog, _ = lm.loss_fn(params, a, {"tokens": tokens, "labels": labels},
                             remat="none", ce_chunk=32)
    w = program.reference_weights(c, ref, key)
    np.testing.assert_allclose(float(ref.loss(w, c, tokens, labels)), float(prog),
                               rtol=1e-5)


def test_fp8_control_departs_from_reference(tiny):
    c = tiny["yi"]
    ref = spec.reference(c)
    w = program.reference_weights(c, ref, weights.seed_key(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 64), 0, c["vocab_size"])
    exact = ref.logits(w, ref.hidden(w, c, tokens))
    low = ref.logits(w, ref.hidden(w, c, tokens, "fp8"), "fp8")
    rel = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-3 < rel < 0.5
