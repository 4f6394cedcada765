"""The sharded serving entry (``train.steps.make_sharded_serve``) on a
(1, 4) mesh of CPU devices: a tiny yi prefilled two sequences at a time,
inserted into a batch cache and decoded four steps, against the plain
float32 reference's full forward (``chipbench/reference/yi.py``) and
against the same program on one device.

The mesh needs four devices, and every other test sees one, so the
program runs in a child process with four forced host devices; it saves
the logits and the tests compare them.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
# a tiny yi: 2 layers, 16 query heads over 4 kv heads of 8, float32
TINY = {"name": "tiny-yi", "family": "llama", "program_arch": "yi-9b",
        "reference": "yi", "num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 16, "num_key_value_heads": 4, "head_dim": 8,
        "intermediate_size": 96, "vocab_size": 256, "padded_vocab_size": 256,
        "rope_theta": 5000000.0, "param_dtype": "float32"}
BATCH, ROWS, PROMPT, STEPS, MAX_LEN = 4, 2, 27, 4, 32
SEED = 2**31 + 15


def serve(out):
    """The child: prefill, insert and decode through the sharded entry and
    on one device; the reference's logits at the same positions."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from chipbench import spec, weights
    from repro.configs import get_config
    from repro.launch.mesh import make_chip_mesh
    from repro.models import lm
    from repro.train.steps import make_sharded_serve

    jax.config.update("jax_default_matmul_precision", "highest")
    c = TINY
    cfg = dataclasses.replace(
        get_config("yi-9b"), n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"])
    ref = spec.reference(c)
    table = ref.weight_table(c)
    w = weights.make_jit(table, weights.served_dtypes(table, c))(
        weights.seed_key(SEED))
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(SEED % 1000), (BATCH, PROMPT + STEPS), 0, c["vocab_size"]))

    def run(params, prefill, insert, decode, place):
        cache = place(lm.init_cache(cfg, BATCH, MAX_LEN, jnp.float32))
        first = []
        for g in range(0, BATCH, ROWS):
            logits, part = prefill(params, place(tokens[g:g + ROWS, :PROMPT]))
            cache = insert(cache, part, g)
            first.append(logits)
        out = [jnp.concatenate(first)]
        for i in range(STEPS):
            t = PROMPT + i
            logits, cache = decode(params, cache, place(tokens[:, t:t + 1]))
            out.append(logits)
        return np.stack([np.asarray(x) for x in out], axis=1)  # (B, 1 + STEPS, V)

    s = make_sharded_serve(cfg, make_chip_mesh(4), BATCH, MAX_LEN)
    params = weights.nest(w)
    sharded = run(jax.device_put(params, s.params), s.prefill, s.insert, s.decode,
                  lambda x: jax.device_put(x, s.cache if isinstance(x, dict)
                                           else s.tokens))
    one = run(params,
              jax.jit(lambda p, t: lm.prefill(
                  p, cfg, lm.init_cache(cfg, t.shape[0], MAX_LEN, jnp.float32),
                  tokens=t)),
              jax.jit(lambda c_, p, r: lm.insert_rows(cfg, c_, p, r)),
              jax.jit(lambda p, c_, t: lm.decode_step(p, cfg, c_, t)),
              lambda x: x)
    h = ref.hidden(w, c, jnp.asarray(tokens))
    reference = np.asarray(ref.logits(w, h[:, PROMPT - 1:]))
    np.savez(out, sharded=sharded, one=one, reference=reference)
    print(json.dumps({"devices": len(jax.devices()),
                      "cache": str(s.cache["layers"]["k"].spec)}))


@pytest.fixture(scope="module")
def logits(tmp_path_factory):
    out = tmp_path_factory.mktemp("sharded_serve") / "logits.npz"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")])}
    proc = subprocess.run([sys.executable, __file__, str(out)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    info = json.loads(proc.stdout.strip().splitlines()[-1])
    assert info["devices"] == 4
    # the cache is split over its kv heads
    assert "model" in info["cache"], info
    return dict(np.load(out))


def test_sharded_serve_matches_reference(logits):
    assert logits["sharded"].shape == (BATCH, 1 + STEPS, TINY["padded_vocab_size"])
    # float32 against float32: summation order only (test_reference.py's 2e-4)
    np.testing.assert_allclose(logits["sharded"], logits["reference"],
                               rtol=2e-4, atol=2e-4)


def test_sharded_serve_matches_one_device(logits):
    np.testing.assert_allclose(logits["sharded"], logits["one"], rtol=2e-4,
                               atol=2e-4)


if __name__ == "__main__":
    serve(sys.argv[1])
