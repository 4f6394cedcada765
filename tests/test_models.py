"""Per-architecture smoke + decode-consistency tests (reduced configs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import lm

ARCH_NAMES = sorted(ARCHS)


def _batch(cfg, key, B=2, S=32):
    ks = jax.random.split(key, 2)
    if cfg.frontend:
        return {"embeds": 0.02 * jax.random.normal(
                    ks[0], (B, S, cfg.d_model), jnp.float32),
                "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab)}
    return {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab),
            "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_forward_loss(name):
    cfg = ARCHS[name].reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = _batch(cfg, jax.random.PRNGKey(1))
    loss, aux = lm.loss_fn(params, cfg, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{name}: non-finite loss"
    assert int(aux["tokens"]) == batch["labels"].size


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_train_step_no_nans(name):
    from repro.optim import init_train_state
    from repro.train import make_train_step
    cfg = ARCHS[name].reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    state = init_train_state(params)
    step = make_train_step(cfg, lr=1e-3, remat="none", ce_chunk=16)
    state, metrics = jax.jit(step)(state, _batch(cfg, jax.random.PRNGKey(2)))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    for leaf in jax.tree.leaves(state["params"]):
        assert bool(jnp.all(jnp.isfinite(leaf)))


@pytest.mark.parametrize("name", [n for n in ARCH_NAMES
                                  if ARCHS[n].has_decoder
                                  and not ARCHS[n].frontend])
def test_prefill_decode_matches_forward(name):
    """logits(prefill(t[:-1]) then decode(t[-1])) == forward(t)[-1]."""
    import dataclasses
    cfg = ARCHS[name].reduced()
    if cfg.moe is not None:
        # capacity-based MoE drops depend on the token count, which differs
        # between the full forward (S) and prefill (S-1); use no-drop capacity
        # so the comparison is exact
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 2, 17
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)

    # ground truth: full forward, last position
    x, _ = lm.forward(params, cfg, tokens=tokens, mode="train", remat="none")
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    full_logits = jnp.einsum("bd,dv->bv", x[:, -1], head)

    cache = lm.init_cache(cfg, B, 64, jnp.float32)
    _, cache = lm.prefill(params, cfg, cache, tokens=tokens[:, :-1])
    logits, cache = lm.decode_step(params, cfg, cache, tokens[:, -1:])
    assert int(cache["pos"]) == S
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full_logits),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("name", ["minicpm3-4b", "recurrentgemma-2b",
                                  "rwkv6-7b", "yi-9b"])
def test_multi_token_decode_consistency(name):
    """Greedy decode step-by-step matches teacher-forced full forwards."""
    cfg = ARCHS[name].reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S, extra = 1, 12, 4
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab)
    cache = lm.init_cache(cfg, B, 64, jnp.float32)
    _, cache = lm.prefill(params, cfg, cache, tokens=tokens[:, :-1])
    seq = tokens
    cur = tokens[:, -1:]
    for _ in range(extra):
        logits, cache = lm.decode_step(params, cfg, cache, cur)
        x, _ = lm.forward(params, cfg, tokens=seq, mode="train", remat="none")
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        ref = jnp.einsum("bd,dv->bv", x[:, -1], head)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   atol=3e-4, rtol=3e-4)
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        seq = jnp.concatenate([seq, cur], axis=1)


def test_decode_of_prefilled_rows_inserted_into_a_batch_cache():
    """Caches prefilled a few rows at a time and inserted along the batch
    axis (axis 1 of every cache leaf) into one batch cache decode as the
    whole batch's teacher-forced forwards do."""
    cfg = ARCHS["yi-9b"].reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, G, S, M = 4, 2, 9, 32
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S + 2), 0, cfg.vocab)
    cache = lm.init_cache(cfg, B, M, jnp.float32)
    for g in range(0, B, G):
        _, part = lm.prefill(params, cfg, lm.init_cache(cfg, G, M, jnp.float32),
                             tokens=tokens[g:g + G, :S])
        cache = lm.insert_rows(cfg, cache, part, g)
    for t in (S, S + 1):
        logits, cache = lm.decode_step(params, cfg, cache, tokens[:, t:t + 1])
        x, _ = lm.forward(params, cfg, tokens=tokens[:, :t + 1], mode="train",
                          remat="none")
        ref = jnp.einsum("bd,dv->bv", x[:, -1], params["lm_head"])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   atol=3e-4, rtol=3e-4)


def test_decode_step_keeps_one_stacked_cache():
    """The donated stacked cache is the decode step's only copy of it: the
    step writes each layer's token in place and stacks no second cache
    (e.g. as a layer loop's per-layer outputs)."""
    import dataclasses
    cfg = dataclasses.replace(ARCHS["yi-9b"].reduced(), n_layers=4)
    params = lm.abstract_params(cfg, jnp.float32)
    cache = lm.abstract_cache(cfg, 2, 32768, jnp.float32)
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    mem = jax.jit(lambda p, c, t: lm.decode_step(p, cfg, c, t),
                  donate_argnums=1).lower(params, cache, tokens).compile(
                  ).memory_analysis()
    stack = sum(a.size * a.dtype.itemsize
                for a in jax.tree.leaves(cache["layers"]))
    assert mem.alias_size_in_bytes >= stack
    assert mem.temp_size_in_bytes < stack / 2, (mem.temp_size_in_bytes, stack)


def test_local_attention_window_ring_buffer():
    """recurrentgemma decode beyond the window stays consistent."""
    cfg = ARCHS["recurrentgemma-2b"].reduced()  # window = 16
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 1, 24  # prompt longer than the 16-token window
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab)
    x, _ = lm.forward(params, cfg, tokens=tokens, mode="train", remat="none")
    head = params["embed"].T
    ref = jnp.einsum("bd,dv->bv", x[:, -1], head)
    cache = lm.init_cache(cfg, B, 64, jnp.float32)
    _, cache = lm.prefill(params, cfg, cache, tokens=tokens[:, :-1])
    logits, _ = lm.decode_step(params, cfg, cache, tokens[:, -1:])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("name,scopes", [
    ("yi-9b", ("embed", "layers", "attn/qkv", "attn/core", "attn/out", "mlp",
               "head")),
    ("minicpm3-4b", ("layers", "attn/qkv", "attn/core", "attn/out")),
    ("qwen3-moe-30b-a3b", ("layers", "attn/core", "moe")),
    ("rwkv6-7b", ("layers", "rwkv/time_mix", "rwkv/channel_mix")),
    ("recurrentgemma-2b", ("layers", "rglru", "attn/core", "mlp")),
])
def test_forward_names_its_layers(name, scopes):
    """The layer scopes of ``repro/obs.py`` reach the compiled program as
    ``op_name``s, so a device trace splits by layer."""
    import re
    cfg = ARCHS[name].reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tokens = jnp.zeros((1, 16), jnp.int32)
    text = jax.jit(lambda p, t: lm.forward(p, cfg, tokens=t)[0]).lower(
        params, tokens).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in names), scope


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_param_count_matches_analytic(name):
    """Schema-materialized parameter count == logical params + the analytic
    head/expert padding delta (full cfg, abstract shapes — no allocation)."""
    cfg = ARCHS[name]
    aparams = lm.abstract_params(cfg)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(aparams))
    assert n == cfg.n_params() + cfg.padding_delta(), (
        n, cfg.n_params(), cfg.padding_delta())
