"""AdamW with f32 master weights, global-norm clipping and a cosine schedule.

Train state is a plain dict pytree:
  {"params": bf16 compute params, "master"/"mu"/"nu": f32 (ZeRO-1 sharded),
   "step": scalar}

ZeRO-1: optimizer leaves get one extra data-parallel partition on the first
dimension that is unsharded and divisible by the DP world size — XLA then
materializes the reduce-scatter(grads) / all-gather(params) pattern.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def global_norm(tree):
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def cosine_schedule(base_lr, warmup, total):
    def lr(step):
        step = step.astype(jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = 0.5 * base_lr * (1.0 + jnp.cos(jnp.pi * frac))
        return jnp.where(step < warmup, warm, cos)
    return lr


def adamw_init(params):
    # a copy even where params are already f32, so the train state never
    # holds one buffer twice (a donated state must not)
    f32 = lambda t: jax.tree.map(lambda x: jnp.array(x, jnp.float32), t)
    zeros = lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), t)
    return {"master": f32(params), "mu": zeros(params), "nu": zeros(params)}


def init_train_state(params):
    st = adamw_init(params)
    st["params"] = params
    st["step"] = jnp.zeros((), jnp.int32)
    return st


def adamw_update(state, grads, *, lr, b1=0.9, b2=0.95, eps=1e-8,
                 weight_decay=0.1, clip=1.0):
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    lr_t = lr(step) if callable(lr) else lr

    def upd(m, mu, nu, g):
        g = g.astype(jnp.float32) * scale
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        t = step.astype(jnp.float32)
        mu_hat = mu / (1 - b1 ** t)
        nu_hat = nu / (1 - b2 ** t)
        m = m - lr_t * (mu_hat / (jnp.sqrt(nu_hat) + eps) + weight_decay * m)
        return m, mu, nu

    flat_m, tdef = jax.tree.flatten(state["master"])
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    flat_g = jax.tree.leaves(grads)
    out = [upd(m, mu, nu, g)
           for m, mu, nu, g in zip(flat_m, flat_mu, flat_nu, flat_g)]
    master = jax.tree.unflatten(tdef, [o[0] for o in out])
    mu = jax.tree.unflatten(tdef, [o[1] for o in out])
    nu = jax.tree.unflatten(tdef, [o[2] for o in out])
    params = jax.tree.map(
        lambda m, p: m.astype(p.dtype), master, state["params"])
    return {"params": params, "master": master, "mu": mu, "nu": nu,
            "step": step}, {"grad_norm": gnorm}


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------

def _zero1(spec: P, shape, dp_axes, mesh) -> P:
    """Add a DP partition on the first unsharded, divisible dim."""
    if dp_axes is None:
        return spec
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, s) in enumerate(zip(parts, shape)):
        if p is None and s % dp_size == 0 and s > 0:
            parts[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            return P(*parts)
    return spec


def train_state_specs(param_spec_tree, abstract_param_tree, mesh, rules):
    """Build PartitionSpecs for the full train state (ZeRO-1 optimizer)."""
    dp = rules.get("batch")
    dp_axes = (dp,) if isinstance(dp, str) else dp

    def z(spec, aparam):
        return _zero1(spec, aparam.shape, dp_axes, mesh)

    opt_spec = jax.tree.map(z, param_spec_tree, abstract_param_tree,
                            is_leaf=lambda x: isinstance(x, P))
    return {
        "params": param_spec_tree,
        "master": opt_spec,
        "mu": opt_spec,
        "nu": opt_spec,
        "step": P(),
    }
