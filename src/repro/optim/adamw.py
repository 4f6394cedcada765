"""AdamW with f32 master weights, global-norm clipping and a cosine schedule.

Train state is a plain dict pytree:
  {"params": bf16 compute params, "master"/"mu"/"nu": f32, "step": scalar}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs


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
    with obs.scope(obs.OPTIMIZER):
        return _adamw_update(state, grads, lr=lr, b1=b1, b2=b2, eps=eps,
                             weight_decay=weight_decay, clip=clip)


def _adamw_update(state, grads, *, lr, b1, b2, eps, weight_decay, clip):
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
