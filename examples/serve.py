"""Batched serving example: prefill a batch of prompts, then decode with a
shared KV cache (``lm.prefill``, then ``lm.decode_step`` once per token).

    PYTHONPATH=src python examples/serve.py [--arch rwkv6-7b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.models import lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    choices=[n for n in sorted(ARCHS)
                             if ARCHS[n].has_decoder and not ARCHS[n].frontend])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)

    cache = lm.init_cache(cfg, args.batch,
                          args.prompt_len + args.new_tokens + 8, jnp.float32)
    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, c, t: lm.prefill(p, cfg, c, tokens=t))(params, cache, prompts)
    print(f"[serve] prefill {args.batch}x{args.prompt_len} "
          f"in {time.time()-t0:.2f}s")

    decode = jax.jit(lambda p, c, t: lm.decode_step(p, cfg, c, t))
    cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [cur]
    t0 = time.time()
    for _ in range(args.new_tokens - 1):
        logits, cache = decode(params, cache, cur)
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(cur)
    dt = time.time() - t0
    toks = jnp.concatenate(out, axis=1)
    print(f"[serve] decoded {args.new_tokens} tokens/seq x {args.batch} seqs "
          f"in {dt:.2f}s ({args.batch*args.new_tokens/dt:.1f} tok/s)")
    print("[serve] sample:", toks[0].tolist())


if __name__ == "__main__":
    main()
