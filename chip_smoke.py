#!/usr/bin/env python3
"""Run the system once on a TPU, through the entry points a user calls.

    python3 chip_smoke.py [--seed N]        # one chip
    python3 chip_smoke.py --four-chips      # yi-9b serve, tensor-parallel

One process, one pass, failing on the first fault.  Phases, in order:

  device     a TPU is present (no CPU fallback)
  scheduler  the host path: one Dally simulation of the "smoke" scenario
  kernel     the Pallas kernels at real widths against their jnp paths (f32)
  serve      yi-9b, published widths, 8 layers: bf16 prefill + decode
  train      yi-9b, published widths, 1 layer, through repro.launch.train

Weights and data are random, made from --seed.  Each phase prints its name,
wall time and the device's peak bytes in use on its own line; the last line
is one JSON object naming the device.  ``--four-chips`` runs only the serve
phase, sharded over a (1, 4) ("data", "model") mesh, against the same step
on one of the four chips.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"
if not (SRC / "repro").is_dir():
    sys.exit(f"chip_smoke.py: no repro package under {SRC}")
sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Tolerances, each with its reason.
# Kernel vs jnp path, both f32 with full-precision contractions: only the
# order of f32 summation differs (over <= 2048 keys, or 64-wide state rows),
# about sqrt(2048) * 1.2e-7 ~ 5e-6 relative; 1e-4 of the reference's largest
# magnitude leaves a 20x margin and still catches any wrong mask or block.
KERNEL_RTOL = 1e-4
# bf16 logits of two computations of the same thing (prefill vs prefill +
# decode; four chips vs one): activations are rounded to bf16 (2^-8 relative)
# at every layer, and the two sides round in different places (different
# attention accumulation, different partial sums), through 8 layers.  A
# relative L2 error of 5e-2 is ~10x the per-rounding error; a wrong cache
# position, mask or shard gives O(1).
LOGITS_REL_L2 = 5e-2

YI_LAYERS_SERVE = 8
YI_LAYERS_TRAIN = 1


class SmokeFailure(RuntimeError):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


@contextlib.contextmanager
def phase(name):
    t0 = time.perf_counter()
    yield
    print(f"[phase] {name} wall_s={time.perf_counter() - t0} "
          f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}", flush=True)


@contextlib.contextmanager
def step(label):
    """A timed step inside a phase; the step syncs before it ends."""
    t0 = time.perf_counter()
    yield
    print(f"  [{label}] wall_s={time.perf_counter() - t0}", flush=True)


def rel_l2(x, ref):
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def max_rel(x, ref):
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


def yi(n_layers):
    from repro.configs import get_config
    return dataclasses.replace(get_config("yi-9b"), n_layers=n_layers)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(n_chips):
    devs = jax.devices()
    print(f"[device] {devs} kind={devs[0].device_kind}", flush=True)
    check(devs[0].platform == "tpu", f"no TPU: platform {devs[0].platform}")
    check(len(devs) >= n_chips, f"need {n_chips} chips, have {len(devs)}")
    return devs


def phase_scheduler():
    from repro.api import run_one
    art = run_one("smoke", policy="dally", seed=0)
    m = art["metrics"]
    print(f"[scheduler] smoke/dally: n_finished={m['n_finished']} "
          f"n_unfinished={m['n_unfinished']} makespan={m['makespan']}",
          flush=True)
    check(m["n_finished"] > 0 and np.isfinite(m["makespan"]),
          "scheduler finished no job")


def phase_kernel(seed):
    from repro.kernels.flash_attention import chunked_attention, flash_attention
    from repro.kernels.rglru_scan import rglru_reference, rglru_scan
    from repro.kernels.rwkv6_wkv import rwkv6_reference, rwkv6_wkv

    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    # one yi-9b layer's heads: 32 query heads, 4 kv heads, head_dim 128
    q = jax.random.normal(ks[0], (1, 2048, 32, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2048, 4, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2048, 4, 128), jnp.float32)
    pallas = jax.jit(lambda q, k, v: flash_attention(q, k, v, backend="pallas"))
    hlo = pallas.lower(q, k, v).compile().as_text()
    check("tpu_custom_call" in hlo, "flash_attention compiled without the kernel")
    out = pallas(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = chunked_attention(q, k, v)
    err = max_rel(out, ref)
    print(f"[kernel] flash_attention yi-9b heads S=2048 f32: "
          f"max|pallas-chunked|/max|chunked|={err} tol={KERNEL_RTOL}", flush=True)
    check(err <= KERNEL_RTOL, "flash_attention disagrees with the chunked path")

    # rwkv6-7b heads (64 x 64) and recurrentgemma-2b lru width (2560)
    shape = (1, 1024, 64, 64)
    r, kk, vv = (0.5 * jax.random.normal(ks[3 + i], shape) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[6], shape))
    u = 0.5 * jax.random.normal(ks[7], (64, 64))
    with jax.default_matmul_precision("highest"):
        ry, rs = jax.jit(rwkv6_reference)(r, kk, vv, w, u)
    py, ps = jax.jit(lambda *a: rwkv6_wkv(*a, backend="pallas"))(r, kk, vv, w, u)
    err = max(max_rel(py, ry), max_rel(ps, rs))
    print(f"[kernel] rwkv6_wkv 64x64 heads T=1024 f32: max rel err={err} "
          f"tol={KERNEL_RTOL}", flush=True)
    check(err <= KERNEL_RTOL, "rwkv6_wkv disagrees with its reference")

    a = jax.nn.sigmoid(jax.random.normal(ks[8], (2, 1024, 2560)))
    b = 0.1 * jax.random.normal(ks[9], (2, 1024, 2560))
    rh, rl = jax.jit(rglru_reference)(a, b)
    ph, pl_ = jax.jit(lambda a, b: rglru_scan(a, b, backend="pallas"))(a, b)
    err = max(max_rel(ph, rh), max_rel(pl_, rl))
    print(f"[kernel] rglru_scan width 2560 batch 2 T=1024 f32: max rel err={err} "
          f"tol={KERNEL_RTOL}", flush=True)
    check(err <= KERNEL_RTOL, "rglru_scan disagrees with its reference")


def _serve_fns(cfg, max_len):
    from repro.models import lm
    from repro.train.steps import make_decode_step, make_prefill_step
    prefill_step = make_prefill_step(cfg)

    def prefill(params, tokens):
        # the zero cache only gives shapes; building it inside the program
        # keeps it out of device memory
        cache = lm.init_cache(cfg, tokens.shape[0], max_len)
        return prefill_step(params, cache, {"tokens": tokens})

    def decode(params, cache, tokens):
        return make_decode_step(cfg)(params, cache, {"tokens": tokens})

    return prefill, decode


def phase_serve(seed, batch=16, prompt=8192, decode_steps=32):
    from repro.models import lm
    cfg = yi(YI_LAYERS_SERVE)
    max_len = prompt + decode_steps
    kp, kt = jax.random.split(jax.random.PRNGKey(seed))
    with step("serve: init weights (compile + run)"):
        params = jax.jit(lambda key: lm.init_params(cfg, key, jnp.bfloat16))(kp)
        tokens = jax.random.randint(kt, (batch, prompt), 0, cfg.vocab,
                                    jnp.int32).block_until_ready()
    prefill_fn, decode_fn = _serve_fns(cfg, max_len)
    prefill = jax.jit(prefill_fn)
    decode = jax.jit(decode_fn, donate_argnums=(1,))

    with step("serve: prefill compile"):
        compiled = prefill.lower(params, tokens).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled prefill holds no Pallas kernel")
    print(f"[serve] yi-9b L={cfg.n_layers} batch={batch} prompt={prompt}: "
          f"prefill memory_analysis {compiled.memory_analysis()}", flush=True)
    with step("serve: prefill run"):
        logits_full, cache = compiled(params, tokens)
        logits_full.block_until_ready()
    check(logits_full.shape == (batch, cfg.padded_vocab), "prefill logits shape")
    check(bool(jnp.all(jnp.isfinite(logits_full))), "prefill logits not finite")

    nxt = jnp.argmax(logits_full, axis=-1).astype(jnp.int32)[:, None]
    all_finite = True
    with step(f"serve: {decode_steps} decode steps, the first compiles"):
        for _ in range(decode_steps):
            logits, cache = decode(params, cache, nxt)
            all_finite &= bool(jnp.all(jnp.isfinite(logits)))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        nxt.block_until_ready()
    print(f"[serve] cache pos={int(cache['pos'])}", flush=True)
    check(all_finite, "decode logits not finite")
    check(int(cache["pos"]) == max_len, "cache position after decode")
    del cache, logits
    gc.collect()

    # prefill of prompt[:n] + one decode step == prefill of prompt[:n+1]
    with step("serve: prefill[:n] (compile + run) + one decode step"):
        logits_n, cache_n = prefill(params, tokens[:, :-1])
        logits_dec, _ = decode(params, cache_n, tokens[:, -1:])
        logits_dec.block_until_ready()
    err = rel_l2(logits_dec, logits_full)
    print(f"[serve] prefill[:n]+decode vs prefill[:n+1] logits (bf16): "
          f"rel_l2={err} max_abs={float(jnp.max(jnp.abs(logits_dec - logits_full)))} "
          f"tol rel_l2={LOGITS_REL_L2}", flush=True)
    check(err <= LOGITS_REL_L2, "decode step disagrees with a longer prefill")


def phase_serve_four_chips(seed, devs, batch=8, prompt=4096):
    from repro.launch.mesh import make_chip_mesh
    from repro.models import lm
    from repro.train.steps import make_sharded_serve

    cfg = yi(YI_LAYERS_SERVE)
    max_len = prompt + 1
    serve = make_sharded_serve(cfg, make_chip_mesh(4), batch, max_len)
    rules = serve.rules
    print(f"[serve4] mesh {dict(serve.mesh.shape)} heads->{rules['heads']} "
          f"kv_heads->{rules['kv_heads']} ffn->{rules['ffn']} "
          f"vocab->{rules['vocab']}", flush=True)

    kp, kt = jax.random.split(jax.random.PRNGKey(seed))
    one = jax.sharding.SingleDeviceSharding(devs[0])
    with step("serve4: init weights on chip 0"):
        params1 = jax.jit(lambda key: lm.init_params(cfg, key, jnp.bfloat16),
                          out_shardings=one)(kp)
        tokens = jax.random.randint(kt, (batch, prompt), 0, cfg.vocab,
                                    jnp.int32).block_until_ready()
    prefill_fn, decode_fn = _serve_fns(cfg, max_len)

    # one chip: the same step, unsharded, on the first of the four
    with step("serve4: one-chip prefill + decode (compile + run)"):
        ref_logits, ref_cache = jax.jit(prefill_fn)(params1, tokens)
        ref_next = jnp.argmax(ref_logits, axis=-1).astype(jnp.int32)[:, None]
        ref_dec, _ = jax.jit(decode_fn)(params1, ref_cache, ref_next)
        ref_dec.block_until_ready()
    del ref_cache
    params4 = jax.device_put(params1, serve.params)
    del params1
    gc.collect()

    tokens4 = jax.device_put(tokens, serve.tokens)
    next4 = jax.device_put(ref_next, serve.tokens)
    with step("serve4: sharded prefill compile"):
        compiled = serve.prefill.lower(params4, tokens4).compile()
    hlo = compiled.as_text()
    check("tpu_custom_call" in hlo, "sharded prefill holds no Pallas kernel")
    print(f"[serve4] sharded prefill: all-gather ops={hlo.count('all-gather(')} "
          f"all-reduce ops={hlo.count('all-reduce(')}; memory_analysis "
          f"{compiled.memory_analysis()}", flush=True)
    with step("serve4: sharded prefill run + decode (compile + run)"):
        logits4, cache4 = compiled(params4, tokens4)
        dec4, _ = serve.decode(params4, cache4, next4)
        dec4.block_until_ready()
    e_pre, e_dec = rel_l2(logits4, ref_logits), rel_l2(dec4, ref_dec)
    print(f"[serve4] 4 chips vs 1 chip logits (bf16): prefill rel_l2={e_pre} "
          f"decode rel_l2={e_dec} tol rel_l2={LOGITS_REL_L2}", flush=True)
    check(max(e_pre, e_dec) <= LOGITS_REL_L2, "four chips disagree with one")


def phase_train(seed):
    from repro.launch import train
    losses = train.run([
        "--arch", "yi-9b", "--layers", str(YI_LAYERS_TRAIN), "--steps", "6",
        "--batch", "2", "--seq", "4096", "--remat", "full",
        "--lr", "3e-4", "--repeat-batch", "--log-every", "1",
        "--seed", str(seed)])
    print(f"[train] losses={losses}", flush=True)
    check(all(np.isfinite(losses)), "train loss not finite")
    check(losses[-1] < losses[0], "train loss did not fall on a repeated batch")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the yi-9b serve phase over a (1, 4) mesh")
    args = ap.parse_args(argv)
    print(f"[chip_smoke] compile cache: {enable_compile_cache()}", flush=True)

    with phase("device"):
        devs = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        with phase("serve4"):
            phase_serve_four_chips(args.seed, devs)
    else:
        with phase("scheduler"):
            phase_scheduler()
        with phase("kernel"):
            phase_kernel(args.seed)
        with phase("serve"):
            phase_serve(args.seed)
        gc.collect()
        with phase("train"):
            phase_train(args.seed)
    dev = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
