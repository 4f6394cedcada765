"""Fault-tolerant training driver.

Runs any --arch at any scale (reduced configs on CPU; production mesh on a
real fleet).  Fault-tolerance contract (the paper's preemption semantics):

* checkpoints every --ckpt-every steps (atomic + async, see checkpoint/)
* SIGTERM / SIGINT trigger a final checkpoint and a clean exit 0, so the
  cluster scheduler can preempt at any time
* on start, resumes from the latest checkpoint if one exists; the data
  pipeline is step-addressed, so resume is exactly deterministic
* checkpoints are topology-agnostic: restart may use a different mesh
  (elastic scaling)

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --steps 50 --batch 8 --seq 64

Example (one TPU chip, published widths at a cut depth):
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --layers 1 \
      --steps 5 --batch 1 --seq 2048 --remat full
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, get_config
from repro.data import SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.optim import init_train_state
from repro.train import make_train_step


def run(argv=None) -> list:
    """Train as ``main`` does; returns the per-step losses."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU smoke) config")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers at the published widths")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots"])
    ap.add_argument("--repeat-batch", action="store_true",
                    help="train every step on the batch of step 0")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    print(f"[train] compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        print(f"[train] depth cut: {cfg.n_layers} -> {args.layers} layers",
              flush=True)
        cfg = dataclasses.replace(cfg, n_layers=args.layers)

    dtype = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed), dtype)
    state = init_train_state(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] arch={cfg.name} params={n/1e6:.2f}M backend="
          f"{jax.default_backend()}", flush=True)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if ckpt is not None:
        restored = ckpt.restore(state)
        if restored is not None:
            state = restored
            start_step = int(state["step"])
            print(f"[train] resumed from step {start_step}", flush=True)

    stop = {"now": False}

    def _handle(sig, frame):
        print(f"[train] signal {sig}: checkpoint + clean exit", flush=True)
        stop["now"] = True

    signal.signal(signal.SIGTERM, _handle)
    signal.signal(signal.SIGINT, _handle)

    data = SyntheticLMDataset(cfg.vocab, args.seq, seed=args.seed)
    step_fn = jax.jit(make_train_step(
        cfg, lr=args.lr, warmup=10, total=args.steps, remat=args.remat,
        ce_chunk=min(512, args.seq)), donate_argnums=(0,))

    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = data.batch(0 if args.repeat_batch else step, args.batch)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if cfg.frontend:
            # modality stub: project token ids to pseudo-embeddings
            key = jax.random.fold_in(jax.random.PRNGKey(args.seed), step)
            emb = jax.random.normal(
                key, (args.batch, args.seq, cfg.d_model), dtype) * 0.02
            batch = {"embeds": emb, "labels": batch["labels"]}
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if (step + 1) % args.log_every == 0:
            print(f"[train] step {step+1:5d} loss={losses[-1]:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0)/(step-start_step+1):.2f}s/step)",
                  flush=True)
        if ckpt is not None and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, state)
        if stop["now"]:
            if ckpt is not None:
                ckpt.save(step + 1, state, blocking=True)
            print("[train] exited cleanly after preemption", flush=True)
            return losses
    if ckpt is not None:
        ckpt.save(args.steps, state, blocking=True)
    print(f"[train] done: first-10 avg loss {sum(losses[:10])/max(len(losses[:10]),1):.4f}"
          f" -> last-10 avg {sum(losses[-10:])/max(len(losses[-10:]),1):.4f}",
          flush=True)
    return losses


def main(argv=None):
    run(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
