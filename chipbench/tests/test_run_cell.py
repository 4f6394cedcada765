"""A whole run of each driver at tiny widths on the CPU: set-up, window,
result line and correctness check, skipping only the look for a chip.  And
with the timed path broken underneath, ``correct`` comes out false."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from chipbench import run

PREFILL = {"kind": "prefill", "batch": 1, "prompt_len": 128, "prompts": 2,
           "check_sample": 2}
TRAIN = {"kind": "train", "batch": 2, "seq_len": 64, "remat": "full", "lr": 3e-4,
         "warmup": 10, "total_steps": 100, "clip": 1.0, "weight_decay": 0.1,
         "ce_chunk": 32, "checked_steps": 3}
DECODE = {"kind": "decode", "batch": 2, "prompt_len": 64, "max_len": 96,
          "fill_group": 1}
# float32 program against the float32 reference: summation order only
LIMITS = {
    "prefill": {"logit_rel_l2": {"limit": 1e-3}, "token_gap": {"limit": 1e-3}},
    "decode": {"logit_rel_l2": {"limit": 1e-3}, "token_gap": {"limit": 1e-3}},
    "train": {"loss_gap": {"limit": 1e-4}, "grad_gap": {"limit": 1e-3},
              "change_gap": {"limit": 1e-3}, "batch_mismatch": {"limit": 0}},
}
BENCH = {"end_to_end": [
    {"name": "setup_s", "unit": "s"},
    {"name": "prefill_tokens_per_s", "unit": "tokens/s", "workloads": ["c-prefill"]},
    {"name": "train_tokens_per_s", "unit": "tokens/s", "workloads": ["c-train"]},
    {"name": "decode_tokens_per_s", "unit": "tokens/s", "workloads": ["c-decode"]},
    {"name": "decode_step_ms_p95", "unit": "ms", "workloads": ["c-decode"]}],
    "per_layer": []}


def run_tiny(tiny, kind, traffic, seconds=0.5, seed=2**31 + 7):
    cfg = dict(tiny["yi"])
    cell = {"name": f"c-{kind}", "config": cfg["name"], "traffic": kind, "chips": 1}
    with jax.default_matmul_precision("highest"):
        res = run.run_cell(BENCH, cell, seed, seconds, 0, jax.devices(), cfg=cfg,
                           traffic=traffic, limits=LIMITS[kind])
    json.dumps(res)
    return res


@pytest.mark.parametrize("kind,traffic", [("prefill", PREFILL), ("train", TRAIN),
                                          ("decode", DECODE)])
def test_run_is_correct(tiny, kind, traffic):
    res = run_tiny(tiny, kind, traffic)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {m["name"] for m in BENCH["end_to_end"]
             if "workloads" not in m or f"c-{kind}" in m["workloads"]}
    assert set(res["metrics"]) == names
    assert res["device"]["platform"] == "cpu"


def _altered(fn):
    """Wrap a step builder so the logits it returns have one served token
    changed: the largest logit of each row drops below the rest."""
    def build(cfg):
        step = fn(cfg)

        def altered(*args):
            logits, cache = step(*args)
            top = logits.argmax(-1)
            return logits.at[np.arange(logits.shape[0]), top].add(-1e3), cache
        return altered
    return build


@pytest.mark.parametrize("kind,traffic,builder", [
    ("prefill", PREFILL, "make_prefill_step"), ("decode", DECODE, "make_decode_step")])
def test_altered_token_is_not_correct(tiny, monkeypatch, kind, traffic, builder):
    import repro.train.steps as steps
    monkeypatch.setattr(steps, builder, _altered(getattr(steps, builder)))
    assert not run_tiny(tiny, kind, traffic)["correct"]


def _train_fault(fault):
    import jax.numpy as jnp

    import repro.train.steps as steps
    real = steps.make_train_step

    def build(cfg, **kw):
        step = real(cfg, **kw)

        def broken(state, batch):
            if fault == "unchanged":
                _, m = step(jax.tree.map(jnp.copy, state), batch)
                return state, m
            if fault == "half_batch":
                half = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
                return step(state, half)
            if fault == "token":
                return step(state, {**batch, "tokens": batch["tokens"].at[0, 0].add(1)})
            raise ValueError(fault)
        return broken
    return build


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "token"])
def test_train_fault_is_not_correct(tiny, monkeypatch, fault):
    import repro.train as train
    monkeypatch.setattr(train, "make_train_step", _train_fault(fault))
    assert not run_tiny(tiny, "train", TRAIN)["correct"]


def test_train_token_altered_in_the_pipeline_is_not_correct(tiny, monkeypatch):
    from repro.data import SyntheticLMDataset
    real = SyntheticLMDataset.batch

    def altered(self, step, batch_size, **kw):
        b = real(self, step, batch_size, **kw)
        if step == 5:
            b["tokens"] = b["tokens"].copy()
            b["tokens"][0, 0] = (b["tokens"][0, 0] + 1) % self.vocab
        return b

    monkeypatch.setattr(SyntheticLMDataset, "batch", altered)
    res = run_tiny(tiny, "train", TRAIN)
    assert res["checks"]["batch_mismatch"]["value"] == 1
    assert not res["correct"]
