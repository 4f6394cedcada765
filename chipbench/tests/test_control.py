"""The controls at a size a test run holds: the fp8 reference in the
program's place reads above each served cell's limit on one of its numbers,
as it does on the chip at the cell's own size (``chipbench/control.py``)."""
from __future__ import annotations

import jax
import pytest

from chipbench import run, spec
from test_run_cell import BENCH, DECODE, LIMITS, PREFILL


@pytest.mark.parametrize("kind,traffic,cell", [("prefill", PREFILL, "yi9b-prefill-32k"),
                                               ("decode", DECODE, "yi9b-decode-32k")])
def test_control_fails_the_cell_limits(tiny, kind, traffic, cell):
    cfg = dict(tiny["yi"], param_dtype="bfloat16")
    keep = []
    res = run.run_cell(BENCH, {"name": f"c-{kind}", "config": cfg["name"], "traffic": kind,
                               "chips": 1}, 11, 0.5, 0, jax.devices(), cfg=cfg,
                       traffic=traffic, limits=LIMITS[kind], keep=keep)
    assert res["attempted"] > 0
    limits = spec.limits(cell)
    readings = keep[0].control()
    assert any(readings[name] > lim["limit"] for name, lim in limits.items()), readings


def test_train_control_and_faults_fail_the_cell_limits(tiny):
    from test_run_cell import TRAIN

    cfg = dict(tiny["yi"], param_dtype="bfloat16")
    keep = []
    run.run_cell(BENCH, {"name": "c-train", "config": cfg["name"], "traffic": "train",
                         "chips": 1}, 12, 0.5, 0, jax.devices(), cfg=cfg,
                 traffic=dict(TRAIN, warmup=1), limits=LIMITS["train"], keep=keep)
    limits = spec.limits("yi9b-train-4k")
    readings = keep[0].control()
    for what in ("control", "half_batch"):
        assert any(readings[what][name] > limits[name]["limit"]
                   for name in readings[what]), (what, readings[what])
