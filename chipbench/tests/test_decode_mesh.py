"""A whole run of the ``decode_mesh`` driver at tiny widths on four CPU
devices, tensor-parallel 4 ways: set-up, window, result line and
correctness check.  With the timed path broken underneath, ``correct``
comes out false.  And the collective share of a hand-built trace.

Every other test sees one device, so each run is a child process with four
forced host devices; it prints the result line.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench import trace_reduce

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
DECODE_MESH = {"kind": "decode_mesh", "batch": 4, "prompt_len": 64, "max_len": 96,
               "fill_group": 2}
# float32 weights against the float32 reference, with the cache in bf16 as
# served: keys and values rounded to 2^-9 move the logits by ~1e-3 relative
# L2 (0.0018 on this seed, as on one device); a wrong shard, row or position
# moves them by O(1)
LIMITS = {"logit_rel_l2": {"limit": 5e-3}, "token_gap": {"limit": 5e-3}}
BENCH = {"end_to_end": [{"name": "setup_s", "unit": "s"},
                        {"name": "decode_tokens_per_s", "unit": "tokens/s"},
                        {"name": "decode_step_ms_p95", "unit": "ms"}],
         "per_layer": []}


def child(fault):
    """Run the cell on four devices, then as the ``decode`` driver on one;
    ``fault`` "altered" drops each served token's logit below the rest, as
    ``test_run_cell.py`` does."""
    import jax

    import repro.configs as configs
    import repro.train.steps as steps
    from chipbench import run
    from chipbench.tests.test_run_cell import _altered
    from conftest import TINY_YI, tiny_arch

    real = configs.get_config
    configs.get_config = lambda n: (tiny_arch(TINY_YI, real) if n == TINY_YI["program_arch"]
                                    else real(n))
    if fault == "altered":
        steps.make_decode_step = _altered(steps.make_decode_step)
    cfg = dict(TINY_YI, tensor_parallel=4)
    cell = {"name": "c-decode-mesh", "config": cfg["name"], "traffic": "t", "chips": 4}
    with jax.default_matmul_precision("highest"):
        res = [run.run_cell(BENCH, cell, 2**31 + 9, 0.5, 0, jax.devices()[:n], cfg=cfg,
                            traffic={**DECODE_MESH, "kind": kind}, limits=LIMITS)
               for kind, n in (("decode_mesh", 4), ("decode", 1))]
    print(json.dumps(res))


def run_child(fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src"), str(HERE)])}
    proc = subprocess.run([sys.executable, __file__, fault], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_decode_mesh_run_is_correct():
    res, one = run_child("none")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 4
    # the same readings as the one-device program's, to summation order
    for name, v in res["checks"].items():
        assert v["value"] == pytest.approx(one["checks"][name]["value"], abs=1e-5)


def test_decode_mesh_altered_token_is_not_correct():
    res, one = run_child("altered")
    assert not res["correct"] and not one["correct"]


def test_collective_share_over_chips():
    from chipbench import spec
    reader = spec.metric_reader("collective_share.tp4")
    op_s = {"all-reduce-start.3": 0.5, "all-reduce-done.3": 0.1,
            "all-gather.1": 0.2, "fusion.7": 9.0, "all-reduce-scatter-fusion.2": 0.4,
            "reduce-scatter.4": 0.3, "collective-permute-done": 0.1,
            "all-to-all.2": 0.2, "reduce.5": 1.0, "convert_reduce_fusion": 1.0}
    t = trace_reduce.TraceSummary(window_s=2.0, busy_s=1.5, devices=4, op_s=op_s)
    # 1.8 s of collectives, summed over the chips, over 2 s x 4 chips
    assert reader.read({"trace": t}) == pytest.approx(100 * 1.8 / 8.0)
    assert reader.read({"trace": trace_reduce.TraceSummary(0.0, 0.0, 0)}) is None


if __name__ == "__main__":
    child(sys.argv[1])
