#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the cell's
own size, in one process:

- the program's numbers on each of ``--seeds`` (a run with a window of
  ``--seconds``, as the benchmark makes it): the lower readings;
- the control on each of ``--control-seeds``: the fp8 reference in the
  program's place, against the float32 reference; for a training cell also
  the faults planted in the reference (half of the batch left out; one
  token altered where the batch is made): the upper readings.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \\
        --control-seeds 4,5,6 --seconds 3 [--out readings.jsonl]

The benchmark's own runs never run this.  Each reading is one JSON line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import run, spec
    from repro.launch.compile_cache import enable_compile_cache

    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    devs = run.devices(cell["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    def emit(rec):
        line = json.dumps({"workload": cell["name"], **rec})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in seeds + [s for s in controls if s not in seeds]:
        keep = []
        t0 = time.perf_counter()
        res = run.run_cell(bench, cell, seed, args.seconds, 0, devs, keep=keep,
                           t_start=t0)
        t1 = time.perf_counter()
        rec = {"seed": seed, "program": {k: v["value"] for k, v in res["checks"].items()},
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "memory_peak_bytes": res["device"]["memory_peak_bytes"],
               "run_s": t1 - t0}
        if seed in controls:
            ctl = keep[0].control()
            rec.update(ctl if "control" in ctl else {"control": ctl})
            rec["control_s"] = time.perf_counter() - t1
        emit(rec)
        del keep, res
    return 0


if __name__ == "__main__":
    sys.exit(main())
