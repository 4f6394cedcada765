#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print one JSON result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix; the
traffic's ``kind`` picks the driver (``chipbench/drivers/<kind>.py``).  The
run makes weights and inputs from the seed, compiles and warms every shape
it will use (set-up), measures for ``--seconds``, then compares what the
window produced with the configuration's plain float32 reference.  With
``--trace 1`` the window is traced and the per-layer metrics are reported
in place of the end-to-end ones.

A TPU is required: with no TPU, or fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "chipbench" / "out" / "trace"


class NoDevice(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices(chips):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def run_cell(bench, cell, seed, seconds, trace, devs, *, cfg=None, traffic=None,
             limits=None, t_start=None, keep=None):
    """Everything of a run after the look for a chip; returns the result
    dict.  ``cfg``, ``traffic`` and ``limits`` default to the cell's files;
    the driver is appended to the list ``keep`` where one is given."""
    import jax

    from chipbench import harness, spec, trace_reduce

    t_start = T_START if t_start is None else t_start
    cfg = cfg or spec.config(cell["config"])
    traffic = traffic or spec.traffic(cell["traffic"])
    limits = spec.limits(cell["name"]) if limits is None else limits
    ref = spec.reference(cfg)
    drv = spec.driver(traffic["kind"]).Driver(cfg, traffic, seed, ref)
    if keep is not None:
        keep.append(drv)
    drv.setup()
    setup_s = time.perf_counter() - t_start

    compiles = []

    def on_compile(name, *args, **kwargs):
        if name.endswith("backend_compile_duration"):
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            res = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(on_compile)
    if compiles:
        print(f"warning: {len(compiles)} compilations inside the window", file=sys.stderr)

    dev = devs[0]
    memory = max(max(harness.peak_bytes(d) for d in devs), drv.footprint)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": memory}
    out = {"attempted": res["attempted"], "failed": res["failed"]}

    if trace:
        summary = trace_reduce.reduce_file(TRACE_DIR)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        run = {"cell": cell["name"], "kind": traffic["kind"], "config": cfg,
               "traffic": traffic, "units": res["units"], "elapsed_s": res["elapsed_s"],
               "trace": summary, "spans_s": dict(drv.spans.seconds),
               "peaks": peaks(dev.device_kind), **drv.counts()}
        metrics = {}
        for m in spec.per_layer(bench, cell["name"]):
            value = spec.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = summary.breakdown()
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in spec.end_to_end(bench, cell["name"]):
            if m["name"] != "setup_s":
                metrics[m["name"]] = {"value": res["metrics"][m["name"]], "unit": m["unit"]}

    drv.release()
    t_check = time.perf_counter()
    readings = drv.check()
    print(f"reference check took {time.perf_counter() - t_check:.1f} s; "
          f"set-up {setup_s:.1f} s, window {res['elapsed_s']:.1f} s", file=sys.stderr)
    checks = {name: {"value": readings[name], "limit": lim["limit"]}
              for name, lim in limits.items()}
    correct = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
                  for v in checks.values()) and res["failed"] == 0 and bool(checks)
    for name, v in checks.items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    return {"correct": correct, **out, "metrics": metrics, "device": device,
            "checks": checks}


def peaks(kind):
    from chipbench import spec
    table = spec.load_json(ROOT / "chipbench" / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return table[kind]


def main(argv=None):
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import spec
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    try:
        devs = devices(cell["chips"])
    except NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 4
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(bench, cell, args.seed, args.seconds, args.trace, devs)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
