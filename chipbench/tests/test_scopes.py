"""Device time by layer (``chipbench/scopes.py``): the programs the drivers
compile carry the layer scopes, the rules that place an op on a layer, the
readers of the scope shares, and the stall witness."""
from __future__ import annotations

import gc
import pathlib
import sys
import time

import jax
import pytest

from chipbench import run, scopes, spec, stall_watch, trace_reduce
from chipbench.tests.test_run_cell import BENCH, DECODE, LIMITS, PREFILL, TRAIN

TRACE = pathlib.Path(__file__).resolve().parents[1] / "testdata" / "prefill_32k_window.xplane.pb"
NO_OP = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
CONTROL = {"while", "conditional", "call"}
SEED = 2**31 + 11
NEW = ("scan_share.decode", "attention_share.decode", "scan_share.train",
       "attention_bwd_share.train", "optimizer_share.train")
ATTN = {"attn/qkv", "attn/core", "attn/out"}
# prefill writes its cache where the scan stacks the layers' outputs: XLA
# fuses the pad into that update, whose metadata is the scan's
RUNS = {
    "prefill": (PREFILL, {"embed", "layers/scan", "mlp", "head"} | ATTN),
    "decode": (DECODE, {"embed", "layers/scan", "attn/cache_write", "mlp", "head"} | ATTN),
    "train": (TRAIN, {"embed", "layers/scan", "mlp", "head", "loss", "optimizer"} | ATTN),
}


def module_proto(compiled):
    return compiled.runtime_executable().hlo_modules()[0].as_serialized_hlo_module_proto()


def executed(proto):
    """Names of the instructions that run as ops: those of the entry and of
    the computations control flow runs, less those that move no data."""
    instrs, comps = scopes.parse_module(proto)
    called = {c for ins in instrs.values() for c in ins[4]}
    todo, seen = [c for c in comps if c not in called], set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for i in comps[c]:
            if instrs[i][1] in CONTROL:
                todo.extend(instrs[i][4])
    return [instrs[i][0] for c in seen for i in comps[c] if instrs[i][1] not in NO_OP]


def compiled_driver(tiny, kind, traffic):
    cfg = dict(tiny["yi"])
    drv = spec.driver(kind).Driver(cfg, traffic, SEED, spec.reference(cfg))
    with jax.default_matmul_precision("highest"):
        drv.setup()
    return drv


def test_taxonomy_is_the_programs():
    from repro import obs
    assert scopes.TAXONOMY == obs.SCOPES


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/layers/while/body/dynamic_slice", ("layers/scan", "fwd")),
    ("jit(step)/layers/while/body/closed_call/attn/core/dot_general", ("attn/core", "fwd")),
    ("jit(f)/transpose(jvp(layers))/while/body/closed_call/checkpoint/mlp/mul",
     ("mlp", "bwd")),
    ("jit(f)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/core/tanh", ("attn/core", "remat")),
    ("jit(f)/transpose(jvp(attn/qkv))/dot_general", ("attn/qkv", "bwd")),
    ("jit(step)/transpose(jvp(layers))/while/body/flash_attention_bwd_chunked_jnp/"
     "jit(chunked_attention)/while", ("attn/core", "bwd")),
    ("jit(s)/transpose(jvp(layers))/while/body/checkpoint/attn/core/"
     "flash_attention_bwd_chunked_jnp/transpose(jvp(jit(chunked_attention)))/while/"
     "body/checkpoint/rematted_computation/mul", ("attn/core", "bwd")),
    ("jit(s)/transpose(jvp(while))/body/flash_attention_bwd_chunked_jnp/mul", None),
    ("jit(step)/optimizer/sqrt", ("optimizer", "fwd")),
    ("jit(loss)/mul", None),
    ("jit(step)/while/body/dynamic_update_slice", None),
    ("jit(step)/headroom/add", None),
])
def test_scope_of_an_op_name(op_name, want):
    assert scopes.scope_of(op_name) == want


@pytest.mark.parametrize("kind", sorted(RUNS))
def test_driver_program_carries_every_scope(tiny, kind):
    traffic, want = RUNS[kind]
    drv = compiled_driver(tiny, kind, traffic)
    proto = module_proto(drv.fn)
    ops = executed(proto)
    s = scopes.scopes_s({n: 1.0 for n in ops}, [proto])
    found = {k.rpartition("/")[0] for k in s if k not in (scopes.UNSCOPED, scopes.OTHER)}
    assert want <= found, want - found
    assert s.get(scopes.UNSCOPED, 0.0) < 0.05 * len(ops), s
    assert scopes.OTHER not in s
    if kind == "train":
        assert {"attn/core/bwd", "attn/core/remat", "optimizer/fwd"} <= set(s)
    if kind == "decode":
        assert {"layers/scan/fwd", "attn/cache_write/fwd"} <= set(s)


HLO = """HloModule m

body {
  p = (s32[], f32[4]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  x = f32[4] get-tuple-element(p), index=1
  y = f32[4] sine(x), metadata={op_name="jit(f)/transpose(jvp(layers))/while/body/attn/core/sin"}
  c = f32[4] copy(y)
  n = f32[4] negate(x)
  one = s32[] constant(1)
  j = s32[] add(i, one), metadata={op_name="jit(f)/layers/while/body/add"}
  ROOT t = (s32[], f32[4]) tuple(j, c)
}

cond {
  p = (s32[], f32[4]) parameter(0)
  i = s32[] get-tuple-element(p), index=0
  k = s32[] constant(3)
  ROOT lt = pred[] compare(i, k), direction=LT
}

ENTRY e {
  a = f32[4] parameter(0)
  z = s32[] constant(0)
  t = (s32[], f32[4]) tuple(z, a)
  w = (s32[], f32[4]) while(t), condition=cond, body=body, metadata={op_name="jit(f)/layers/while"}
  g = f32[4] get-tuple-element(w), index=1
  out = f32[4] copy(g)
  b = f32[4] bitcast(a)
  cb = f32[4] copy(b)
  u = f32[4] exponential(a), metadata={op_name="jit(f)/exp"}
  r = f32[4] add(out, cb), metadata={op_name="jit(f)/head/add"}
  ROOT v = f32[4] add(r, u)
}
"""


def test_op_without_metadata_takes_its_operands_scope():
    from jax._src.lib import xla_client
    proto = xla_client._xla.hlo_module_from_text(HLO).as_serialized_hlo_module_proto()
    m = scopes.resolve(proto)
    assert m["c"] == ("attn/core", "bwd")     # its operand's
    assert m["out"] == ("layers/scan", "fwd")  # through the get-tuple-element
    assert m["n"] == ("layers/scan", "fwd")    # no scoped operand: the while's
    assert m["cb"] == ("head", "fwd")          # no scoped operand: its user's
    assert m["r"] == ("head", "fwd")
    assert m["u"] is None                      # metadata that names no layer
    s = scopes.scopes_s({"c": 2.0, "out": 1.0, "cb": 0.5, "u": 0.125, "fusion.9": 0.25},
                        [proto])
    assert s == {"attn/core/bwd": 2.0, "layers/scan/fwd": 1.0, "head/fwd": 0.5,
                 "unscoped": 0.125, "other": 0.25}


def test_recorded_trace_carries_its_program():
    """The v5e trace holds the HLO module that ran: every op of the window
    is one of its instructions.  That program is from before the layer
    scopes, so the readers report nothing for it."""
    summary = trace_reduce.reduce_file(TRACE)
    protos = scopes.trace_programs(TRACE.read_bytes())
    assert len(protos) == 1
    s = scopes.scopes_s(summary.op_s, protos)
    assert set(s) == {scopes.UNSCOPED}
    assert s[scopes.UNSCOPED] == pytest.approx(sum(summary.op_s.values()))
    assert scopes.from_trace(summary, TRACE) is None
    assert scopes.main([str(TRACE)]) == 1


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_without_scopes(tmp_path, monkeypatch, name):
    read = spec.metric_reader(name).read
    assert read({}) is None
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    summary = trace_reduce.TraceSummary(window_s=1.0, busy_s=0.5, devices=1,
                                        op_s={"fusion.1": 0.5})
    assert read({"trace": summary}) is None
    summary = trace_reduce.reduce_file(TRACE)
    monkeypatch.setattr(scopes, "TRACE_DIR", TRACE)
    assert read({"trace": summary}) is None
    s = {"layers/scan/fwd": 0.25, "attn/core/bwd": 0.125, "attn/core/remat": 0.0625,
         "attn/cache_write/fwd": 0.5, "optimizer/fwd": 0.03125, "unscoped": 1.0}
    want = {"scan_share": 25.0, "attention_share": 68.75, "attention_bwd_share": 12.5,
            "optimizer_share": 3.125}[name.split(".")[0]]
    monkeypatch.setattr(scopes, "of_run", lambda run: s)
    assert read({"trace": trace_reduce.TraceSummary(1.0, 1.0, 1)}) == want


def test_traced_run_reports_the_scope_shares(tiny, tmp_path, monkeypatch):
    """A traced decode run at tiny widths on the CPU, each op of its
    program given a microsecond (the CPU's trace has no device ops): the
    shares come from the program the trace carries, and sum to the busy
    share."""
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / "trace")
    keep, real = [], trace_reduce.reduce_file

    def reduce_file(path):
        summary = real(path)
        ops = executed(module_proto(keep[0].fn))
        summary.op_s = {n: 1e-6 for n in ops}
        summary.busy_s = 1e-6 * len(ops)
        summary.window_s = 4 * summary.busy_s
        summary.devices = 1
        return summary

    monkeypatch.setattr(trace_reduce, "reduce_file", reduce_file)
    v5e = run.peaks("TPU v5 lite")
    monkeypatch.setattr(run, "peaks", lambda kind: v5e)
    bench = dict(BENCH, per_layer=[
        {"name": "scan_share.decode", "unit": "%", "workloads": ["c-decode"]},
        {"name": "attention_share.decode", "unit": "%", "workloads": ["c-decode"]},
        {"name": "idle_share.decode", "unit": "%", "workloads": ["c-decode"]}])
    cfg = dict(tiny["yi"])
    cell = {"name": "c-decode", "config": cfg["name"], "traffic": "decode", "chips": 1}
    with jax.default_matmul_precision("highest"):
        res = run.run_cell(bench, cell, SEED, 0.2, 1, jax.devices(), cfg=cfg,
                           traffic=DECODE, limits=LIMITS["decode"], keep=keep)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == {"scan_share.decode", "attention_share.decode", "idle_share.decode"}
    assert m["idle_share.decode"] == pytest.approx(75.0)
    assert 0 < m["scan_share.decode"] < 25 and 0 < m["attention_share.decode"] < 25
    summary = trace_reduce.TraceSummary(1.0, 1.0, 1)
    s = scopes.from_trace(summary, tmp_path / "trace")   # no ops: nothing to split
    assert s is None


def test_table_lists_scopes_largest_first():
    rows = scopes.table({"mlp/fwd": 1.0, "layers/scan/fwd": 3.0, "unscoped": 0.5}, 10.0, 4.5)
    assert [r.split()[0] for r in rows] == ["scope/phase", "layers/scan/fwd", "mlp/fwd",
                                            "unscoped", "(busy)", "(idle)"]
    assert rows[1].split()[-1] == "30.000"


def test_watch_sees_a_held_lock_and_a_collection():
    old = sys.getswitchinterval()
    w = stall_watch.Watch()
    try:
        sys.setswitchinterval(0.5)
        w.start()
        time.sleep(0.05)
        t = time.perf_counter()
        while time.perf_counter() - t < 0.3:   # holds the interpreter lock
            pass
        gc.collect()
    finally:
        w.stop()
        sys.setswitchinterval(old)
    assert not w._thread.is_alive()
    kinds = [e[0] for e in w.events]
    assert "oversleep" in kinds and "gc2" in kinds
    over = next(e for e in w.events if e[0] == "oversleep")
    assert 0.0 <= over[1] < 0.4 and over[2] > stall_watch.OVERSLEEP_S


def test_idle_gaps_lie_in_the_window():
    summary = trace_reduce.reduce_file(TRACE)
    gaps = stall_watch.idle_gaps_at(TRACE, min_s=0.0)
    assert gaps and all(0 <= at and at + s <= summary.window_s + 1e-9 for _, at, s in gaps)
    assert sum(s for _, _, s in gaps) == pytest.approx(summary.window_s - summary.busy_s)
