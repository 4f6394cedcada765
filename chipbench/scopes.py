#!/usr/bin/env python3
"""Device time by layer: each op of the timed program mapped to the layer
scope it was traced in, and a trace's per-op self time summed by scope.

The program names its layers with ``jax.named_scope`` (``repro/obs.py``).
The compiled program keeps those names as each instruction's ``op_name``,
and a TPU profiler trace carries the optimized HLO module of every program
that ran (the ``/host:metadata`` plane's ``Hlo Proto``), whose instructions
name the trace's op events.  So no reading beyond the trace is needed:

- An instruction's scope is the innermost layer name on its ``op_name``.
  Ops of ``layers`` under no block scope are the layer scan's own slicing
  of stacked params and caches and its stacking of outputs: ``layers/scan``.
  Its phase is ``bwd`` under a jnp backward rule's scope (its recompute
  included), else ``remat`` under ``rematted_computation``, ``bwd`` under
  ``transpose(``, and ``fwd`` otherwise.
- An instruction with no metadata (XLA's own copies carry none) takes the
  scope of its first operand that has one, looking through copy, bitcast,
  get-tuple-element and tuple; failing that, of its first user that has one,
  looking through the same (a copy into a loop's state takes the loop's);
  failing that, of the instruction that runs its computation (the
  ``while`` of a loop body).
- What is left is ``unscoped``; a trace op that is no instruction of the
  program is ``other``.

``scopes_s`` maps ``<scope>/<phase>`` (``attn/core/bwd``), ``unscoped`` and
``other`` to seconds of device self time (``trace_reduce.py``'s ``op_s``).
A program that names no layers (one from before ``repro/obs.py``) has no
``scopes_s``: every reader of it then reports nothing.

    python3 chipbench/scopes.py <trace dir or .xplane.pb>

prints the scope table of a saved trace (``chipbench/out/trace`` after a
``--trace 1`` run), with no chip.
"""
from __future__ import annotations

import pathlib
import re
import sys

# the layer names of ``repro/obs.py``; the benchmark keeps its own copy, so
# that it reads a program from before them without importing anything new
TAXONOMY = ("embed", "layers", "attn/qkv", "attn/cache_write", "attn/core",
            "attn/out", "mlp", "moe", "rwkv/time_mix", "rwkv/channel_mix",
            "rglru", "head", "loss", "optimizer")
# the jnp backward rules' own scopes, named inside their block's scope
INNER = {"flash_attention_bwd_chunked_jnp": "attn/core",
         "rwkv6_wkv_bwd_ref_jnp": "rwkv/time_mix",
         "rglru_scan_bwd_ref_jnp": "rglru"}
SCAN, UNSCOPED, OTHER = "layers/scan", "unscoped", "other"
LOOK_THROUGH = frozenset({"copy", "bitcast", "get-tuple-element", "tuple"})
TRACE_DIR = pathlib.Path(__file__).resolve().parent / "out" / "trace"  # run.py's
HLO_PROTO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"

_names = sorted((*TAXONOMY, *INNER), key=len, reverse=True)
# a name is a whole path component, or wrapped by a transform
# (``transpose(jvp(layers))``), never a jitted function's name (``jit(loss)``)
_SCOPE = re.compile(r"(?:^|/|(?<!jit)\()(" + "|".join(map(re.escape, _names))
                    + r")(?=$|[/)])")


def scope_of(op_name):
    """(scope, phase) named by an ``op_name``, or None where it names no
    layer (a backward rule's scope alone, as in a program from before the
    layer scopes, names none)."""
    found = _SCOPE.findall(op_name)
    if not any(n in TAXONOMY for n in found):
        return None
    scope = INNER.get(found[-1], found[-1])
    if scope == "layers":
        scope = SCAN
    if any(n in INNER for n in found):
        return scope, "bwd"   # the rule's own recompute is backward work
    if "rematted_computation" in op_name:
        return scope, "remat"
    return scope, "bwd" if "transpose(" in op_name else "fwd"


# --- protobuf wire format: just enough of XSpace and HloModuleProto -------

def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one message: an int, or a memoryview for a
    length-delimited field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not understood")
        yield key >> 3, v


def _ints(v):
    """A repeated integer field's value: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _text(v):
    return bytes(v).decode()


def trace_programs(xspace):
    """The serialized HloModuleProtos an XSpace's metadata plane carries."""
    out = []
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if not any(g == 2 and _text(v) == METADATA_PLANE for g, v in fields):
            continue
        stat_ids = set()
        for g, v in fields:
            if g == 5:       # stat_metadata: map<int64, XStatMetadata>
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                if _text(meta.get(2, b"")) == HLO_PROTO_STAT:
                    stat_ids.add(entry.get(1, 0))
        for g, v in fields:
            if g != 4:       # event_metadata: map<int64, XEventMetadata>
                continue
            for h, stat in _fields(dict(_fields(v)).get(2, b"")):
                s = dict(_fields(stat)) if h == 5 else {}
                if s.get(1, 0) in stat_ids and 6 in s:
                    # HloProto: field 1 is the HloModuleProto
                    out.extend(bytes(m) for k, m in _fields(s[6]) if k == 1)
    return out


def parse_module(proto):
    """{id: (name, opcode, op_name, operand ids, called computation ids)}
    and {computation id: [instruction ids]} of a serialized HloModuleProto."""
    instrs, comps = {}, {}
    for f, comp in _fields(memoryview(proto)):
        if f != 3:
            continue
        cid, ids = 0, []
        for g, v in _fields(comp):
            if g == 5:
                cid = v
            elif g == 2:
                name = opcode = op_name = ""
                iid, operands, called = 0, [], []
                for h, w in _fields(v):
                    if h == 1:
                        name = _text(w)
                    elif h == 2:
                        opcode = _text(w)
                    elif h == 7:
                        op_name = _text(dict(_fields(w)).get(2, b""))
                    elif h == 35:
                        iid = w
                    elif h == 36:
                        operands += _ints(w)
                    elif h == 38:
                        called += _ints(w)
                instrs[iid] = (name, opcode, op_name, operands, called)
                ids.append(iid)
        comps[cid] = ids
    return instrs, comps


def resolve(proto):
    """{instruction name: (scope, phase) or None} of a serialized module."""
    instrs, comps = parse_module(proto)
    caller = {i: iid for iid, ins in instrs.items() for c in ins[4]
              for i in comps.get(c, ())}
    users = {}
    for iid, ins in instrs.items():
        for o in ins[3]:
            users.setdefault(o, []).append(iid)
    memo = {}

    def through(iid, nxt):
        """The scope an operand (or user) hands on: its own, or, for a
        look-through op without metadata, that of its first operand (or
        user, as ``nxt`` gives them) that has one."""
        _, opcode, op_name, _, _ = instrs[iid]
        if op_name:
            return scope_of(op_name)
        if opcode in LOOK_THROUGH:
            return first(nxt(iid), nxt)
        return None

    def first(ids, nxt):
        return next((s for i in ids if i in instrs
                     and (s := through(i, nxt)) is not None), None)

    def of(iid):
        if iid not in memo:
            _, _, op_name, operands, _ = instrs[iid]
            if op_name:
                memo[iid] = scope_of(op_name)
            else:
                memo[iid] = (first(operands, lambda i: instrs[i][3])
                             or first(users.get(iid, ()), lambda i: users.get(i, ())))
                if memo[iid] is None and iid in caller:
                    memo[iid] = of(caller[iid])
        return memo[iid]

    return {ins[0]: of(iid) for iid, ins in instrs.items()}


def scopes_s(op_s, protos):
    """Seconds of device self time per ``<scope>/<phase>``, ``unscoped`` and
    ``other``, of the module among ``protos`` that covers most of ``op_s``."""
    maps = [resolve(p) for p in protos]
    best = max(maps, key=lambda m: sum(t for n, t in op_s.items() if n in m),
               default={})
    out = {}
    for name, t in op_s.items():
        if name not in best:
            key = OTHER
        elif best[name] is None:
            key = UNSCOPED
        else:
            key = "/".join(best[name])
        out[key] = out.get(key, 0.0) + t
    return out


def from_trace(summary, path):
    """``scopes_s`` of a reduced trace (``trace_reduce.TraceSummary``) from
    the programs in its file, or None where the trace carries no program or
    the program names no layers."""
    from chipbench import trace_reduce
    try:
        xplane = trace_reduce.find_xplane(path)
    except FileNotFoundError:
        return None
    protos = trace_programs(xplane.read_bytes())
    if not protos:
        return None
    out = scopes_s(summary.op_s, protos)
    if all(k in (UNSCOPED, OTHER) for k in out):
        return None
    return out


_last = []   # [(summary, scopes_s)] of the newest traced run


def of_run(run):
    """``scopes_s`` of a traced run, from the trace ``run.py`` wrote; None
    for a run without a trace or a program that names no layers."""
    summary = run.get("trace")
    if summary is None:
        return None
    if not _last or _last[0][0] is not summary:
        _last[:] = [(summary, from_trace(summary, TRACE_DIR))]
    return _last[0][1]


def share(run, scopes, phase=None):
    """Percent of the traced window the device spent in ``scopes`` (in
    ``phase`` only, where given), or None where the run has no scopes."""
    s = of_run(run)
    window = getattr(run.get("trace"), "window_s", 0)
    if s is None or window <= 0:
        return None
    t = 0.0
    for key, sec in s.items():
        scope, _, ph = key.rpartition("/")
        if scope in scopes and (phase is None or ph == phase):
            t += sec
    return 100.0 * t / window


def table(s, window_s, busy_s):
    """Lines of a scope table, largest first."""
    rows = [f"{'scope/phase':<28} {'seconds':>12} {'% window':>9}"]
    for key, t in sorted(s.items(), key=lambda kv: -kv[1]):
        rows.append(f"{key:<28} {t:12.6f} {100 * t / window_s:9.3f}")
    rows.append(f"{'(busy)':<28} {busy_s:12.6f} {100 * busy_s / window_s:9.3f}")
    rows.append(f"{'(idle)':<28} {window_s - busy_s:12.6f} "
                f"{100 * (1 - busy_s / window_s):9.3f}")
    return rows


def main(argv):
    if len(argv) != 1:
        print(__doc__.strip().splitlines()[-4], file=sys.stderr)
        return 2
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    from chipbench import trace_reduce
    summary = trace_reduce.reduce_file(argv[0])
    s = from_trace(summary, argv[0])
    if s is None:
        print("the trace carries no program that names its layers", file=sys.stderr)
        return 1
    print("\n".join(table(s, summary.window_s, summary.busy_s)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
