"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The harness wraps its measured window in a host span named ``bench:window``
and the work inside it in spans ``bench:<what>`` (data, dispatch, wait,
readback).  From the trace this takes:

- ``window_s``: the length of the ``bench:window`` span;
- ``busy_s``: per device, the union of the intervals in which an operation
  ran on it inside the window, averaged over the devices that ran any;
- ``op_s``: per operation (its HLO name), its summed device self time
  inside the window: an op that encloses others (a ``while`` loop) counts
  only the time none of them covers;
- the idle gaps of the first busy device, each attributed to the host span
  that overlaps it most.

    python3 chipbench/trace_reduce.py <trace dir or .xplane.pb>
"""
from __future__ import annotations

import collections
import json
import pathlib
import re
import sys
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int
    op_s: dict = field(default_factory=dict)
    op_count: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)   # [(seconds, span name)]
    spans_s: dict = field(default_factory=dict)

    def kernel_s(self, kernel):
        """Summed device time of every operation named after ``kernel``."""
        return sum(t for name, t in self.op_s.items() if kernel_matches(name, kernel))

    def breakdown(self, n=10):
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:n]
        return {"device_ops": [[name, t] for name, t in ops],
                "idle_gaps": [[name, t] for t, name in gaps]}


def kernel_matches(op_name, kernel):
    """An op is the kernel's where its name is the kernel's name, or that
    name with a numeric suffix (``flash_attention_fwd.3``)."""
    return op_name == kernel or re.fullmatch(re.escape(kernel) + r"[._-]?\d*", op_name)


def find_xplane(path):
    path = pathlib.Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def reduce_profile(profile) -> TraceSummary:
    """``profile``: a ``jax.profiler.ProfileData``."""
    host_spans = []
    window = None
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for ev in _events(plane):
            if ev.name == WINDOW_SPAN:
                window = (ev.start_ns, ev.end_ns)
            elif ev.name.startswith(SPAN_PREFIX):
                host_spans.append((ev.start_ns, ev.end_ns,
                                   ev.name[len(SPAN_PREFIX):]))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window

    busy, op_s, op_count, first_busy = [], collections.Counter(), collections.Counter(), None
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ivs = []
        for name, a, b, own in _self_times(_events(plane, OPS_LINE), lo, hi):
            ivs.append((a, b))
            op_s[name] += own * 1e-9
            op_count[name] += 1
        if not ivs:
            continue
        merged = _union(ivs)
        busy.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged

    spans_s = collections.Counter()
    for a, b, name in host_spans:
        a, b = _clip(a, b, lo, hi)
        if b > a:
            spans_s[name] += (b - a) * 1e-9

    gaps = []
    if first_busy:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9, _attribute(a, b, host_spans)))
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=(sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
        devices=len(busy), op_s=dict(op_s), op_count=dict(op_count),
        gaps=gaps, spans_s=dict(spans_s))


def short_name(hlo_text):
    """``%flash_attention_fwd.6 = bf16[...] custom-call(...)`` ->
    ``flash_attention_fwd.6``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def _self_times(events, lo, hi):
    """(name, start, end, self ns) of each op inside [lo, hi].  Ops nest on
    the device's op line (a ``while`` spans its body's ops); an op's self
    time leaves out the time of the ops inside it."""
    evs = []
    for ev in events:
        a, b = _clip(ev.start_ns, ev.end_ns, lo, hi)
        if b > a:
            evs.append([short_name(ev.name), a, b, b - a])
    evs.sort(key=lambda e: (e[1], -e[2]))
    stack = []
    for e in evs:
        while stack and stack[-1][2] <= e[1]:
            stack.pop()
        if stack:
            stack[-1][3] -= min(e[2], stack[-1][2]) - e[1]
        stack.append(e)
    return [tuple(e) for e in evs]


def _attribute(a, b, spans):
    """The host span that overlaps [a, b] the most, or "host" for none."""
    best, name = 0, "host"
    for s, e, n in spans:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, name = ov, n
    return name


def reduce_file(path) -> TraceSummary:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(str(find_xplane(path))))


if __name__ == "__main__":
    s = reduce_file(sys.argv[1])
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      "devices": s.devices, "spans_s": s.spans_s,
                      **s.breakdown()}, indent=1))
