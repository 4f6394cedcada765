#!/usr/bin/env python3
"""Stall witness for a traced window: what the host process did while the
device sat idle.

While the window is traced, a daemon thread naps 5 ms at a time and
records each nap that overran by more than 50 ms: for that long the
process was not scheduled, or another thread held the interpreter lock.
A ``gc.callbacks`` hook records each garbage collection.  Each event is
``[kind, start_s, seconds]``, ``start_s`` from the start of the traced
window; ``kind`` is ``oversleep`` or ``gc<generation>``.  An idle gap of
the device that meets neither is time the main thread waited in the
runtime.

    python3 chipbench/stall_watch.py --workload <cell> --seed <n> --seconds <s>

runs one cell as ``run.py --trace 1`` does, with the witness on, and prints
its result line with two more keys in ``breakdown``: ``host_events``, and
``idle_gaps_at``, each idle gap of the device over 20 ms as
``[span, start_s, seconds]`` on the same clock.  It reads no ``/proc``.
"""
from __future__ import annotations

import gc
import json
import pathlib
import sys
import threading
import time

NAP_S = 0.005
OVERSLEEP_S = 0.05
GAP_S = 0.02


class Watch:
    """The witness: ``start()`` when the window starts, ``stop()`` after;
    ``events`` then holds what it saw."""

    def __init__(self, nap_s=NAP_S, over_s=OVERSLEEP_S):
        self.nap_s, self.over_s = nap_s, over_s
        self.events, self._gc_t0, self.t0 = [], None, None
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        self.t0 = time.perf_counter()
        self._stop.clear()
        self._thread = threading.Thread(target=self._nap, name="stall-watch", daemon=True)
        self._thread.start()
        gc.callbacks.append(self._on_gc)

    def stop(self):
        gc.callbacks.remove(self._on_gc)
        self._stop.set()
        self._thread.join(timeout=1.0)

    def _nap(self):
        while not self._stop.is_set():
            a = time.perf_counter()
            time.sleep(self.nap_s)
            late = time.perf_counter() - a - self.nap_s
            if late > self.over_s:
                self.events.append(["oversleep", a - self.t0, late])

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
        elif self._gc_t0 is not None:
            self.events.append([f"gc{info['generation']}", self._gc_t0 - self.t0,
                                now - self._gc_t0])
            self._gc_t0 = None


def idle_gaps_at(path, min_s=GAP_S):
    """[span, start_s, seconds] of each idle gap of the first busy device
    inside the ``bench:window`` span of a trace, start_s from the span's
    start, and the host span that overlaps the gap most."""
    import jax

    from chipbench import trace_reduce as tr
    profile = jax.profiler.ProfileData.from_file(str(tr.find_xplane(path)))
    window, spans = None, []
    for plane in profile.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            continue
        for ev in tr._events(plane):
            if ev.name == tr.WINDOW_SPAN:
                window = (ev.start_ns, ev.end_ns)
            elif ev.name.startswith(tr.SPAN_PREFIX):
                spans.append((ev.start_ns, ev.end_ns, ev.name[len(tr.SPAN_PREFIX):]))
    if window is None:
        return []
    lo, hi = window
    for plane in profile.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        ivs = [(a, b) for _, a, b, _ in tr._self_times(tr._events(plane, tr.OPS_LINE), lo, hi)]
        if not ivs:
            continue
        edges = [lo] + [x for iv in tr._union(ivs) for x in iv] + [hi]
        return [[tr._attribute(a, b, spans), (a - lo) * 1e-9, (b - a) * 1e-9]
                for a, b in zip(edges[::2], edges[1::2]) if (b - a) * 1e-9 >= min_s]
    return []


def main(argv=None):
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    import jax

    from chipbench import run, spec
    args = run.parse(argv)
    bench = spec.benchmark(root)
    cell = spec.cell(bench, args.workload)
    try:
        devs = run.devices(cell["chips"])
    except run.NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    # the witness runs exactly while the profiler traces the window
    watch, start, stop = Watch(), jax.profiler.start_trace, jax.profiler.stop_trace

    def start_traced(*a, **k):
        start(*a, **k)
        watch.start()

    def stop_traced(*a, **k):
        watch.stop()
        stop(*a, **k)

    jax.profiler.start_trace, jax.profiler.stop_trace = start_traced, stop_traced
    try:
        result = run.run_cell(bench, cell, args.seed, args.seconds, 1, devs)
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
    result["breakdown"]["host_events"] = watch.events
    result["breakdown"]["idle_gaps_at"] = idle_gaps_at(run.TRACE_DIR)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
