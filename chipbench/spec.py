"""Finds a cell's parts by name, so that a cell, a configuration, a traffic
mix or a per-layer metric is added with new files and entries only:

- ``BENCHMARK.json`` at the root names the cell, its configuration and
  traffic, and lists the metrics;
- ``configs/<config>.json``: the model's sizes as run, with its source, the
  keys cut from it and the plain reference module (``reference/<name>.py``);
- ``traffic/<traffic>.json``: the parameters the one driver of its ``kind``
  (``drivers/<kind>.py``) reads;
- ``limits/<cell>.json``: each number the correctness check compares, with
  its limit and the readings the limit was set from;
- ``metrics/<metric>.py``, or ``metrics/<stem>.py`` for a metric named
  ``<stem>.<part>``: a reader with ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def load_json(path):
    with open(path) as f:
        return json.load(f)


def benchmark(root):
    return load_json(pathlib.Path(root) / "BENCHMARK.json")


def cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def config(name, base=HERE):
    return load_json(base / "configs" / f"{name}.json")


def traffic(name, base=HERE):
    return load_json(base / "traffic" / f"{name}.json")


def limits(name, base=HERE):
    return load_json(base / "limits" / f"{name}.json")


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg):
    """The configuration's plain reference module, ``reference/<name>.py``."""
    return importlib.import_module(f"chipbench.reference.{cfg['reference']}")


def driver(kind):
    return importlib.import_module(f"chipbench.drivers.{kind}")


def metric_reader(name, base=HERE):
    """The reader module of a per-layer metric."""
    exact = base / "metrics" / f"{name}.py"
    stem = base / "metrics" / f"{name.split('.')[0]}.py"
    path = exact if exact.exists() else stem
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r}: {exact} or {stem}")
    return _load_module(path, f"chipbench_metric_{path.stem.replace('.', '_')}")


def _applies(metric, cell_name, reported):
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def end_to_end(bench, cell_name):
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer(bench, cell_name):
    """The per-layer metrics this cell reports in a traced run."""
    reported = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"] if _applies(m, cell_name, reported)]
