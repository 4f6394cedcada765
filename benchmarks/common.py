"""Shared benchmark setup — a thin view over repro.experiments.

Every figure/table runs (scenario, policy, seed) cells through
``repro.experiments.run_one`` and consumes the v1 artifact's ``metrics``
dict; this module only adds per-process memoization (figures share cells),
artifact I/O, and CSV row printing.
"""
from __future__ import annotations

import json
import pathlib
import sys

_SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:  # allow `python -m benchmarks.run` without install
    sys.path.insert(0, _SRC)

from repro.configs import ARCHS  # noqa: E402
from repro.core import CommModel  # noqa: E402
from repro.experiments import (  # noqa: E402
    SimOverrides,
    get_scenario,
    run_one_timed,
)

SCHEDULERS = ["gandiva", "tiresias", "dally-manual", "dally-nowait",
              "dally-fullyconsolidated", "dally"]
RACKS = (2, 4, 8, 16)
SEED = 0

ART = pathlib.Path(__file__).parent / "artifacts"

TRACE_SCENARIO = {"batch": "paper-batch", "poisson": "paper-poisson"}


def archs():
    return list(ARCHS.values())


def comm_model() -> CommModel:
    return CommModel.from_configs(archs())


_SIM_CACHE = {}


def run_sim(policy: str, n_racks: int, *, trace="batch", n_jobs=None,
            seed=SEED, comm=None):
    """One simulation cell -> the artifact's metrics dict (+ wall_s)."""
    key = (policy, n_racks, trace, n_jobs, seed, comm is None)
    if comm is None and key in _SIM_CACHE:
        return _SIM_CACHE[key]
    art = run_one_timed(get_scenario(TRACE_SCENARIO[trace]), policy=policy,
                        seed=seed,
                        overrides=SimOverrides(n_racks=n_racks,
                                               n_jobs=n_jobs, comm=comm))
    res = art["metrics"]
    res["wall_s"] = art["wall_s"]
    if comm is None:
        _SIM_CACHE[key] = res
    return res


def save(name: str, data):
    ART.mkdir(parents=True, exist_ok=True)
    (ART / f"{name}.json").write_text(json.dumps(data, indent=1))


def row(name: str, value, derived=""):
    print(f"{name},{value},{derived}", flush=True)
