"""The harness finds a configuration, a traffic mix, a limits file and a
per-layer metric's reader added as new files, with no edit to a file that
is there: a later cell is data and small readers only."""
from __future__ import annotations

import json
import shutil

from chipbench import spec


def test_new_files_are_found(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, base, ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}

    cfg = dict(spec.config("yi9b-serve-8l"), name="yi9b-serve-2l", num_hidden_layers=2)
    (base / "configs" / "yi9b-serve-2l.json").write_text(json.dumps(cfg))
    (base / "traffic" / "prefill-4k.json").write_text(json.dumps(
        {"kind": "prefill", "batch": 4, "prompt_len": 4096, "prompts": 2,
         "check_sample": 1}))
    (base / "limits" / "yi9b-prefill-4k.json").write_text(json.dumps(
        {"logit_rel_l2": {"limit": 0.02}}))
    (base / "metrics" / "calls.prefill.py").write_text(
        "def read(run):\n    return float(run['units'])\n")

    bench = spec.benchmark(spec.HERE.parent)
    bench["workloads"].append({"name": "yi9b-prefill-4k", "config": "yi9b-serve-2l",
                               "traffic": "prefill-4k", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "calls.prefill", "unit": "calls",
                               "moves": "prefill_tokens_per_s",
                               "workloads": ["yi9b-prefill-4k"]})
    cell = spec.cell(bench, "yi9b-prefill-4k")
    assert spec.config(cell["config"], base)["num_hidden_layers"] == 2
    assert spec.traffic(cell["traffic"], base)["prompt_len"] == 4096
    assert spec.limits(cell["name"], base) == {"logit_rel_l2": {"limit": 0.02}}
    assert spec.driver(spec.traffic(cell["traffic"], base)["kind"]).Driver
    names = [m["name"] for m in spec.per_layer(bench, cell["name"])]
    assert "calls.prefill" in names
    assert spec.metric_reader("calls.prefill", base).read({"units": 3}) == 3.0
    # a metric named <stem>.<part> falls back to metrics/<stem>.py
    assert spec.metric_reader("mfu.anything", base).read({"units": 0}) is None
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_every_cell_has_its_files():
    bench = spec.benchmark(spec.HERE.parent)
    for cell in bench["workloads"]:
        cfg = spec.config(cell["config"])
        assert cfg["name"] == cell["config"]
        spec.reference(cfg)
        spec.driver(spec.traffic(cell["traffic"])["kind"])
        assert spec.limits(cell["name"])
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
