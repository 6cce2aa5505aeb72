"""BENCHMARK.json against the data files it names."""

import importlib
import json
import re
from pathlib import Path

import pytest

from cellbench import archs

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        cell = json.loads((HERE / "workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["why"] == w["why"] and len(w["why"]) <= 200
        assert (REPO / configs[w["config"]]["file"]).is_file()
        assert (HERE / "runners" / f"{cell['runner']}.py").is_file()
        assert (w["config"], w["traffic"]) not in [
            (o["config"], o["traffic"]) for o in manifest["workloads"]
            if o is not w]
    for c in manifest["configs"]:
        data = json.loads((REPO / c["file"]).read_text())
        assert data["source"] == c["source"] and len(c["source"]) <= 200
        assert data["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
        # its architecture is the one file its own model_type names
        assert (HERE / "archs" / f"{data['model_type']}.py").is_file()
        arch = archs.load(data)
        for fn in ("dims", "weight_shapes", "init_weights", "leaf_names",
                   "loss_and_grads", "build_module", "program_tree",
                   "named_leaves", "train_flops_per_token", "kernel_work"):
            assert callable(getattr(arch, fn)), (data["model_type"], fn)
        assert {"vocab", "seq", "layers"} <= set(arch.dims(data))
        assert set(arch.STACKED) <= set(arch.weight_shapes(data))


def test_names_and_units_hold_only_permitted_characters(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = ([m["name"] for m in metrics]
             + [w["name"] for w in manifest["workloads"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [c["name"] for c in manifest["configs"]])
    for n in names:
        assert NAME.match(n), n
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for p in HERE.rglob("*"):
        if "__pycache__" not in p.parts:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", p.name), p


def test_bounds_and_the_four_chip_share(manifest):
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert 1 <= manifest["run_seconds"] <= 51


def test_per_layer_metrics_match_their_files_and_readers(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in manifest["end_to_end"]}
    files = {p.stem: json.loads(p.read_text())
             for p in (HERE / "layer_metrics").glob("*.json")}
    # a metric file may wait for its cells: the files of the FSDP
    # collectives name a four-chip cell that BENCHMARK.json does not hold yet
    waiting = set(files) - {m["name"] for m in manifest["per_layer"]}
    assert {m["name"] for m in manifest["per_layer"]} <= set(files)
    for name in waiting:
        assert files[name]["cells"] and not set(files[name]["cells"]) & cells
    layers = set()
    for m in manifest["per_layer"]:
        spec = files[m["name"]]
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec.get("cells") == m.get("workloads")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # the one end-to-end metric it moves is reported by each of its cells
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]]
        module, fn = spec["reader"].split(":")
        assert callable(getattr(importlib.import_module(module), fn))
        layers.add(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert all(len(x) <= 200 and "\n" not in x for x in layers)


def test_run_py_names_no_cell_config_runner_or_metric(manifest):
    text = (HERE / "run.py").read_text()
    names = ([m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
             + [w["name"] for w in manifest["workloads"]]
             + [c["name"] for c in manifest["configs"]]
             + [p.stem for p in (HERE / "runners").glob("*.py")
                if p.stem != "__init__"])
    assert [n for n in names if n in text] == []


def test_peaks_name_their_source():
    peaks = json.loads((HERE / "peaks.json").read_text())
    for kind, row in peaks.items():
        assert row["source"] and row["bf16_flops_per_s"] > 0, kind
