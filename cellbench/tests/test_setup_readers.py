"""The five per-layer metrics of ``setup_s`` that name no cell
(``readers/setup.py``): each reads the LM step's row of the program's
process-wide ``compile_seconds()`` and finds nothing, without raising, in a
process that compiled no step and over a program from before the record."""

import importlib
import json
from pathlib import Path

import pytest

from cellbench.readers import setup
from tpudist.runtime import compilation_cache
from tpudist.telemetry import names

HERE = Path(__file__).resolve().parents[1]
#: metric -> the key of the step's row it reads
METRICS = {"step_trace_s": "trace_s", "step_lower_s": "lower_s",
           "step_compile_or_load_s": "compile_or_load_s",
           "step_cache_hits": "cache_hits",
           "step_cold_compile_s": "cold_compile_s"}
#: a warm run: the step traced, lowered and loaded from the cache
WARM = dict(trace_s=9.5, lower_s=6.25, compile_or_load_s=12.5,
            cold_compile_s=131.0, compiles=1, cache_hits=1)
OF_COMPILES = ("step_compile_or_load_s", "step_cache_hits",
               "step_cold_compile_s")


def read(metric):
    spec = json.loads((HERE / "layer_metrics" / f"{metric}.json").read_text())
    module, fn = spec["reader"].split(":")
    assert module == "cellbench.readers.setup" and fn == metric
    return getattr(importlib.import_module(module), fn)(None)


def filled(monkeypatch, **row):
    monkeypatch.setattr(
        compilation_cache, "compile_seconds",
        lambda: {names.STEP_PROGRAM: dict(row), "make_state": dict(WARM)})


@pytest.mark.parametrize("metric, key", METRICS.items())
def test_a_reader_gives_the_steps_own_figure(metric, key, monkeypatch):
    filled(monkeypatch, **WARM)
    assert read(metric) == WARM[key]


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_finds_nothing_where_no_step_was_compiled(
        metric, monkeypatch):
    monkeypatch.setattr(compilation_cache, "compile_seconds",
                        lambda: {"make_state": dict(WARM)})
    assert read(metric) is None
    # a step that was traced and lowered and never compiled (``.lower()``
    # alone): its trace and lowering are there, its compile is not
    filled(monkeypatch, **dict(WARM, compile_or_load_s=0.0,
                               cold_compile_s=0.0, compiles=0, cache_hits=0))
    assert (read(metric) is None) == (metric in OF_COMPILES)


def test_a_step_that_was_compiled_reads_no_hit_as_zero(monkeypatch):
    filled(monkeypatch, **dict(WARM, cold_compile_s=140.0,
                               compile_or_load_s=140.0, cache_hits=0))
    assert read("step_cache_hits") == 0
    assert read("step_cold_compile_s") == read("step_compile_or_load_s") == 140.0


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("missing", ["compile_seconds", "STEP_PROGRAM"])
def test_a_reader_finds_nothing_over_a_program_from_before_the_record(
        metric, missing, monkeypatch):
    """The parent commit under this PR's benchmark files: ``tpudist`` has
    neither the function nor the name."""
    filled(monkeypatch, **WARM)
    owner = compilation_cache if missing == "compile_seconds" else names
    monkeypatch.delattr(owner, missing)
    assert read(metric) is None


def test_the_five_name_no_cell():
    manifest = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in manifest["per_layer"]}
    # (wherever they stand in the list: later PRs append behind them)
    assert set(METRICS) <= set(entries)
    for metric in METRICS:
        spec = json.loads(
            (HERE / "layer_metrics" / f"{metric}.json").read_text())
        assert "cells" not in spec and "workloads" not in entries[metric]
        assert entries[metric]["moves"] == "setup_s"
        assert entries[metric]["source"] == "program_counter"
        assert entries[metric]["better"] == (
            "higher" if metric == "step_cache_hits" else "lower")
        assert "of this process" in spec["what"]


def test_the_readers_read_the_programs_own_record_of_a_compiled_step():
    """Not a fake: a program named as the step is, through the listener."""
    backend = next(e for e, n in names.XLA_DURATION_SPANS.items()
                   if n == names.XLA_BACKEND_COMPILE)
    before = setup.step_compile_or_load_s(None) or 0.0
    hits = setup.step_cache_hits(None) or 0
    compilation_cache._on_duration(backend, 2.5,
                                   fun_name=f"jit({names.STEP_PROGRAM})")
    assert setup.step_compile_or_load_s(None) == pytest.approx(before + 2.5)
    assert setup.step_cache_hits(None) == hits
