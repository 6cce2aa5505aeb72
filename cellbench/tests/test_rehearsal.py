"""The ``train_lm`` runner end to end at the tiny configuration, on one CPU
device and on four virtual ones (FSDP), through everything of a run but the
harness's look for a chip; the command itself refuses a CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from cellbench import run as harness
from cellbench.runners import train_lm

HERE = Path(__file__).resolve().parents[1]
DATA = HERE / "tests" / "data"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def load(name):
    cell = json.loads((DATA / f"{name}.json").read_text())
    cell["name"] = name
    return cell, json.loads((DATA / "tiny-gpt.json").read_text())


def manifest_with(cell_name):
    """BENCHMARK.json with the tiny cell added the way a later PR adds one:
    new entries, no edit."""
    m = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": cell_name, "config": "tiny-gpt",
                           "traffic": cell_name, "chips": 1, "why": "test"})
    return m


def drive(name, *, trace, tmp_path, seed=7, seconds=1.0):
    cell, config = load(name)
    devices = jax.devices()[:cell["chips"]]
    outcome = train_lm.run(cell=cell, config=config, seed=seed,
                           seconds=seconds, trace=trace, devices=devices,
                           t0=harness.T0, scratch=tmp_path)
    return cell, config, devices, outcome


PEAK = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]


@pytest.mark.parametrize("name", ["tiny-train-1dev", "tiny-train-fsdp4"])
def test_untraced_run_gives_the_contract_line(name, tmp_path):
    cell, config, devices, outcome = drive(name, trace=False,
                                           tmp_path=tmp_path)
    manifest = manifest_with(name)
    line = harness.result_line(outcome, manifest=manifest, cell=cell,
                               config=config, peak=PEAK, devices=devices,
                               trace=False)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert set(line["metrics"]) == {m["name"] for m in manifest["end_to_end"]}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)


def test_traced_run_reports_every_declared_per_layer_metric(
        tmp_path, recorded_trace_dir):
    """The capture runs for real on four virtual devices; a CPU trace has
    no device plane, so the reduction then reads the trace recorded on the
    chip (``tests/data``), as a traced run on the chip reads its own."""
    name = "tiny-train-fsdp4"
    cell, config, devices, outcome = drive(name, trace=True,
                                           tmp_path=tmp_path)
    assert list(Path(outcome["trace_dir"]).glob(
        "plugins/profile/*/*.xplane.pb"))
    outcome["trace_dir"] = recorded_trace_dir
    # the CPU backend has no memory_stats(); a chip reports its peak
    outcome["counters"]["memory_peak_bytes"] = 8 << 30
    manifest = manifest_with(name)
    line = harness.result_line(outcome, manifest=manifest, cell=cell,
                               config=config, peak=PEAK, devices=devices,
                               trace=True)
    assert set(line) == KEYS | {"breakdown"}
    declared = {p.stem for p in (HERE / "layer_metrics").glob("*.json")}
    assert set(line["metrics"]) == declared
    assert {m["name"] for m in manifest["per_layer"]} <= declared
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] >= line["device"]["busy_s"]
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    json.dumps(line)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch, capsys):
    """The timed path broken underneath: the step computes its loss and
    hands the state back as it came."""
    real = train_lm.make_lm_train_step

    def broken(apply_fn, tx, mesh, **kw):
        step = real(apply_fn, tx, mesh, donate_state=False, **kw)
        return jax.jit(lambda state, tokens: (state, step(state, tokens)[1]))

    monkeypatch.setattr(train_lm, "make_lm_train_step", broken)
    _, _, _, outcome = drive("tiny-train-1dev", trace=False,
                             tmp_path=tmp_path, seconds=0.3)
    assert outcome["correct"] is False
    lines = capsys.readouterr().out.splitlines()
    assert [l for l in lines if l.startswith("[check] update_norm_gap=1 ")
            and l.endswith("OVER")]


def test_a_step_that_leaves_out_part_of_the_batch_is_not_correct(
        tmp_path, monkeypatch):
    real = train_lm.make_lm_train_step

    def broken(apply_fn, tx, mesh, **kw):
        step = real(apply_fn, tx, mesh, donate_state=False, **kw)

        def half(state, tokens):   # the second half of the rows is dropped
            n = tokens.shape[0] // 2
            return step(state, tokens.at[n:].set(tokens[:n]))

        return jax.jit(half)

    monkeypatch.setattr(train_lm, "make_lm_train_step", broken)
    _, _, _, outcome = drive("tiny-train-1dev", trace=False,
                             tmp_path=tmp_path, seconds=0.3)
    assert outcome["correct"] is False


def test_an_update_of_the_right_size_the_wrong_way_is_not_correct(
        tmp_path, monkeypatch, capsys):
    """Every step moves the parameters by Adam's update with its sign
    flipped: whatever the norms do, the direction reads about 2."""
    real = train_lm.make_lm_train_step

    def broken(apply_fn, tx, mesh, **kw):
        step = real(apply_fn, tx, mesh, donate_state=False, **kw)

        def flipped(state, tokens):
            new, loss = step(state, tokens)
            params = jax.tree.map(lambda old, p: 2.0 * old - p,
                                  state.params, new.params)
            return type(new)(params=params, opt_state=new.opt_state), loss

        return jax.jit(flipped)

    monkeypatch.setattr(train_lm, "make_lm_train_step", broken)
    _, _, _, outcome = drive("tiny-train-1dev", trace=False,
                             tmp_path=tmp_path, seconds=0.3)
    assert outcome["correct"] is False
    out = capsys.readouterr().out
    over = [l for l in out.splitlines() if l.endswith("OVER")]
    assert any(l.startswith("[check] update_dir_gap=") for l in over)


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    manifest = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    cell = manifest["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=HERE.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "no TPU" in done.stderr
    assert not done.stdout.strip().endswith("}")
