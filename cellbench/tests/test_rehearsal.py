"""The ``train_lm`` runner end to end at the tiny configurations, on one CPU
device and on four virtual ones (FSDP), through everything of a run but the
harness's look for a chip; the command itself refuses a CPU.

The cells of ``data/files_alone`` are of a second architecture that is
added as files alone (its ``archs`` module, its configuration, its cell):
they go through the same runner, the same planted faults and every reader,
and no ``.py`` of ``cellbench`` names them (``conftest.FILES_ALONE_CELLS``
lists the directory)."""

import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from cellbench import run as harness
from cellbench import trace_reduce
from cellbench.runners import train_lm
from cellbench.tests.conftest import (FILES_ALONE, FILES_ALONE_CELLS,
                                      load_cell)

HERE = Path(__file__).resolve().parents[1]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
#: one CPU device: the GPT-2-shaped cell and the files-alone ones
ONE_DEVICE = ["tiny-train-1dev"] + FILES_ALONE_CELLS


def manifest_with(cell_name):
    """BENCHMARK.json with the tiny cell added the way a later PR adds one:
    new entries, no edit."""
    m = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": cell_name, "config": "tiny-gpt",
                           "traffic": cell_name, "chips": 1, "why": "test"})
    return m


def metric_files():
    return {p.stem: json.loads(p.read_text())
            for p in (HERE / "layer_metrics").glob("*.json")}


def drive(name, *, trace, tmp_path, seed=7, seconds=1.0):
    cell, config = load_cell(name)
    devices = jax.devices()[:cell["chips"]]
    outcome = train_lm.run(cell=cell, config=config, seed=seed,
                           seconds=seconds, trace=trace, devices=devices,
                           t0=harness.T0, scratch=tmp_path)
    return cell, config, devices, outcome


PEAK = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]


def test_a_second_architecture_is_there_as_files_alone():
    assert FILES_ALONE_CELLS
    for name in FILES_ALONE_CELLS:
        _, config = load_cell(name)
        assert config["model_type"] != "gpt2" and "n_embd" not in config
        for path in HERE.rglob("*.py"):
            if FILES_ALONE not in path.parents:
                text = path.read_text()
                assert name not in text, path
                assert config["model_type"] not in text, path


@pytest.mark.parametrize("name", ONE_DEVICE + ["tiny-train-fsdp4"])
def test_untraced_run_gives_the_contract_line(name, tmp_path):
    # the window is a time; ten steps of the tiny job take a fraction of a
    # second on an idle host, and a loaded one was seen to fit 5 into 1 s
    cell, config, devices, outcome = drive(name, trace=False,
                                           tmp_path=tmp_path, seconds=4.0)
    manifest = manifest_with(name)
    line = harness.result_line(outcome, manifest=manifest, cell=cell,
                               config=config, peak=PEAK, devices=devices,
                               trace=False)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    assert line["attempted"] == len(outcome["spans"]["dispatch"])
    assert set(line["metrics"]) == {m["name"] for m in manifest["end_to_end"]}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)


def traced_line(name, tmp_path, recorded):
    """The capture runs for real; a CPU trace has no device plane, so the
    reduction then reads a trace recorded on the chip (``tests/data``), as
    a traced run on the chip reads its own."""
    cell, config, devices, outcome = drive(name, trace=True,
                                           tmp_path=tmp_path)
    assert list(Path(outcome["trace_dir"]).glob(
        "plugins/profile/*/*.xplane.pb"))
    outcome["trace_dir"] = recorded
    # the CPU backend has no memory_stats(); a chip reports its peak
    outcome["counters"]["memory_peak_bytes"] = 8 << 30
    line = harness.result_line(outcome, manifest=manifest_with(name),
                               cell=cell, config=config, peak=PEAK,
                               devices=devices, trace=True)
    assert set(line) == KEYS | {"breakdown"}
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] >= line["device"]["busy_s"]
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    json.dumps(line)
    return line


def test_traced_run_reports_every_declared_per_layer_metric(
        tmp_path, scoped_trace_dir):
    """Declared for a cell: the metric files whose ``cells`` is absent or
    names it.  Those with ``cells`` name real cells only, so the rehearsed
    one reports the rest, each of which finds something to read in a trace
    that carries the kernels' names."""
    name = "tiny-train-1dev"
    line = traced_line(name, tmp_path, scoped_trace_dir)
    files = metric_files()
    declared = {k for k, spec in files.items()
                if name in spec.get("cells", [name])}
    assert set(line["metrics"]) == declared
    assert "attn_kernel_roofline" in declared and declared < set(files)
    manifest = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    assert {m["name"] for m in manifest["per_layer"]
            if "workloads" not in m} == declared


def test_traced_fsdp_run_reports_every_declared_per_layer_metric(
        tmp_path, scoped_trace_dir):
    """Four virtual devices under FSDP; the borrowed trace is of one chip
    and carries the kernels' names."""
    name = "tiny-train-fsdp4"
    line = traced_line(name, tmp_path, scoped_trace_dir)
    declared = {k for k, spec in metric_files().items()
                if name in spec.get("cells", [name])}
    assert set(line["metrics"]) == declared
    rows = dict(line["breakdown"]["device_ops"])
    assert "[flash custom calls]" in rows and "[other custom calls]" not in rows


def test_a_traced_run_whose_custom_calls_carry_no_name_ends_with_the_metric(
        tmp_path, recorded_trace_dir):
    """The older four-chip trace: its 96 custom calls carry no kernel name.
    A program that stops naming its kernels does not read as one without
    attention: the run ends, and says which metric could not be read."""
    with pytest.raises(SystemExit, match="attn_kernel_ms_per_step.*"
                       "LookupError.*none carries a kernel name"):
        traced_line("tiny-train-fsdp4", tmp_path, recorded_trace_dir)


@pytest.mark.parametrize("name", FILES_ALONE_CELLS)
def test_every_reader_takes_a_run_of_another_architecture(
        name, tmp_path, scoped_trace_dir, monkeypatch):
    """Every reader of ``layer_metrics/``, those for other cells too, called
    on a traced run of the files-alone architecture gives a number or
    ``None`` and none raises; through the harness, a metric whose ``cells``
    leaves the cell out is not called at all."""
    cell, config, devices, outcome = drive(name, trace=True,
                                           tmp_path=tmp_path)
    # where a traced run leaves its trace, for the readers that read the file
    scratch = tmp_path / "scratch"
    (scratch / "trace").mkdir(parents=True)
    (scratch / "trace" / name).symlink_to(scoped_trace_dir,
                                          target_is_directory=True)
    monkeypatch.setattr(harness, "SCRATCH", scratch)
    reds = trace_reduce.reduce_trace(
        trace_reduce.load(trace_reduce.find_xplane(scoped_trace_dir)),
        **outcome["trace_hints"])
    outcome["counters"]["memory_peak_bytes"] = 8 << 30
    reading = harness.Reading(cell, config, PEAK, outcome["counters"],
                              outcome["spans"], reds)
    called = []
    for metric, spec in metric_files().items():
        module, fn = spec["reader"].split(":")
        reader = getattr(importlib.import_module(module), fn)
        value = reader(reading)
        assert value is None or float(value) == float(value), metric

        def spy(r, metric=metric, reader=reader):
            called.append(metric)
            return reader(r)

        monkeypatch.setattr(importlib.import_module(module), fn, spy)
    got = harness.layer_metrics(reading)
    files = metric_files()
    assert called and all("cells" not in files[m] for m in called)
    assert set(got) <= set(called)
    # the architecture's own count of model FLOPs, not GPT-2's
    assert got["mfu_pct"]["value"] > 0
    # the borrowed trace holds flash kernels, the architecture's step none:
    # it counts no work for them, so the share has nothing to stand on
    assert "attn_kernel_ms_per_step" in got
    assert "attn_kernel_roofline" not in got


def test_a_reader_that_raises_ends_the_run_with_its_metrics_name(
        monkeypatch):
    from cellbench.readers import host

    def broken(r):
        raise KeyError("n_embd")

    monkeypatch.setattr(host, "mfu_pct", broken)
    reading = harness.Reading({"name": "any-cell"}, {}, PEAK, {}, {}, {})
    with pytest.raises(SystemExit, match="mfu_pct.*KeyError.*n_embd"):
        harness.layer_metrics(reading)


@pytest.mark.parametrize("name", ONE_DEVICE)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        name, tmp_path, monkeypatch, capsys):
    """The timed path broken underneath: the step computes its loss and
    hands the state back as it came."""
    real = train_lm.make_lm_train_step

    def broken(apply_fn, tx, mesh, **kw):
        step = real(apply_fn, tx, mesh, donate_state=False, **kw)
        return jax.jit(lambda state, tokens: (state, step(state, tokens)[1]))

    monkeypatch.setattr(train_lm, "make_lm_train_step", broken)
    _, _, _, outcome = drive(name, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    lines = capsys.readouterr().out.splitlines()
    assert [l for l in lines if l.startswith("[check] update_norm_gap=1 ")
            and l.endswith("OVER")]


@pytest.mark.parametrize("name", ONE_DEVICE)
def test_a_step_that_leaves_out_part_of_the_batch_is_not_correct(
        name, tmp_path, monkeypatch):
    real = train_lm.make_lm_train_step

    def broken(apply_fn, tx, mesh, **kw):
        step = real(apply_fn, tx, mesh, donate_state=False, **kw)

        def half(state, tokens):   # the second half of the rows is dropped
            n = tokens.shape[0] // 2
            return step(state, tokens.at[n:].set(tokens[:n]))

        return jax.jit(half)

    monkeypatch.setattr(train_lm, "make_lm_train_step", broken)
    _, _, _, outcome = drive(name, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False


@pytest.mark.parametrize("name", ONE_DEVICE)
def test_an_update_of_the_right_size_the_wrong_way_is_not_correct(
        name, tmp_path, monkeypatch, capsys):
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
    _, _, _, outcome = drive(name, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
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
