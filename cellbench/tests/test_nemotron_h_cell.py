"""The ``nemotron_h`` architecture (``archs/nemotron_h.py``: layers of one
sublayer, Mamba-2 mixers, sigmoid-scored relu2 experts in a latent space
beside a whole shared expert, plain grouped-query attention; one of eight
head shares and a run of the experts held) through the ``train_lm`` runner
end to end on one CPU device, at the tiny configuration
``data/tiny-nemotron-h.json``, added as the real one is (a configuration
file and a cell file; the module is found by the configuration's
``model_type``): the contract line, the float32 reference deciding
``correct``, the three planted faults of ``test_hybrid_cell.py`` and three
of this architecture's own (the carried state zeroed at every chunk
boundary, the choice bias dropped, the scale dropped), the fp8 control
failing the cell's limits, the new readers on a trace without their scopes
and on hand-made scoped events, and the counts the yardstick keeps for the
real cell."""

import dataclasses
import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import archs, checks, flops, reference
from cellbench import run as harness
from cellbench import trace_reduce
from cellbench.runners import train_lm
from cellbench.tests.conftest import load_cell
from cellbench.tests.test_hybrid_cell import (break_step, flipped,
                                              half_batch, unchanged)
from cellbench.tests.test_rehearsal import KEYS, PEAK, drive, manifest_with

HERE = Path(__file__).resolve().parents[1]
CELL = "tiny-nemotron-h-train-1dev"
REAL_CELL = "nemotron3super-train-tp8ep64share-8k"
NEW_METRICS = ("ssm_mixer_ms_per_step", "ssd_scan_ms_per_step",
               "ssd_scan_roofline", "latent_moe_ms_per_step",
               "latent_moe_dispatch_ms_per_step",
               "latent_experts_ms_per_step", "latent_experts_roofline",
               "shared_expert_ms_per_step", "shared_expert_roofline")


def test_the_tiny_cell_is_of_the_real_cells_architecture():
    _, tiny = load_cell(CELL)
    _, real = harness.load_cell(REAL_CELL)
    assert tiny["model_type"] == real["model_type"] == "nemotron_h"
    arch = archs.load(tiny)
    assert arch is archs.load(real)
    t, r = arch.dims(tiny), arch.dims(real)
    assert t["kinds"] == r["kinds"] == tuple("MEMEMEMEM*E")
    # the widths in ratio, an eighth of the heads and one group held
    assert (r["mp"], r["mn"], r["dh"], r["chunk"]) == (64, 128, 128, 128)
    assert (t["mp"] * 8, t["mn"] * 8, t["dh"] * 8) == (64, 128, 128)
    for m in (t, r):
        assert (8 * m["mh"], 8 * m["mg"], 8 * m["heads"]) == (
            m["mh_all"], m["mg_all"], m["heads_all"])
        assert m["mg"] == m["kv"] == 1 and m["first"] == 0
        assert m["experts"] == 64 * m["held"] or m is t
    # the real cell took the issue's fallback (the router's weight held
    # fixed); the tiny one trains it, so both arms are driven
    assert (t["router_trained"], r["router_trained"]) == (True, False)


def test_untraced_run_gives_the_contract_line(tmp_path):
    cell, config, devices, outcome = drive(CELL, trace=False,
                                           tmp_path=tmp_path, seconds=2.0)
    line = harness.result_line(outcome, manifest=manifest_with(CELL),
                               cell=cell, config=config, peak=PEAK,
                               devices=devices, trace=False)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    json.dumps(line)


def overs(capsys) -> list:
    return [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("[check] ") and l.endswith("OVER")]


@pytest.mark.parametrize("fault, over", [
    (unchanged, "update_norm_gap"), (half_batch, None),
    (flipped, "update_dir_gap")], ids=["unchanged", "half_batch", "flipped"])
def test_a_planted_fault_of_the_step_is_not_correct(
        fault, over, tmp_path, monkeypatch, capsys):
    break_step(monkeypatch, fault)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    lines = overs(capsys)
    assert lines
    if over:
        assert any(l.startswith(f"[check] {over}=") for l in lines)


def test_a_state_zeroed_at_every_chunk_boundary_is_not_correct(
        tmp_path, monkeypatch, capsys):
    """The chunked scan forgets what it carried from chunk to chunk (every
    ``chunk_size`` positions start from a zero state).  Each chunk alone is
    still right; the reference, a position at a time, tells."""
    from tpudist.models import hybrid

    real = hybrid.ssd_scan

    def forgets(x, dt, a_log, b, c, d, *, chunk):
        cut = lambda t: t.reshape(-1, chunk, *t.shape[2:])
        return real(cut(x), cut(dt), a_log, cut(b), cut(c), d,
                    chunk=chunk).reshape(x.shape)

    monkeypatch.setattr(hybrid, "ssd_scan", forgets)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    assert overs(capsys)


def with_sizes(monkeypatch, **changed):
    from tpudist.models import hybrid

    real = hybrid.HybridLM

    def other(*args, sizes, **kw):
        return real(*args, sizes=dataclasses.replace(sizes, **changed), **kw)

    monkeypatch.setattr(hybrid, "HybridLM", other)


def test_a_dropped_scale_is_not_correct(tmp_path, monkeypatch, capsys):
    """``routed_scaling_factor`` ignored: the picks' weights sum to 1 and
    not to the scale.  The held experts' gradients shrink by that factor."""
    with_sizes(monkeypatch, routed_scale=1.0)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    assert any(l.startswith("[check] grad_norm_gap=") for l in overs(capsys))


def test_a_dropped_choice_bias_is_not_correct(tmp_path, monkeypatch, capsys):
    """The picks taken by the scores alone, without the choice bias: other
    tokens reach the held experts."""
    from tpudist.parallel import moe

    real = moe.route

    def unbiased(logits, *, choice_bias=None, **kw):
        return real(logits, choice_bias=None, **kw)

    monkeypatch.setattr(moe, "route", unbiased)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    assert overs(capsys)


def test_the_references_own_zeroed_state_differs_from_the_carried_one():
    _, config = load_cell(CELL)
    arch = archs.load(config)
    weights = arch.init_weights(config, reference.split_seed(3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (1, 128), dtype=np.int32))
    carried, _ = arch.loss_and_grads(config, weights, tokens)
    zeroed, _ = arch.loss_and_grads(config, weights, tokens, carry=False)
    assert abs(float(carried) - float(zeroed)) > 1e-5


def test_the_fp8_control_fails_the_cells_limits():
    cell, config = load_cell(CELL)
    job = train_lm.Job(cell, config, jax.devices()[:1])
    rng = np.random.default_rng(5)
    batches = [rng.integers(0, 256, (2, 128), dtype=np.int32)
               for _ in range(cell["check"]["steps"])]
    ref = job.reference_readings(5, batches)
    low = job.reference_readings(5, batches, mode=reference.CONTROL)
    within, lines = checks.judge(checks.train_gaps(low, ref),
                                 cell["check"]["limits"])
    assert not within
    assert [l for l in lines if l.startswith("[check] grad_dir_gap=")
            and l.endswith("OVER")]


def new_metric_files() -> dict:
    return {p.stem: json.loads(p.read_text())
            for p in (HERE / "layer_metrics").glob("*.json")
            if REAL_CELL in json.loads(p.read_text()).get("cells", [])}


def test_the_new_metrics_are_the_real_cells_alone():
    new = new_metric_files()
    assert set(new) == set(NEW_METRICS)
    for spec in new.values():
        assert spec["cells"] == [REAL_CELL]
        assert spec["source"] == "device_trace"
        assert spec["moves"] == "tokens_per_s_per_chip"
        # a reader a metric: test_rehearsal spies on a metric by the name
        # of its reader, so two files may not share one
        assert spec["reader"] == f"cellbench.readers.nemotron_h:{spec['name']}"
    manifest = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    assert [m["name"] for m in manifest["per_layer"]][-9:] == list(
        NEW_METRICS)
    assert manifest["workloads"][-1]["name"] == REAL_CELL
    assert manifest["workloads"][-1]["chips"] == 1


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_trace_without_its_scopes(
        metric, tmp_path, scoped_trace_dir, monkeypatch):
    """The borrowed trace is of the GPT-2 cell: nothing under ``ssm`` or
    ``moe``, no grouped product.  Every new reader returns ``None`` and
    none raises."""
    cell, config = load_cell(CELL)
    scratch = tmp_path / "scratch"
    (scratch / "trace").mkdir(parents=True)
    (scratch / "trace" / CELL).symlink_to(scoped_trace_dir,
                                          target_is_directory=True)
    monkeypatch.setattr(harness, "SCRATCH", scratch)
    reds = trace_reduce.reduce_trace(
        trace_reduce.load(trace_reduce.find_xplane(scoped_trace_dir)),
        vocab=50257)
    reading = harness.Reading(cell, config, PEAK,
                              {"per_chip_batch": 2, "seq_len": 128}, {},
                              reds)
    module, fn = new_metric_files()[metric]["reader"].split(":")
    assert getattr(importlib.import_module(module), fn)(reading) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_program_without_its_scopes(
        metric, monkeypatch):
    """On the parent's program (``names`` without ``SSM`` /
    ``LATENT_PROJ``) the readers return ``None`` before looking at any
    trace."""
    import types

    from cellbench.readers import hybrid, nemotron_h

    old = types.SimpleNamespace(**{
        k: v for k, v in vars(hybrid.names).items()
        if k not in ("SSM", "SSD_SCAN", "LATENT_PROJ")})
    monkeypatch.setattr(hybrid, "names", old)
    monkeypatch.setattr(hybrid, "_chips", lambda r: 1 / 0)
    cell, config = load_cell(CELL)
    reading = harness.Reading(cell, config, PEAK,
                              {"per_chip_batch": 2, "seq_len": 128}, {}, {})
    assert getattr(nemotron_h, metric)(reading) is None


def test_the_readers_pick_their_ops_from_scoped_events(monkeypatch):
    from cellbench.readers import hybrid, nemotron_h, scopes
    from cellbench.trace_reduce import Event

    def op(name, scope, dur, kind="kLoop"):
        e = Event(f"%{name} = f32[8]{{0}} fusion(%x), kind={kind}", 0.0, dur)
        return scopes.Op(e, scope, None, "fwd")

    base = "jit(step)/jvp(HybridLM)/layer_0/"
    moe = "jit(step)/jvp(HybridLM)/layer_1/experts/moe/"
    back = "jit(step)/transpose(jvp(HybridLM))/checkpoint/layer_1/experts/moe/"
    ops = [
        op("fusion.1", base + "ssm/ssm/ssd_scan/exp", 2e6),
        op("fusion.2", base + "ssm/ssm/in_proj/dot_general", 4e6, "kOutput"),
        op("fusion.3", base + "ssm/mixer_norm/mul", 1e6),
        op("fusion.4", moe + "top_k", 8e6),
        op("fusion.5", moe + "latent_proj/latent_down/dot_general", 16e6,
           "kOutput"),
        op("fusion.6", moe + "shared_expert/dot_general", 32e6, "kOutput"),
        op("fusion.7", back + "shared_expert/mul", 64e6),
        op("ragged-dot.3", "", 128e6),
        op("fusion.8", back + "moe_combine/gather", 256e6),
        # another decoder's attention: under none of these scopes
        op("fusion.9", "jit(step)/jvp(HybridLM)/layer_9/attn/attn/o_proj/"
           "dot_general", 512e6, "kOutput"),
    ]
    chips = lambda r: {0: scopes.ChipOps(2, 2e9, ops)}
    monkeypatch.setattr(hybrid, "_chips", chips)
    monkeypatch.setattr(scopes, "_chips", chips)
    _, config = harness.load_cell(REAL_CELL)
    r = harness.Reading({"name": REAL_CELL}, config, PEAK,
                        {"per_chip_batch": 1, "seq_len": 8192}, {}, {})
    assert nemotron_h.ssm_mixer_ms_per_step(r) == (2 + 4 + 1) / 2
    assert nemotron_h.ssd_scan_ms_per_step(r) == 1.0
    assert nemotron_h.latent_moe_ms_per_step(r) == (
        8 + 16 + 32 + 64 + 128 + 256) / 2
    assert nemotron_h.latent_moe_dispatch_ms_per_step(r) == (8 + 256) / 2
    assert nemotron_h.latent_experts_ms_per_step(r) == 64.0
    assert nemotron_h.shared_expert_ms_per_step(r) == (32 + 64) / 2
    arch = archs.load(config)
    for fn, reader, ms, bound in (
            ("ssd_work", nemotron_h.ssd_scan_roofline, 1.0, "memory"),
            ("expert_work", nemotron_h.latent_experts_roofline, 64.0,
             "memory"),
            ("shared_expert_work", nemotron_h.shared_expert_roofline, 48.0,
             "compute")):
        least, which = flops.roofline_seconds(
            *getattr(arch, fn)(config, 1, 8192), PEAK)
        assert which == bound, fn
        assert reader(r) == pytest.approx(100 * least * 1e3 / ms), fn


def test_the_yardsticks_counts_of_the_real_configuration():
    _, config = harness.load_cell(REAL_CELL)
    arch = archs.load(config)
    shapes = arch.weight_shapes(config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == config[
        "as_run"]["parameters"] == 700_862_960
    f = arch.forward_flops_per_token(config, 8192)
    assert f["mamba_matmuls"] == 2 * 4096 * 2320 + 2 * 1024 * 4096
    assert f["ssd"] == 3 * 2 * 16 * 64 * 128
    assert f["router"] == 2 * 4096 * 512
    assert f["latent_proj"] == 2 * 2 * 4096 * 1024
    assert f["held_experts"] == 2 * 2 * 1024 * 2688 * 22 * 8 / 512
    assert f["shared_expert"] == 2 * 2 * 4096 * 5376
    assert f["head"] == 2 * 4096 * 16384
    assert arch.train_flops_per_token(config, 8192) == pytest.approx(
        2.5745e9, rel=1e-4)
    # 4 query heads on one key/value head of 128: k, v, dk, dv a quarter
    # as wide as q, o, do, dq
    work = arch.kernel_work(config, 1, 8192)
    whole = flops.flash_kernel_work(batch=1, seq=8192, d_model=512,
                                    n_layers=1)
    tensor = 8192 * 512 * 2
    for kernel in flops.FLASH_KERNELS:
        assert work[kernel][0] == whole[kernel][0]
    assert work[flops.FLASH_FWD][1] == 2.5 * tensor
    # q, o, do read and dq written; k, v read twice, dk and dv written
    assert sum(b for _, b in work.values()) == (6 + 6 / 4) * tensor
    ops, bytes_ = arch.ssd_work(config, 1, 8192)
    assert ops == 3 * 5 * 8192 * 6 * 16 * 64 * 128
    assert bytes_ == 3 * 5 * 8192 * (2 * (3 * 1024 + 2 * 128) + 4 * 16)
    ops, bytes_ = arch.expert_work(config, 1, 8192)
    assert ops == 5 * 2816 * 6 * 2 * 1024 * 2688
    ops, bytes_ = arch.shared_expert_work(config, 1, 8192)
    assert ops == 5 * 8192 * 6 * 2 * 4096 * 5376


def test_the_configuration_keeps_every_published_number_but_the_cuts():
    """Every number of the catalog's ``config`` under its own key; the keys
    that differ are the ones ``reduced`` lists, with the published value
    beside them; no width among them."""
    _, config = harness.load_cell(REAL_CELL)
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # one whole period of the published pattern, in its 5 : 5 : 1
    assert config["hybrid_override_pattern"] == row["config"][
        "hybrid_override_pattern"][27:38]
    assert sorted(config["hybrid_override_pattern"]).count("M") == 5
    assert config["as_run"]["router_experts"] == row["config"][
        "n_routed_experts"]
    cell, _ = harness.load_cell(REAL_CELL)
    job = cell["job"]
    assert (cell["chips"], job["per_chip_batch"], job["seq_len"],
            job["remat"], job["optimizer"], job["corpus"]["kind"],
            job["collectives_in_step"]) == (
                1, 1, 8192, "nothing",
                {"name": "adam", "learning_rate": 0.0002},
                "increment_chains", [])


def test_every_leaf_is_one_or_two_axes_and_goes_round_the_programs_tree():
    _, config = load_cell(CELL)
    arch = archs.load(config)
    weights = arch.init_weights(config, reference.split_seed(1))
    assert all(w.ndim in (1, 2) for w in weights.values())
    assert set(weights) == set(arch.weight_shapes(config)) | set(
        arch.buffer_shapes(config))
    tree = arch.program_tree(config, weights)
    back = arch.named_leaves(config, tree)
    for name, leaf in zip(arch.leaf_names(config), back):
        np.testing.assert_array_equal(leaf, weights[name], err_msg=name)
    # the program's own init has the same tree
    module = arch.build_module(config, {"remat": "nothing"})
    made = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, tree)
    # a head's state fades by 1/e in between 1 / (A_max x time_step_max)
    # and 1 / (A_min x time_step_min) positions: 0.6 and 1,000 at the real
    # configuration's steps and A, 0.5 and 1,000 at the tiny one's
    rate = np.exp(np.asarray(weights["layer_0.A_log"])) * np.log1p(
        np.exp(np.asarray(weights["layer_0.dt_bias"])))
    run = config["as_run"]
    assert rate.max() == pytest.approx(
        run["A_max"] * config["time_step_max"], rel=1e-3)
    assert rate.min() == pytest.approx(
        run["A_min"] * config["time_step_min"], rel=1e-3)
    _, real = harness.load_cell(REAL_CELL)
    assert (real["as_run"]["A_max"] * real["time_step_max"],
            real["as_run"]["A_min"] * real["time_step_min"]) == (1.6, 0.001)
