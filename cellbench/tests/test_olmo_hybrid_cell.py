"""The ``olmo_hybrid`` architecture (``archs/olmo_hybrid.py``: delta-rule
layers at unequal key / value widths with a write strength up to 2, normed
attention, a dense gated feed-forward, norms after the sublayers, half of
each mixer's heads held) through the ``train_lm`` runner end to end on one
CPU device, at the tiny configuration ``data/tiny-olmo-hybrid.json`` (12 /
24 / 16 where the model has 96 / 192 / 128, two heads held of four), added
as the real one is (a configuration file and a cell file; the module is
found by the configuration's ``model_type``): the contract line, the
float32 reference deciding ``correct``, the planted faults of
``test_hybrid_cell.py`` and one of this architecture's own (``beta`` left
at ``sigmoid``, without its factor 2), the fp8 control failing the cell's
limits, the new readers on a trace without their scopes and on hand-made
scoped events, and the counts the yardstick keeps for the real cell."""

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
CELL = "tiny-olmo-hybrid-train-1dev"
REAL_CELL = "olmohybrid-train-tp2share-8k"
NEW_METRICS = ("gdn_mixer_ms_per_step", "gdn_scan_ms_per_step",
               "gdn_scan_roofline", "gdn_glue_ms_per_step",
               "dense_ffn_ms_per_step", "dense_ffn_roofline")


def test_the_tiny_cell_is_of_the_real_cells_architecture():
    _, tiny = load_cell(CELL)
    _, real = harness.load_cell(REAL_CELL)
    assert tiny["model_type"] == real["model_type"] == "olmo_hybrid"
    arch = archs.load(tiny)
    assert arch is archs.load(real)
    t, r = arch.dims(tiny), arch.dims(real)
    assert t["kinds"] == r["kinds"] == ("linear_attention",) * 3 + (
        "full_attention",)
    # the widths in ratio, half of the heads held, the write strength to 2
    assert (r["dk"], r["dv"], r["dh"]) == (96, 192, 128)
    assert (t["dk"] * 8, t["dv"] * 8, t["dh"] * 8) == (96, 192, 128)
    for m in (t, r):
        assert (2 * m["heads"], 2 * m["nv"]) == (m["heads_all"], m["nv_all"])
        assert m["beta_scale"] == 2.0 and m["r"] == 1


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


@pytest.mark.parametrize("fault, over", [
    (unchanged, "update_norm_gap"), (half_batch, None),
    (flipped, "update_dir_gap")], ids=["unchanged", "half_batch", "flipped"])
def test_a_planted_fault_of_the_step_is_not_correct(
        fault, over, tmp_path, monkeypatch, capsys):
    break_step(monkeypatch, fault)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.endswith("OVER")]
    assert lines
    if over:
        assert any(l.startswith(f"[check] {over}=") for l in lines)


def test_a_state_zeroed_at_every_chunk_boundary_is_not_correct(
        tmp_path, monkeypatch, capsys):
    from tpudist.models import hybrid

    real = hybrid.chunked_gated_delta_rule

    def forgets(q, k, v, g, beta, *, chunk=64, **kw):
        b, s, h, _ = q.shape
        cut = lambda x: x.reshape(b * s // chunk, chunk, *x.shape[2:])
        return real(*map(cut, (q, k, v, g, beta)), chunk=chunk,
                    **kw).reshape(b, s, h, -1)

    monkeypatch.setattr(hybrid, "chunked_gated_delta_rule", forgets)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    assert [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("[check] ") and l.endswith("OVER")]


def test_beta_left_at_sigmoid_is_not_correct(tmp_path, monkeypatch, capsys):
    """This architecture's own fault: the write strength without its factor
    2 (``linear_allow_neg_eigval`` ignored).  Shapes, norms and the loss at
    the seeded weights hardly move; the gradients' directions do."""
    from tpudist.models import hybrid

    real = hybrid.HybridLM

    def without_the_factor(*args, sizes, **kw):
        return real(*args, sizes=dataclasses.replace(sizes, beta_scale=1.0),
                    **kw)

    monkeypatch.setattr(hybrid, "HybridLM", without_the_factor)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    assert [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("[check] ") and l.endswith("OVER")]


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
        assert spec["reader"] == f"cellbench.readers.olmo_hybrid:{spec['name']}"


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_trace_without_its_scopes(
        metric, tmp_path, scoped_trace_dir, monkeypatch):
    """The borrowed trace is of the GPT-2 cell: it has ops under ``mlp``,
    but in no pattern layer, and nothing under ``linear_attn``.  Every new
    reader returns ``None`` and none raises."""
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


def test_the_readers_pick_their_ops_from_scoped_events(monkeypatch):
    from cellbench.readers import hybrid, olmo_hybrid, scopes
    from cellbench.trace_reduce import Event

    def op(name, scope, dur, kind="kLoop"):
        e = Event(f"%{name} = f32[8]{{0}} fusion(%x), kind={kind}", 0.0, dur)
        return scopes.Op(e, scope, None, "fwd")

    base = "jit(step)/jvp(HybridLM)/layer_0/"
    back = "jit(step)/transpose(jvp(HybridLM))/checkpoint/layer_2/"
    ops = [
        op("fusion.1", base + "linear_attn/linear_attn/delta_rule/exp", 2e6),
        op("fusion.2", base + "linear_attn/linear_attn/mul", 1e6),
        op("fusion.3", base + "linear_attn/mixer_norm/mul", 4e6),
        op("fusion.4", base + "linear_attn/linear_attn/q_proj/dot_general",
           8e6, "kOutput"),
        op("fusion.5", base + "mlp/mlp/gate_proj/dot_general", 16e6,
           "kOutput"),
        op("fusion.6", back + "mlp/mlp/mul", 32e6),
        # a GPT-2 block's feed-forward: under ``mlp``, in no pattern layer
        op("fusion.7", "jit(step)/jvp(TransformerLM)/block_3/mlp/fc/"
           "dot_general", 64e6, "kOutput"),
        op("fusion.8", base + "attn/attn/o_proj/dot_general", 128e6,
           "kOutput"),
    ]
    chips = lambda r: {0: scopes.ChipOps(2, 1e9, ops)}
    monkeypatch.setattr(hybrid, "_chips", chips)
    monkeypatch.setattr(scopes, "_chips", chips)
    _, config = harness.load_cell(REAL_CELL)
    r = harness.Reading({"name": REAL_CELL}, config, PEAK,
                        {"per_chip_batch": 1, "seq_len": 8192}, {}, {})
    # the share cell's readings of the same scopes, under this cell's names
    assert (olmo_hybrid.gdn_mixer_ms_per_step(r)
            == hybrid.linear_attn_ms_per_step(r) == (2 + 1 + 4 + 8) / 2)
    assert (olmo_hybrid.gdn_scan_ms_per_step(r)
            == hybrid.delta_rule_ms_per_step(r) == 1.0)
    assert olmo_hybrid.gdn_glue_ms_per_step(r) == (1 + 4) / 2
    assert olmo_hybrid.dense_ffn_ms_per_step(r) == (16 + 32) / 2
    arch = archs.load(config)
    least, bound = flops.roofline_seconds(
        *arch.delta_rule_work(config, 1, 8192), PEAK)
    assert bound == "memory"
    assert (olmo_hybrid.gdn_scan_roofline(r)
            == hybrid.delta_rule_roofline(r)
            == pytest.approx(100 * least * 1e3))
    least, bound = flops.roofline_seconds(
        *arch.dense_ffn_work(config, 1, 8192), PEAK)
    assert bound == "compute"
    assert olmo_hybrid.dense_ffn_roofline(r) == pytest.approx(
        100 * least * 1e3 / 24.0)


def test_the_yardsticks_counts_of_the_real_configuration():
    _, config = harness.load_cell(REAL_CELL)
    arch = archs.load(config)
    shapes = arch.weight_shapes(config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == config[
        "as_run"]["parameters"] == 766_241_946
    f = arch.forward_flops_per_token(config, 8192)
    assert f["ffn"] == 3 * 2 * 3840 * 11008
    assert f["head"] == 2 * 3840 * 12544
    assert f["delta_rule"] == 3 * 2 * 15 * 96 * 192
    assert arch.train_flops_per_token(config, 8192) == pytest.approx(
        4.4171e9, rel=1e-4)
    # 15 equal heads of 128: the three kernels as flops.py splits an MHA
    assert arch.kernel_work(config, 1, 8192) == flops.flash_kernel_work(
        batch=1, seq=8192, d_model=1920, n_layers=1)
    ops, bytes_ = arch.dense_ffn_work(config, 1, 8192)
    assert ops == 4 * 8192 * 6 * 3 * 3840 * 11008
    ops, bytes_ = arch.delta_rule_work(config, 1, 8192)
    assert ops == 3 * 3 * 8192 * 6 * 15 * 96 * 192
    assert bytes_ == 3 * 3 * 8192 * 15 * (2 * (2 * 96 + 2 * 192) + 8)


def test_the_configuration_keeps_every_published_number_but_the_cuts():
    """Every number of the catalog's ``config`` under its own key; the keys
    that differ are the ones ``reduced`` lists, with the published count
    beside them; ``layer_types`` is kept whole."""
    _, config = harness.load_cell(REAL_CELL)
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "Olmo-Hybrid-7B")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


def test_every_leaf_is_one_or_two_axes_and_goes_round_the_programs_tree():
    _, config = load_cell(CELL)
    arch = archs.load(config)
    weights = arch.init_weights(config, reference.split_seed(1))
    assert all(w.ndim in (1, 2) for w in weights.values())
    tree = arch.program_tree(config, weights)
    back = arch.named_leaves(config, tree)
    for name, leaf in zip(arch.leaf_names(config), back):
        np.testing.assert_array_equal(leaf, weights[name], err_msg=name)
    # the program's own init has the same tree
    module = arch.build_module(config, {"remat": "nothing"})
    made = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, tree)
    a_log = weights["layer_0.A_log"]
    decay = np.exp(-np.exp(np.asarray(a_log)) * np.log(2.0))
    assert decay.max() == pytest.approx(0.999, abs=1e-4)
    assert decay.min() == pytest.approx(0.5, abs=1e-4)
