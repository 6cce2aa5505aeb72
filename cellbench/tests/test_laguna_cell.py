"""The ``laguna`` architecture (``archs/laguna.py``: softmax attention of
two kinds at unequal head counts behind a gate a head, causal to everything
with YaRN on half of a head's dims and inside a sliding window with plain
rotary positions, a leading dense layer, sigmoid-scored gated-SiLU experts
taken through windows beside a whole shared expert; one of two head shares
and a run of the experts held) through the ``train_lm`` runner end to end
on one CPU device, at the tiny configuration ``data/tiny-laguna.json``,
added as the real one is (a configuration file and a cell file; the module
is found by the configuration's ``model_type``): the contract line, the
float32 reference deciding ``correct``, the three planted faults of
``test_hybrid_cell.py`` and five of this architecture's own (the window
dropped, the gate dropped, YaRN's scale dropped, YaRN's factor dropped, the
routed scale dropped), the fp8 control failing the cell's limits, the new
readers on a trace without their scopes and on hand-made scoped events, and
the counts the yardstick keeps for the real cell."""

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
CELL = "tiny-laguna-train-1dev"
REAL_CELL = "lagunas21-train-tp2ep32share-8k"
NEW_METRICS = ("window_attn_ms_per_step", "window_attn_kernel_ms_per_step",
               "window_attn_kernel_roofline", "full_attn_ms_per_step",
               "lead_ffn_ms_per_step", "lead_ffn_roofline",
               "routed_moe_ms_per_step", "routed_moe_dispatch_ms_per_step",
               "routed_experts_ms_per_step", "routed_experts_roofline")


def test_the_tiny_cell_is_of_the_real_cells_architecture():
    _, tiny = load_cell(CELL)
    _, real = harness.load_cell(REAL_CELL)
    assert tiny["model_type"] == real["model_type"] == "laguna"
    arch = archs.load(tiny)
    assert arch is archs.load(real)
    t, r = arch.dims(tiny), arch.dims(real)
    assert t["kinds"] == r["kinds"] == (
        arch.FULL, arch.SLIDING, arch.SLIDING, arch.SLIDING, arch.FULL)
    assert t["ffns"] == r["ffns"] == (arch.DENSE,) + (arch.SPARSE,) * 4
    # the widths in ratio, half of the heads and a 32nd of the experts held
    assert (r["dh"], r["window"], r["heads"], r["kv"]) == (
        128, 512, (24, 36, 36, 36, 24), 4)
    assert (t["dh"] * 8, t["window"] * 16, t["heads"], t["kv"]) == (
        128, 512, (4, 6, 6, 6, 4), 2)
    for m in (t, r):
        assert tuple(2 * h for h in m["heads"]) == m["heads_all"]
        assert m["experts"] == 32 * m["held"] and m["first"] == 0
        assert m["scale"] == 2.5 and m["router_trained"]
        assert m["rope"][arch.FULL]["rotary"] * 2 == m["rope"][
            arch.SLIDING]["rotary"] == m["dh"]
        assert m["rope"][arch.FULL]["yarn"] and not m["rope"][
            arch.SLIDING]["yarn"]


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


def with_sizes(monkeypatch, change):
    """The program's decoder built from ``change(sizes)``."""
    from tpudist.models import hybrid

    real = hybrid.HybridLM

    def other(*args, sizes, **kw):
        return real(*args, sizes=change(sizes), **kw)

    monkeypatch.setattr(hybrid, "HybridLM", other)


def of_kind(kind_attr: str, change):
    """``sizes -> sizes`` with one softmax kind's own sizes changed."""
    from tpudist.telemetry import names

    kind = getattr(names, kind_attr)
    return lambda sizes: dataclasses.replace(sizes, softmax_kinds=tuple(
        (k, change(a) if k == kind else a) for k, a in sizes.softmax_kinds))


def ungated(monkeypatch):
    """Every gate one: the projection is there and multiplies nothing."""
    from tpudist.models import hybrid

    real = hybrid._dense

    def dense(features, name, dtype):
        made = real(features, name, dtype)
        if name != "g_proj":
            return made
        return lambda x: 0.0 * made(x) + 30.0      # sigmoid(30) is 1

    monkeypatch.setattr(hybrid, "_dense", dense)


FAULTS = {
    # the sliding layers attend causally to everything
    "window_dropped": lambda mp: with_sizes(mp, of_kind(
        "WINDOW", lambda a: dataclasses.replace(a, window=None))),
    "gate_dropped": ungated,
    # cos and sin of the full layers' rotary positions not multiplied by
    # the attention factor
    "yarn_scale_dropped": lambda mp: with_sizes(mp, of_kind(
        "FULL", lambda a: dataclasses.replace(
            a, yarn=dataclasses.replace(a.yarn, scale=1.0)))),
    # the full layers' frequencies left plain
    "yarn_factor_dropped": lambda mp: with_sizes(mp, of_kind(
        "FULL", lambda a: dataclasses.replace(a, yarn=None))),
    # the picks' weights sum to 1 and not to 2.5
    "routed_scale_dropped": lambda mp: with_sizes(
        mp, lambda z: dataclasses.replace(z, routed_scale=1.0)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_of_the_architecture_is_not_correct(
        fault, tmp_path, monkeypatch, capsys):
    FAULTS[fault](monkeypatch)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    assert overs(capsys)


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
        assert spec["reader"] == f"cellbench.readers.laguna:{spec['name']}"
    manifest = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    # in their order, wherever later entries come to stand behind them
    listed = [m["name"] for m in manifest["per_layer"]
              if m["name"] in NEW_METRICS]
    assert listed == list(NEW_METRICS)
    (cell,) = [w for w in manifest["workloads"] if w["name"] == REAL_CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "laguna-s-2.1", "train-tp2ep32share-8k")


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_trace_without_its_scopes(
        metric, tmp_path, scoped_trace_dir, monkeypatch):
    """The borrowed trace is of the GPT-2 cell: nothing under
    ``window_attn`` or ``moe``, no grouped product, no ``mlp`` inside a
    pattern layer.  Every new reader but the one of scope ``attn`` (a
    GPT-2 block runs under it too) returns ``None``, and none raises."""
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
    got = getattr(importlib.import_module(module), fn)(reading)
    if metric == "full_attn_ms_per_step":
        assert got > 0
    else:
        assert got is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_program_without_its_scopes(
        metric, monkeypatch):
    """On the parent's program (``names`` without ``WINDOW_ATTN``) the
    readers return ``None`` before looking at any trace."""
    import types

    from cellbench.readers import hybrid, laguna

    old = types.SimpleNamespace(**{
        k: v for k, v in vars(hybrid.names).items()
        if k not in ("WINDOW_ATTN", "HEAD_GATE")})
    monkeypatch.setattr(hybrid, "names", old)
    monkeypatch.setattr(hybrid, "_chips", lambda r: 1 / 0)
    cell, config = load_cell(CELL)
    reading = harness.Reading(cell, config, PEAK,
                              {"per_chip_batch": 2, "seq_len": 128}, {}, {})
    assert getattr(laguna, metric)(reading) is None


def test_the_readers_pick_their_ops_from_scoped_events(monkeypatch):
    from cellbench.readers import hybrid, laguna, scopes
    from cellbench.trace_reduce import Event

    def op(name, scope, dur, kind="kLoop", kernel=None):
        e = Event(f"%{name} = f32[8]{{0}} fusion(%x), kind={kind}", 0.0, dur)
        return scopes.Op(e, scope, kernel, "fwd")

    fwd = "jit(step)/jvp(HybridLM)/"
    bwd = "jit(step)/transpose(jvp(HybridLM))/checkpoint/"
    moe = fwd + "layer_2/experts/moe/"
    ops = [
        op("fusion.1", fwd + "layer_1/window_attn/window_attn/q_proj/"
           "dot_general", 2e6, "kOutput"),
        op("flash_fwd.1", fwd + "layer_1/window_attn/window_attn/pallas_call",
           4e6, kernel=flops.FLASH_FWD),
        op("flash_bwd_dq.1", bwd + "layer_1/window_attn/window_attn/"
           "pallas_call", 8e6, kernel=flops.FLASH_BWD_DQ),
        op("fusion.2", fwd + "layer_1/window_attn/window_attn/head_gate/mul",
           16e6),
        # a full layer's: under attn, under no window_attn
        op("flash_fwd.2", fwd + "layer_0/attn/attn/pallas_call", 32e6,
           kernel=flops.FLASH_FWD),
        op("fusion.3", fwd + "layer_0/attn/attn/head_gate/mul", 64e6),
        op("fusion.4", fwd + "layer_0/mlp/mlp/gate_proj/dot_general", 128e6,
           "kOutput"),
        # a GPT-2 block's feed-forward: under mlp, in no pattern layer
        op("fusion.5", fwd + "block_3/mlp/fc/dot_general", 256e6, "kOutput"),
        op("fusion.6", moe + "top_k", 512e6),
        op("fusion.7", moe + "shared_expert/dot_general", 1024e6, "kOutput"),
        op("ragged-dot.3", "", 2048e6),
        op("fusion.8", bwd + "layer_2/experts/moe/moe_combine/scatter-add",
           4096e6),
    ]
    chips = lambda r: {0: scopes.ChipOps(2, 2e9, ops)}
    monkeypatch.setattr(hybrid, "_chips", chips)
    monkeypatch.setattr(scopes, "_chips", chips)
    _, config = harness.load_cell(REAL_CELL)
    r = harness.Reading({"name": REAL_CELL}, config, PEAK,
                        {"per_chip_batch": 1, "seq_len": 8192}, {}, {})
    assert laguna.window_attn_ms_per_step(r) == (2 + 4 + 8 + 16) / 2
    assert laguna.window_attn_kernel_ms_per_step(r) == (4 + 8) / 2
    assert laguna.full_attn_ms_per_step(r) == (32 + 64) / 2
    assert laguna.lead_ffn_ms_per_step(r) == 128 / 2
    assert laguna.routed_moe_ms_per_step(r) == (
        512 + 1024 + 2048 + 4096) / 2
    assert laguna.routed_moe_dispatch_ms_per_step(r) == (512 + 4096) / 2
    assert laguna.routed_experts_ms_per_step(r) == 2048 / 2
    arch = archs.load(config)
    work = arch.window_kernel_work(config, 1, 8192)
    least, which = flops.roofline_seconds(
        sum(o for o, _ in work.values()), sum(b for _, b in work.values()),
        PEAK)
    assert which == "compute"
    assert laguna.window_attn_kernel_roofline(r) == pytest.approx(
        100 * least * 1e3 / 6.0)
    for fn, reader, ms, bound in (
            ("lead_ffn_work", laguna.lead_ffn_roofline, 64.0, "compute"),
            ("expert_work", laguna.routed_experts_roofline, 1024.0,
             "memory")):
        least, which = flops.roofline_seconds(
            *getattr(arch, fn)(config, 1, 8192), PEAK)
        assert which == bound, fn
        assert reader(r) == pytest.approx(100 * least * 1e3 / ms), fn


def test_the_yardsticks_counts_of_the_real_configuration():
    _, config = harness.load_cell(REAL_CELL)
    arch = archs.load(config)
    shapes = arch.weight_shapes(config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == config[
        "as_run"]["parameters"] == 672_125_952
    *layers, head = arch.forward_flops_per_token(config, 8192)
    band = 512 * 513 / 2 + (8192 - 512) * 512
    assert band == 4_063_488 == arch.live_pairs(8192, 512)
    assert arch.live_pairs(8192) == flops.causal_pairs(8192)
    full, sliding = layers[0], layers[1]
    assert full["attn_matmuls"] == 2 * 22_093_824
    assert sliding["attn_matmuls"] == 2 * 31_567_872
    assert full["attn_pairs"] == 4 * flops.causal_pairs(8192) * 24 * 128 / 8192
    assert sliding["attn_pairs"] == 4 * band * 36 * 128 / 8192
    assert full["dense_ffn"] == 2 * 113_246_208 and "router" not in full
    assert sliding["router"] == 2 * 3072 * 256
    assert sliding["held_experts"] == 3 * 2 * 3072 * 1024 * 10 * 8 / 256
    assert sliding["shared_expert"] == 3 * 2 * 3072 * 1024
    assert layers[4] == {**sliding, "attn_matmuls": full["attn_matmuls"],
                         "attn_pairs": full["attn_pairs"]}
    assert head == {"head": 2 * 3072 * 12544}
    assert arch.train_flops_per_token(config, 8192) == pytest.approx(
        2.4445e9, rel=1e-4)
    # the kernels at their LIVE pairs, both kinds together and the sliding
    # layers alone; k, v, dk, dv a sixth and a ninth as wide as q, o, do, dq
    work = arch.kernel_work(config, 1, 8192)
    window = arch.window_kernel_work(config, 1, 8192)
    ops = 4 * (2 * flops.causal_pairs(8192) * 24 + 3 * band * 36) * 128
    for kernel in flops.FLASH_KERNELS:
        assert work[kernel][0] == ops
        assert window[kernel][0] == 4 * 3 * band * 36 * 128
    wide = 8192 * 128 * 2 * (2 * 24 + 3 * 36)
    narrow = 8192 * 128 * 2 * 5 * 4
    assert work[flops.FLASH_FWD][1] == 2 * wide + 2 * narrow
    assert sum(b for _, b in work.values()) == 6 * wide + 6 * narrow
    ops, _ = arch.expert_work(config, 1, 8192)
    assert ops == 4 * 2560 * 6 * 3 * 3072 * 1024
    ops, _ = arch.lead_ffn_work(config, 1, 8192)
    assert ops == 8192 * 6 * 3 * 3072 * 12288


def test_the_configuration_keeps_every_published_number_but_the_cuts():
    """Every number of the catalog's ``config`` under its own key; the keys
    that differ are the ones ``reduced`` lists, with the published value
    beside them; no width among them."""
    _, config = harness.load_cell(REAL_CELL)
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "Laguna-S-2.1")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # layers 0-4 of the published lists: the leading dense layer and the
    # whole period that follows it; half of every layer's heads
    published = row["config"]
    assert config["layer_types"] == published["layer_types"][:5]
    assert config["mlp_layer_types"] == published["mlp_layer_types"][:5]
    assert [2 * h for h in config["num_attention_heads_per_layer"]] == (
        published["num_attention_heads_per_layer"][:5])
    assert config["num_key_value_heads"] * 2 == published[
        "num_key_value_heads"]
    assert config["as_run"]["router_experts"] == published["num_experts"]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("departures", "assumed", "deployment"):
        assert config[key]
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
    assert set(weights) == set(arch.weight_shapes(config))
    tree = arch.program_tree(config, weights)
    back = arch.named_leaves(config, tree)
    for name, leaf in zip(arch.leaf_names(config), back):
        np.testing.assert_array_equal(leaf, weights[name], err_msg=name)
    # the program's own init has the same tree
    module = arch.build_module(config, {"remat": "nothing"})
    made = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, tree)
    # the gates spread: a normed input of 64 dims times normal(0, 0.14)
    u = jax.random.normal(jax.random.PRNGKey(2), (512, 64))
    gates = np.asarray(jax.nn.sigmoid(u @ weights["layer_1.g_proj"]))
    assert 0.1 < np.quantile(gates, 0.1) < 0.3 < 0.7 < np.quantile(
        gates, 0.9) < 0.9
