"""The ``granitemoehybrid`` architecture (``archs/granitemoehybrid.py``:
Mamba-2 mixers that hold half the heads of the ONE group of ``B`` and ``C``,
one position-free attention layer at a softmax scale of its own, a gated
feed-forward behind every mixer, four scalar multipliers, a tied head)
through the ``train_lm`` runner end to end on one CPU device, at the tiny
configuration ``data/tiny-granite-hybrid.json``, added as the real one is (a
configuration file and a cell file; the module is found by the
configuration's ``model_type``): the contract line, the float32 reference
deciding ``correct``, the three planted faults of ``test_hybrid_cell.py`` and
seven of this architecture's own (the carried state zeroed at every chunk
boundary, the residual multiplier dropped, the softmax scale left at ``1 /
sqrt(head_dim)``, the logits' divisor dropped, the embedding multiplier
dropped, the head's gradient cut from the embedding, the gated norm's gate
dropped), the fp8 control failing the cell's limits, the new readers on a
trace without their scopes and on hand-made scoped events, and the counts
the yardstick keeps for the real cell."""

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
CELL = "tiny-granite-hybrid-train-1dev"
REAL_CELL = "granite4hmicro-train-tp2share-8k"
NEW_METRICS = ("mamba_mixer_ms_per_step", "mamba_scan_ms_per_step",
               "mamba_scan_roofline", "mamba_conv_ms_per_step",
               "mamba_norm_ms_per_step", "shared_mlp_ms_per_step",
               "shared_mlp_roofline", "nope_attn_ms_per_step",
               "nope_attn_relayout_ms_per_step", "tied_head_ms_per_step")


def test_the_tiny_cell_is_of_the_real_cells_architecture():
    _, tiny = load_cell(CELL)
    _, real = harness.load_cell(REAL_CELL)
    assert tiny["model_type"] == real["model_type"] == "granitemoehybrid"
    arch = archs.load(tiny)
    assert arch is archs.load(real)
    t, r = arch.dims(tiny), arch.dims(real)
    assert set(t["kinds"]) == set(r["kinds"]) == {"mamba", "attention"}
    assert r["kinds"].count("mamba") == 9 and r["depth"] == 10
    # the widths in ratio; half of the heads and the one group whole
    assert (r["mp"], r["mn"], r["dh"], r["ffn"]) == (64, 128, 64, 8192)
    assert (t["mp"] * 8, t["mn"] * 8, t["dh"] * 4) == (64, 128, 64)
    for m in (t, r):
        assert (2 * m["mh"], 2 * m["heads"]) == (m["mh_all"], m["heads_all"])
        assert m["mg"] == m["mg_all"] == 1
        assert m["heads"] // m["kv"] == (4 if m is r else 2)
        assert (m["emb_scale"], m["res_scale"], m["logit_div"]) == (
            12.0, 0.22, 8.0)
        # the softmax scale is NOT 1 / sqrt(head_dim), and times
        # sqrt(head_dim) it is a power of two
        assert m["attn_scale"] * m["dh"] ** 0.5 in (0.125, 0.5)
        assert m["ffn_products_kept"] is False


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


def with_sizes(monkeypatch, **changed):
    """The program's decoder built from the sizes with ``changed``."""
    from tpudist.models import hybrid

    real = hybrid.HybridLM

    def other(*args, sizes, **kw):
        return real(*args, sizes=dataclasses.replace(sizes, **changed), **kw)

    monkeypatch.setattr(hybrid, "HybridLM", other)


def forgets(monkeypatch):
    """The chunked scan forgets what it carried from chunk to chunk (every
    chunk starts from a zero state).  Each chunk alone is still right; the
    reference, a position at a time, tells."""
    from tpudist.models import hybrid

    real = hybrid.ssd_scan

    def cut_scan(x, dt, a_log, b, c, d, *, chunk):
        cut = lambda t: t.reshape(-1, chunk, *t.shape[2:])
        return real(cut(x), cut(dt), a_log, cut(b), cut(c), d,
                    chunk=chunk).reshape(x.shape)

    monkeypatch.setattr(hybrid, "ssd_scan", cut_scan)


def head_cut_from_the_embedding(monkeypatch):
    """``stop_gradient`` on the head's read of ``tok_embed``: the tied
    tensor's gradient is the gather's scatter-add alone."""
    from tpudist.models import hybrid

    def attend(self, query):
        q, e = hybrid.nn.dtypes.promote_dtype(
            query, jax.lax.stop_gradient(self.embedding), dtype=self.dtype)
        return jnp.dot(q, e.T)

    monkeypatch.setattr(hybrid.nn.Embed, "attend", attend)


def ungated(monkeypatch):
    """``silu(z)`` is 1 everywhere: ``in_proj``'s ``z`` columns are there
    and gate nothing (``silu(1.2784645) = 1``)."""
    from tpudist.models import hybrid

    real = hybrid._dense
    _, config = load_cell(CELL)
    m = archs.load(config).dims(config)
    inner = m["mh"] * m["mp"]    # ``z`` is ``in_proj``'s first columns

    def dense(features, name, dtype):
        made = real(features, name, dtype)
        if name != "in_proj":
            return made
        return lambda x: made(x).at[..., :inner].set(1.2784645)

    monkeypatch.setattr(hybrid, "_dense", dense)


FAULTS = {
    "state_zeroed_at_chunk_boundaries": forgets,
    # x + sublayer(norm(x)), the multiplier of 0.22 ignored
    "residual_multiplier_dropped": lambda mp: with_sizes(
        mp, residual_scale=1.0),
    # the scores at 1 / sqrt(head_dim), attention_multiplier ignored
    "softmax_scale_left_at_rsqrt": lambda mp: with_sizes(
        mp, softmax_scale=None),
    "logits_divisor_dropped": lambda mp: with_sizes(mp, logits_divisor=1.0),
    "embedding_multiplier_dropped": lambda mp: with_sizes(
        mp, embedding_scale=1.0),
    "head_gradient_cut_from_the_embedding": head_cut_from_the_embedding,
    "gated_norms_gate_dropped": ungated,
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
        assert spec["reader"] == (
            f"cellbench.readers.granitemoehybrid:{spec['name']}")
    manifest = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    # in their order, wherever later entries come to stand behind them
    listed = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in listed] == list(NEW_METRICS)
    for m in listed:
        assert m["workloads"] == [REAL_CELL]
        assert m["layer"] == new[m["name"]]["layer"]
        assert (m["unit"] == "%") == m["name"].endswith("_roofline")
    (cell,) = [w for w in manifest["workloads"] if w["name"] == REAL_CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "granite-4.0-h-micro", "train-tp2share-8k")
    (config,) = [c for c in manifest["configs"]
                 if c["name"] == "granite-4.0-h-micro"]
    _, real = harness.load_cell(REAL_CELL)
    assert (config["source"], config["reduced"]) == (real["source"],
                                                     real["reduced"])
    assert all(len(e["why"]) <= 200 for e in (cell, config))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_trace_without_its_scopes(
        metric, tmp_path, scoped_trace_dir, monkeypatch):
    """The borrowed trace is of the GPT-2 cell: nothing under ``ssm``, no
    ``mlp`` inside a pattern layer.  Every new reader but those of scopes a
    GPT-2 block runs under too (``attn``; ``embed``, ``head``, ``loss``)
    returns ``None``, and none raises."""
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
    if metric.startswith(("nope_attn", "tied_head")):
        assert got > 0
    else:
        assert got is None


def test_the_readers_pick_their_ops_from_scoped_events(monkeypatch):
    from cellbench.readers import granitemoehybrid as readers
    from cellbench.readers import hybrid, scopes
    from cellbench.trace_reduce import Event

    def op(name, scope, dur, kind="kLoop", kernel=None):
        e = Event(f"%{name} = f32[8]{{0}} fusion(%x), kind={kind}", 0.0, dur)
        return scopes.Op(e, scope, kernel, "fwd")

    fwd = "jit(step)/jvp(HybridLM)/"
    bwd = "jit(step)/transpose(jvp(HybridLM))/checkpoint/"
    ops = [
        op("fusion.1", fwd + "layer_0/ssm/ssm/ssd_scan/exp", 2e6),
        op("fusion.2", fwd + "layer_0/ssm/ssm/in_proj/dot_general", 4e6,
           "kOutput"),
        op("fusion.3", fwd + "layer_0/ssm/ssm/ssm_conv/mul", 8e6),
        op("fusion.4", bwd + "layer_0/ssm/ssm/ssm_norm/rsqrt", 16e6),
        op("fusion.5", fwd + "layer_0/ssm/mixer_norm/mul", 32e6),
        op("fusion.6", fwd + "layer_0/mlp/mlp/gate_proj/dot_general", 64e6,
           "kOutput"),
        # a GPT-2 block's feed-forward: under mlp, in no pattern layer
        op("fusion.7", fwd + "block_3/mlp/fc/dot_general", 128e6, "kOutput"),
        op("flash_fwd.1", fwd + "layer_5/attn/attn/pallas_call", 256e6,
           kernel=flops.FLASH_FWD),
        op("fusion.8", fwd + "layer_5/attn/attn/q_proj/dot_general", 512e6,
           "kOutput"),
        op("copy.1", bwd + "layer_5/attn/attn/transpose", 1024e6),
        op("fusion.9", fwd + "embed/tok_embed/take", 2048e6),
        op("fusion.10", bwd + "head/tok_embed/dot_general", 4096e6,
           "kOutput"),
        op("fusion.11", "jit(step)/jvp(loss)/log_softmax", 8192e6),
        op("fusion.12", "jit(step)/optimizer/add", 16384e6),
    ]
    chips = lambda r: {0: scopes.ChipOps(2, 4e10, ops)}
    monkeypatch.setattr(hybrid, "_chips", chips)
    monkeypatch.setattr(scopes, "_chips", chips)
    _, config = harness.load_cell(REAL_CELL)
    r = harness.Reading({"name": REAL_CELL}, config, PEAK,
                        {"per_chip_batch": 1, "seq_len": 8192}, {}, {})
    assert readers.mamba_mixer_ms_per_step(r) == (2 + 4 + 8 + 16 + 32) / 2
    assert readers.mamba_scan_ms_per_step(r) == 2 / 2
    assert readers.mamba_conv_ms_per_step(r) == 8 / 2
    assert readers.mamba_norm_ms_per_step(r) == 16 / 2
    assert readers.shared_mlp_ms_per_step(r) == 64 / 2
    assert readers.nope_attn_ms_per_step(r) == (256 + 512 + 1024) / 2
    assert readers.nope_attn_relayout_ms_per_step(r) == 1024 / 2
    assert readers.tied_head_ms_per_step(r) == (2048 + 4096 + 8192) / 2
    arch = archs.load(config)
    for fn, reader, ms, bound in (
            ("ssd_work", readers.mamba_scan_roofline, 1.0, "memory"),
            ("mlp_work", readers.shared_mlp_roofline, 32.0, "compute")):
        least, which = flops.roofline_seconds(
            *getattr(arch, fn)(config, 1, 8192), PEAK)
        assert which == bound, fn
        assert reader(r) == pytest.approx(100 * least * 1e3 / ms), fn


def test_the_yardsticks_counts_of_the_real_configuration():
    _, config = harness.load_cell(REAL_CELL)
    arch = archs.load(config)
    shapes = arch.weight_shapes(config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == config[
        "as_run"]["parameters"] == 730_040_416
    f = arch.forward_flops_per_token(config, 8192)
    assert f["mamba_matmuls"] == 2 * 13_172_736
    assert f["ssd"] == 3 * 2 * 32 * 64 * 128
    assert f["attn_matmuls"] == 2 * 5_242_880
    assert f["attn_pairs"] == 4 * flops.causal_pairs(8192) * 16 * 64 / 8192
    assert f["mlp"] == 2 * 50_331_648
    assert f["head"] == 2 * 102_760_448
    # the dense products a token: nine mixers, ten feed-forwards, one
    # attention layer, the tied head once
    assert 9 * f["mamba_matmuls"] + 10 * f["mlp"] + f["attn_matmuls"] + f[
        "head"] == 2 * 729_874_432
    per_token = arch.train_flops_per_token(config, 8192)
    assert per_token == 3 * (2 * 729_874_432 + 9 * f["ssd"]
                             + f["attn_pairs"])
    assert per_token * 8192 == pytest.approx(36.635e12, rel=1e-4)
    # 16 query heads on 4 key/value heads of 64: k, v, dk, dv a quarter as
    # wide as q, o, do, dq
    work = arch.kernel_work(config, 1, 8192)
    whole = flops.flash_kernel_work(batch=1, seq=8192, d_model=1024,
                                    n_layers=1)
    tensor = 8192 * 1024 * 2
    for kernel in flops.FLASH_KERNELS:
        assert work[kernel][0] == whole[kernel][0]
    assert work[flops.FLASH_FWD][1] == 2.5 * tensor
    assert sum(b for _, b in work.values()) == (6 + 6 / 4) * tensor
    ops, bytes_ = arch.ssd_work(config, 1, 8192)
    assert ops == 3 * 9 * 8192 * 6 * 32 * 64 * 128
    assert bytes_ == 3 * 9 * 8192 * (2 * (3 * 2048 + 2 * 128) + 4 * 32)
    least, which = flops.roofline_seconds(ops, bytes_, PEAK)
    assert which == "memory" and least * 1e3 == pytest.approx(3.49, rel=5e-3)
    ops, bytes_ = arch.mlp_work(config, 1, 8192)
    assert ops == 10 * 8192 * 6 * 50_331_648
    least, which = flops.roofline_seconds(ops, bytes_, PEAK)
    assert which == "compute" and least * 1e3 == pytest.approx(125.6,
                                                               rel=1e-3)


def test_the_configuration_keeps_every_published_number_but_the_cuts():
    """Every number of the catalog's ``config`` under its own key; the keys
    that differ are the ones ``reduced`` lists, with the published value
    beside them; no width among them."""
    _, config = harness.load_cell(REAL_CELL)
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "granite-4.0-h-micro")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "mamba_n_heads",
        "num_attention_heads", "num_key_value_heads", "vocab_size"}
    assert {k: row["config"][k] for k in differs} == config["published"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    published = row["config"]
    # layers 0-9 of the published list: one whole period, nine to one
    assert config["layer_types"] == published["layer_types"][:10] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    for key in ("mamba_n_heads", "num_attention_heads",
                "num_key_value_heads", "vocab_size"):
        assert 2 * config[key] == published[key], key
    # the one group is held whole, the feed-forward too
    assert config["mamba_n_groups"] == published["mamba_n_groups"] == 1
    assert config["shared_intermediate_size"] == 8192
    for key in ("departures", "assumed", "deployment", "held_whole"):
        assert config[key]
    assert "nothing of the mathematics" in config["assumed"][0]
    cell, _ = harness.load_cell(REAL_CELL)
    job = cell["job"]
    assert (cell["chips"], job["per_chip_batch"], job["seq_len"],
            job["remat"], job["optimizer"], job["corpus"]["kind"],
            job["collectives_in_step"], job["custom_calls_per_layer"]) == (
                1, 1, 8192, "nothing",
                {"name": "adam", "learning_rate": 0.0002},
                "increment_chains", [], 4)


def test_every_leaf_goes_round_the_programs_tree():
    _, config = load_cell(CELL)
    arch = archs.load(config)
    weights = jax.jit(lambda words: arch.init_weights(config, words))(
        reference.split_seed(1))
    assert set(weights) == set(arch.weight_shapes(config))
    # a stacked entry has the Mamba layers on axis 0; every tensor a model
    # holds is one or two axes
    assert all(w.ndim - (name in arch.STACKED) in (1, 2)
               for name, w in weights.items())
    tree = arch.program_tree(config, weights)
    back = arch.named_leaves(config, tree)
    m = arch.dims(config)
    for name, leaf in zip(arch.leaf_names(config), back):
        layer, _, tail = name.rpartition(".")
        want = arch.of_layer(weights, int(layer.rpartition("_")[2]),
                             m)[tail] if layer else weights[name]
        np.testing.assert_array_equal(leaf, want, err_msg=name)
    module = arch.build_module(config, {"remat": "nothing"})
    made = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 64), jnp.int32))
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, tree)
