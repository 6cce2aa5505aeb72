"""The ``kimi_linear`` architecture (``archs/kimi_linear.py``: a delta rule
whose decay is a number a channel behind low-rank gates three layers in four,
latent attention without positions in the fourth, a leading dense layer,
sigmoid + bias scored gated-SiLU experts taken through windows beside a
plain shared expert; a run of the experts held) through the ``train_lm``
runner end to end on one CPU device, at the tiny configuration
``data/tiny-kimi-linear.json``, added as the real one is (a configuration
file and a cell file; the module is found by the configuration's
``model_type``): the contract line, the float32 reference deciding
``correct``, the three planted faults of ``test_hybrid_cell.py`` and three of
this architecture's own (a decay taken a head and not a channel, the output
gate a SiLU, the query's two parts swapped), the fp8 control failing the
cell's limits, the new readers on the tiny cell's own lowered scopes, on a
trace without their scopes and on hand-made scoped events, and the counts the
yardstick keeps for the real cell, each against a count written out here by
hand."""

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
CELL = "tiny-kimi-linear-train-1dev"
REAL_CELL = "kimilinear-train-ep32share-8k"
NEW_METRICS = ("kda_mixer_ms_per_step", "kda_gate_ms_per_step",
               "kda_scan_ms_per_step", "kda_scan_roofline",
               "latent_attn_ms_per_step", "latent_kv_ms_per_step",
               "kimi_moe_ms_per_step", "kimi_moe_dispatch_ms_per_step",
               "kimi_experts_roofline")


def test_the_tiny_cell_is_of_the_real_cells_architecture():
    _, tiny = load_cell(CELL)
    _, real = harness.load_cell(REAL_CELL)
    assert tiny["model_type"] == real["model_type"] == "kimi_linear"
    arch = archs.load(tiny)
    assert arch is archs.load(real)
    t, r = arch.dims(tiny), arch.dims(real)
    assert t["kinds"] == r["kinds"] == (
        arch.KDA, arch.KDA, arch.KDA, arch.LATENT, arch.KDA)
    assert t["ffns"] == r["ffns"] == (arch.DENSE,) + (arch.SPARSE,) * 4
    # the widths in ratio (an eighth), the key's parts unequal in the tiny
    # one too, a run of the experts held
    assert (r["kh"], r["kd"], r["gate_rank"], r["conv"], r["heads"],
            r["own"], r["shared_key"], r["dv"], r["rank"]) == (
        32, 128, 128, 4, 32, 128, 64, 128, 512)
    assert (t["kh"], t["kd"], t["gate_rank"], t["conv"], t["heads"],
            t["own"], t["shared_key"], t["dv"], t["rank"]) == (
        2, 16, 16, 4, 2, 24, 8, 16, 12)
    assert (r["experts"], r["held"], t["experts"], t["held"]) == (
        256, 8, 32, 8)
    for m in (t, r):
        assert m["first"] == 0 and m["top_k"] == 8 and m["chunk"] == 64
        assert m["scale"] == 2.446
    # the real cell holds its router's weight fixed (the issue's fallback,
    # taken on a reading: the configuration's ``departures``); the tiny one
    # trains it, and ``tests/test_kimi_linear.py`` holds both ways
    assert t["router_trained"] and not r["router_trained"]


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


def a_decay_a_head(monkeypatch):
    """Every channel of a head forgets at the head's mean rate."""
    from tpudist.models import hybrid

    real = hybrid.chunked_gated_delta_rule

    def scan(q, k, v, g, beta, **kw):
        return real(q, k, v, jnp.mean(g, axis=-1), beta, **kw)

    monkeypatch.setattr(hybrid, "chunked_gated_delta_rule", scan)


def silu_gate(monkeypatch):
    """The output gate of the per-head mixer: SiLU where this is a sigmoid
    (the write strength, a number a head, keeps its sigmoid)."""
    real = jax.nn.sigmoid
    monkeypatch.setattr(jax.nn, "sigmoid", lambda x: (
        jax.nn.silu(x) if x.ndim == 3 and x.shape[-1] == 32 else real(x)))


def parts_swapped(monkeypatch):
    """The latent projection's output split the other way round: the part
    all heads share first."""
    from tpudist.models import hybrid

    real = jnp.split

    def split(x, at, axis=0):
        if at == [12]:      # the tiny latent's rank, of 12 + 8 columns
            shared, latent = real(x, [8], axis=axis)
            return [latent, shared]
        return real(x, at, axis=axis)

    monkeypatch.setattr(hybrid.jnp, "split", split)


FAULTS = {"a_decay_a_head": a_decay_a_head, "silu_gate": silu_gate,
          "parts_swapped": parts_swapped}


@pytest.mark.parametrize("fault", [unchanged, half_batch, flipped,
                                   *sorted(FAULTS)],
                         ids=lambda f: f if isinstance(f, str) else f.__name__)
def test_a_planted_fault_is_not_correct(fault, tmp_path, monkeypatch, capsys):
    """The step that returns its state unchanged, drops half the batch or
    flips its update's sign, and the three faults of this architecture's
    own: each reads ``correct`` false by at least one limit."""
    if isinstance(fault, str):
        FAULTS[fault](monkeypatch)
    else:
        break_step(monkeypatch, fault)
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


def test_the_cells_files_are_what_the_manifest_says():
    new = new_metric_files()
    assert set(new) == set(NEW_METRICS)
    for spec in new.values():
        assert spec["cells"] == [REAL_CELL]
        assert spec["source"] == "device_trace"
        assert spec["moves"] == "tokens_per_s_per_chip"
        # a reader a metric: test_rehearsal spies on a metric by the name
        # of its reader, so two files may not share one
        assert spec["reader"] == (
            f"cellbench.readers.kimi_linear:{spec['name']}")
    manifest = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    listed = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in listed] == list(NEW_METRICS)
    for m in listed:
        spec = new[m["name"]]
        assert m == {**{k: spec[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")},
            "workloads": [REAL_CELL]}
    (entry,) = [w for w in manifest["workloads"] if w["name"] == REAL_CELL]
    cell, config = harness.load_cell(REAL_CELL)
    assert (entry["chips"], entry["config"], entry["traffic"],
            entry["why"]) == (1, "kimi-linear-48b-a3b", "train-ep32share-8k",
                              cell["why"])
    (listed_config,) = [c for c in manifest["configs"]
                        if c["name"] == entry["config"]]
    assert listed_config["file"] == "cellbench/configs/kimi-linear-48b-a3b.json"
    assert listed_config["source"] == config["source"]
    assert listed_config["reduced"] == config["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"]
    assert len(manifest["workloads"]) == 7 and len(manifest["configs"]) == 7
    job = cell["job"]
    assert (cell["chips"], job["per_chip_batch"], job["seq_len"],
            job["accum_steps"], job["remat"], job["optimizer"],
            job["corpus"], job["collectives_in_step"],
            job["state_layout"]) == (
        1, 1, 8192, 1, "nothing", {"name": "adam", "learning_rate": 0.0002},
        {"kind": "increment_chains", "windows": 1024, "stride": 1}, [],
        "replicated")
    assert set(job["reasons"]) >= {"seq_len", "per_chip_batch", "remat",
                                   "custom_calls_per_layer"}


@pytest.fixture(scope="module")
def tiny_step_ops():
    """An operation for every ``op_name`` of the tiny cell's lowered train
    step, a millisecond each: the program's OWN scopes as a trace would
    carry them (this machine's profiler writes no device plane for the
    readers to read); a grouped product by the instruction's own name, as
    the chip's compiler names it."""
    import re

    import optax

    from cellbench.readers import scopes
    from cellbench.trace_reduce import Event
    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import init_lm_state, make_lm_train_step

    cell, config = load_cell(CELL)
    arch = archs.load(config)
    module = arch.build_module(config, cell["job"])
    tx = optax.adam(1e-3)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    state = jax.eval_shape(lambda words: init_lm_state(arch.program_tree(
        config, arch.init_weights(config, words)), tx),
        reference.split_seed(0))
    text = make_lm_train_step(module.apply, tx, mesh).lower(
        state, jax.ShapeDtypeStruct((2, 128), jnp.int32)).as_text(
            debug_info=True)
    ops = []
    for i, name in enumerate(sorted(set(re.findall(r'loc\("([^"]+)"', text)))):
        grouped = "ragged_dot" in name
        kind = "kOutput" if "dot_general" in name else "kLoop"
        short = f"ragged-dot.{i}" if grouped else f"fusion.{i}"
        ops.append(scopes.Op(Event(
            f"%{short} = f32[8]{{0}} fusion(%x), kind={kind}", 0.0, 1e6),
            "" if grouped else name, None, "fwd"))
    return ops


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_every_new_reader_reads_a_number_off_the_tiny_cells_own_scopes(
        metric, tiny_step_ops, monkeypatch):
    from cellbench.readers import hybrid, kimi_linear, scopes

    chips = lambda r: {0: scopes.ChipOps(1, 1e6 * len(tiny_step_ops),
                                         tiny_step_ops)}
    monkeypatch.setattr(hybrid, "_chips", chips)
    monkeypatch.setattr(scopes, "_chips", chips)
    cell, config = load_cell(CELL)
    r = harness.Reading(cell, config, PEAK,
                        {"per_chip_batch": 2, "seq_len": 128}, {}, {})
    got = getattr(kimi_linear, metric)(r)
    assert got is not None and got > 0, metric
    if metric == "kda_mixer_ms_per_step":
        # the mixers whole hold their gates and their scan, and more
        assert got > kimi_linear.kda_gate_ms_per_step(
            r) + kimi_linear.kda_scan_ms_per_step(r)
    if metric == "latent_attn_ms_per_step":
        assert got > kimi_linear.latent_kv_ms_per_step(r)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_trace_without_its_scopes(
        metric, tmp_path, scoped_trace_dir, monkeypatch):
    """The borrowed trace is of the GPT-2 cell: nothing under ``kda``,
    ``latent_attn`` or ``moe``, no grouped product.  Every new reader
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
    assert getattr(importlib.import_module(module), fn)(reading) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_in_a_program_without_its_scopes(
        metric, monkeypatch):
    """On the parent's program (``names`` without ``KDA``) the readers
    return ``None`` before looking at any trace."""
    import types

    from cellbench.readers import hybrid, kimi_linear

    old = types.SimpleNamespace(**{
        k: v for k, v in vars(hybrid.names).items()
        if k not in ("KDA", "KDA_GATE", "LATENT_ATTN", "LATENT_KV")})
    monkeypatch.setattr(hybrid, "names", old)
    monkeypatch.setattr(hybrid, "_chips", lambda r: 1 / 0)
    cell, config = load_cell(CELL)
    reading = harness.Reading(cell, config, PEAK,
                              {"per_chip_batch": 2, "seq_len": 128}, {}, {})
    assert getattr(kimi_linear, metric)(reading) is None


def test_the_readers_pick_their_ops_from_scoped_events(monkeypatch):
    from cellbench.readers import hybrid, kimi_linear, scopes
    from cellbench.trace_reduce import Event

    def op(name, scope, dur, kind="kLoop", kernel=None):
        e = Event(f"%{name} = f32[8]{{0}} fusion(%x), kind={kind}", 0.0, dur)
        return scopes.Op(e, scope, kernel, "fwd")

    fwd = "jit(step)/jvp(HybridLM)/"
    bwd = "jit(step)/transpose(jvp(HybridLM))/checkpoint/"
    kda, latent = "layer_1/kda/kda/", "layer_3/latent_attn/latent_attn/"
    moe = fwd + "layer_2/experts/moe/"
    ops = [
        op("fusion.1", fwd + kda + "q_proj/dot_general", 2e6, "kOutput"),
        op("fusion.2", fwd + kda + "kda_gate/f_b_proj/dot_general", 4e6,
           "kOutput"),
        op("fusion.3", bwd + kda + "kda_gate/mul", 8e6),
        op("fusion.4", fwd + kda + "delta_rule/while/body/dot_general", 16e6,
           "kOutput"),
        op("fusion.5", bwd + kda + "delta_rule/exp", 32e6),
        # another decoder's per-head mixer: under linear_attn, not under kda
        op("fusion.6", fwd + "layer_0/linear_attn/linear_attn/mul", 64e6),
        op("fusion.7", fwd + latent + "latent_kv/kv_b_proj/dot_general",
           128e6, "kOutput"),
        op("flash_fwd.1", fwd + latent + "pallas_call", 256e6,
           kernel=flops.FLASH_FWD),
        op("fusion.8", bwd + latent + "transpose", 512e6),
        op("fusion.9", moe + "top_k", 1024e6),
        op("fusion.10", moe + "shared_expert/dot_general", 2048e6, "kOutput"),
        op("ragged-dot.3", "", 4096e6),
        op("fusion.11", bwd + "layer_2/experts/moe/moe_combine/scatter-add",
           8192e6),
    ]
    chips = lambda r: {0: scopes.ChipOps(2, 2e10, ops)}
    monkeypatch.setattr(hybrid, "_chips", chips)
    monkeypatch.setattr(scopes, "_chips", chips)
    _, config = harness.load_cell(REAL_CELL)
    r = harness.Reading({"name": REAL_CELL}, config, PEAK,
                        {"per_chip_batch": 1, "seq_len": 8192}, {}, {})
    assert kimi_linear.kda_mixer_ms_per_step(r) == (2 + 4 + 8 + 16 + 32) / 2
    assert kimi_linear.kda_gate_ms_per_step(r) == (4 + 8) / 2
    assert kimi_linear.kda_scan_ms_per_step(r) == (16 + 32) / 2
    assert kimi_linear.latent_attn_ms_per_step(r) == (128 + 256 + 512) / 2
    assert kimi_linear.latent_kv_ms_per_step(r) == 128 / 2
    assert kimi_linear.kimi_moe_ms_per_step(r) == (
        1024 + 2048 + 4096 + 8192) / 2
    assert kimi_linear.kimi_moe_dispatch_ms_per_step(r) == (1024 + 8192) / 2
    arch = archs.load(config)
    for fn, reader, ms in (
            ("kda_scan_work", kimi_linear.kda_scan_roofline, 24.0),
            ("expert_work", kimi_linear.kimi_experts_roofline, 2048.0)):
        least, which = flops.roofline_seconds(
            *getattr(arch, fn)(config, 1, 8192), PEAK)
        assert which == "memory", fn
        assert reader(r) == pytest.approx(100 * least * 1e3 / ms), fn


def test_the_yardsticks_counts_of_the_real_configuration():
    """Each count against one written out by hand: a KDA layer, the flash
    kernels at 192 | 128, the experts, the step."""
    _, config = harness.load_cell(REAL_CELL)
    arch = archs.load(config)
    shapes = {**arch.weight_shapes(config), **arch.buffer_shapes(config)}
    assert sum(int(np.prod(s)) for s in shapes.values()) == config[
        "as_run"]["parameters"] == 602_434_432
    *layers, head = arch.forward_flops_per_token(config, 8192)
    kda, latent = layers[1], layers[3]
    # q, k, v, o at 2304 x 4096; two gates of 2304 x 128 and 128 x 4096;
    # the write strength 2304 x 32
    assert kda["kda_matmuls"] == 2 * (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    # a position of a head's chunked recurrence: three products against the
    # state (128 x 128 each) and, over the 32 positions before it in its
    # chunk of 64 in the mean, its row of A, of q k^T and of T into w (128
    # each) and of T into u and of (q k^T) v' (128 each)
    per_head = 3 * 128 * 128 + 32 * (3 * 128 + 2 * 128)
    assert per_head == 69_632
    assert kda["kda_scan"] == 2 * 32 * per_head == arch.kda_scan_flops_per_token(
        arch.dims(config))
    assert layers[0] == {**{k: kda[k] for k in ("kda_matmuls", "kda_scan")},
                         "dense_ffn": 2 * 3 * 2304 * 9216}
    assert latent["latent_matmuls"] == 2 * (
        2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304)
    pairs = 8192 * 8193 // 2
    assert pairs == flops.causal_pairs(8192)
    # scores at 192 wide and values at 128
    assert latent["attn_pairs"] == 2 * pairs * 32 * (192 + 128) / 8192
    assert kda["router"] == 2 * 2304 * 256
    assert kda["held_experts"] == 3 * 2 * 2304 * 1024 * 8 * 8 / 256
    assert kda["shared_expert"] == 3 * 2 * 2304 * 1024
    assert head == {"head": 2 * 2304 * 20480}
    assert arch.train_flops_per_token(config, 8192) == 3 * sum(
        sum(part.values()) for part in (*layers, head)) == pytest.approx(
            2.3187e9, rel=1e-4)
    work = arch.kernel_work(config, 1, 8192)
    # one latent layer: each kernel two products a live pair, one at the
    # scores' width and one at the values'
    for kernel in flops.FLASH_KERNELS:
        assert work[kernel][0] == 2 * pairs * 32 * (192 + 128)
    wide, narrow = 8192 * 32 * 192 * 2, 8192 * 32 * 128 * 2   # bf16 tensors
    # forward q, k | v, o; dq writes dq, dk/dv dk and dv; the backward
    # kernels share the reads of q, k | v, o, do
    assert work[flops.FLASH_FWD][1] == 2 * wide + 2 * narrow
    assert work[flops.FLASH_BWD_DQ][1] == wide + (2 * wide + 3 * narrow) / 2
    assert work[flops.FLASH_BWD_DKV][1] == wide + narrow + (
        2 * wide + 3 * narrow) / 2
    assert sum(work[k][1] for k in flops.FLASH_KERNELS) == (
        6 * wide + 6 * narrow)
    # four KDA layers: forward once, backward twice; q, k, v, o in bf16, g a
    # number a channel and beta a number a head in float32
    ops, bytes_ = work[arch.KDA_SCAN]
    assert (ops, bytes_) == arch.kda_scan_work(config, 1, 8192)
    assert ops == 3 * 4 * 8192 * 2 * 32 * per_head
    assert bytes_ == 3 * 4 * 8192 * 32 * (2 * 4 * 128 + 4 * 128 + 4)
    least, which = flops.roofline_seconds(ops, bytes_, PEAK)
    assert which == "memory" and least * 1e3 == pytest.approx(5.915, rel=1e-3)
    ops, _ = arch.expert_work(config, 1, 8192)
    assert ops == 4 * 2048 * 6 * 3 * 2304 * 1024


def test_the_configuration_keeps_every_published_number_but_the_cuts():
    """Every number of the catalog's ``config`` under its own key; the keys
    that differ are the ones ``reduced`` lists, with the published value
    beside them; no width among them."""
    _, config = harness.load_cell(REAL_CELL)
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, catalog.read_text().splitlines())
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"])
    assert {k: row["config"][k] for k in differs} == config["published"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # layers 1-5 of the published lists: the leading dense layer and the
    # whole period that follows it; the group's widths as published
    published = row["config"]["linear_attn_config"]
    ours = config["linear_attn_config"]
    assert ours["kda_layers"] == [i for i in published["kda_layers"]
                                  if i <= 5] == [1, 2, 3, 5]
    assert ours["full_attn_layers"] == [
        i for i in published["full_attn_layers"] if i <= 5] == [4]
    assert {k: v for k, v in ours.items() if not k.endswith("_layers")} == {
        k: v for k, v in published.items() if not k.endswith("_layers")}
    assert config["as_run"]["router_experts"] == row["config"]["num_experts"]
    assert config["num_experts"] * 32 == row["config"]["num_experts"]
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["first_k_dense_replace"] == 1
    for key in ("departures", "assumed", "deployment"):
        assert config[key]
    run = config["as_run"]
    assert (run["kda_chunk"], run["kda_sub_block"], run["kda_gate_floor"],
            run["compute_dtype"], run["param_dtype"]) == (
        64, 16, 5.0, "bfloat16", "float32")


def test_every_leaf_is_one_or_two_axes_and_goes_round_the_programs_tree():
    _, config = load_cell(CELL)
    arch = archs.load(config)
    weights = arch.init_weights(config, reference.split_seed(1))
    assert set(weights) == set(arch.weight_shapes(config)) | set(
        arch.buffer_shapes(config))
    # layers 1 and 2 are of one shape and follow each other: their tensors
    # are stacked
    assert {k: v.shape[0] for k, v in weights.items()
            if k in arch.STACKED} == dict.fromkeys(arch.STACKED, 2)
    a_layer = arch.unstacked(config, weights)
    assert all(w.ndim in (1, 2) for w in a_layer.values())
    tree = arch.program_tree(config, weights)
    back = arch.named_leaves(config, tree)
    for name, leaf in zip(arch.leaf_names(config), back):
        np.testing.assert_array_equal(leaf, a_layer[name], err_msg=name)
    # the layers of one shape are seeded apart
    assert not np.array_equal(a_layer["layer_1.q_proj"],
                              a_layer["layer_2.q_proj"])
    # the program's own init has the same tree
    module = arch.build_module(config, {"remat": "nothing"})
    made = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, tree)
