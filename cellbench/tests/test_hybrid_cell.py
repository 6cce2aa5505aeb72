"""The ``qwen3_next`` architecture (``archs/qwen3_next.py``: delta-rule
layers, gated attention, a share of routed experts) through the ``train_lm``
runner end to end on one CPU device, at the tiny configuration
``data/tiny-hybrid.json`` added as a real one is (a configuration file and a
cell file; the module is found by the configuration's ``model_type``):
the contract line, the float32 reference deciding ``correct``, the three
planted faults of ``test_rehearsal.py`` and one of this architecture's own
(the carried state zeroed at every chunk boundary), the fp8 control failing
the cell's limits, the new readers on a run without their scopes, and the
counts the yardstick keeps for it."""

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
from cellbench.tests.test_rehearsal import KEYS, PEAK, drive, manifest_with

HERE = Path(__file__).resolve().parents[1]
CELL = "tiny-hybrid-train-1dev"
REAL_CELL = "qwen3next-train-ep16share-8k"


def test_the_tiny_cell_is_of_the_real_cells_architecture():
    _, tiny = load_cell(CELL)
    _, real = harness.load_cell(REAL_CELL)
    assert tiny["model_type"] == real["model_type"] == "qwen3_next"
    assert archs.load(tiny) is archs.load(real)
    assert archs.load(tiny).dims(tiny)["kinds"] == archs.load(real).dims(
        real)["kinds"]


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


def break_step(monkeypatch, wrap):
    real = train_lm.make_lm_train_step

    def broken(apply_fn, tx, mesh, **kw):
        return jax.jit(wrap(real(apply_fn, tx, mesh, donate_state=False,
                                 **kw)))

    monkeypatch.setattr(train_lm, "make_lm_train_step", broken)


def unchanged(step):
    return lambda state, tokens: (state, step(state, tokens)[1])


def half_batch(step):
    def half(state, tokens):
        n = tokens.shape[0] // 2
        return step(state, tokens.at[n:].set(tokens[:n]))
    return half


def flipped(step):
    def flip(state, tokens):
        new, loss = step(state, tokens)
        params = jax.tree.map(lambda old, p: 2.0 * old - p, state.params,
                              new.params)
        return type(new)(params=params, opt_state=new.opt_state), loss
    return flip


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
    """This architecture's own fault: the chunked scan forgets what it
    carried from chunk to chunk (every 64 positions start from a zero
    state).  Each chunk alone is still right, the shapes and the norms
    hardly move; the reference, a position at a time, tells."""
    from tpudist.models import hybrid

    real = hybrid.chunked_gated_delta_rule

    def forgets(q, k, v, g, beta, *, chunk=64):
        b, s, h, _ = q.shape
        cut = lambda x: x.reshape(b * s // chunk, chunk, *x.shape[2:])
        return real(*map(cut, (q, k, v, g, beta)),
                    chunk=chunk).reshape(b, s, h, -1)

    monkeypatch.setattr(hybrid, "chunked_gated_delta_rule", forgets)
    _, _, _, outcome = drive(CELL, trace=False, tmp_path=tmp_path,
                             seconds=0.3)
    assert outcome["correct"] is False
    assert [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("[check] grad_dir_gap=") and l.endswith("OVER")]


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


def test_the_new_readers_find_nothing_in_a_trace_without_their_scopes(
        tmp_path, scoped_trace_dir, monkeypatch):
    """The borrowed trace is of the GPT-2 cell, from before these scopes:
    every new reader returns ``None`` and none raises, which is what the
    parent commit's program gives a traced run of this PR's readers."""
    cell, config, devices, outcome = drive(CELL, trace=True,
                                           tmp_path=tmp_path, seconds=0.3)
    scratch = tmp_path / "scratch"
    (scratch / "trace").mkdir(parents=True)
    (scratch / "trace" / CELL).symlink_to(scoped_trace_dir,
                                          target_is_directory=True)
    monkeypatch.setattr(harness, "SCRATCH", scratch)
    reds = trace_reduce.reduce_trace(
        trace_reduce.load(trace_reduce.find_xplane(scoped_trace_dir)),
        **outcome["trace_hints"])
    reading = harness.Reading(cell, config, PEAK, outcome["counters"],
                              outcome["spans"], reds)
    new = {p.stem: json.loads(p.read_text())
           for p in (HERE / "layer_metrics").glob("*.json")
           if REAL_CELL in json.loads(p.read_text()).get("cells", [])}
    assert len(new) == 7
    for metric, spec in new.items():
        module, fn = spec["reader"].split(":")
        assert module == "cellbench.readers.hybrid"
        assert getattr(importlib.import_module(module), fn)(reading) is None


def test_the_readers_pick_the_scopes_and_the_grouped_products(monkeypatch):
    from cellbench.readers import hybrid, scopes
    from cellbench.trace_reduce import Event

    def op(name, scope, dur):
        e = Event(f"%{name} = f32[8]{{0}} fusion(%x), kind=kLoop", 0.0, dur)
        return scopes.Op(e, scope, None, "fwd")

    grouped = Event('%ragged-dot-none.3 = bf16[8,8]{1,0} custom-call(%a), '
                    'custom_call_target="tpu_custom_call"', 0.0, 4e6)
    consumer = Event("%fusion.9 = f32[8]{0} fusion(%ragged-dot-none.3), "
                     "kind=kLoop", 0.0, 16e6)
    ops = [
        op("fusion.1", "jit(step)/jvp(HybridLM)/layer_0/linear_attn/"
           "linear_attn/delta_rule/exp", 2e6),
        op("fusion.2", "jit(step)/jvp(HybridLM)/layer_0/linear_attn/"
           "mixer_norm/mul", 1e6),
        scopes.Op(grouped, "ragged-dot-none", None, None),
        scopes.Op(consumer, "jit(step)/jvp(HybridLM)/layer_0/experts/moe/"
                  "scatter-add", None, "fwd"),
        op("fusion.4", "jit(step)/jvp(HybridLM)/layer_0/experts/moe/"
           "shared_expert/mul", 8e6),
        scopes.Op(Event("%fusion.5 = f32[8]{0} fusion(%x), kind=kOutput",
                        0.0, 32e6),
                  "jit(step)/jvp(HybridLM)/layer_0/experts/moe/dot_general",
                  None, "fwd"),
    ]
    monkeypatch.setattr(hybrid, "_chips",
                        lambda r: {0: scopes.ChipOps(2, 1e9, ops)})
    monkeypatch.setattr(scopes, "_chips",
                        lambda r: {0: scopes.ChipOps(2, 1e9, ops)})
    _, config = harness.load_cell(REAL_CELL)
    r = harness.Reading({"name": REAL_CELL}, config, PEAK,
                        {"per_chip_batch": 2, "seq_len": 8192}, {}, {})
    assert hybrid.linear_attn_ms_per_step(r) == 1.5
    assert hybrid.delta_rule_ms_per_step(r) == 1.0
    # the grouped product by its own name; its consumer is not one
    assert hybrid.experts_ms_per_step(r) == 2.0
    assert hybrid.moe_ms_per_step(r) == 2.0 + 8.0 + 4.0 + 16.0
    assert hybrid.moe_dispatch_ms_per_step(r) == 8.0
    arch = archs.load(config)
    least, _ = flops.roofline_seconds(*arch.delta_rule_work(config, 2, 8192),
                                      PEAK)
    assert hybrid.delta_rule_roofline(r) == pytest.approx(100 * least * 1e3)
    least, _ = flops.roofline_seconds(*arch.expert_work(config, 2, 8192),
                                      PEAK)
    assert hybrid.experts_roofline(r) == pytest.approx(
        100 * least * 1e3 / 2.0)


def test_the_yardsticks_counts_of_the_real_configuration():
    _, config = harness.load_cell(REAL_CELL)
    arch = archs.load(config)
    shapes = arch.weight_shapes(config)
    assert sum(int(np.prod(s)) for s in shapes.values()) == config[
        "as_run"]["parameters"] == 625_667_136
    f = arch.forward_flops_per_token(config, 8192)
    # three of four layers linear; the head over the held slice of the
    # vocabulary; the held experts at their expected load
    assert f["held_experts"] == pytest.approx(
        3 * 2 * 2048 * 512 * 10 * 32 / 512)
    assert f["head"] == 2 * 2048 * 18992
    assert arch.train_flops_per_token(config, 8192) == pytest.approx(
        1.3809e9, rel=1e-3)
    work = arch.kernel_work(config, 2, 8192)
    assert set(work) == set(flops.FLASH_KERNELS)
    mha = flops.flash_kernel_work(batch=2, seq=8192, d_model=4096,
                                  n_layers=1)
    for kernel in flops.FLASH_KERNELS:
        assert work[kernel][0] == mha[kernel][0]
        assert work[kernel][1] < mha[kernel][1]     # grouped k, v
    # the algorithm's 12 tensors a layer: q, o forward and q, o, do, dq
    # backward are 4,096 wide; k, v forward and k, v, dk, dv backward an
    # eighth of that
    tensor = 2 * 8192 * 4096 * 2
    assert sum(b for _, b in work.values()) == pytest.approx(
        6 * tensor + 6 * tensor / 8)


def test_every_leaf_is_one_or_two_axes_and_goes_round_the_programs_tree():
    _, config = load_cell(CELL)
    arch = archs.load(config)
    weights = arch.init_weights(config, reference.split_seed(1))
    assert all(w.ndim in (1, 2) for w in weights.values())
    back = arch.named_leaves(config, arch.program_tree(config, weights))
    for name, leaf in zip(arch.leaf_names(config), back):
        np.testing.assert_array_equal(leaf, weights[name], err_msg=name)
    # per-position decays of the seeded init span slowest to fastest
    a_log = weights["layer_0.A_log"]
    decay = np.exp(-np.exp(np.asarray(a_log)) * np.log(2.0))
    assert decay.max() == pytest.approx(0.999, abs=1e-4)
    assert decay.min() == pytest.approx(0.5, abs=1e-4)
