"""Ask the v5e's compiler, from a sandbox without the chip, whether it
accepts what ``chip_smoke.py`` will run there: every Pallas kernel at the
real widths and pool size, and the whole d1024 train step.

Interpret-mode tests cannot see what Mosaic refuses (block shapes that
break the (8, 128) rule, SMEM/VMEM overflow at a real pool size, a bf16
matmul accumulator) — five serving kernels passed every interpret test
and none compiled.  A compile here is a rehearsal: nothing runs, so it
says nothing about results or speed (``chip_smoke.py`` checks numerics on
the chip).

The topology is described inside a module-scoped fixture, never at
import or collection time: only one process may hold the TPU library,
and under pytest-xdist every worker imports every test file.  All the
compiles live in this one file, in the test's own process, with the
persistent compilation cache off (a cache entry compiled for a described
chip cannot be read back without one).

What it costs tier-1 (the driver's command: six workers, ``--dist
loadfile``, a limit of 1,470 s): this file is one worker's for 586 s of
PR 46's whole run of 931 s (621 of 1,055 s in the driver's run on the
tree before), the cells' steps 66-147 s each.  It is heavy for cause: one
compile a cell holds that cell's bytes and programs.  One a cell, not one
a PR; and a split has to leave each part MANY tests (xdist hands files out
by their number of tests, largest first: a long file of few tests starts
last and ends the run).
"""

import contextlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402

W = chip_smoke.FULL


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args, sharding):
    """Lower + compile ``fn`` for the described chip from shapes alone."""
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    return jax.jit(fn).lower(*shapes).compile()


def test_topology_is_the_v5e(topo):
    """The peaks table and the attention tiles' table are keyed by this
    very device kind — an unknown kind is an error on the chip."""
    from tpudist.ops.attention import TILES
    from tpudist.utils.flops import chip_hbm_bytes_per_s, chip_peak_flops

    dev = topo.devices[0]
    assert dev.platform == "tpu" and len(topo.devices) == 4
    assert chip_peak_flops(dev) == 197e12
    assert chip_hbm_bytes_per_s(dev) == 8.19e11
    assert TILES[dev.device_kind].sub == V5E_SUB


# (batch, heads, seq, dh, block_q, block_k): the default row of
# tpudist.ops.attention.TILES — 512/512 from seq 1024, 512/1024 from seq
# 8192 — and the tiles its row for this chip selects (1024/1024)
FLASH = {
    "seq2048-dh128-512x512": (8, 8, 2048, 128, 512, 512),
    "seq2048-dh128-1024x1024": (8, 8, 2048, 128, 1024, 1024),
    "seq8192-dh64-512x1024": (4, 4, 8192, 64, 512, 1024),
}
# (batch, q heads, kv heads, seq, dh, block_q, block_k): the gated attention
# of cell qwen3next-train-ep16share-8k, 8 query heads a kv head at dh 256,
# with the tiles the table's row for this chip selects at 8,192 positions
FLASH_GQA_256 = (2, 16, 2, 8192, 256, 1024, 1024)
# the squares that row cuts a tile on the diagonal into
V5E_SUB = 256


@contextlib.contextmanager
def _counting_cut_tiles():
    """``(block, sub)`` of every kernel body traced with its diagonal tiles
    cut into squares: one a kernel."""
    import importlib

    kernels = importlib.import_module("tpudist.ops.flash_attention")
    cut, real = [], kernels._diagonal_strips
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kernels, "_diagonal_strips",
                      lambda *a: cut.append(a) or real(*a))
        yield cut


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("tiling", sorted(FLASH))
def test_flash_attention_compiles(one_chip, tiling, grad):
    from tpudist.ops import flash_attention

    b, h, s, dh, bq, bk = FLASH[tiling]

    def loss(q, k, v):
        return flash_attention(q, k, v, True, bq, bk, False).astype(
            jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else loss
    qkv = (jax.ShapeDtypeStruct((b, h, s, dh), jnp.bfloat16),) * 3
    text = _compile(fn, qkv, one_chip).as_text()
    # forward kernel; + dq and dk/dv kernels under grad
    assert text.count("tpu_custom_call") == (3 if grad else 1)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_packed_flash_compiles_at_head_dim_256_grouped_8_to_1(one_chip, grad):
    """``(1, 1024, 256)`` column blocks of a ``[2, 8192, 20 * 256]`` array:
    twice the VMEM a head of 128 takes, never run before this cell."""
    from tpudist.ops import flash_attention_packed

    b, h, kv, s, dh, bq, bk = FLASH_GQA_256

    def loss(qkv):
        return flash_attention_packed(qkv, h, kv, True, bq, bk, False, None,
                                      V5E_SUB).astype(jnp.float32).sum()

    qkv = jax.ShapeDtypeStruct((b, s, (h + 2 * kv) * dh), jnp.bfloat16)
    with _counting_cut_tiles() as cut:
        text = _compile(jax.grad(loss) if grad else loss, (qkv,),
                        one_chip).as_text()
    assert text.count("tpu_custom_call") == (3 if grad else 1)
    # Mosaic takes the strips' slices of the (1, 1024, 256) column blocks
    assert cut == [(bq, V5E_SUB)] * (3 if grad else 1)


def test_chunked_delta_rule_compiles_at_the_cells_shape(one_chip):
    """Plain XLA, forward and backward, 32 heads of 128 x 128 over 8,192
    positions; what the backward keeps is the carried state a chunk."""
    from tpudist.ops.gated_delta import chunked_gated_delta_rule

    b, s, h, d = 2, 8192, 32, 128

    def loss(q, k, v, g, beta):
        return chunked_gated_delta_rule(q, k, v, g, beta).astype(
            jnp.float32).sum()

    wide = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    gate = jax.ShapeDtypeStruct((b, s, h), jnp.float32)
    mem = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                   (wide, wide, wide, gate, gate), one_chip).memory_analysis()
    assert mem.temp_size_in_bytes < 4e9


def test_chunked_delta_rule_compiles_at_unequal_widths_beyond_beta_1(
        one_chip):
    """Cell ``olmohybrid-train-tp2share-8k``'s scan: 15 heads of key width
    96 and value width 192 (neither a multiple of the 128 lanes) over one
    row of 8,192 positions, the inverse by halves from 4-row blocks
    (``beta_max`` 2), forward and backward."""
    from tpudist.ops.gated_delta import chunked_gated_delta_rule

    b, s, h, dk, dv = 1, 8192, 15, 96, 192

    def loss(q, k, v, g, beta):
        return chunked_gated_delta_rule(q, k, v, g, beta, beta_max=2.0).astype(
            jnp.float32).sum()

    key = jax.ShapeDtypeStruct((b, s, h, dk), jnp.bfloat16)
    value = jax.ShapeDtypeStruct((b, s, h, dv), jnp.bfloat16)
    gate = jax.ShapeDtypeStruct((b, s, h), jnp.float32)
    mem = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                   (key, key, value, gate, gate), one_chip).memory_analysis()
    assert mem.temp_size_in_bytes < 1e9


def test_chunked_delta_rule_compiles_at_a_decay_a_channel(one_chip):
    """Cell ``kimilinear-train-ep32share-8k``'s scan: 32 heads of 128 x 128
    over one row of 8,192 positions with ``g [b, s, h, dk]``, forward and
    backward: the sub-blocks' scaled keys are ``[128, 1, 32, 4, 64, 128]``
    (268 MB in bf16) and nothing of the chunk's ``[16, 16, dk]`` pairs is
    ever laid out."""
    from tpudist.ops.gated_delta import chunked_gated_delta_rule

    b, s, h, d = 1, 8192, 32, 128

    def loss(q, k, v, g, beta):
        return chunked_gated_delta_rule(q, k, v, g, beta).astype(
            jnp.float32).sum()

    wide = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    channel = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32)
    head = jax.ShapeDtypeStruct((b, s, h), jnp.float32)
    mem = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                   (wide, wide, wide, channel, head),
                   one_chip).memory_analysis()
    assert mem.temp_size_in_bytes < 4e9


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_compiles_at_values_narrower_than_keys(one_chip,
                                                               grad):
    """The latent layer's call: 32 heads whose queries and keys are 192 wide
    (one and a half lane tiles: every block's last dimension is the array's
    own, whole) on values of 128, head-major, the row's 1024 x 1024 tiles by
    squares of 256; the three kernels under their names, the outputs at the
    values' width and ``dq``, ``dk`` at the scores'."""
    from tpudist.ops import flash_attention

    b, h, s, d, d_v = 1, 32, 8192, 192, 128

    def loss(q, k, v):
        return flash_attention(q, k, v, True, 1024, 1024, False, None,
                               V5E_SUB).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else loss
    wide = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
    narrow = jax.ShapeDtypeStruct((b, h, s, d_v), jnp.bfloat16)
    with _counting_cut_tiles() as cut:
        text = _compile(fn, (wide, wide, narrow), one_chip).as_text()
    assert sorted(_kernels_named(text)) == (
        ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"] if grad
        else ["flash_fwd"])
    assert cut == [(1024, V5E_SUB)] * (3 if grad else 1)
    # the output at the values' width, ``dq`` and ``dk`` at the scores'
    if grad:
        assert [x.shape[-1] for x in jax.eval_shape(
            fn, wide, wide, narrow)] == [d, d, d_v]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_packed_flash_attention_compiles_at_the_cells_shape(one_chip, grad):
    """The packed entry's blocks — ``(1, 1024, 128)`` column blocks of a
    ``[4, 2048, 36·128]`` array — at cell ``cgpt590m-train-1chip``'s shape
    and the tuned tiles: what Mosaic's block rule has to accept."""
    from tpudist.ops import flash_attention_packed

    b, s, h, dh = 4, 2048, 12, 128

    def loss(qkv):
        return flash_attention_packed(qkv, h, h, True, 1024, 1024, False,
                                      None, V5E_SUB).astype(jnp.float32).sum()

    qkv = jax.ShapeDtypeStruct((b, s, 3 * h * dh), jnp.bfloat16)
    with _counting_cut_tiles() as cut:
        text = _compile(jax.grad(loss) if grad else loss, (qkv,),
                        one_chip).as_text()
    assert text.count("tpu_custom_call") == (3 if grad else 1)
    # Mosaic takes the strips' slices of the (1, 1024, 128) column blocks
    assert cut == [(1024, V5E_SUB)] * (3 if grad else 1)


KERNEL_FAMILIES = ("paged_attention", "paged_prefill", "fused_sample",
                   "fused_residual", "fused_rope_qkv", "lora_delta")


@pytest.mark.parametrize("family", KERNEL_FAMILIES)
def test_serving_kernel_compiles_at_real_size(one_chip, family):
    """The very cases ``chip_smoke.py`` checks numerically on the chip —
    32 slots, 8 layers x 1024 blocks x 8 kv heads x dh 128, bf16 and int8
    pools, vocab 256 and 32768 — compiled from their shapes."""
    cases = [c for c in chip_smoke.kernel_cases(W)
             if c.name.split("/")[0] == family]
    assert cases, family
    for case in cases:
        args = jax.eval_shape(
            lambda: case.make(np.random.default_rng(0)))
        compiled = _compile(
            lambda *a: case.fn(*a, interpret=False), args, one_chip)
        assert "tpu_custom_call" in compiled.as_text(), case.name


def _abstract_lm(w, tx):
    """``(module, ModelState of ShapeDtypeStructs)`` for ``w`` — nothing
    is materialized (there is no device to hold it)."""
    from tpudist.models import create_transformer
    from tpudist.train import init_lm_state

    made = {}

    def init():
        made["module"], params = create_transformer(
            jax.random.PRNGKey(0), seq_len=w.seq, **w.model_kwargs())
        return init_lm_state(params, tx)

    return made, jax.eval_shape(init)


@pytest.fixture(scope="module")
def d1024_step(topo):
    """The whole train step of ``chip_smoke.py``'s first phase, compiled
    once from ``jax.eval_shape`` shapes.  The model picks its attention by
    asking JAX for the platform, so the test (not a new option of the
    program) answers with the described devices."""
    import optax

    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import make_lm_train_step, token_sharding

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
        mesh = make_mesh(MeshConfig(data=1), devices=topo.devices[:1])
        repl = NamedSharding(mesh, PartitionSpec())
        tx = optax.adam(1e-3)
        made, abstract = _abstract_lm(W, tx)
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
            abstract)
        tokens = jax.ShapeDtypeStruct((W.batch, W.seq), jnp.int32,
                                      sharding=token_sharding(mesh))
        return make_lm_train_step(made["module"].apply, tx, mesh).lower(
            state, tokens).compile()


def test_d1024_train_step_compiles_with_the_flash_kernel(d1024_step):
    # one forward + two backward kernels per layer, nothing gave way to
    # the blockwise XLA formulation
    assert d1024_step.as_text().count("tpu_custom_call") == 3 * W.n_layers
    # params + Adam moments + activations fit the chip's 16 GB of HBM
    mem = d1024_step.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 15e9


def _entry_ops(text: str):
    """``(opcode, shape, op_name)`` of every instruction the compiled
    module runs as an operation of its own: everything outside the
    computations a fusion or a reduction calls."""
    import re

    called = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    comp = None
    for line in text.split("\n"):
        header = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if header:
            comp = header.group(1)
            continue
        found = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (.*?)\s([a-z][\w\-]*)\(",
                         line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if found and op_name and comp not in called:
            yield found.group(2), found.group(1), op_name.group(1)


def test_d1024_step_moves_no_activation_round_the_attention(d1024_step):
    """Under scope ``attn`` nothing of ``b·s·d`` elements or more is
    transposed, copied or concatenated as an operation of its own: the
    flash kernels read the projection's output where it lies and the
    projection's backward reads dq, dk, dv where the kernels left them
    (the join is fused into its matmuls).  The parent's step had eleven
    such copies a layer.  The small f32 stats of ``delta`` may move."""
    import math
    import re

    from tpudist.telemetry import names

    moved = []
    for opcode, shape, op_name in _entry_ops(d1024_step.as_text()):
        if (opcode in ("copy", "transpose", "concatenate")
                and f"/{names.ATTN}/" in op_name):
            sizes = [math.prod(int(n) for n in dims.split(",") if n)
                     for dims in re.findall(r"\[([\d,]*)\]", shape)]
            if max(sizes, default=0) >= W.batch * W.seq * W.d_model:
                moved.append((opcode, shape, op_name))
    assert not moved, moved


def test_d1024_step_needs_no_more_temporaries_than_head_major(d1024_step):
    # With head-major operands (q, k, v, o transposed and a seq-major copy
    # of o, all kept for the backward) this step compiled to 3,178,668,032
    # bytes of temporaries (PR 27's parent); packed it keeps the projection's
    # output and one o a layer: 2,908,942,336.
    assert d1024_step.memory_analysis().temp_size_in_bytes <= 3_178_668_032


def _kernels_named(text: str) -> list:
    """The program's name of every Mosaic custom call of a compiled text,
    read where a device trace carries it: the call's ``kernel_metadata``
    (``pallas_call(metadata=)``), whatever the instruction is called."""
    import re

    return re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*'
        r'kernel_metadata=\{\s*"kernel"\s*:\s*"([^"]+)"', text)


def _kernel_grids(text: str) -> list:
    """``(name, grid)`` of every Mosaic custom call of a compiled text: the
    name as :func:`_kernels_named` reads it, the grid out of the call's own
    serialized body."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    grids = []
    for name, body in re.findall(
            r'"kernel"\s*:\s*"([^"]+)"\s*\}\}[^\n]*?"body":"([A-Za-z0-9+/=]+)"',
            text):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm()
        bounds = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>", asm)
        grids.append((name, tuple(int(n) for n in bounds.group(1).split(","))))
    return grids


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
def test_each_flash_kernel_is_named_once_a_layer(d1024_step, kernel):
    from tpudist.telemetry import names

    assert kernel in names.FLASH_KERNELS
    named = _kernels_named(d1024_step.as_text())
    assert len(named) == 3 * W.n_layers   # no custom call goes unnamed
    assert named.count(kernel) == W.n_layers


def test_four_chip_fsdp_step_keeps_the_flash_kernel(topo, monkeypatch):
    """Mosaic kernels cannot be partitioned automatically: inside a
    multi-chip jit the flash kernel must arrive wrapped per shard
    (``ops.attention._per_shard`` under the step builder's ambient mesh) —
    code that has only seen a CPU virtual mesh takes the XLA attention
    there and never meets the refusal.  Two layers: the point is the
    partitioning, not the size."""
    import dataclasses

    import optax

    from tpudist.parallel import fsdp_sharding
    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import make_lm_train_step, token_sharding

    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    w = dataclasses.replace(W, n_layers=2)
    mesh = make_mesh(MeshConfig(data=4), devices=topo.devices)
    tx = optax.adam(1e-3)
    made, abstract = _abstract_lm(w, tx)
    sharding = fsdp_sharding(mesh, abstract)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, sharding)
    tokens = jax.ShapeDtypeStruct((w.batch, w.seq), jnp.int32,
                                  sharding=token_sharding(mesh))
    text = make_lm_train_step(
        made["module"].apply, tx, mesh, state_sharding=sharding).lower(
            state, tokens).compile().as_text()
    assert text.count("tpu_custom_call") == 3 * w.n_layers
    assert "all-gather" in text and "reduce-scatter" in text
    # under the per-shard wrapper the instructions are ``shard_map.<n>``;
    # the kernel's own name still rides in its metadata
    assert sorted(_kernels_named(text)) == sorted(
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"] * w.n_layers)


def _cell_step(topo, name):
    """``(compiled step, job, dims)`` of a benchmark cell, built as the
    benchmark's runner builds it: the architecture's module from the cell's
    files, ``make_lm_train_step(module.apply, tx, mesh)``, compiled from
    shapes."""
    import json

    import optax

    from cellbench import archs, reference
    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import (init_lm_state, make_lm_train_step,
                               token_sharding)

    root = Path(__file__).resolve().parent.parent / "cellbench"
    cell = json.loads((root / "workloads" / f"{name}.json").read_text())
    config = json.loads(
        (root / "configs" / f"{cell['config']}.json").read_text())
    job, arch = cell["job"], archs.load(config)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
        mesh = make_mesh(MeshConfig(data=1), devices=topo.devices[:1])
        repl = NamedSharding(mesh, PartitionSpec())
        tx = optax.adam(job["optimizer"]["learning_rate"])
        abstract = jax.eval_shape(
            lambda words: init_lm_state(arch.program_tree(
                config, arch.init_weights(config, words)), tx),
            reference.split_seed(0))
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
            abstract)
        tokens = jax.ShapeDtypeStruct(
            (job["per_chip_batch"], job["seq_len"]), jnp.int32,
            sharding=token_sharding(mesh))
        step = make_lm_train_step(
            arch.build_module(config, job).apply, tx, mesh,
            accum_steps=job["accum_steps"]).lower(state, tokens).compile()
    return step, job, arch.dims(config)


@pytest.fixture(scope="module")
def hybrid_step(topo):
    """The whole train step of cell ``qwen3next-train-ep16share-8k`` (one
    chip's share of a 16-way expert-parallel deployment).
    ``memory_analysis()`` of THIS step is what fixes the cell's rows and
    remat policy."""
    return _cell_step(topo, "qwen3next-train-ep16share-8k")


@pytest.fixture(scope="module")
def gpt2_cell_step(topo):
    """The whole train step of cell ``cgpt590m-train-1chip``: 18 layers of
    12 heads of 128 over 2,048 positions, 4 rows, no remat."""
    return _cell_step(topo, "cgpt590m-train-1chip")


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
def test_gpt2_cell_step_names_each_flash_kernel_once_a_layer(gpt2_cell_step,
                                                             kernel):
    """The benchmark's runner ends the run unless the compiled step holds
    EXACTLY the custom calls the cell's file states (54 = 3 a layer), and
    its reader unless each carries a known name: cutting the diagonal
    tiles adds no call and renames none."""
    step, job, m = gpt2_cell_step
    text = step.as_text()
    assert text.count("tpu_custom_call") == (
        job["custom_calls_per_layer"] * m["layers"]) == 54
    assert _kernels_named(text).count(kernel) == m["layers"]


def test_gpt2_cell_step_needs_no_more_memory_than_whole_tiles_did(
        gpt2_cell_step):
    # PR 32's tree (whole diagonal tiles): arguments 8,006,918,656 +
    # temporaries 7,865,907,712 bytes; the kernels' scratch is VMEM
    mem = gpt2_cell_step[0].memory_analysis()
    assert mem.argument_size_in_bytes == 8_006_918_656
    assert mem.temp_size_in_bytes <= 7_865_907_712


def test_hybrid_cell_step_fills_one_chip_and_fits(hybrid_step):
    step, job, m = hybrid_step
    assert job["per_chip_batch"] == 2 and job["seq_len"] == 8192
    mem = step.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # 625,667,136 parameters x 12 bytes resident
    assert 7.4e9 < mem.argument_size_in_bytes < 7.6e9
    # the cell fills the chip (12 GB or more of the 16).  13.86 GiB since
    # PR 48 (13.98 before it): each layer's activation between mixer and
    # experts is kept under remat, so the experts' backward pass (buffers of
    # 81,920 rows, a block of 8,192 tokens' picks) does not run while the
    # mixer's recomputed forward is alive; a delta-rule layer keeps its
    # chunks' float32 inverse too (134 MB a layer, named flat: as it lies,
    # 64 columns in 128 lanes, the issue writer's rehearsal of the same step
    # held 14.86 GiB), and holds LESS at the compiler's peak than the step
    # that solved for it again there; the compiler allows 15.75 GiB
    assert 12e9 < held < 14.5 * 2 ** 30


def test_hybrid_cell_step_keeps_the_flash_kernels_and_groups_the_experts(
        hybrid_step):
    """One full-attention layer in the four: the three flash kernels by
    name (the forward twice, its layer being rematerialised), at head
    width 256 over 2 kv heads.  The expert layers' products are the
    compiler's grouped matmuls over the 32 experts HELD: no product over
    512 experts, and the count the benchmark's runner holds the step to."""
    import re

    step, job, m = hybrid_step
    text = step.as_text()
    assert sorted(_kernels_named(text)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd", "flash_fwd"]
    assert re.search(r"ragged-dot", text)
    assert text.count("tpu_custom_call") == 64 == (
        job["custom_calls_per_layer"] * m["layers"])
    assert not re.search(r"\[512,2048,512\]|\[512,512,2048\]", text)
    assert re.search(r"\[32,2048,512\]", text)
    # a 16th of the experts held: one buffer of the bound a block, no
    # windows (``moe.share_windows``)
    assert "[81920,2048]" in text and "[81920,512]" in text


@pytest.fixture(scope="module")
def olmo_hybrid_step(topo):
    """The whole train step of cell ``olmohybrid-train-tp2share-8k`` (one
    chip's share of a 2-way head-parallel deployment): 1 row x 8,192."""
    return _cell_step(topo, "olmohybrid-train-tp2share-8k")


def test_olmo_hybrid_cell_step_fills_one_chip_and_fits(olmo_hybrid_step):
    step, job, m = olmo_hybrid_step
    assert job["per_chip_batch"] == 1 and job["seq_len"] == 8192
    mem = step.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # 766,241,946 parameters x 12 bytes resident
    assert 9.1e9 < mem.argument_size_in_bytes < 9.3e9
    # 13.32 GiB: temporaries 5,106,875,392 bytes, the float32 gradient
    # among them (13.29 GiB and 5,077,698,560 before PR 48: a delta-rule
    # layer keeps its chunks' float32 inverse, 31.5 MB a layer over 15
    # heads).  PR 34's step, which computed each layer's feed-forward
    # twice, held 12.50 GiB (temporaries 4,226,611,712); the outputs of
    # the feed-forward's three products are now kept from forward to
    # backward (423.6 MB a layer, 1.69 GB in all), of which 0.85 GB are
    # still alive where the compiler's peak lies.  The issue's ceiling is
    # 15.0 GiB, the compiler allows 15.75
    assert 12e9 < held < 15.0 * 2 ** 30
    assert mem.temp_size_in_bytes >= 4_226_611_712 + 0.8e9


def test_olmo_hybrid_cell_step_computes_each_feed_forward_product_once(
        olmo_hybrid_step):
    """Four layers x (3 forward + 6 backward) products with the
    feed-forward's projections in their names, and not the 48 of a step
    that rematerialises the three forward ones (the compiler writes a
    product as a ``convolution``, alone or at the root of a fusion)."""
    import re

    step, job, m = olmo_hybrid_step
    products = [line for line in step.as_text().splitlines()
                if re.search(r" convolution\(", line) and re.search(
                    r"/mlp/(gate|up|down)_proj/dot_general", line)]
    assert len(products) == 4 * 9


def test_olmo_hybrid_cell_step_carries_exactly_the_three_flash_kernels(
        olmo_hybrid_step):
    """One full-attention layer in the four at 15 equal heads of 128: the
    three flash kernels by name, the forward twice (its layer is
    rematerialised), and no other custom call: the delta-rule scan and the
    dense feed-forward are plain XLA."""
    step, job, m = olmo_hybrid_step
    text = step.as_text()
    assert sorted(_kernels_named(text)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd", "flash_fwd"]
    assert text.count("tpu_custom_call") == 4 == (
        job["custom_calls_per_layer"] * m["layers"])
    assert "ragged-dot" not in text
    assert job["collectives_in_step"] == []
    assert "all-reduce" not in text and "all-gather" not in text


@pytest.mark.parametrize("fixture, arguments, temporaries, instructions", [
    ("hybrid_step", 7_508_078_592, 7_370_897_408, 22_866),
    ("olmo_hybrid_step", 9_195_254_272, 5_106_875_392, 17_156),
    ("nemotron_step", 8_410_485_760, 3_634_439_680, 23_365),
    ("gpt2_cell_step", 8_006_918_656, 7_865_907_712, 20_502),
    ("laguna_step", 8_065_987_072, 4_298_534_400, 17_838)],
    ids=["share_cell", "olmo_cell", "nemotron_cell", "gpt2_cell",
         "laguna_cell"])
def test_the_other_pattern_cells_steps_are_what_they_were(
        request, fixture, arguments, temporaries, instructions):
    """Since PR 48 a rematerialised layer whose mixer scans by the delta
    rule keeps its chunks' inverse (``names.DELTA_INVERSE``, float32, named
    flat), so the two such cells here are read again: one rematerialised
    inverse a layer is gone (ten batched products, the stacks, slices and
    concatenates of the halves), the share cell's step holds 739
    instructions fewer (23,605 before) and the olmo cell's 1,495 fewer
    (18,651: its inverse goes by four levels of halves from a base of 4
    rows); ``T`` costs the olmo cell 29.2 MB of temporaries (5,077,698,560
    before), and the share cell holds 133.3 MB LESS (7,504,172,032 before):
    what the rematerialised solve held at the compiler's peak was more than
    three layers' ``T``.  The nemotron, GPT-2 and laguna rows hold to the
    byte and the instruction: nothing of their steps scans by the delta
    rule, and their optimized HLO is the parent's text for text
    (``benchmarks/step_hlo.py``).  Before it:
    since PR 45 the share cell (one buffer), the two cells without
    experts here and the granite cell hold what they held (their optimized
    HLO is the parent's text for text, ``benchmarks/step_hlo.py``), and the
    two cells whose share goes by windows are read again: a trip scatters
    its window by strips in a loop of its own, so the nemotron step holds
    1,113 instructions more (22,252 before) and 3.4 MB of temporaries less
    (3,637,826,560), and the laguna step 941 more (16,897) and 73.9 MB more
    (4,224,679,936: the inner loop's tuple carries the window's result and
    its index vectors beside the sum).  Before it:
    since PR 44 the two cells without experts hold what they held, to
    the byte and the instruction (their optimized HLO is the parent's text
    for text, ``benchmarks/step_hlo.py``), and the two expert cells are
    read again: a rematerialised expert layer keeps its router's picks and
    their scores, and one of two sublayers its router's logits too, so the
    nemotron step lost 1,452 instructions (the rematerialised sorts, the
    gathers of the picks' scores, the backward's scatters) and 22.4 MB of
    temporaries, and the share cell's 430 instructions and holds 65.5 MB
    more at the compiler's peak (a layer's logits are 33.6 MB over its
    16,384 tokens).  Before it, since PR 41 all
    four accepted cells: the pattern decoder's sizes a
    softmax kind, its feed-forward arm a layer, rotary angles from given
    frequencies and the dispatch's tiles of a windowed call are data none
    of them states, so their steps hold the bytes and the instructions to
    the last one that PR 40's held (their optimized HLO was the parent's
    text for text once source locations were stripped: PERF.md section 6,
    PR 41).  Before it:
    both run ``remat_keeps`` and the share cell ``ExpertShare`` and
    ``expert_share``, where PR 40 names the outputs of an expert layer's
    dense products for a ONE-sublayer layer to keep: a name no policy
    names is an identity the compiler drops, so their steps hold the bytes
    and the instructions to the last one that PR 39's held (their
    optimized HLO was the parent's text for text once source locations
    were stripped: PERF.md section 6, PR 40)."""
    import re

    step, job, m = request.getfixturevalue(fixture)
    mem = step.memory_analysis()
    assert mem.argument_size_in_bytes == arguments
    assert mem.temp_size_in_bytes == temporaries
    assert len(re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = ", step.as_text(),
                          flags=re.M)) == instructions


def _step_text(line: int, op: str, body_op: str) -> str:
    """What ``as_text()`` of a compiled step looks like to
    ``benchmarks/step_hlo.py::strip``: the location tables, an instruction
    with its metadata, a Mosaic call whose serialized body names the line of
    its call site."""
    import base64

    body = base64.b64encode(
        f'module {{ "{body_op}"() : () -> () loc("/tree/ops/k.py":{line}:1) }}'
        .encode()).decode()
    return (f"HloModule jit_step\n\nFileNames\n1 \"/tree/models/m.py\"\n\n"
            f"FunctionNames\n1 \"f\"\n\nFileLocations\n1 {{file_name_id=1 "
            f"line={line}}}\n\nStackFrames\n1 {{file_location_id=1}}\n\n\n"
            f"ENTRY %main {{\n  %a = f32[8]{{0}} {op}(%x, %y), metadata="
            f"{{op_name=\"jit(step)/ssm_norm/{op}\" stack_frame_id={line}}}\n"
            f"  %k = f32[8]{{0}} custom-call(%a), custom_call_target="
            f"\"tpu_custom_call\", backend_config={{\"custom_call_config\": "
            f"{{\"body\":\"{body}\"}}}}\n}}\n")


@pytest.mark.parametrize("other, same", [
    ((7, "add", "k.op"), True),        # a line edited above the call sites
    ((3, "subtract", "k.op"), False),  # another instruction
    ((3, "add", "k.other"), False)],   # another kernel body
    ids=["locations", "instruction", "kernel_body"])
def test_step_hlo_strips_locations_and_nothing_else(other, same):
    """``benchmarks/step_hlo.py compare`` is what "the accepted cells' steps
    come out as they are" is judged by (PERF.md section 6, PR 43): source
    lines, scope names and the Mosaic bodies' locations go, every
    instruction and every kernel body stays."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "benchmarks"))
    try:
        from step_hlo import strip
    finally:
        sys.path.pop(0)
    was = strip(_step_text(3, "add", "k.op"))
    assert "/tree/" not in was and "ssm_norm" not in was
    assert "add(%x, %y)" in was and "tpu_custom_call" in was
    assert (strip(_step_text(*other)) == was) is same


def _router_sorts(text: str, n_experts: int) -> list:
    """The operands of each ``sort`` of a compiled step whose first result
    is ``[8192, n_experts]``: the router's sorts over a row of scores."""
    import re

    return [shapes.count(f"[8192,{n_experts}]") for shapes in re.findall(
        rf"= \((f32\[8192,{n_experts}\][^=]*?)\) sort\(", text)]


def _scatter_updates(text: str, result: str) -> list:
    """The shape of the update of every ``scatter`` instruction of a
    compiled step whose result is ``result`` (``"f32[8192,1024]"``)."""
    import re

    return [re.search(rf"%{re.escape(update)} = (\w+\[[\d,]*\])", text)[1]
            for update in re.findall(
                rf"= {re.escape(result)}\S* scatter\(%\S+, %\S+, %(\S+?)\)",
                text)]


def _picks_gathered(text: str, k: int) -> int:
    """The instructions of a compiled step that gather ``[8192, k]`` scores
    at the router's picks under an expert layer's scope: what
    ``take_along_axis`` behind a ``top_k`` compiles to (the compiler
    flattens it: a fusion over ``8192 x k`` numbers named for the
    gather, reshaped to ``[8192, k]``)."""
    import re

    from tpudist.telemetry import names

    return len(re.findall(
        rf"= f32\[8192,{k}\]\S* reshape\([^\n]*/{names.MOE}/"
        rf"jit\(take_along_axis\)/gather", text))


@pytest.fixture(scope="module")
def nemotron_step(topo):
    """The whole train step of cell ``nemotron3super-train-tp8ep64share-8k``
    (one chip's share of a deployment in which 64 chips share each layer,
    heads 8 ways and experts 64 ways): 1 row x 8,192."""
    return _cell_step(topo, "nemotron3super-train-tp8ep64share-8k")


def test_nemotron_cell_step_fills_one_chip_and_fits(nemotron_step):
    step, job, m = nemotron_step
    assert job["per_chip_batch"] == 1 and job["seq_len"] == 8192
    assert job["remat"] == "nothing" and job["accum_steps"] == 1
    mem = step.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # 700,862,960 parameters (and 5 x 512 numbers of choice bias) x 12
    # bytes resident
    assert 8.40e9 < mem.argument_size_in_bytes < 8.42e9
    # 11.22 GiB = 12.05 GB: temporaries 3,634,439,680 bytes (3,637,826,560
    # before PR 45's strips; 3,660,219,904
    # before PR 44 kept the router's picks, 1.4 MB a layer, and so freed
    # the rematerialised sorts' operands), the float32
    # gradient (2.80 GB) among them.  An expert layer takes what arrived
    # through windows of 22,528 rows (46 MB at 1,024 wide, 121 MB at 2,688)
    # and keeps from forward to backward its share's result and, since PR
    # 40, the outputs of the dense products its backward pass reads (the
    # router's logits, ``latent_down``'s, the shared expert's ``up``: 138.4
    # MB a layer, 0.61 GB more in all, of which 0.70 GB show at the
    # compiler's peak); PR 38's step, which ran those three products twice,
    # held 10.59 GiB (temporaries 2,962,675,200); PR 37's, which ran all
    # 180,224 assignments a layer through buffers of 369 MB and 969 MB,
    # 11.81 GiB (4,271,943,680).  The issue's ceiling is 15.0 GiB, the
    # compiler allows 15.75
    assert 11.9e9 < held < 11.5 * 2 ** 30 < 15.0 * 2 ** 30
    assert 2_962_675_200 + 0.6e9 <= mem.temp_size_in_bytes <= (
        2_962_675_200 + 0.8e9)


def test_nemotron_cell_step_keeps_the_flash_kernels_and_groups_the_experts(
        nemotron_step):
    """One attention layer in the eleven at 4 query heads on 1 key/value
    head of 128: the three flash kernels by name, the forward twice (its
    layer is rematerialised).  The five expert layers' products are the
    compiler's grouped matmuls over the 8 experts HELD at the latent
    width: no product over 512 experts.  They run over windows of 22,528
    rows (eight even shares of the 180,224 assignments a layer), inside
    two loops a layer, forward and backward (2 + 1 and 6 + 2 calls): the
    rematerialised forward's loop is dead, because the layer keeps the
    share's result, so the step counts the 59 calls the benchmark's
    runner holds it to.  The expert layers' dense products whose outputs
    the layer keeps run once: five layers x (``up`` forward and two
    backward, ``down`` forward and two backward) products under the shared
    expert's scope, 30, and not the 35 of a step that runs ``up`` again.
    The state-space scan is plain XLA.  No collective: one chip's share."""
    import re

    from tpudist.telemetry import names

    step, job, m = nemotron_step
    text = step.as_text()
    assert sorted(_kernels_named(text)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd", "flash_fwd"]
    assert text.count("tpu_custom_call") == 59 == (
        job["custom_calls_per_layer"] * m["layers"])
    assert re.search(r"ragged-dot", text)
    assert re.search(r"\[8,1024,2688\]", text)
    assert not re.search(r"\[512,1024,2688\]|\[512,2688,1024\]", text)
    assert "[22528,2688]" in text and "[22528,1024]" in text
    assert "[180224,2688]" not in text and "[180224,1024]" not in text
    # a trip scatters its window by strips of one even share (PR 45): the
    # ten scatter-adds into the result and into ``d_x`` take 2,816 rows, the
    # five of the weights' gradient as many numbers, none a window's 22,528
    assert _scatter_updates(text, "f32[8192,1024]") == ["f32[2816,1024]"] * 10
    assert _scatter_updates(text, "f32[180224]") == ["f32[2816]"] * 5
    assert job["collectives_in_step"] == []
    assert "all-reduce" not in text and "all-gather" not in text
    shared = [line for line in text.splitlines()
              if re.search(r" convolution\(", line)
              and f"/{names.SHARED_EXPERT}/" in line]
    assert len(shared) == 5 * 6
    # the router runs once a step (PR 44): ONE sort over the 512 scores a
    # layer, with the scores as its third operand, where the forward and
    # the rematerialised forward each had a top-22 of their own; and no
    # gather picks the 22 scores out of ``[8192, 512]``
    assert _router_sorts(text, 512) == [3] * 5
    assert _picks_gathered(text, 22) == 0


@pytest.fixture(scope="module")
def laguna_step(topo):
    """The whole train step of cell ``lagunas21-train-tp2ep32share-8k`` (one
    chip's share of a deployment in which 32 chips share each layer, heads 2
    ways and experts 32 ways): 1 row x 8,192."""
    return _cell_step(topo, "lagunas21-train-tp2ep32share-8k")


def test_laguna_cell_step_fills_one_chip_and_fits(laguna_step):
    step, job, m = laguna_step
    assert job["per_chip_batch"] == 1 and job["seq_len"] == 8192
    assert job["remat"] == "nothing" and job["accum_steps"] == 1
    mem = step.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # 672,125,952 parameters x 12 bytes resident
    assert 8.06e9 < mem.argument_size_in_bytes < 8.07e9
    # 11.51 GiB = 12.36 GB: temporaries 4,298,534,400 bytes (4,224,679,936
    # before PR 45, whose loop over a trip's strips carries the window's
    # result beside the sum; 4,254,528,512 before PR 44), the float32
    # gradient (2.69 GB) among them; layer 0 keeps its dense feed-forward's
    # three products from forward to backward (503 MB with ``mixer_out``),
    # the expert layers ``mixer_out`` (50 MB each) and, since PR 44, their
    # router's logits and picks (9 MB each), and an expert layer
    # takes what arrived through windows of 20,480 rows (126 MB at 3,072
    # wide).  The issue asks over 12 GB and under 15.0 GiB; the compiler
    # allows 15.75
    assert 12.0e9 < held < 11.8 * 2 ** 30 < 15.0 * 2 ** 30


def test_laguna_cell_step_runs_the_band_and_groups_the_experts(laguna_step):
    """Five attention layers, three of them inside a window of 512: the
    three flash kernels by name, the forward twice a layer (every layer is
    rematerialised), 20 calls; the full layers' grids over every 1024 x
    1024 tile, the sliding layers' over the band's run of 512 x 512 tiles
    (two key tiles a query tile, two query tiles a key tile and group
    member).  The four expert layers' products are the compiler's grouped
    matmuls over the 8 experts HELD at the model's width, over windows of
    20,480 rows (eight even shares of the 81,920 assignments a layer): 15 a
    layer, 80 in all, what the benchmark's runner holds the step to.  No
    collective: one chip's share."""
    import re

    step, job, m = laguna_step
    text = step.as_text()
    assert sorted(_kernels_named(text)) == (
        ["flash_bwd_dkv"] * 5 + ["flash_bwd_dq"] * 5 + ["flash_fwd"] * 10)
    assert text.count("tpu_custom_call") == 80 == (
        job["custom_calls_per_layer"] * m["layers"])
    assert re.search(r"ragged-dot", text)
    assert re.search(r"\[8,3072,1024\]", text)
    assert not re.search(r"\[256,3072,1024\]|\[256,1024,3072\]", text)
    assert "[20480,3072]" in text and "[20480,1024]" in text
    assert "[81920,3072]" not in text and "[81920,1024]" not in text
    # a trip scatters its window by strips of one even share (PR 45): 2,560
    # rows a scatter-add, none a window's 20,480
    assert _scatter_updates(text, "f32[8192,3072]") == ["f32[2560,3072]"] * 8
    assert _scatter_updates(text, "f32[81920]") == ["f32[2560]"] * 4
    assert job["collectives_in_step"] == []
    assert "all-reduce" not in text and "all-gather" not in text
    # the router runs once a step (PR 44): ONE top-10 over the 256 scores a
    # layer (scores and columns: no bias, so the picks' scores are the
    # sort's own values) where forward and rematerialised forward each had
    # one, and no gather picks the 10 scores out of ``[8192, 256]``
    assert _router_sorts(text, 256) == [2] * 4
    assert _picks_gathered(text, 10) == 0
    # the three sliding layers hold 36 query heads, the two full ones 24
    outs = re.findall(r"%flash_fwd[\w.]* = \(bf16\[1,8192,(\d+)\]", text)
    assert sorted(outs) == ["3072"] * 4 + ["4608"] * 6
    # 24 heads over 8 x 8 tiles, 6 a key/value head; 36 heads over 16 query
    # tiles x the band's 2, 9 a key/value head
    assert sorted(_kernel_grids(text)) == sorted(
        [("flash_fwd", (24, 8, 8))] * 4 + [("flash_bwd_dq", (24, 8, 8))] * 2
        + [("flash_bwd_dkv", (4, 8, 8 * 6))] * 2
        + [("flash_fwd", (36, 16, 2))] * 6
        + [("flash_bwd_dq", (36, 16, 2))] * 3
        + [("flash_bwd_dkv", (4, 16, 2 * 9))] * 3)


@pytest.fixture(scope="module")
def granite_step(topo):
    """The whole train step of cell ``granite4hmicro-train-tp2share-8k`` (one
    chip's share of a 2-way head-parallel deployment whose state-space group
    both members hold whole): 1 row x 8,192."""
    return _cell_step(topo, "granite4hmicro-train-tp2share-8k")


def test_granite_cell_step_fills_one_chip_and_fits(granite_step):
    step, job, m = granite_step
    assert job["per_chip_batch"] == 1 and job["seq_len"] == 8192
    assert job["remat"] == "nothing" and job["accum_steps"] == 1
    mem = step.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # 730,040,416 parameters x 12 bytes resident: 52% of the chip
    assert 8.76e9 < mem.argument_size_in_bytes < 8.77e9
    # 13.74 GiB = 14.76 GB: temporaries 5,994,230,784 bytes, the float32
    # gradient (2.92 GB) among them, the 8,192 x 50,176 logits and their
    # gradient, and ten layers' inputs and ``mixer_out`` (33.6 MB each).
    # The feed-forwards' products are NOT kept (``as_run.ffn_products_kept``
    # false, ISSUE 43's choice): kept, they are 302 MB a layer, 3.0 GB, and
    # the step fits at 14.55 GiB only because the compiler then runs the
    # head's product twice of its own accord (PERF.md section 6, PR 43).
    # The issue asks over 12 GB and under 15.0 GiB; the compiler allows
    # 15.75
    assert 12.0e9 < held < 15.0 * 2 ** 30
    assert held > 13.5 * 2 ** 30


def test_granite_cell_step_runs_head_major_flash_and_mlp_inputs_twice(
        granite_step):
    """One attention layer in the ten at 16 query heads on 4 key/value heads
    of 64: the three flash kernels by name on the dispatch's head-major
    route (a 64-wide head is half a 128-lane tile: ``[1, 16, 8192, 64]``
    operands, 1024 x 1024 tiles: 16 heads over 8 x 8 tiles, 4 query heads a
    key/value head), the forward twice (its layer is
    rematerialised), and no other custom call: the state-space scan and the
    feed-forward are plain XLA.  A feed-forward keeps nothing of its three
    products, so each layer's rematerialised forward runs ``gate`` and
    ``up`` again (``down``'s output is read by nothing in the backward
    pass: the norm comes before the sublayer): ten layers x (3 forward + 2
    again + 6 backward) products with the projections in their names, where
    a step that kept them would hold 90.  No collective: one chip's
    share."""
    import re

    step, job, m = granite_step
    text = step.as_text()
    assert sorted(_kernels_named(text)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd", "flash_fwd"]
    assert text.count("tpu_custom_call") == 4 == (
        job["custom_calls_per_layer"] * m["layers"])
    assert sorted(_kernel_grids(text)) == sorted(
        [("flash_fwd", (16, 8, 8))] * 2 + [("flash_bwd_dq", (16, 8, 8))]
        + [("flash_bwd_dkv", (4, 8, 8 * 4))])
    assert "ragged-dot" not in text
    assert job["collectives_in_step"] == []
    assert "all-reduce" not in text and "all-gather" not in text
    products = [line for line in text.splitlines()
                if re.search(r" convolution\(", line) and re.search(
                    r"/mlp/(gate|up|down)_proj/dot_general", line)]
    assert len(products) == 10 * (3 + 2 + 6)


@pytest.fixture(scope="module")
def kimi_step(topo):
    """The whole train step of cell ``kimilinear-train-ep32share-8k`` (one
    chip's share of a deployment in which 32 chips share each layer, experts
    32 ways): 1 row x 8,192."""
    return _cell_step(topo, "kimilinear-train-ep32share-8k")


def test_kimi_cell_step_fills_one_chip_and_fits(kimi_step):
    import re

    step, job, m = kimi_step
    assert job["per_chip_batch"] == 1 and job["seq_len"] == 8192
    assert job["remat"] == "nothing" and job["accum_steps"] == 1
    mem = step.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # 602,434,432 parameters and buffer entries x 12 bytes resident
    assert 7.22e9 < mem.argument_size_in_bytes < 7.24e9
    # 12.16 GiB = 13.06 GB: temporaries 5,826,928,128 bytes, the float32
    # gradient (2.41 GB) among them (11.38 GiB and 4,985,193,984 before
    # PR 48: a KDA layer keeps its chunks' float32 inverse, 67 MB a layer,
    # 242.6 MB more at the compiler's peak over four layers; 11.60 GiB and
    # 5,227,819,008 before PR 49: a KDA layer keeps the outputs of
    # ``q_proj``, ``k_proj`` and ``v_proj``, 8,192 x 4,096 x 2 bytes = 67
    # MB each, 201 MB a layer, 599.1 MB more at the peak); layer 0 keeps
    # its dense feed-forward's three products from forward to backward (377
    # MB with ``mixer_out``), the expert layers ``mixer_out`` (38 MB each)
    # and their router's logits and picks (9 MB each); a KDA layer's
    # rematerialised forward holds the sub-blocks' scaled keys (268 MB in
    # bf16) and a chunk's pairs, and an
    # expert layer takes what arrived through windows of 16,384 rows.  No
    # lighter policy fits: ``dots_no_batch`` holds 16.41 GiB and no remat is
    # refused by the compiler.  The issue asks over 12 GB and under 15.0
    # GiB; the compiler allows 15.75
    assert 12.0e9 < held < 12.4 * 2 ** 30 < 15.0 * 2 ** 30
    # and the compiler rematerialises nothing of its own: sets of kept names
    # that tip its schedule (every layer's weight-gradient products left to
    # the end of the program, 16.0-16.4 GiB held: ROADMAP S29) carry its
    # ``.remat`` instructions in the text
    assert not re.findall(r"^\s*(?:ROOT )?%[\w.\-]*\.remat[\w.\-]* = ",
                          step.as_text(), flags=re.M)


def test_kimi_cell_step_runs_the_flash_kernels_at_192_on_128(kimi_step):
    """One latent layer in the five: the three flash kernels by name on the
    dispatch's head-major route at 192-wide queries and keys on 128-wide
    values (nothing padded: the forward's output is ``[32, 8192, 128]``),
    the forward twice (its layer is rematerialised), 32 heads over 8 x 8
    tiles.  The four expert layers' products are the compiler's grouped
    matmuls over the 8 experts HELD, over windows of 16,384 rows (four even
    shares of the 65,536 assignments a layer): 15 a layer, 64 custom calls
    in all, what the benchmark's runner holds the step to.  The KDA scans
    are plain XLA.  No collective: one chip's share."""
    import re

    step, job, m = kimi_step
    text = step.as_text()
    assert sorted(_kernels_named(text)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd", "flash_fwd"]
    assert text.count("tpu_custom_call") == 64 == (
        job["custom_calls_per_layer"] * m["layers"])
    assert sorted(_kernel_grids(text)) == sorted(
        [("flash_fwd", (32, 8, 8))] * 2 + [("flash_bwd_dq", (32, 8, 8))]
        + [("flash_bwd_dkv", (32, 8, 8))])
    outs = re.findall(r"%flash_fwd[\w.]* = \(bf16\[(\d+),8192,(\d+)\]", text)
    assert outs == [("32", "128")] * 2
    assert re.search(r"ragged-dot", text)
    assert re.search(r"\[8,2304,1024\]", text)
    assert not re.search(r"\[256,2304,1024\]|\[256,1024,2304\]", text)
    assert "[16384,2304]" in text and "[65536,2304]" not in text
    # ONE sort a layer over score + bias, the scores and the columns
    assert _router_sorts(text, 256) == [3] * 4
    assert job["collectives_in_step"] == []
    assert "all-reduce" not in text and "all-gather" not in text


def test_a_512_band_over_8192_keys_computes_what_the_table_gives():
    """The figure the table gives the cell's sliding layers: a windowed
    call runs tiles of the window's own width, 31 x 512^2 entries a head
    whole for 4,063,488 live pairs, 2.00 times, and by squares of 256 three
    quarters of that, 1.50 times (the row's 1024 x 1024 tiles, which a
    call without a window keeps, would compute 15 x 1024^2, 3.87 times:
    PERF.md section 6, PR 41 and PR 42)."""
    from tpudist.ops.attention import computed_over_live, route
    from tpudist.ops.flash_attention import diag_sub

    r = route("TPU v5 lite", 8192, 128)
    assert (r.block_q, r.block_k, r.sub) == (1024, 1024, V5E_SUB)
    r = route("TPU v5 lite", 8192, 128, 512)
    assert (r.block_q, r.block_k, r.sub) == (512, 512, 256)
    assert diag_sub(r.block_q, r.block_k, 0, 512, r.sub) == 256
    assert computed_over_live(8192, 1024, 1024, 0, 512) == (
        15 * 1024 ** 2 / 4_063_488)
    assert computed_over_live(8192, 512, 512, 0, 512) == (
        31 * 512 ** 2 / 4_063_488)
    assert computed_over_live(8192, 512, 512, r.sub, 512) == (
        31 * 3 * 256 ** 2 / 4_063_488) <= 2.1
