"""The plain reference against the system at the tiny configurations (the
GPT-2-shaped one and the architecture added as files alone), and the
control: the comparison that decides ``correct`` has to be shown to fail
when the arithmetic drops a precision."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import archs, checks, reference
from cellbench.runners import train_lm
from cellbench.tests.conftest import FILES_ALONE_CELLS, load_cell


@pytest.fixture(scope="module", params=["tiny-train-1dev"] + FILES_ALONE_CELLS)
def tiny(request):
    return load_cell(request.param)


def batches(config, seed, n=3, rows=4):
    m = archs.load(config).dims(config)
    rng = np.random.default_rng(seed)
    return [rng.integers(0, m["vocab"], (rows, m["seq"]), dtype=np.int32)
            for _ in range(n)]


def test_system_in_float32_equals_the_reference(tiny):
    """Loss and every gradient tensor of the architecture's module +
    ``lm_loss`` (the forward and autodiff the step uses) within 1e-5
    relative."""
    from tpudist.models.transformer import lm_loss

    cell, config = tiny
    arch = archs.load(config)
    config = copy.deepcopy(config)
    config["as_run"]["compute_dtype"] = "float32"
    module = arch.build_module(config, cell["job"])
    weights = arch.init_weights(config, reference.split_seed(3))
    tokens = jnp.asarray(batches(config, 3, n=1)[0])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: lm_loss(
            module.apply(p, tokens), tokens))(
                arch.program_tree(config, weights))
    ref_loss, ref_grads = arch.loss_and_grads(config, weights, tokens)
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * float(ref_loss)
    got = arch.program_tree(config, ref_grads)
    assert jax.tree.structure(grads) == jax.tree.structure(got)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(got)):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert err <= 1e-5, (jax.tree_util.keystr(path), err)
    # the tree and back: named_leaves is program_tree's inverse
    leaves = arch.named_leaves(config, arch.program_tree(config, weights))
    assert len(leaves) == len(arch.leaf_names(config))
    norms = reference.leaf_norms(arch, config, weights)
    np.testing.assert_allclose(
        [float(jnp.linalg.norm(x)) for x in leaves], norms, rtol=1e-6)


def test_large_seeds_are_data_not_programs(tiny):
    _, config = tiny
    arch = archs.load(config)
    a = arch.init_weights(config, reference.split_seed(2 ** 31 + 5))
    b = arch.init_weights(config, reference.split_seed(5))
    head = max(a, key=lambda k: a[k].size)
    assert not np.allclose(a[head], b[head])
    with pytest.raises(ValueError):
        reference.split_seed(-1)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_program_passes_and_the_8_bit_control_fails(tiny, seed):
    """The cell's own limits: the system in bf16 is inside every one; the
    reference with its matmul operands rounded to 8 bits (fp8 e4m3, the
    precision below bf16) put in the program's place is over at least one."""
    cell, config = tiny
    job = train_lm.Job(cell, config, jax.devices()[:1])
    first = batches(config, seed)
    _, program = job.first_steps(seed, first)
    ref = job.reference_readings(seed, first)
    sound = checks.train_gaps(program, ref)
    ok, lines = checks.judge(sound, cell["check"]["limits"])
    assert ok, lines
    low = job.reference_readings(seed, first, mode=reference.CONTROL)
    control = checks.train_gaps(low, ref)
    ok, lines = checks.judge(control, cell["check"]["limits"])
    assert not ok, lines
    # the number that separates them does so by a factor of three or more
    assert control["grad_dir_gap"] >= 3 * sound["grad_dir_gap"]
    assert control["update_dir_gap"] > cell["check"]["limits"]["update_dir_gap"]


def test_reference_row_blocks_do_not_change_the_readings(tiny):
    _, config = tiny
    arch = archs.load(config)
    first = batches(config, 9, n=2)
    a = reference.train_readings(arch, config, 9, first, lr=1e-3,
                                 rows_per_block=4)
    b = reference.train_readings(arch, config, 9, first, lr=1e-3,
                                 rows_per_block=1)
    np.testing.assert_allclose(a["losses"], b["losses"], rtol=1e-5)
    np.testing.assert_allclose(a["grad_norms"], b["grad_norms"], rtol=1e-4)
    np.testing.assert_allclose(a["update_norms"], b["update_norms"],
                               rtol=1e-3)


def test_worst_leaf_gap_is_held_against_the_median_tensor():
    ref = np.array([1.0, 1.0, 1e-6, 2.0])
    got = np.array([1.0, 1.1, 2e-6, 2.0])
    gap, i = checks.worst_leaf_gap(got, ref)
    assert i == 1 and abs(gap - 0.1) < 1e-12   # the all-but-zero tensor is not
    gap, i = checks.worst_leaf_gap(np.zeros(4), ref)   # an unchanged state
    assert gap == 1.0



def test_an_unknown_model_type_names_the_file_to_add():
    with pytest.raises(LookupError, match=r"cellbench/archs/mamba9\.py"):
        archs.load({"name": "x", "model_type": "mamba9"})
    with pytest.raises(LookupError, match="no model_type"):
        archs.load({"name": "x"})
