"""The pattern decoder's ``kimi_linear`` arms (``tpudist/models/hybrid.py``:
a delta-rule mixer whose decay is a number a CHANNEL behind low-rank gates,
latent attention without positions whose values are narrower than its keys,
behind a leading dense layer and expert layers picked by sigmoid score + a
bias), the chunked scan at a decay a channel (``tpudist/ops/gated_delta.py``)
and the flash kernels at values of a width of their own
(``tpudist/ops/flash_attention.py``), held to the per-position loop, to
``attention_reference`` and to the plain float32 reference of the benchmark
(``cellbench/archs/kimi_linear.py``) at tiny widths on the CPU: d 64, 2 KDA
heads of 16 with gates of rank 16, 2 latent heads at 24 | 8 wide keys on
16-wide values out of a latent of 12 (unequal parts, so that a swapped split
shows), a dense feed-forward of 160, 32 experts of 48, top 8, vocabulary 256,
5 layers (KDA + dense, KDA, KDA, latent, KDA); 8 of 32 experts held.

Tolerances, and why.  Float32 against float32 differs only by the order of
sums (chunks and sub-blocks against a position at a time, the dispatch's
blocks against dense masked scores, grouped products against masked ones):
1e-5 of an output's largest entry for the scan (2e-7 to 1.2e-6 read), 3e-5
of the logits' (2.7e-6 read), 1e-4 of a gradient's norm (1.6e-5 read).  In
bf16 the scan reads 4e-3 to 8e-3 of the output's largest entry against a
bound of 2.5e-2 (the per-head path's own bound: ``T``, ``u``, ``w`` and the
scaled operands rounded to 2^-9 before the scan multiplies them), and the
decoder's dense gradients up to 0.076 of their norm against 0.12, which the
fp8 control (0.21 and up) fails on every one (three layers, the routing
held apart: see the test).
"""

import dataclasses
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import reference
from cellbench.archs import kimi_linear as arch
from tests.decoder_reference import (DATA, dense_products, highest, logits,
                                     reference_logits, reference_pair, rel,
                                     run_steps, seeded, tiny, worst)
from tpudist import telemetry
from tpudist.models import hybrid
from tpudist.models.transformer import lm_loss
from tpudist.ops import attention
from tpudist.ops.flash_attention import (attention_reference,
                                         blockwise_attention, flash_attention)
from tpudist.ops.gated_delta import (GATE_FLOOR, SUB_BLOCK,
                                     chunked_gated_delta_rule,
                                     gated_delta_rule_reference)
from tpudist.telemetry import names

TINY = json.loads((DATA / "tiny-kimi-linear.json").read_text())
REAL = json.loads((DATA.parents[1] / "configs"
                   / "kimi-linear-48b-a3b.json").read_text())
EXPERT_MEMBERS = 4


@pytest.fixture(autouse=True)
def highest_precision():
    with highest():
        yield


# ---------------------------------------------------------------------------
# (a) the chunked scan at a decay a channel against the per-position loop

#: the gate's range a position a channel: decays of 0.9 to 1; of e^-1 to 1;
#: AT the floor, a sub-block's span of e^-67 to e^-75
MILD, WIDE, AT_FLOOR = (0.0, 0.1), (0.0, 1.0), (4.5, GATE_FLOOR - 0.01)
SCAN_BOUNDS = {jnp.float32: 1e-5, jnp.bfloat16: 2.5e-2}


def scan_inputs(dtype, decay, chunks=2, dk=16, dv=16, heads=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (2, chunks * 64, heads)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], shape + (dk,))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], shape + (dk,)))
    v = jax.random.normal(ks[2], shape + (dv,))
    g = -jax.random.uniform(ks[3], shape + (dk,), minval=decay[0],
                            maxval=decay[1])
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape))
    return [x.astype(dtype) for x in (q, k, v)] + [g, beta]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("decay, chunks, dk", [
    (MILD, 2, 16), (WIDE, 3, 16), (AT_FLOOR, 2, 16), (WIDE, 1, 16),
    (WIDE, 2, 8)],
    ids=["mild", "wide", "at_the_floor", "one_chunk", "unequal_widths"])
def test_the_chunked_form_is_the_loop_at_a_decay_a_channel(dtype, decay,
                                                           chunks, dk):
    args = scan_inputs(dtype, decay, chunks, dk)
    got = jax.jit(chunked_gated_delta_rule)(*args)
    assert got.dtype == dtype and got.shape == args[2].shape
    assert worst(got.astype(jnp.float32), jax.jit(
        gated_delta_rule_reference)(*args)) < SCAN_BOUNDS[dtype]


@pytest.mark.parametrize("decay", [WIDE, AT_FLOOR], ids=["wide", "at_floor"])
def test_the_chunked_forms_gradients_are_the_loops(decay):
    """Every operand's gradient, over two chunks (the carried state's too).
    At the floor the gate's own gradient is of what is left after 15
    positions of e^-5 each (1e-4 of the others' size): it is held to 1e-3 of
    its norm where the others are held to 1e-5."""
    args = scan_inputs(jnp.float32, decay)
    cot = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    through = lambda fn: jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, got, want in zip("qkvgb", through(chunked_gated_delta_rule),
                               through(gated_delta_rule_reference)):
        assert bool(jnp.all(jnp.isfinite(got))), name
        bound = 1e-3 if name == "g" and decay is AT_FLOOR else 1e-5
        assert rel(got, want) < bound, name


def test_a_decay_constant_over_channels_is_the_per_head_path():
    """``g [b, s, h, dk]`` with one number a head IS ``g [b, s, h]``: the
    two paths of the chunked form agree to float32 rounding, and both with
    the loop."""
    q, k, v, g, beta = scan_inputs(jnp.float32, WIDE)
    a_head = g[..., 0]
    a_channel = jnp.broadcast_to(a_head[..., None], g.shape)
    by_head = jax.jit(chunked_gated_delta_rule)(q, k, v, a_head, beta)
    by_channel = jax.jit(chunked_gated_delta_rule)(q, k, v, a_channel, beta)
    assert worst(by_channel, by_head) < 2e-6
    loop = jax.jit(gated_delta_rule_reference)
    assert np.array_equal(loop(q, k, v, a_head, beta),
                          loop(q, k, v, a_channel, beta))
    assert worst(by_channel, loop(q, k, v, a_channel, beta)) < 1e-5


def test_beyond_the_floor_the_gate_is_clamped_and_nothing_overflows():
    """A channel that forgets by more than e^-5 a position is computed as
    forgetting by e^-5: the recurrence of ``max(g, -GATE_FLOOR)``, finite in
    value and gradient, and not the recurrence as written."""
    q, k, v, g, beta = scan_inputs(jnp.float32, (0.0, 30.0))
    assert float(jnp.min(g)) < -25 and SUB_BLOCK == 16 and GATE_FLOOR == 5.0
    got, grads = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(chunked_gated_delta_rule(*a) ** 2),
        argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in (got, *grads))
    loop = jax.jit(gated_delta_rule_reference)
    clamped = jnp.maximum(g, -GATE_FLOOR)
    out = jax.jit(chunked_gated_delta_rule)(q, k, v, g, beta)
    assert worst(out, loop(q, k, v, clamped, beta)) < 1e-5
    assert worst(out, loop(q, k, v, g, beta)) > 1e-3
    # the gradient through a clamped gate is zero
    assert not np.any(np.asarray(grads[3])[np.asarray(g) < -GATE_FLOOR])


def test_a_chunk_is_a_whole_number_of_sub_blocks():
    q, k, v, g, beta = scan_inputs(jnp.float32, MILD, chunks=3)
    with pytest.raises(ValueError, match="sub-blocks of 16"):
        chunked_gated_delta_rule(q, k, v, g, beta, chunk=24)
    # a decay a head has no sub-blocks and takes such a chunk
    assert chunked_gated_delta_rule(q, k, v, g[..., 0], beta,
                                    chunk=24).shape == v.shape


# ---------------------------------------------------------------------------
# (b) the flash kernels at values of a width of their own


def attention_inputs(heads, kv, d, d_v, seq, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(ks[0], (2, heads, seq, d), dtype),
            jax.random.normal(ks[1], (2, kv, seq, d), dtype),
            jax.random.normal(ks[2], (2, kv, seq, d_v), dtype),
            jax.random.normal(ks[3], (2, heads, seq, d_v), dtype))


def dense(q, k, v):
    group = q.shape[1] // k.shape[1]
    return attention_reference(q, jnp.repeat(k, group, 1),
                               jnp.repeat(v, group, 1), causal=True)


@pytest.mark.parametrize("heads, kv, d, d_v", [
    (2, 2, 192, 128), (4, 2, 24, 16), (2, 2, 16, 16)],
    ids=["192_128", "24_16_grouped", "equal"])
def test_the_flash_kernels_take_values_narrower_than_keys(heads, kv, d, d_v):
    """Forward and all three gradients in interpret mode against the dense
    reference: the real widths (scores at 192, values at 128), grouped
    key/value heads at unequal widths, and equal widths as before; two
    query tiles, so that a tile on the diagonal (by strips of 64) and an
    interior one both run."""
    q, k, v, cot = attention_inputs(heads, kv, d, d_v, 256)
    flash = lambda q, k, v: flash_attention(q, k, v, True, 128, 128, True,
                                            None, 64)
    got = jax.jit(flash)(q, k, v)
    assert got.shape == (2, heads, 256, d_v)
    assert worst(got, jax.jit(dense)(q, k, v)) < 1e-5
    through = lambda f: jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(f(q, k, v) * cot), argnums=(0, 1, 2)))(
            q, k, v)
    for name, a, b in zip("qkv", through(flash), through(dense)):
        assert a.shape == b.shape, name
        assert rel(a, b) < 1e-5, name


def test_the_routes_off_the_chip_take_them_too():
    """The dispatch's head-major entry hands ``v`` on as it is: the dense
    reference under 1,024 positions and the blockwise scan from there on
    (this machine's routes) both give the flash kernels' result."""
    q, k, v, _ = attention_inputs(2, 2, 24, 16, 1024)
    want = jax.jit(dense)(q, k, v)
    assert attention.route("cpu", 1024, 24).kernel == attention.BLOCKWISE
    assert worst(jax.jit(attention.default_attention)(q, k, v), want) < 1e-5
    assert worst(jax.jit(lambda q, k, v: blockwise_attention(
        q, k, v, causal=True, block_k=256))(q, k, v), want) < 1e-5
    assert attention.route("cpu", 256, 24).kernel == attention.REFERENCE
    assert jax.eval_shape(attention.default_attention, q[:, :, :256],
                          k[:, :, :256], v[:, :, :256]).shape == (
                              2, 2, 256, 16)
    with pytest.raises(ValueError, match="v alone may have a width"):
        flash_attention(q, k[..., :16], v, True, 128, 128, True)


def test_a_head_of_192_runs_the_flash_kernels_head_major_on_the_chip():
    r = attention.route("TPU v5 lite", 8192, 192)
    assert (r.kernel, r.layout, r.why_not, r.block_q, r.block_k, r.sub) == (
        attention.FLASH, names.HEAD_MAJOR, names.WHY_DH, 1024, 1024, 256)


# ---------------------------------------------------------------------------
# (c) the two mixers alone


def mixer_sizes(**changed) -> hybrid.HybridSizes:
    sizes = arch.build_module(tiny(TINY), {"remat": None}).sizes
    return dataclasses.replace(sizes, **changed)


def seeded_params(module, x, scale=0.2):
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)["params"]
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return jax.tree.unflatten(tree, [
        scale * jax.random.normal(key, leaf.shape)
        for key, leaf in zip(keys, leaves)])


def latent_plainly(x, p, z, shared_first=False):
    """Plain ``jnp``, all heads at once over ``[s, s]`` scores."""
    s = x.shape[0]
    h, (own, shared), dv = z.n_heads, z.latent_key_dims, z.latent_value_dim
    q = (x @ p["q_proj"]["kernel"]).reshape(s, h, own + shared)
    c = x @ p["kv_a_proj"]["kernel"]
    latent, k_shared = c[:, :z.latent_rank], c[:, z.latent_rank:]
    if shared_first:
        latent, k_shared = c[:, shared:], c[:, :shared]
    latent = latent * jax.lax.rsqrt(jnp.mean(
        latent * latent, axis=-1, keepdims=True) + z.eps) * p["kv_norm"]["scale"]
    kv = (latent @ p["kv_b_proj"]["kernel"]).reshape(s, h, own + dv)
    k = jnp.concatenate([kv[..., :own], jnp.broadcast_to(
        k_shared[:, None], (s, h, shared))], axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(own + shared)
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None]
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), kv[..., own:])
    return out.reshape(s, h * dv) @ p["o_proj"]["kernel"]


def test_latent_attention_is_a_dense_masked_softmax_over_assembled_keys():
    z = mixer_sizes()
    assert (z.n_heads, z.latent_rank, z.latent_key_dims,
            z.latent_value_dim) == (2, 12, (24, 8), 16)
    module = hybrid.LatentAttention(z, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
    params = seeded_params(module, x)
    assert {k: v["kernel"].shape for k, v in params.items()
            if k != "kv_norm"} == {
        "q_proj": (64, 2 * 32), "kv_a_proj": (64, 12 + 8),
        "kv_b_proj": (12, 2 * (24 + 16)), "o_proj": (2 * 16, 64)}
    got = jax.jit(module.apply)({"params": params}, x)
    plainly = lambda **fault: jax.jit(lambda x, p: jnp.stack([
        latent_plainly(row, p, z, **fault) for row in x]))(x, params)
    assert worst(got, plainly()) < 1e-5
    # the split of the latent's projection taken the other way round is
    # another result (the parts are 12 | 8: unequal)
    assert worst(got, plainly(shared_first=True)) > 0.1


def half_the_heads(params: dict, member: int, columns: dict, rows: str,
                   per_head: dict) -> dict:
    """Member ``member``'s half of a mixer's weights: its heads' columns of
    the projections ``columns`` names (with a head's width there), their
    rows of the output projection, and a head's own entries of the vectors
    ``per_head`` names."""
    out = dict(params)
    for name, width in columns.items():
        w = params[name]["kernel"]
        half = w.shape[1] // 2
        assert half % width == 0
        out[name] = {"kernel": w[:, member * half:(member + 1) * half]}
    w = params[rows]["kernel"]
    half = w.shape[0] // 2
    out[rows] = {"kernel": w[member * half:(member + 1) * half]}
    for name, axis in per_head.items():
        w = params[name]
        half = w.shape[axis] // 2
        out[name] = jax.lax.slice_in_dim(w, member * half, (member + 1) * half,
                                         axis=axis)
    return out


@pytest.mark.parametrize("kind", ["kda", "latent"])
def test_two_head_shares_of_a_mixer_add_up_to_the_uncut_mixer(kind):
    """This cut holds the heads whole; the mixers hold a share of them like
    the others all the same.  Each of two members holds one of the two
    heads: alone (no axis) its partial output, and the two add up to the
    uncut mixer's; told ``heads_axis``, under a ``vmap`` with that axis
    name, ``o_proj``'s partial sums are added up and each member's output
    IS the uncut one.  No statistic crosses the cut: the norms are a
    head's own (KDA) or of the latent, which every member holds whole."""
    z = mixer_sizes()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64))
    if kind == "kda":
        whole = hybrid.KimiDeltaAttention(z, jnp.float32)
        held = dict(linear_key_heads=1, linear_value_heads=1,
                    linear_value_heads_total=2)
        build = lambda sizes: hybrid.KimiDeltaAttention(sizes, jnp.float32)
        share = lambda p, i: half_the_heads(
            p, i, {"q_proj": 16, "k_proj": 16, "v_proj": 16, "f_b_proj": 16,
                   "g_b_proj": 16, "b_proj": 1}, "o_proj",
            {"A_log": 0, "dt_bias": 0})
    else:
        whole = hybrid.LatentAttention(z, jnp.float32)
        held = dict(n_heads=1, n_heads_total=2)
        build = lambda sizes: hybrid.LatentAttention(sizes, jnp.float32)
        share = lambda p, i: half_the_heads(
            p, i, {"q_proj": 32, "kv_b_proj": 40}, "o_proj", {})
    params = seeded_params(whole, x)
    if kind == "kda":
        # the convolution's channels are q's, k's and v's heads side by side
        params["A_log"] = jnp.asarray([-3.0, -1.0])
        conv = params["conv"].reshape(3, 2, 16, -1)
    want = jax.jit(whole.apply)({"params": params}, x)
    shares = []
    for member in range(2):
        p = share(params, member)
        if kind == "kda":
            p["conv"] = conv[:, member].reshape(3 * 16, -1)
        shares.append(p)
    alone = build(dataclasses.replace(z, **held))
    parts = [jax.jit(alone.apply)({"params": p}, x) for p in shares]
    assert worst(sum(parts), want) < 1e-5
    assert worst(parts[0], want) > 1e-1
    shared = build(dataclasses.replace(z, heads_axis="heads", **held))
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *shares)
    every = jax.jit(jax.vmap(lambda p: shared.apply({"params": p}, x),
                             axis_name="heads"))(stacked)
    for member in range(2):
        assert worst(every[member], want) < 1e-5


# ---------------------------------------------------------------------------
# (d) the decoder against the reference


@pytest.fixture(scope="module")
def f32_pair():
    return reference_pair(arch, tiny(TINY))


def test_the_module_takes_the_arms_the_architecture_names(f32_pair):
    module = f32_pair["module"]
    z = module.sizes
    assert module.layer_types == (
        names.CHANNEL_LINEAR,) * 3 + (names.LATENT, names.CHANNEL_LINEAR)
    assert module.feed_forwards == (names.DENSE_FFN,) + (
        names.EXPERT_SHARE,) * 4
    assert (z.norm, z.norm_after, z.one_sublayer, z.eps) == (
        names.PLAIN, False, False, 1e-5)
    assert (z.linear_value_heads, z.linear_key_dim, z.linear_value_dim,
            z.linear_conv_width, z.linear_gate_rank) == (2, 16, 16, 4, 16)
    assert (z.scoring, z.routed_scale, z.top_k, z.n_experts, z.held,
            z.expert_fn, z.shared_scored, z.router_trained) == (
        names.SIGMOID_BIAS, 2.446, 8, 32, 8, names.GATED_SILU, False, True)
    real = arch.build_module(REAL, {"remat": "nothing"}).sizes
    assert (real.d_model, real.linear_value_heads, real.linear_key_dim,
            real.linear_gate_rank, real.n_heads, real.latent_rank,
            real.latent_key_dims, real.latent_value_dim, real.ffn_width,
            real.n_experts, real.held, real.top_k, real.expert_width) == (
        2304, 32, 128, 128, 32, 512, (128, 64), 128, 9216, 256, 8, 8, 1024)


def test_logits_match_the_reference(f32_pair):
    assert worst(logits(f32_pair), reference_logits(f32_pair)) < 3e-5


def test_loss_matches_the_reference(f32_pair):
    assert float(f32_pair["loss"]) == pytest.approx(
        float(f32_pair["ref_loss"]), rel=1e-6)


@pytest.mark.parametrize("name", arch.leaf_names(TINY))
def test_every_gradient_matches_the_reference(f32_pair, name):
    p = f32_pair
    got = dict(zip(arch.leaf_names(p["config"]),
                   arch.named_leaves(p["config"], p["grads"])))[name]
    want = arch.unstacked(p["config"], p["ref_grads"])[name]
    assert float(jnp.linalg.norm(want)) > 0, name
    assert rel(got, want.reshape(got.shape)) < 1e-4, name


def test_the_choice_bias_is_a_buffer_outside_the_gradient(f32_pair):
    p = f32_pair
    # layers 1 and 2 are of one shape and follow each other: the reference
    # stacks them
    assert sorted(arch.buffer_shapes(p["config"])) == [
        "kda_moe.choice_bias", "layer_3.choice_bias", "layer_4.choice_bias"]
    weights, ref_grads = (arch.unstacked(p["config"], p[k])
                          for k in ("weights", "ref_grads"))
    biases = [f"layer_{i}.choice_bias" for i in range(1, 5)]
    assert not set(biases) & set(arch.leaf_names(p["config"]))
    for name in biases:
        layer = name.partition(".")[0]
        assert float(jnp.linalg.norm(weights[name])) > 0
        assert not np.any(np.asarray(ref_grads[name]))
        assert not np.any(np.asarray(
            p["grads"]["params"][layer]["experts"]["choice_bias"]))


@pytest.mark.parametrize("fault", [
    {arch.KDA: dict(by_channel=False)}, {arch.KDA: dict(carry=False)},
    {arch.LATENT: dict(shared_first=True)}],
    ids=["a_decay_a_head", "state_not_carried", "split_swapped"])
def test_a_planted_fault_of_the_reference_is_another_result(f32_pair, fault):
    """The seeded decays differ over a head's channels, a wrong carried
    state reaches the logits, and the query's two parts are unequal: the
    reference with each fault planted is far from the program, so the
    agreement above is of THIS recurrence and THIS split."""
    p = f32_pair
    other = jax.jit(lambda w: arch.forward(p["config"], w, p["tokens"],
                                           faults=fault))(p["weights"])
    assert worst(logits(p), other) > 1e-3


@pytest.mark.parametrize("wrong", ["choice_without_bias", "scale_dropped",
                                   "silu_gate"])
def test_another_arm_is_not_this_architecture(f32_pair, wrong, monkeypatch):
    p = f32_pair
    if wrong == "silu_gate":
        # the output gate of the per-head mixer: silu where this is sigmoid
        real = jax.nn.sigmoid
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: (
            jax.nn.silu(x) if x.shape[-1] == 32 else real(x)))
        module = p["module"]
    else:
        module = dataclasses.replace(p["module"], sizes=dataclasses.replace(
            p["module"].sizes, **{
                "choice_without_bias": dict(scoring=names.SIGMOID),
                "scale_dropped": dict(routed_scale=1.0)}[wrong]))
    params = p["params"]
    if wrong == "choice_without_bias":
        params = jax.tree.map(lambda x: x, params)
        for i in range(1, 5):
            del params["params"][f"layer_{i}"]["experts"]["choice_bias"]
    assert worst(jax.jit(module.apply)(params, p["tokens"]),
                 reference_logits(p)) > 1e-3


def test_bf16_is_within_a_stated_tolerance_and_the_fp8_control_is_not():
    """The program in bf16 compute against the float32 reference, on the
    fixture cut to three layers (every kind of layer, half the compile) and
    with the router's weight seeded at ZERO, so that the picks go by the
    seeded bias alone and are the same at every precision (on the whole
    fixture with a live router a token in five has its 8th and 9th scores
    within bf16's error of the tokens, goes to another expert in bf16, and
    drowns the arithmetic: dense gradients then read up to 0.61 of their
    norm where the fp8 control's least is 0.43).  It fails the float32
    tolerances by an order and more, and yet it is the same mathematics:
    every dense matrix's gradient within 0.12 of its norm (0.076 read, at
    ``layer_2.q_proj``: bf16's 2^-9 through three layers, two of them a
    scan whose operands are rounded twice).  The fp8 control, the reference
    itself with its matmul operands rounded to 8 bits, fails that tolerance
    on every such matrix (0.21 the least, the head's) and reads 6.8x the
    program's gap or more on each."""
    config = three_layers()
    config["as_run"].update(compute_dtype="bfloat16", router_init_std=0.0)
    p = reference_pair(arch, config)
    leaves = arch.leaf_names(p["config"])
    ref_grads = arch.unstacked(p["config"], p["ref_grads"])
    gaps = dict(zip(leaves, (
        rel(g, ref_grads[n].reshape(g.shape)) for n, g in zip(
            leaves, arch.named_leaves(p["config"], p["grads"])))))
    assert worst(logits(p).astype(jnp.float32), reference_logits(p)) > 1e-3
    dense = [n for n in leaves if n.endswith("_proj") or "ffn_" in n
             or n in ("embed", "head")]
    assert len(dense) == 2 * 9 + 4 + 3 + 3 + 2
    assert min(gaps[n] for n in dense) > 50 * 1e-4
    assert max(gaps[n] for n in dense) < 0.12
    _, control = jax.jit(lambda w: arch.loss_and_grads(
        p["config"], w, p["tokens"], "fp8"))(p["weights"])
    control = arch.unstacked(p["config"], control)
    low = {n: rel(control[n], ref_grads[n]) for n in dense}
    assert min(low.values()) > 0.12
    assert min(low[n] / gaps[n] for n in dense) > 4


def three_layers() -> dict:
    """The fixture cut to KDA + dense, latent, KDA."""
    return tiny(TINY, num_hidden_layers=3, linear_attn_config=dict(
        TINY["linear_attn_config"], kda_layers=[1, 3], full_attn_layers=[2]))


def test_three_adam_steps_follow_the_reference():
    """``make_lm_train_step`` over the float32 program against the
    reference's own Adam: losses to 1e-5, every tensor's change after three
    steps to 2e-3 of its norm (Adam divides by the root of the second
    moment, which magnifies the 1e-4 of a gradient where it is small).  On
    the fixture cut to three layers (KDA + dense, latent, KDA: every kind,
    half the compile), and with the router's weight seeded at ZERO: it
    trains from there, and the picks go by the bias until it has moved.
    From a live router that magnified noise moves a token's 8th and 9th
    scores past each other by the third step (read: losses equal to 3e-7 at
    steps 1 and 2 and 1e-4 apart at step 3, ``A_log``'s change 4e-3 apart;
    from zero 1e-7 and 4e-5)."""
    config = three_layers()
    config["as_run"]["router_init_std"] = 0.0
    weights = seeded(arch, config, 7)
    module = arch.build_module(config, {"remat": "nothing"})
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 256, (2, 128), dtype=np.int32)
               for _ in range(3)]
    state, losses, _ = run_steps(arch, config, module, weights, batches, 2e-3)
    ref = reference.train_readings(arch, config, 7, batches, lr=2e-3,
                                   rows_per_block=2)
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    moved = jax.tree.map(jnp.subtract, state.params,
                         arch.program_tree(config, weights))
    norms = np.array([float(jnp.linalg.norm(x))
                      for x in arch.named_leaves(config, moved)])
    # (layer 2's bias picks none of the experts held: their gradient is zero
    # in the program and rounding noise in the reference's masked sum, which
    # Adam turns into a change of 5e-7: the absolute term)
    np.testing.assert_allclose(norms, ref["update_norms"], rtol=2e-3,
                               atol=1e-5)
    # the buffer stays where it was seeded
    for i in (1, 2):
        assert not np.any(np.asarray(
            moved["params"][f"layer_{i}"]["experts"]["choice_bias"]))


def test_a_router_held_fixed_still_hands_its_gradient_to_the_tokens():
    """``as_run.router_trained`` false, the real cell's way: the router's
    weight gets no gradient, here and in the reference, and every other
    tensor's gradient (through the scores into the tokens too) agrees."""
    config = three_layers()
    config["as_run"]["router_trained"] = False
    p = reference_pair(arch, config, options={"remat": None})
    assert not p["module"].sizes.router_trained
    want = arch.unstacked(config, p["ref_grads"])
    got = dict(zip(arch.leaf_names(config),
                   arch.named_leaves(config, p["grads"])))
    assert sum(name.endswith(".router") for name in got) == 2
    for name, g in got.items():
        if name.endswith(".router"):
            assert not np.any(np.asarray(g)) and not np.any(
                np.asarray(want[name])), name
        else:
            assert rel(g, want[name].reshape(g.shape)) < 1e-4, name


def test_the_gradient_under_remat_is_the_gradient_without(f32_pair):
    """What a layer keeps changes no number: layer 0 keeps its dense
    feed-forward's three products beside ``mixer_out``, the expert layers
    ``mixer_out`` and what their router computed; a KDA layer besides what
    its mixer computes on the way to the scan (since PR 49:
    ``names.KDA_KEEPS``) and its chunks' inverse (since PR 48), the latent
    layer nothing of its mixer's, which runs again whole; a delta rule at a
    decay a head the inverse alone, as before PR 49."""
    p = f32_pair
    z = p["module"].sizes
    dense = dataclasses.replace(z, feed_forward=names.DENSE_FFN)
    assert hybrid.remat_keeps(dense) == (
        names.MIXER_OUT,) + names.DENSE_FFN_KEEPS
    assert hybrid.remat_keeps(z, names.LATENT) == hybrid.remat_keeps(z) == (
        names.MIXER_OUT, names.ROUTER_LOGITS, names.ROUTER_PICKS)
    assert hybrid.remat_keeps(z, names.CHANNEL_LINEAR) == (
        names.MIXER_OUT, names.ROUTER_LOGITS, names.ROUTER_PICKS,
        *names.KDA_KEEPS, names.DELTA_INVERSE)
    assert names.KDA_KEEPS == (names.KDA_Q, names.KDA_K, names.KDA_V)
    assert hybrid.remat_keeps(z, names.LINEAR) == (
        names.MIXER_OUT, names.ROUTER_LOGITS, names.ROUTER_PICKS,
        names.DELTA_INVERSE)
    text = str(jax.make_jaxpr(jax.grad(lambda q: lm_loss(
        p["module"].apply(q, p["tokens"]), p["tokens"])))(p["params"]))
    for name in (names.DELTA_INVERSE,) + names.KDA_KEEPS:
        assert text.count(f"name={name}") >= p[
            "module"].layer_types.count(names.CHANNEL_LINEAR) > 0, name
    plain = dataclasses.replace(p["module"], remat=False)
    grads = jax.jit(jax.grad(lambda q: lm_loss(
        plain.apply(q, p["tokens"]), p["tokens"])))(p["params"])
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(p["grads"])):
        # (the compiler fuses the two programs' sums differently)
        assert not np.any(np.asarray(b)) or worst(a, b) < 1e-4


@pytest.mark.parametrize("kept", [
    *((name,) for name in names.KDA_KEEPS), names.KDA_KEEPS],
    ids=[*names.KDA_KEEPS, "all_three"])
def test_a_kept_projection_is_one_product_fewer_a_kda_layer(kept,
                                                            monkeypatch):
    """The tiny architecture, rematerialised (``nothing``), in bf16: for
    each of ``q_proj``, ``k_proj`` and ``v_proj`` whose output a KDA layer
    keeps, the gradient's jaxpr holds one ``dot_general`` fewer in each of
    the four KDA layers than under the parent's policy (what a layer kept
    before PR 49: the name sits on the product's own output, not on a
    copy), and with all three kept three fewer."""
    config = tiny(TINY, "bfloat16")
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 128), 0,
                                config["vocab_size"])
    module = arch.build_module(config, {"remat": "nothing"})
    params = arch.program_tree(config, seeded(arch, config, 7))
    loss = lambda p: lm_loss(module.apply(p, tokens), tokens)
    since_49 = hybrid.remat_keeps

    def products(keep):
        monkeypatch.setattr(hybrid, "remat_keeps", lambda z, kind: tuple(
            name for name in since_49(z, kind)
            if name not in names.KDA_KEEPS or name in keep))
        return dense_products(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)

    layers = module.layer_types.count(names.CHANNEL_LINEAR)
    assert layers == 4
    assert products(()) - products(kept) == layers * len(kept)


def test_the_real_cells_layers_keep_their_bytes():
    """Layer 0: ``mixer_out`` and the dense arm's three products over 8,192
    tokens in bf16, 8192 x (2304 + 2 x 9216 + 2304) x 2 = 377.5 MB; the
    expert layers ``mixer_out``, 37.7 MB, the router's float32 logits over
    256 experts, 8.4 MB, and its 8 picks and their scores, 0.5 MB; a KDA
    layer its 128 chunks' inverse besides, 8,192 x 32 heads x 64 x 4 bytes
    = 67.1 MB in float32, and each of ``names.KDA_KEEPS``, 8,192 x 4,096
    columns (32 heads of 128) x 2 bytes = 67.1 MB in bf16."""
    z = arch.build_module(REAL, {"remat": "nothing"}).sizes
    dense = dataclasses.replace(z, feed_forward=names.DENSE_FFN)
    assert hybrid.kept_bytes(hybrid.remat_keeps(dense), dense, 8192,
                             jnp.bfloat16) == 377_487_360
    assert hybrid.kept_bytes(hybrid.remat_keeps(z), z, 8192,
                             jnp.bfloat16) == (
        37_748_736 + 8_388_608 + 524_288)
    for name in names.KDA_KEEPS:
        assert hybrid.kept_bytes((name,), z, 8192, jnp.bfloat16) == (
            8192 * 4096 * 2) == 67_108_864, name
    for kind, mixer in ((names.CHANNEL_LINEAR,
                         (1 + len(names.KDA_KEEPS)) * 67_108_864),
                        (names.LINEAR, 67_108_864), (names.LATENT, 0)):
        assert hybrid.kept_bytes(
            hybrid.remat_keeps(z, kind), z, 8192, jnp.bfloat16) == (
                37_748_736 + 8_388_608 + 524_288 + mixer)


# ---------------------------------------------------------------------------
# (e) the share ties to the model


def expert_params(m: dict, w: dict, first: int, held: int) -> dict:
    d, width = m["d"], m["width"]
    at = slice(first, first + held)
    return {"router": w["router"], "choice_bias": w["choice_bias"],
            "gate": w["experts_gate"].reshape(-1, d, width)[at],
            "up": w["experts_up"].reshape(-1, d, width)[at],
            "down": w["experts_down"].reshape(-1, width, d)[at],
            **{f"shared_{n}": w[f"shared_{n}"] for n in ("gate", "up",
                                                         "down")}}


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """Section 4's share test.  Each of 4 members holds 8 of the 32 experts
    and the whole of router, choice bias and shared expert.  The four
    results, with the shared expert (what every member computes alike)
    counted once, add up to what the uncut reference gives for the whole
    layer; a member alone gives what the reference gives when handed its
    share."""
    held = tiny(TINY)
    full = tiny(TINY, num_experts=held["published"]["num_experts"])
    m_full = arch.dims(full)
    assert (m_full["held"], m_full["experts"]) == (32, 32)
    w = arch.of_layer(seeded(arch, full, 11), 1, m_full)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64), jnp.float32)
    reference_of = lambda **held: jax.jit(lambda x, w: jnp.stack([
        arch.experts(row, w, m=m_full, mode="f32", **held) for row in x]))(
            x, w)
    want, shared_alone = reference_of(), reference_of(held=0)
    sizes = arch.build_module(held, {"remat": None}).sizes
    per = sizes.held
    assert per * EXPERT_MEMBERS == sizes.n_experts == 32
    total, loads = 0.0, []
    for member in range(EXPERT_MEMBERS):
        module = hybrid.ExpertShare(dataclasses.replace(
            sizes, first_expert=member * per), jnp.float32)
        got, state = jax.jit(lambda p: module.apply(
            p, x, mutable=["intermediates"]))(
                {"params": expert_params(m_full, w, member * per, per)})
        if member in (0, 3):
            given = reference_of(first=member * per, held=per)
            assert worst(got, given) < 1e-5
        total = total + got
        loads.append(np.asarray(
            state["intermediates"]["moe_expert_tokens"][0]))
    assert worst(total - (EXPERT_MEMBERS - 1) * shared_alone, want) < 1e-5
    assert worst(total - EXPERT_MEMBERS * shared_alone, want) > 1e-2
    # every one of a token's 8 picks is some member's
    assert np.concatenate(loads).sum() == 2 * 128 * 8


# ---------------------------------------------------------------------------
# (f) counts, events, scopes


def test_the_real_configurations_parameters_to_the_parameter():
    # (the reference stacks layers 1 and 2: one shape, one after another)
    assert arch.stacked_layers(arch.dims(REAL)) == [1, 2]
    assert arch.stacked_layers(arch.dims(three_layers())) == []
    shapes = {}
    for name, shape in arch.weight_shapes(REAL).items():
        if name in arch.STACKED:
            shapes.update({f"layer_{i}.{name.partition('.')[2]}": shape[1:]
                           for i in [1, 2]})
        else:
            shapes[name] = shape
    count = lambda names_: sum(int(np.prod(shapes[n])) for n in names_)
    of = lambda i, pick: count(n for n in shapes if n.startswith(
        f"layer_{i}.") and pick(n.partition(".")[2]))
    mixer = lambda leaf: leaf.endswith("_proj") or leaf in (
        "conv", "A_log", "dt_bias", "gated_norm", "kv_norm")
    assert [of(i, mixer) for i in range(5)] == [
        39_514_272, 39_514_272, 39_514_272, 29_114_880, 39_514_272]
    assert of(0, lambda leaf: leaf.startswith("ffn_") and leaf != "ffn_norm"
              ) == 63_700_992
    arm = lambda leaf: "experts" in leaf or "shared" in leaf or leaf == "router"
    buffers = arch.buffer_shapes(REAL)
    assert buffers == {"kda_moe.choice_bias": (2, 256),
                       "layer_3.choice_bias": (256,),
                       "layer_4.choice_bias": (256,)}
    assert of(1, arm) + 256 == 64_291_072
    assert count(["embed", "head", "final_norm"]) == 94_374_144
    assert count(shapes) + 4 * 256 == REAL["as_run"]["parameters"] == (
        4 * 39_514_272 + 29_114_880 + 63_700_992 + 4 * 64_291_072
        + 5 * 4_608 + 94_374_144) == 602_434_432


def test_the_seeded_decays_differ_over_heads_and_channels():
    """``exp(g)`` at a zero gate projection runs from 0.999 (head 0's first
    channel) to 0.5 (the last head's last), and a head's own channels are
    4.2x apart in rate: a decay taken a head would be another model."""
    # (the real gates at toy widths elsewhere: only their shapes are read)
    real_gates = dict(REAL, hidden_size=8, vocab_size=8, intermediate_size=8,
                      moe_intermediate_size=8, num_experts=1)
    for config in (TINY, real_gates):
        m = arch.dims(config)
        w = jax.jit(lambda words: arch.init_weights(config, words))(
            reference.split_seed(3))
        rate = np.exp(np.asarray(w["layer_0.A_log"]))[:, None] * np.log1p(
            np.exp(np.asarray(w["layer_0.dt_bias"]).reshape(m["kh"], m["kd"])))
        decay = np.exp(-rate)
        assert decay[0, 0] == pytest.approx(0.999, abs=1e-5)
        assert decay[-1, -1] == pytest.approx(0.5, abs=1e-4)
        assert np.all(np.diff(rate, axis=1) > 0) and np.all(
            np.diff(rate, axis=0) > 0)
        assert rate[0, -1] / rate[0, 0] == pytest.approx(4.19, abs=0.01)
        assert rate.max() < GATE_FLOOR / 5


def test_the_layout_events_say_the_mixers_and_the_share(tmp_path, f32_pair):
    p = f32_pair
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        jax.jit(p["module"].apply)(p["params"],
                                   p["tokens"]).block_until_ready()
        ring = list(session.ring)
    finally:
        telemetry.finish(write_report=False)
    said = lambda r: {k: v for k, v in r.items() if k not in (
        "t", "kind", "name", "dur", "gen", "rank")}
    decoder, *sites = [r for r in ring if r["name"] == names.MIXER_LAYOUT]
    assert decoder["kinds"] == [names.CHANNEL_LINEAR] * 3 + [
        names.LATENT, names.CHANNEL_LINEAR]
    assert (decoder["linear_gate_rank"], decoder["latent_rank"],
            decoder["latent_key_dims"], decoder["latent_value_dim"],
            decoder["attn_heads"]) == (16, 12, [24, 8], 16, [2, 2])
    assert decoder["feed_forwards"] == [names.DENSE_FFN] + [
        names.EXPERT_SHARE] * 4
    # one a KDA call site
    assert [said(r) for r in sites] == [dict(
        decay=names.CHANNEL, heads=[2, 2], dk=16, dv=16, chunk=64,
        sub_block=16, gate_floor=5.0, gate_rank=16)] * 4
    # and one a latent call site: head-major, on this machine for its length
    (latent,) = [r for r in ring if r["name"] == names.ATTN_LAYOUT]
    assert said(latent) == dict(
        layout=names.HEAD_MAJOR, reason=names.WHY_SEQ,
        kernel=attention.REFERENCE, qk_dim=32, v_dim=16, latent_rank=12)
    layouts = [r for r in ring if r["name"] == names.MOE_LAYOUT]
    assert len(layouts) == 4
    for r in layouts:
        assert (r["scoring"], r["scale"], r["experts"], r["held"],
                r["top_k"]) == (names.SIGMOID_BIAS, 2.446, 32, 8, 8)
    source = (DATA.parents[2] / "tpudist" / "telemetry"
              / "names.py").read_text()
    for field in (*said(sites[0]), *said(latent), "linear_gate_rank",
                  "latent_key_dims", "latent_value_dim"):
        assert f"``{field}=``" in source, field


def test_the_layers_names_carry_what_the_readers_look_for(f32_pair):
    """A KDA layer's ops lie under ``kda`` (its gates' under ``kda_gate``,
    its scan's under ``delta_rule``) and under no ``linear_attn``; the
    latent layer's under ``latent_attn`` (``latent_kv`` nested) and under
    no ``attn``; layer 0's feed-forward under ``mlp``, the others' under
    ``moe``; forward and backward."""
    p = f32_pair
    text = jax.jit(jax.grad(lambda q: lm_loss(
        p["module"].apply(q, p["tokens"]), p["tokens"]))).lower(
            p["params"]).as_text(debug_info=True)
    found = set(re.findall(r'loc\("([^"]+)"', text))
    under = lambda scope, n: re.search(
        rf"(^|[/(]){scope}([/)]|$)", n) is not None
    for i, kind in enumerate(p["module"].layer_types):
        ops = [n for n in found if under(f"layer_{i}", n)]
        mixer, nested, others = {
            names.CHANNEL_LINEAR: (names.KDA, (names.KDA_GATE,
                                               names.DELTA_RULE),
                                   (names.LINEAR_ATTN, names.LATENT_ATTN)),
            names.LATENT: (names.LATENT_ATTN, (names.LATENT_KV,),
                           (names.ATTN, names.KDA))}[kind]
        for scope in nested:
            for mark in ("jvp(", names.BACKWARD_MARK):
                assert [n for n in ops if under(mixer, n) and under(scope, n)
                        and mark in n], (i, scope, mark)
        for other in others:
            assert not [n for n in ops if under(other, n)], (i, other)
        ffn, not_ffn = (names.MLP, names.MOE) if i == 0 else (
            names.MOE, names.MLP)
        assert [n for n in ops if under(ffn, n)], i
        assert not [n for n in ops if under(not_ffn, n)], i


def test_an_unknown_layer_kind_is_named_against_the_table(f32_pair):
    p = f32_pair
    assert tuple(hybrid.MIXERS) == (
        names.LINEAR, names.FULL, names.WINDOW, names.STATE_SPACE,
        names.CHANNEL_LINEAR, names.LATENT)
    with pytest.raises(ValueError) as e:
        dataclasses.replace(p["module"], layer_types=("retention",) * 5).init(
            jax.random.PRNGKey(0), p["tokens"])
    for kind in hybrid.MIXERS:
        assert kind in str(e.value)
