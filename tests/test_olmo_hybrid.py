"""The pattern decoder's ``olmo_hybrid`` arms (``tpudist/models/hybrid.py``:
norms after the sublayers, :class:`NormedAttention`, the delta-rule mixer
with separate projections at unequal key / value widths and a write
strength of ``2 * sigmoid``, :class:`GatedMLP`, a share of the heads) and
the chunked scan beyond ``beta = 1`` (``tpudist/ops/gated_delta.py``), held
to the plain float32 reference of the benchmark
(``cellbench/archs/olmo_hybrid.py``) at tiny widths on the CPU: d 64, heads
of 12 (key) / 24 (value) / 16 (attention) where the model has 96 / 192 /
128, two heads held of four, feed-forward 176, vocabulary 256, 4 layers in
the 3:1 pattern.

Tolerances, and why.  Float32 against float32 differs only by the order of
sums (the chunked form against the recurrence): 3e-5 of the logits' largest
entry (1.2e-5 read), 1e-4 of a gradient's norm (1.5e-5 read).  The scan
alone: ``SCAN_BOUNDS`` below, each with its reading.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import reference
from cellbench.archs import olmo_hybrid as arch
from tests.decoder_reference import (DATA, highest, logits, reference_logits,
                                     reference_pair, rel, run_steps, seeded,
                                     tiny, worst)
from tpudist import telemetry
from tpudist.models import hybrid
from tpudist.models.transformer import lm_loss
from tpudist.ops.gated_delta import (chunked_gated_delta_rule,
                                     gated_delta_rule_reference)
from tpudist.telemetry import names

TINY = json.loads((DATA / "tiny-olmo-hybrid.json").read_text())


@pytest.fixture(autouse=True)
def highest_precision():
    with highest():
        yield


# ---------------------------------------------------------------------------
# (a) the chunked scan at dk != dv with a write strength up to 2


def scan_inputs(dtype, alike=None, seed=0, heads=3, dk=12, dv=24, chunks=3):
    """Unit keys, ``beta`` drawn from ``(0, 2)``, per-position decays from
    0.37 to 0.999.  ``alike``: the second chunk's 64 keys are ONE key, its
    ``beta`` is ``alike`` throughout and it hardly forgets (0.999 a
    position), so that its 64 writes of alternating sign cancel: the case
    in which the powers of the chunk's ``A`` grow."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (2, chunks * 64, heads)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], shape + (dk,))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], shape + (dk,)))
    v = jax.random.normal(ks[2], shape + (dv,))
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=-7.0, maxval=0.0))
    beta = jax.random.uniform(ks[4], shape, minval=0.0, maxval=2.0)
    if alike is not None:
        k = k.at[:, 64:128].set(k[:, 64:65])
        beta = beta.at[:, 64:128].set(alike)
        g = g.at[:, 64:128].set(-1e-3)
    return [x.astype(dtype) for x in (q, k, v)] + [g, beta]


#: (dtype, a chunk of identical keys at beta 1.9?) -> the error held, of the
#: output's (a gradient's) largest entry.  Read: float32 4e-7 / 6e-7 on
#: independent keys, 9.6e-6 / 1.4e-5 on the chunk of identical keys (5.7e-2
#: with the finite product over 16 rows, which is why ``beta_max > 1`` takes
#: it over 4); bf16 7.5e-3 / 7.0e-3 and 7.6e-2 / 1.1e-1: the operands'
#: rounding (T, u, w in bf16), the same for every way of taking the inverse
SCAN_BOUNDS = {(jnp.float32, False): 1e-5, (jnp.float32, True): 5e-5,
               (jnp.bfloat16, False): 2.5e-2, (jnp.bfloat16, True): 0.25}
CASES = [pytest.param(d, a, id=f"{jnp.dtype(d).name}-"
                      f"{'identical_keys_beta_1.9' if a else 'independent'}")
         for d in (jnp.float32, jnp.bfloat16) for a in (False, True)]


def wide(*args, **kw):
    return chunked_gated_delta_rule(*args, beta_max=2.0, **kw)


@pytest.mark.parametrize("dtype, alike", CASES)
def test_chunked_scan_gives_the_recurrences_values(dtype, alike):
    args = scan_inputs(dtype, 1.9 if alike else None)
    got = jax.jit(wide)(*args)
    assert got.dtype == dtype and got.shape == args[2].shape
    assert worst(got.astype(jnp.float32), jax.jit(
        gated_delta_rule_reference)(*args)) < SCAN_BOUNDS[dtype, alike]


@pytest.mark.parametrize("dtype, alike", CASES)
def test_chunked_scan_gives_the_recurrences_gradients(dtype, alike):
    args = scan_inputs(dtype, 1.9 if alike else None, seed=1)

    def through(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            fn(*a).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4)))(*args)

    for name, got, want in zip("q k v g beta".split(), through(wide),
                               through(gated_delta_rule_reference)):
        assert worst(got.astype(jnp.float32), want.astype(
            jnp.float32)) < SCAN_BOUNDS[dtype, alike], name


def test_the_product_over_16_rows_loses_identical_keys_beyond_beta_1():
    """What ``beta_max`` is for: told that ``beta`` stays in ``[0, 1]`` the
    inverse is the finite product over 16 rows, whose powers of ``A`` reach
    1e6 on a chunk of identical keys at ``beta`` 1.9 and cancel badly even in
    float32 at the highest precision; told the truth, it holds 5e-5."""
    args = scan_inputs(jnp.float32, 1.9)
    narrow = jax.jit(chunked_gated_delta_rule)
    want = jax.jit(gated_delta_rule_reference)(*args)
    assert worst(narrow(*args), want) > 1e-2
    assert worst(jax.jit(wide)(*args), want) < SCAN_BOUNDS[jnp.float32, True]
    # within [0, 1] the two agree to float32's rounding
    q, k, v, g, beta = scan_inputs(jnp.float32)
    assert worst(jax.jit(wide)(q, k, v, g, beta / 2),
                 narrow(q, k, v, g, beta / 2)) < 1e-5


@pytest.mark.parametrize("beta_max", [0.0, 2.5])
def test_scan_refuses_a_write_strength_it_does_not_hold_for(beta_max):
    with pytest.raises(ValueError, match=r"\[0, 2\]"):
        chunked_gated_delta_rule(*scan_inputs(jnp.float32, chunks=1),
                                 beta_max=beta_max)


# ---------------------------------------------------------------------------
# (b) the decoder against the reference


@pytest.fixture(scope="module")
def f32_pair():
    return reference_pair(arch, tiny(TINY))


def test_the_module_takes_the_arms_the_architecture_names(f32_pair):
    z = f32_pair["module"].sizes
    assert (z.attention, z.linear_projections, z.norm, z.norm_after,
            z.feed_forward, z.beta_scale, z.rotary_dim, z.heads_axis) == (
                names.NORMED_ATTN, names.SEPARATE, names.PLAIN, True,
                names.DENSE_FFN, 2.0, 0, None)
    assert (z.n_heads, z.n_heads_total, z.linear_value_heads,
            z.linear_value_heads_total) == (2, 4, 2, 4)
    assert (z.linear_key_dim, z.linear_value_dim, z.head_dim) == (12, 24, 16)


def test_logits_match_the_reference(f32_pair):
    assert worst(logits(f32_pair), reference_logits(f32_pair)) < 3e-5


def test_loss_matches_the_reference(f32_pair):
    assert abs(float(f32_pair["loss"]) - float(f32_pair["ref_loss"])) < 2e-6


@pytest.mark.parametrize("name", arch.leaf_names(TINY))
def test_every_gradient_matches_the_reference(f32_pair, name):
    p = f32_pair
    leaves = arch.leaf_names(p["config"])
    got = arch.named_leaves(p["config"], p["grads"])[leaves.index(name)]
    assert rel(got, p["ref_grads"][name]) < 1e-4


def test_remat_keeps_each_layers_activation_between_mixer_and_feed_forward(
        f32_pair):
    p = f32_pair
    text = str(jax.make_jaxpr(jax.grad(
        lambda q: lm_loss(p["module"].apply(q, p["tokens"]), p["tokens"])))(
            p["params"]))
    assert "checkpoint" in text or "remat" in text
    assert text.count(f"name={names.MIXER_OUT}") >= 4


def products(jaxpr) -> list:
    """The operand shapes of every matrix product of a jaxpr, sub-jaxprs
    (the rematerialised layers, the scans) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("dot_general", "ragged_dot",
                                  "ragged_dot_general"):
            found.append(tuple(v.aval.shape for v in eqn.invars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += products(sub)
    return found


def bf16_program(arch_, config, policy):
    """``(module, params, loss)`` of an architecture's tiny configuration
    in bf16, rematerialised by ``policy``."""
    config = tiny(config, "bfloat16")
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 128), 0,
                                config["vocab_size"])
    module = arch_.build_module(config, {"remat": policy})
    params = arch_.program_tree(config, seeded(arch_, config, 7))
    return module, params, lambda p: lm_loss(module.apply(p, tokens), tokens)


@pytest.mark.parametrize("policy", ["nothing", "dots_no_batch"])
def test_a_rematerialised_layer_computes_its_feed_forward_once(policy,
                                                               monkeypatch):
    """Under remat a dense-arm layer keeps its feed-forward's three
    products' outputs besides ``MIXER_OUT``, whatever the policy: the
    gradient then holds 9 products with a feed-forward operand a layer (3
    forward, 6 backward) where recomputing them makes it 12, the kept
    residuals are those three tensors, and the gradients are the very bits
    of the program that keeps ``MIXER_OUT`` alone."""
    from jax._src.ad_checkpoint import saved_residuals

    module, params, loss = bf16_program(arch, TINY, policy)
    z, layers = module.sizes, TINY["num_hidden_layers"]
    assert hybrid.remat_keeps(z) == (
        names.MIXER_OUT, names.FFN_GATE, names.FFN_UP, names.FFN_OUT)

    def of_the_ffn(jaxpr):
        return sum(any(z.ffn_width in shape for shape in operands)
                   for operands in products(jaxpr))

    def ffn_residuals():
        return sorted(aval.shape[-1] for aval, why in saved_residuals(
            loss, params) if "GatedMLP" in why)

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert of_the_ffn(jaxpr.jaxpr) == 9 * layers
    for name in hybrid.remat_keeps(z):
        assert str(jaxpr).count(f"name={name}") >= layers
    assert ffn_residuals() == sorted(
        layers * [z.d_model, z.ffn_width, z.ffn_width])
    grads = jax.jit(jax.grad(loss))(params)

    monkeypatch.setattr(hybrid, "remat_keeps",
                        lambda z, kind: (names.MIXER_OUT,))
    assert ffn_residuals() == []
    # the policy that saves every product without a batch dimension saves
    # these three already; ``nothing`` runs them again
    assert of_the_ffn(jax.make_jaxpr(jax.grad(loss))(params).jaxpr) == (
        12 if policy == "nothing" else 9) * layers
    for got, want in zip(jax.tree.leaves(grads),
                         jax.tree.leaves(jax.jit(jax.grad(loss))(params))):
        assert got.dtype == want.dtype and bool(jnp.all(got == want))


def test_the_expert_share_arm_keeps_what_it_kept():
    """``MIXER_OUT`` and, since PR 44, what its router computed (the
    logits, the picks and their scores): the count of products in its
    gradient is PR 34's (352 under ``nothing``, CPU) less the router's
    rematerialised product, one a layer, and, since PR 48, less the ten
    products of a chunk's inverse in each of its three delta-rule layers,
    which keep ``DELTA_INVERSE``."""
    from cellbench.archs import qwen3_next

    module, params, loss = bf16_program(
        qwen3_next, json.loads((DATA / "tiny-hybrid.json").read_text()),
        "nothing")
    assert module.sizes.feed_forward == names.EXPERT_SHARE
    assert hybrid.remat_keeps(module.sizes) == (
        names.MIXER_OUT, names.ROUTER_LOGITS, names.ROUTER_PICKS)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    linear = module.layer_types.count(names.LINEAR)
    assert linear == 3 and hybrid.remat_keeps(
        module.sizes, names.LINEAR)[-1] == names.DELTA_INVERSE
    assert len(products(jaxpr.jaxpr)) == (
        352 - len(module.layer_types) - 10 * linear)
    assert not any(f"name={name}" in str(jaxpr)
                   for name in names.DENSE_FFN_KEEPS)


@pytest.mark.parametrize("wrong", [
    dict(beta_scale=1.0), dict(norm_after=False),
    dict(norm=names.ZERO_CENTRED)],
    ids=["beta_without_its_factor", "norm_before", "zero_centred_norm"])
def test_another_arm_is_not_this_architecture(f32_pair, wrong):
    """Each of the architecture's choices shows in the logits by far more
    than the tolerance: none of them is decoration at these weights."""
    p = f32_pair
    other = p["module"].clone(sizes=dataclasses.replace(
        p["module"].sizes, **wrong))
    assert worst(logits(p, other), reference_logits(p)) > 1e-2


def test_three_adam_steps_follow_the_reference():
    """``make_lm_train_step`` over the float32 program against the
    reference's own Adam: losses to 1e-5, every tensor's change after three
    steps to 2e-3 of its norm."""
    config = tiny(TINY)
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
    np.testing.assert_allclose(norms, ref["update_norms"], rtol=2e-3)


# ---------------------------------------------------------------------------
# (c) the share of the heads


WHOLE = dict(num_attention_heads=4, num_key_value_heads=4,
             linear_num_key_heads=4, linear_num_value_heads=4)


def half_of(m: dict, w: dict, kind: str, member: int) -> dict:
    """Member ``member``'s half of one layer's mixer weights: its heads'
    columns of the input projections (and of the norms over all heads, the
    convolution's taps, the per-head decays), their rows of the output
    projection."""
    def columns(x, heads, width):
        per = heads // 2 * width
        return x[..., member * per:(member + 1) * per]

    def rows(x, heads, width):
        return columns(x.T, heads, width).T

    if kind == arch.FULL:
        h, dh = m["heads"], m["dh"]
        out = {n: columns(w[n], h, dh) for n in (
            "q_proj", "k_proj", "v_proj", "q_norm", "k_norm")}
        out["o_proj"] = rows(w["o_proj"], h, dh)
        return out
    nk, nv, dk, dv = m["nk"], m["nv"], m["dk"], m["dv"]
    out = {n: columns(w[n], nk, dk) for n in ("q_proj", "k_proj")}
    out.update({n: columns(w[n], nv, dv) for n in ("v_proj", "g_proj")})
    out.update({n: columns(w[n], nv, 1) for n in (
        "b_proj", "a_proj", "A_log", "dt_bias")})
    q, k, v = jnp.split(w["conv"], [nk * dk, 2 * nk * dk])
    out["conv"] = jnp.concatenate([rows(q, nk, dk), rows(k, nk, dk),
                                   rows(v, nv, dv)])
    out["gated_norm"] = w["gated_norm"]
    out["out_proj"] = rows(w["out_proj"], nv, dv)
    return out


@pytest.mark.parametrize("kind, layer", [(arch.LINEAR, 0), (arch.FULL, 3)],
                         ids=["linear_layer", "full_layer"])
def test_the_two_halves_of_the_heads_add_up_to_the_uncut_layer(kind, layer):
    """Section 4's share test.  The uncut reference holds all four heads.
    Each member holds two, told ``heads_axis``: under a ``vmap`` with that
    axis name the mean square of ``q_norm`` / ``k_norm`` runs over all four
    heads' dims and the output projections' partial sums are added up, so
    each member's mixer output IS the uncut one.  A member alone (no axis:
    the cell) gives what the reference gives when handed that half."""
    whole, held = tiny(TINY, **WHOLE), tiny(TINY)
    m_whole, m_held = arch.dims(whole), arch.dims(held)
    w = arch.of_layer(seeded(arch, whole, 11), layer)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64), jnp.float32)
    reference_of = lambda m: jax.jit(lambda x, w: jnp.stack([arch.mixer(
        row, w, kind=kind, m=m, mode="f32") for row in x]))
    want = reference_of(m_whole)(x, w)
    halves = [half_of(m_whole, w, kind, member) for member in (0, 1)]

    def program_params(half):
        tree = arch.program_tree(held, {f"layer_{layer}.{k}": v
                                        for k, v in half.items()})
        (mixer,) = tree["params"][f"layer_{layer}"].values()
        return mixer

    sizes = arch.build_module(held, {"remat": None}).sizes
    cls = (hybrid.NormedAttention if kind == arch.FULL
           else hybrid.GatedDeltaNet)
    shared = cls(dataclasses.replace(sizes, heads_axis="heads"),
                 jnp.float32)
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                           *map(program_params, halves))
    both = jax.jit(jax.vmap(lambda p: shared.apply({"params": p}, x),
                            axis_name="heads"))(stacked)
    for member in (0, 1):
        assert worst(both[member], want) < 1e-5
    alone, given_a_half = jax.jit(cls(sizes, jnp.float32).apply), (
        reference_of(m_held))
    parts = []
    for half in halves:
        got = alone({"params": program_params(half)}, x)
        given = given_a_half(x, half)
        assert worst(got, given) < 1e-5
        parts.append(got)
    if kind == arch.LINEAR:
        # no statistic crosses the delta-rule mixer's heads: its two
        # partial outputs add up as they are
        assert worst(parts[0] + parts[1], want) < 1e-5
    else:
        # the statistic over the held heads alone is another number
        assert worst(parts[0] + parts[1], want) > 1e-3


def test_the_whole_layers_parameters_are_the_two_halves(f32_pair):
    whole, held = tiny(TINY, **WHOLE), f32_pair["config"]
    count = lambda c: {k: int(np.prod(s))
                       for k, s in arch.weight_shapes(c).items()}
    n_whole, n_held = count(whole), count(held)
    # held whole: the feed-forward, the norms over d_model or over one
    # head's dims, the embedding and the head; halved: everything a head owns
    whole_here = ("mixer_norm", "ffn_norm", "gated_norm", "final_norm",
                  "ffn_gate", "ffn_up", "ffn_down", "embed", "head")
    for name, n in n_whole.items():
        if name.rpartition(".")[2] in whole_here:
            assert n_held[name] == n, name
        else:
            assert 2 * n_held[name] == n, name


# ---------------------------------------------------------------------------
# (d) what the decoder says of itself


def test_the_layout_event_says_the_arms_and_the_share(tmp_path, f32_pair):
    p = f32_pair
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        jax.jit(p["module"].apply)(p["params"],
                                   p["tokens"]).block_until_ready()
        events = [r for r in session.ring if r.get("kind") == "event"
                  and r["name"] == names.MIXER_LAYOUT]
    finally:
        telemetry.finish(write_report=False)
    (e,) = events
    assert e["kinds"] == [names.LINEAR] * 3 + [names.FULL]
    assert (e["attention"], e["attn_heads"], e["attn_kv_heads"],
            e["head_dim"]) == (names.NORMED_ATTN, [2, 4], 2, 16)
    assert (e["linear_heads"], e["linear_key_heads"], e["linear_key_dim"],
            e["linear_value_dim"], e["linear_projections"],
            e["beta_scale"]) == ([2, 4], 2, 12, 24, names.SEPARATE, 2.0)
    assert (e["feed_forward"], e["norm"], e["norm_after"],
            e["heads_axis"]) == (names.DENSE_FFN, names.PLAIN, True, None)
    # what a rematerialised layer keeps, a layer: the three delta-rule
    # layers their chunks' inverse besides (2 held heads x 64 float32 a
    # position), the attention layer nothing of its mixer's
    dense = [names.MIXER_OUT, *names.DENSE_FFN_KEEPS]
    assert e["remat_keeps"] == [dense + [names.DELTA_INVERSE]] * 3 + [dense]
    held = e["remat_kept_bytes_per_layer"]
    assert held[0] == held[1] == held[2] == held[3] + p[
        "tokens"].size * 2 * 64 * 4


def test_the_layers_names_carry_what_the_readers_look_for(f32_pair):
    """``dense_ffn_ms_per_step`` reads ops under ``mlp`` inside a component
    ``<PATTERN_LAYER>_<i>``: both are in the lowered step's locations."""
    import re

    p = f32_pair
    text = jax.jit(p["module"].apply).lower(
        p["params"], p["tokens"]).as_text(debug_info=True)
    found = set(re.findall(r'loc\("([^"]+)"', text))
    layer = rf"{names.PATTERN_LAYER}_\d+"
    assert any(re.search(rf"/{layer}/{names.MLP}/", f) for f in found)
    assert any(re.search(rf"/{layer}/{names.LINEAR_ATTN}/.*{names.DELTA_RULE}",
                         f) for f in found)
    assert any(re.search(rf"/{layer}/{names.ATTN}/", f) for f in found)
