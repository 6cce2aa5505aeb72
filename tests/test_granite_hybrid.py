"""The pattern decoder's ``granitemoehybrid`` arms (``tpudist/models/
hybrid.py``: a Mamba-2 mixer that holds half the heads of ONE group of ``B``
and ``C``, plain grouped-query attention at a softmax scale of its own, a
gated feed-forward behind every mixer, the four multipliers, a tied head),
held to the published module where it is on this machine
(``transformers.models.granitemoehybrid.GraniteMoeHybridForCausalLM``;
``torch`` on the CPU) and to the plain float32 reference of the benchmark
(``cellbench/archs/granitemoehybrid.py``) at tiny widths on the CPU: d 64,
Mamba heads of 8 with a state of 16 in chunks of 32, attention heads of 16 at
a scale of 1 / 8, a feed-forward of 96, vocabulary 256, layers ``mamba,
mamba, attention, mamba``; one of two head shares held, the group whole.

Tolerances, and why.  Float32 against float32 differs only by the order of
sums (the chunked scan against the recurrence): 3e-5 of the logits' largest
entry (3e-7 read), 1e-4 of a gradient's norm (under 1e-5 read).  Against
``torch``: 2e-5 (its float32 sums in another order; 1e-6 read).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import reference
from cellbench.archs import granitemoehybrid as arch
from tests.decoder_reference import (DATA, highest, logits, reference_logits,
                                     reference_pair, rel, seeded, tiny, worst)
from tpudist import telemetry
from tpudist.models import hybrid
from tpudist.models.transformer import lm_loss
from tpudist.telemetry import names

TINY = json.loads((DATA / "tiny-granite-hybrid.json").read_text())
REAL = json.loads((DATA.parents[1] / "configs"
                   / "granite-4.0-h-micro.json").read_text())
MEMBERS = 2
SHARED = ("mamba_n_heads", "num_attention_heads", "num_key_value_heads")


def whole(**keys) -> dict:
    """The uncut tiny model: every published head (layers and vocabulary as
    the share's)."""
    return tiny(TINY, **{k: TINY["published"][k] for k in SHARED}, **keys)


@pytest.fixture(autouse=True)
def highest_precision():
    with highest():
        yield


# ---------------------------------------------------------------------------
# (a) against the published module


def test_the_decoder_is_the_published_modules_on_shared_weights():
    """The uncut tiny configuration through ``HybridLM`` and through
    ``GraniteMoeHybridForCausalLM`` (its ``torch_forward`` path) on the same
    weights: both layer kinds, the multipliers, no positions, the gated norm
    over all heads, the tied head.  This is what leaves ``assumed`` with
    nothing of the mathematics."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.granitemoehybrid")

    config = whole()
    m = arch.dims(config)
    their = hf.GraniteMoeHybridForCausalLM(hf.GraniteMoeHybridConfig(
        **{k: v for k, v in config.items() if k not in (
            "name", "source", "model_type", "reduced", "published",
            "deployment", "as_run", "departures", "assumed")},
        attn_implementation="eager")).eval()
    weights = seeded(arch, config, 5)
    t = lambda a: torch.tensor(np.asarray(a))
    state = {"model.embed_tokens.weight": t(weights["embed"]),
             "lm_head.weight": t(weights["embed"]),
             "model.norm.weight": t(weights["final_norm"])}
    theirs = {"norm": "input_layernorm.weight",
              "mlp_norm": "post_attention_layernorm.weight",
              "conv_bias": "mamba.conv1d.bias", "A_log": "mamba.A_log",
              "D": "mamba.D", "dt_bias": "mamba.dt_bias",
              "gated_norm": "mamba.norm.weight"}
    matrices = {"in_proj": "mamba.in_proj", "out_proj": "mamba.out_proj",
                "mlp_down": "shared_mlp.output_linear",
                **{f"{n}_proj": f"self_attn.{n}_proj" for n in "qkvo"}}
    for i in range(m["depth"]):
        w = arch.of_layer(weights, i, m)
        at = f"model.layers.{i}."
        for leaf, value in w.items():
            if leaf in theirs:
                state[at + theirs[leaf]] = t(value)
            elif leaf in matrices:
                state[at + matrices[leaf] + ".weight"] = t(value.T)
        if "conv" in w:
            state[at + "mamba.conv1d.weight"] = t(w["conv"][:, None, :])
        # the gate is the first half of ``input_linear``
        state[at + "shared_mlp.input_linear.weight"] = t(jnp.concatenate(
            [w["mlp_gate"], w["mlp_up"]], axis=-1).T)
    missing, unexpected = their.load_state_dict(state, strict=False)
    assert not unexpected and not missing, (missing, unexpected)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 96), 0,
                                config["vocab_size"])
    with torch.no_grad():
        want = their(torch.tensor(np.asarray(tokens)).long()).logits.numpy()
    module = arch.build_module(config, {"remat": None})
    assert module.sizes.heads_axis is None
    got = jax.jit(module.apply)(arch.program_tree(config, weights), tokens)
    assert worst(got, want) < 2e-5


# ---------------------------------------------------------------------------
# (b) the decoder against the reference, on a share


@pytest.fixture(scope="module")
def f32_pair():
    return reference_pair(arch, tiny(TINY))


def test_the_module_takes_the_arms_the_architecture_names(f32_pair):
    module = f32_pair["module"]
    z = module.sizes
    assert module.layer_types == (names.STATE_SPACE, names.STATE_SPACE,
                                  names.FULL, names.STATE_SPACE)
    assert (z.one_sublayer, z.attention, z.norm, z.norm_after,
            z.feed_forward, z.heads_axis, z.ffn_width) == (
                False, names.GROUPED_ATTN, names.PLAIN, False,
                names.DENSE_FFN, None, 96)
    assert (z.ssm_heads, z.ssm_heads_total, z.ssm_groups,
            z.ssm_groups_total, z.ssm_head_dim, z.ssm_state,
            z.ssm_chunk) == (8, 16, 1, 1, 8, 16, 32)
    assert hybrid.ssm_group_members(z) == 2
    assert (z.n_heads, z.n_heads_total, z.n_kv_heads, z.head_dim) == (
        2, 4, 1, 16)
    assert (z.softmax_scale, z.residual_scale, z.embedding_scale,
            z.logits_divisor, z.tied_head) == (0.125, 0.22, 12.0, 8.0, True)
    # the dense arm's products are NOT kept in this decoder
    assert z.ffn_products_kept is False
    assert hybrid.remat_keeps(z) == (names.MIXER_OUT,)
    assert "head" not in f32_pair["params"]["params"]


def test_logits_match_the_reference(f32_pair):
    assert worst(logits(f32_pair), reference_logits(f32_pair)) < 3e-5


def test_loss_matches_the_reference(f32_pair):
    assert abs(float(f32_pair["loss"]) - float(f32_pair["ref_loss"])) < 2e-6


def reference_leaf(config, tree, name):
    """A ``leaf_names`` name's tensor of a reference tree (a Mamba layer's
    is a slice of a stacked entry)."""
    layer, _, leaf = name.rpartition(".")
    if not layer:
        return tree[name]
    return arch.of_layer(tree, int(layer.rpartition("_")[2]),
                         arch.dims(config))[leaf]


@pytest.mark.parametrize("name", arch.leaf_names(TINY))
def test_every_gradient_matches_the_reference(f32_pair, name):
    """Among them ``embed``: the tied embedding's ONE gradient, the
    gather's scatter-add plus the head's product."""
    p = f32_pair
    leaves = arch.leaf_names(p["config"])
    got = arch.named_leaves(p["config"], p["grads"])[leaves.index(name)]
    want = reference_leaf(p["config"], p["ref_grads"], name)
    assert float(jnp.linalg.norm(want)) > 0
    assert rel(got, want) < 1e-4


def test_the_tied_embeddings_gradient_has_both_paths(f32_pair):
    """Cut the head's read of ``tok_embed`` from the gradient and what is
    left is the gather's alone: rows of tokens that never occur are zero,
    and it is not the reference's."""
    p = f32_pair
    real = hybrid.nn.Embed.attend

    def cut(self, query):
        q, e = hybrid.nn.dtypes.promote_dtype(
            query, jax.lax.stop_gradient(self.embedding), dtype=self.dtype)
        return jnp.dot(q, e.T)

    try:
        hybrid.nn.Embed.attend = cut
        grads = jax.jit(jax.grad(lambda q: lm_loss(
            p["module"].apply(q, p["tokens"]), p["tokens"])))(p["params"])
    finally:
        hybrid.nn.Embed.attend = real
    gather = grads["params"]["tok_embed"]["embedding"]
    unseen = np.setdiff1d(np.arange(256), np.asarray(p["tokens"]))
    assert len(unseen) and float(jnp.abs(gather[unseen]).max()) == 0.0
    both = p["grads"]["params"]["tok_embed"]["embedding"]
    assert float(jnp.abs(both[unseen]).max()) > 0
    assert rel(gather, p["ref_grads"]["embed"]) > 1e-2


def test_the_stacked_weights_go_round_the_programs_tree(f32_pair):
    p = f32_pair
    config, weights = p["config"], p["weights"]
    assert set(weights) == set(arch.weight_shapes(config))
    assert {k: v.shape for k, v in weights.items()} == arch.weight_shapes(
        config)
    back = arch.named_leaves(config, p["params"])
    for name, leaf in zip(arch.leaf_names(config), back):
        np.testing.assert_array_equal(
            leaf, reference_leaf(config, weights, name), err_msg=name)
    made = jax.eval_shape(p["module"].init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 64), jnp.int32))
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape,
                                                         p["params"])
    # the order of ``reference.leaf_norms``: a stacked entry a layer
    norms = jax.jit(lambda w: reference.leaf_norms(arch, config, w))(weights)
    ours = jnp.stack([jnp.linalg.norm(x) for x in back])
    np.testing.assert_allclose(norms, ours, rtol=1e-6)
    # A = 1 .. heads held, steps inside the module's limits
    layer = arch.of_layer(weights, 0, arch.dims(config))
    np.testing.assert_allclose(np.exp(layer["A_log"]), np.arange(1, 9),
                               rtol=1e-6)
    steps = np.asarray(jax.nn.softplus(weights["mamba.dt_bias"]))
    run = config["as_run"]
    assert run["time_step_min"] <= steps.min() < steps.max() <= run[
        "time_step_max"]


def test_an_adam_step_over_the_stacked_tree_is_optaxs(f32_pair):
    """The reference's own Adam over its stacked tree moves every tensor as
    ``optax.adam`` moves the program's, from the gradients above (the three
    steps through ``make_lm_train_step`` and the runner are
    ``cellbench/tests/test_granite_hybrid_cell.py``'s)."""
    import optax

    p = f32_pair
    tx = optax.adam(2e-3)
    updates, _ = jax.jit(tx.update)(p["grads"], tx.init(p["params"]),
                                    p["params"])
    zeros = jax.tree.map(jnp.zeros_like, p["weights"])
    moved, mu, _ = jax.jit(reference.adam_update)(
        p["weights"], p["ref_grads"], zeros, zeros, jnp.float32(1),
        jnp.float32(2e-3))
    got = arch.named_leaves(p["config"], updates)
    for name, leaf in zip(arch.leaf_names(p["config"]), got):
        want = (reference_leaf(p["config"], moved, name)
                - reference_leaf(p["config"], p["weights"], name))
        assert rel(leaf, want) < 2e-3, name
    np.testing.assert_allclose(mu["embed"], 0.1 * p["ref_grads"]["embed"],
                               rtol=1e-6)


def test_the_references_own_zeroed_state_differs_from_the_carried_one():
    config = tiny(TINY)
    weights = seeded(arch, config, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (1, 128), dtype=np.int32))
    carried, zeroed = jax.jit(lambda w: tuple(
        arch._row_loss(config, w, tokens[0], "f32", carry)
        for carry in (True, False)))(weights)
    assert abs(float(carried) - float(zeroed)) > 1e-3


@pytest.mark.parametrize("kinds", [
    ("attention", "mamba"), ("mamba", "attention"),
    ("mamba", "attention", "attention", "mamba", "attention")],
    ids=["attention_first", "attention_last", "two_in_a_row"])
def test_an_attention_layer_runs_where_the_pattern_has_it(kinds):
    """The reference's one loop over the stacked Mamba layers runs an
    attention layer at the step of the Mamba layer that follows it, and
    behind the loop where none does: the program's unrolled layers agree
    wherever the pattern puts them."""
    config = tiny(TINY, layer_types=list(kinds),
                  num_hidden_layers=len(kinds))
    weights = seeded(arch, config, 2)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0, 256)
    module = arch.build_module(config, {"remat": None})
    got, want = jax.jit(lambda w: (
        module.apply(arch.program_tree(config, w), tokens),
        arch.forward(config, w, tokens)))(weights)
    assert worst(got, want) < 3e-5


# ---------------------------------------------------------------------------
# (c) the share ties to the model


def share_of(m: dict, w: dict, kind: str, member: int) -> dict:
    """Member ``member``'s half of one uncut layer's mixer: its heads'
    columns of the input projections, taps and per-head numbers and their
    rows of the output projection; of a Mamba layer the ONE group's ``B``
    and ``C`` columns, taps and bias WHOLE, on both members alike."""
    def part(x, start, size, axis=-1):
        return jax.lax.slice_in_dim(x, start + member * size,
                                    start + (member + 1) * size, axis=axis)

    if kind == arch.MAMBA:
        heads = m["mh"] // MEMBERS
        inner, bc, wide = m["mh"] * m["mp"], m["mg"] * m["mn"], heads * m[
            "mp"]
        both = lambda x, at: jax.lax.slice_in_dim(x, at, at + 2 * bc, axis=-1)
        proj = w["in_proj"]
        return {"in_proj": jnp.concatenate(
                    [part(proj, 0, wide), part(proj, inner, wide),
                     both(proj, 2 * inner),
                     part(proj, 2 * inner + 2 * bc, heads)], axis=-1),
                "conv": jnp.concatenate([part(w["conv"].T, 0, wide),
                                         both(w["conv"].T, inner)], -1).T,
                "conv_bias": jnp.concatenate(
                    [part(w["conv_bias"], 0, wide),
                     both(w["conv_bias"], inner)]),
                **{k: part(w[k], 0, heads) for k in ("A_log", "D",
                                                     "dt_bias")},
                "gated_norm": part(w["gated_norm"], 0, wide),
                "out_proj": part(w["out_proj"], 0, wide, axis=0)}
    wide, narrow = (m[k] // MEMBERS * m["dh"] for k in ("heads", "kv"))
    return {"q_proj": part(w["q_proj"], 0, wide),
            "k_proj": part(w["k_proj"], 0, narrow),
            "v_proj": part(w["v_proj"], 0, narrow),
            "o_proj": part(w["o_proj"], 0, wide, axis=0)}


def mixer_params(kind: str, share: dict) -> dict:
    """A mixer's own parameters in the program's tree."""
    out: dict = {}
    for leaf, value in share.items():
        _, *path = arch._LAYER_PATHS[kind][leaf]
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = value
    return out


def bc_columns(m: dict) -> slice:
    """Where a member's ``in_proj`` holds the group's ``B`` and ``C``."""
    inner = m["mh"] * m["mp"]
    return slice(2 * inner, 2 * inner + 2 * m["mg"] * m["mn"])


def reference_mixer(w, x, kind, m):
    """The reference's mixer over every row of ``x [rows, s, d]``."""
    return jax.jit(jax.vmap(lambda row, w: arch.sublayer(
        row, w, kind=kind, m=m, mode="f32"), in_axes=(0, None)))(x, w)


@pytest.fixture(scope="module")
def shares():
    """The uncut layer of each kind, its two shares and what the uncut
    reference gives."""
    with highest():
        full, held = whole(), tiny(TINY)
        m_full, m_held = arch.dims(full), arch.dims(held)
        weights = seeded(arch, full, 11)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64),
                              jnp.float32)
        sizes = arch.build_module(held, {"remat": None}).sizes
        out = dict(x=x, m_full=m_full, m_held=m_held, sizes=sizes)
        for kind, layer in ((arch.MAMBA, 0), (arch.ATTENTION, 2)):
            w = {k: v for k, v in arch.of_layer(weights, layer,
                                                m_full).items()
                 if k in arch._LAYER_PATHS[kind] and not k.startswith(
                     "mlp") and k != "norm"}
            want = reference_mixer(w, x, kind, m_full)
            out[kind] = dict(w=w, want=want, shares=[
                share_of(m_full, w, kind, i) for i in range(MEMBERS)])
        return out


def test_a_lone_attention_share_is_the_reference_given_that_share(shares):
    """No statistic crosses attention's cut: a member alone gives what the
    reference gives when handed its half, and the two partial outputs add
    up to the uncut layer's."""
    s, a = shares, shares[arch.ATTENTION]
    alone = hybrid.GroupedAttention(s["sizes"], jnp.float32)
    parts = []
    for share in a["shares"]:
        got = jax.jit(alone.apply)(
            {"params": mixer_params(arch.ATTENTION, share)}, s["x"])
        given = reference_mixer(share, s["x"], arch.ATTENTION, s["m_held"])
        assert worst(got, given) < 1e-5
        parts.append(got)
    assert worst(sum(parts), a["want"]) < 1e-5
    assert worst(parts[0], a["want"]) > 1e-1


def test_a_lone_mamba_share_is_the_reference_given_that_share(shares):
    """Without an axis the gated norm's mean square runs over the heads
    HELD: what the reference gives when handed the same share.  That is the
    cell.  The two lone outputs do NOT add up to the uncut layer's: the
    norm's statistic is over half the group's channels each."""
    s, a = shares, shares[arch.MAMBA]
    alone = hybrid.Mamba2Mixer(s["sizes"], jnp.float32)
    parts = []
    for share in a["shares"]:
        got = jax.jit(alone.apply)(
            {"params": mixer_params(arch.MAMBA, share)}, s["x"])
        given = reference_mixer(share, s["x"], arch.MAMBA, s["m_held"])
        assert worst(got, given) < 1e-5
        parts.append(got)
    assert worst(sum(parts), a["want"]) > 1e-3


@pytest.mark.parametrize("kind", [arch.MAMBA, arch.ATTENTION])
def test_the_two_head_shares_add_up_to_the_uncut_layer(shares, kind):
    """Section 4's share test.  The uncut reference holds all 16 Mamba
    heads on the one group (4 query heads on 2 key/value heads).  Told
    ``heads_axis``, under a ``vmap`` with that axis name, each member holds
    its half of the heads and (Mamba) ``B`` and ``C`` whole; the gated
    norm's sum of squares and count are reduced over the members, the
    output projections' partial sums added up, and each member's output IS
    the uncut layer's."""
    s, a = shares, shares[kind]
    cls = hybrid.Mamba2Mixer if kind == arch.MAMBA else (
        hybrid.GroupedAttention)
    shared = cls(dataclasses.replace(s["sizes"], heads_axis="heads"),
                 jnp.float32)
    stacked = jax.tree.map(lambda *t: jnp.stack(t), *(
        mixer_params(kind, share) for share in a["shares"]))
    every = jax.jit(jax.vmap(lambda p: shared.apply({"params": p}, s["x"]),
                             axis_name="heads"))(stacked)
    for member in range(MEMBERS):
        assert worst(every[member], a["want"]) < 1e-5


def test_the_members_gradients_of_b_and_c_add_up_to_the_uncut_layers(shares):
    """``B`` and ``C`` are on both members: each member's gradient of those
    columns of ``in_proj`` (and of their taps and bias) is its own heads'
    part, and the two add up to the uncut layer's, which is what the
    deployment's all-reduce of a replicated tensor's gradient gives."""
    s, a = shares, shares[arch.MAMBA]
    m_full, m_held = s["m_full"], s["m_held"]
    shared = hybrid.Mamba2Mixer(
        dataclasses.replace(s["sizes"], heads_axis="heads"), jnp.float32)
    probe = jax.random.normal(jax.random.PRNGKey(9), a["want"].shape)
    stacked = jax.tree.map(lambda *t: jnp.stack(t), *(
        mixer_params(arch.MAMBA, share) for share in a["shares"]))

    def loss(stacked):
        every = jax.vmap(lambda p: shared.apply({"params": p}, s["x"]),
                         axis_name="heads")(stacked)
        # ONE member's output: the psum's transpose hands its cotangent to
        # every member's partial sum, as the deployment's backward does
        return jnp.sum(every[0] * probe)

    got = jax.jit(jax.grad(loss))(stacked)
    want = jax.jit(jax.grad(lambda w: jnp.sum(jnp.stack([arch.sublayer(
        row, w, kind=arch.MAMBA, m=m_full, mode="f32")
        for row in s["x"]]) * probe)))(a["w"])
    held, full = bc_columns(m_held), bc_columns(m_full)
    assert rel(got["in_proj"]["kernel"][:, :, held].sum(0),
               want["in_proj"][:, full]) < 1e-4
    inner_held, inner = (m["mh"] * m["mp"] for m in (m_held, m_full))
    assert rel(got["conv"][:, inner_held:].sum(0),
               want["conv"][inner:]) < 1e-4
    assert rel(got["conv_bias"][:, inner_held:].sum(0),
               want["conv_bias"][inner:]) < 1e-4
    # one member's alone is not it; a head's own tensors are not summed
    assert rel(got["in_proj"]["kernel"][0][:, held],
               want["in_proj"][:, full]) > 1e-2
    for member in range(MEMBERS):
        assert rel(got["out_proj"]["kernel"][member], share_of(
            m_full, want, arch.MAMBA, member)["out_proj"]) < 1e-4


def mixer_as_before(params, x, z, dtype):
    """``Mamba2Mixer.__call__`` as it stood before a group could span the
    members (PR 42), on the same parameters."""
    b, s, d = x.shape
    h, g, p, n = z.ssm_heads, z.ssm_groups, z.ssm_head_dim, z.ssm_state
    inner, bc = h * p, g * n
    proj = jnp.dot(x.astype(dtype), params["in_proj"]["kernel"].astype(dtype))
    gate, mixed, dt = jnp.split(proj, [inner, 2 * inner + 2 * bc], axis=-1)
    mixed = jax.nn.silu(hybrid.causal_depthwise_conv(mixed, params["conv"])
                        + params["conv_bias"]).astype(dtype)
    u, b_in, c_out = jnp.split(mixed, [inner, inner + bc], axis=-1)
    y = hybrid.ssd_scan(
        u.reshape(b, s, h, p),
        jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"]),
        params["A_log"], b_in.reshape(b, s, g, n), c_out.reshape(b, s, g, n),
        params["D"], chunk=z.ssm_chunk)
    y = (y.reshape(b, s, inner).astype(jnp.float32)
         * jax.nn.silu(gate.astype(jnp.float32)))
    y = y.reshape(b, s, g, inner // g)
    y = (y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                           + z.eps)).reshape(b, s, inner) * params["norm"]
    return jnp.dot(y.astype(dtype), params["out_proj"]["kernel"].astype(
        dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("axis", [None, "heads"], ids=["alone", "axis"])
def test_a_member_that_holds_whole_groups_computes_what_it_did(dtype, axis):
    """2 of 16 heads in 1 of 8 groups (the nemotron cell's case in ratio):
    bit for bit the mixer as it was, and with ``heads_axis`` nothing of the
    norm is reduced (only ``out_proj``'s partial sums are)."""
    dtype = jnp.dtype(dtype)
    z = hybrid.HybridSizes(
        d_model=64, head_dim=16, ssm_heads=2, ssm_groups=1, ssm_head_dim=8,
        ssm_state=16, ssm_chunk=32, ssm_heads_total=16, ssm_groups_total=8,
        eps=1e-5)
    assert hybrid.ssm_group_members(z) == 1
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64))
    mixer = hybrid.Mamba2Mixer(z, dtype)
    params = jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"]
    params["dt_bias"] = jnp.full_like(params["dt_bias"], -2.0)
    params["conv_bias"] = 0.1 + params["conv_bias"]
    want = jax.jit(lambda p: mixer_as_before(p, x, z, dtype))(params)
    if axis is None:
        got = jax.jit(mixer.apply)({"params": params}, x)
    else:
        shared = hybrid.Mamba2Mixer(
            dataclasses.replace(z, heads_axis=axis), dtype)
        twice = jax.tree.map(lambda t: jnp.stack([t, t]), params)
        got = jax.jit(jax.vmap(lambda p: shared.apply({"params": p}, x),
                               axis_name=axis))(twice)[0]
        want = want + want      # the two members' partial sums, nothing else
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("sizes, said", [
    (dict(ssm_heads=4, ssm_groups=1, ssm_heads_total=16, ssm_groups_total=2),
     "every member holds every group"),
    (dict(ssm_heads=3, ssm_groups=1, ssm_heads_total=16, ssm_groups_total=1),
     "equal part")], ids=["a_group_over_some_members", "unequal_parts"])
def test_a_group_over_some_of_the_members_is_refused(sizes, said):
    z = hybrid.HybridSizes(d_model=64, head_dim=16, **sizes)
    with pytest.raises(ValueError, match=said):
        hybrid.ssm_group_members(z)


def test_the_group_members_by_the_sizes():
    z = lambda **k: hybrid.HybridSizes(d_model=64, head_dim=16, **k)
    assert hybrid.ssm_group_members(z()) == 1            # no state space
    assert hybrid.ssm_group_members(z(ssm_heads=8)) == 1   # nothing shared
    assert hybrid.ssm_group_members(z(
        ssm_heads=16, ssm_groups=1, ssm_heads_total=128,
        ssm_groups_total=8)) == 1
    assert hybrid.ssm_group_members(z(
        ssm_heads=32, ssm_groups=1, ssm_heads_total=64,
        ssm_groups_total=1)) == 2
    assert hybrid.ssm_group_members(z(
        ssm_heads=8, ssm_groups=2, ssm_heads_total=32,
        ssm_groups_total=2)) == 4


# ---------------------------------------------------------------------------
# (d) the softmax scale and the multipliers


def dense_attention(params, x, *, h, kv, dh, scale):
    q, k, v = (jnp.dot(x, params[f"{n}_proj"]["kernel"]).reshape(
        *x.shape[:2], heads, dh) for n, heads in (("q", h), ("k", kv),
                                                  ("v", kv)))
    k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    seen = jnp.tril(jnp.ones((x.shape[1],) * 2, bool))
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), v)
    return jnp.dot(attn.reshape(*x.shape[:2], h * dh),
                   params["o_proj"]["kernel"])


def test_the_softmax_scale_is_the_one_the_sizes_name():
    """64-wide heads at ``attention_multiplier`` 1 / 64: a dense masked
    softmax at that scale, and not at ``1 / sqrt(64)``; without a scale the
    arm attends at ``head_dim ** -0.5`` as it did."""
    z = hybrid.HybridSizes(d_model=128, head_dim=64, n_heads=4, n_kv_heads=1,
                           attention=names.GROUPED_ATTN,
                           softmax_scale=0.015625)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 128))
    attn = hybrid.GroupedAttention(z, jnp.float32)
    params = jax.jit(attn.init)(jax.random.PRNGKey(1), x)["params"]
    # weights large enough that the scale shows in the softmax
    params = jax.tree.map(lambda w: 4.0 * w, params)
    got = jax.jit(attn.apply)({"params": params}, x)
    dense = lambda scale: dense_attention(params, x, h=4, kv=1, dh=64,
                                          scale=scale)
    assert worst(got, dense(1 / 64)) < 1e-5
    assert worst(got, dense(1 / 8)) > 1e-2
    plain = hybrid.GroupedAttention(
        dataclasses.replace(z, softmax_scale=None), jnp.float32)
    assert worst(jax.jit(plain.apply)({"params": params}, x),
                 dense(1 / 8)) < 1e-5


@pytest.mark.parametrize("arm", [names.GATED_ATTN, names.NORMED_ATTN,
                                 names.HEAD_GATED_ATTN])
def test_a_softmax_scale_is_the_grouped_arms_alone(arm):
    z = hybrid.HybridSizes(
        d_model=64, head_dim=16, n_heads=2, n_kv_heads=1, attention=arm,
        softmax_scale=0.125, feed_forward=names.DENSE_FFN, ffn_width=32,
        softmax_kinds=((names.FULL, hybrid.SoftmaxSizes(2, 1)),))
    with pytest.raises(ValueError, match="softmax_scale"):
        hybrid.HybridLM(vocab=16, layer_types=(names.FULL,), sizes=z).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))


def primitives(jaxpr) -> dict:
    """How often each primitive occurs, sub-jaxprs included."""
    found: dict = {}

    def walk(j):
        for eqn in j.eqns:
            found[eqn.primitive.name] = found.get(eqn.primitive.name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return found


def plain_sizes(**keys):
    return hybrid.HybridSizes(
        d_model=64, head_dim=16, n_heads=2, n_kv_heads=1,
        attention=names.GROUPED_ATTN, ssm_heads=8, ssm_head_dim=8,
        ssm_state=16, ssm_chunk=32, norm=names.PLAIN,
        feed_forward=names.DENSE_FFN, ffn_width=96, eps=1e-5, **keys)


def decoder_jaxpr(sizes):
    kinds = (names.STATE_SPACE, names.FULL, names.STATE_SPACE)
    module = hybrid.HybridLM(vocab=256, layer_types=kinds, sizes=sizes,
                             dtype=jnp.bfloat16)
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
    return jax.make_jaxpr(module.apply)(params, tokens).jaxpr


@pytest.mark.parametrize("named, more", [
    # a multiplier a sublayer: three layers of two
    (dict(residual_scale=0.22), {"mul": 6}),
    (dict(embedding_scale=12.0), {"mul": 1}),
    (dict(logits_divisor=8.0), {"div": 1}),
    # the one attention layer's queries
    (dict(softmax_scale=0.125), {"mul": 1})],
    ids=["residual", "embedding", "logits", "softmax"])
def test_a_multiplier_is_one_instruction_a_site_and_none_at_1(named, more):
    """Each multiplier at its default leaves the decoder's jaxpr as it was
    (a decoder that names 1 / None is the same text as one that names
    nothing), and naming it adds exactly one instruction a site."""
    base = decoder_jaxpr(plain_sizes())
    at_one = {k: (None if k == "softmax_scale" else 1.0) for k in named}
    assert str(decoder_jaxpr(plain_sizes(**at_one))) == str(base)
    before, after = primitives(base), primitives(decoder_jaxpr(
        plain_sizes(**named)))
    changed = {k: after.get(k, 0) - before.get(k, 0)
               for k in set(before) | set(after)
               if after.get(k, 0) != before.get(k, 0)}
    assert changed == more


def test_a_tied_head_has_no_head_parameter_and_the_same_products():
    base, tied = (decoder_jaxpr(plain_sizes(tied_head=t))
                  for t in (False, True))
    count = lambda j: primitives(j).get("dot_general", 0)
    assert count(base) == count(tied)
    module = lambda t: hybrid.HybridLM(
        vocab=256, layer_types=(names.STATE_SPACE,),
        sizes=plain_sizes(tied_head=t))
    shapes = lambda t: jax.eval_shape(
        module(t).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 64), jnp.int32))["params"]
    assert "head" in shapes(False) and "head" not in shapes(True)
    assert set(shapes(False)) - set(shapes(True)) == {"head"}


# ---------------------------------------------------------------------------
# (e) what the decoder says of itself, and the real configuration


def test_the_layout_event_says_the_share_and_the_multipliers(tmp_path,
                                                             f32_pair):
    p = f32_pair
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        jax.jit(p["module"].apply)(p["params"],
                                   p["tokens"]).block_until_ready()
        events = [r for r in session.ring if r.get("kind") == "event"]
    finally:
        telemetry.finish(write_report=False)
    (e,) = [r for r in events if r["name"] == names.MIXER_LAYOUT]
    assert e["kinds"] == list(p["module"].layer_types)
    assert (e["ssm_heads"], e["ssm_groups"], e["ssm_group_members"],
            e["ssm_norm_over"]) == ([8, 16], [1, 1], 2, names.HELD)
    assert (e["softmax_scale"], e["residual_scale"], e["embedding_scale"],
            e["logits_divisor"], e["tied_head"]) == (
                0.125, 0.22, 12.0, 8.0, True)
    assert e["remat_keeps"] == [names.MIXER_OUT]
    assert e["dense_products_kept"] == [False] * 4
    assert e["remat_kept_bytes_per_layer"] == 2 * 128 * 64 * 4
    assert (e["attention"], e["attn_heads"], e["attn_kv_heads"]) == (
        names.GROUPED_ATTN, [2, 4], 1)


def test_the_layout_event_names_the_axis_the_norm_is_taken_over(tmp_path):
    z = plain_sizes(ssm_heads_total=16, ssm_groups_total=1,
                    heads_axis="heads", n_heads_total=4)
    module = hybrid.HybridLM(vocab=256, layer_types=(names.STATE_SPACE,),
                             sizes=z)
    tokens = jnp.zeros((1, 64), jnp.int32)
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        jax.eval_shape(jax.vmap(lambda k: module.init(k, tokens),
                                axis_name="heads"),
                       jax.random.split(jax.random.PRNGKey(0), 2))
        events = [r for r in session.ring if r.get("kind") == "event"]
    finally:
        telemetry.finish(write_report=False)
    e = [r for r in events if r["name"] == names.MIXER_LAYOUT][-1]
    assert (e["ssm_group_members"], e["ssm_norm_over"]) == (2, "heads")


def test_the_layers_names_carry_what_the_readers_look_for(f32_pair):
    """``ssm_conv`` and ``ssm_norm`` nest in ``ssm`` beside ``ssd_scan``;
    the feed-forward runs under ``mlp`` in every layer."""
    import re

    p = f32_pair
    text = jax.jit(p["module"].apply).lower(
        p["params"], p["tokens"]).as_text(debug_info=True)
    found = set(re.findall(r'loc\("([^"]+)"', text))
    mamba = [i for i, k in enumerate(p["module"].layer_types)
             if k == names.STATE_SPACE]
    for inside in (names.SSM_CONV, names.SSM_NORM, names.SSD_SCAN):
        for i in mamba:
            assert [n for n in found if
                    f"/{names.PATTERN_LAYER}_{i}/ssm/{names.SSM}/{inside}/"
                    in n], (i, inside)
    for i in range(4):
        assert [n for n in found if
                f"/{names.PATTERN_LAYER}_{i}/mlp/{names.MLP}/" in n], i
    assert {names.SSM_CONV, names.SSM_NORM} <= set(names.SCOPES)


def test_the_real_configurations_parameters_to_the_parameter():
    shapes = arch.weight_shapes(REAL)
    count = lambda names_: sum(int(np.prod(shapes[n])) for n in names_)
    mamba = count(n for n in shapes if n.startswith("mamba."))
    attention = count(n for n in shapes if n.startswith("layer_5."))
    assert mamba == 9 * 63_522_144
    assert attention == 55_578_624
    assert count(("embed", "final_norm")) == 102_762_496
    assert count(shapes) == REAL["as_run"]["parameters"] == 730_040_416
    assert "head" not in shapes and shapes["embed"] == (50_176, 2048)
    assert shapes["mamba.in_proj"] == (9, 2048, 4384)
    assert shapes["mamba.conv"] == (9, 2304, 4)
    assert shapes["mamba.out_proj"] == (9, 2048, 2048)
    assert shapes["layer_5.q_proj"] == (2048, 1024)
    assert shapes["layer_5.k_proj"] == (2048, 256)
    assert shapes["layer_5.o_proj"] == (1024, 2048)
    assert shapes["mamba.mlp_gate"] == (9, 2048, 8192)
    m = arch.dims(REAL)
    assert m["kinds"] == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (m["mh"], m["mh_all"], m["mg"], m["mg_all"], m["heads"],
            m["heads_all"], m["kv"], m["dh"], m["chunk"]) == (
                32, 64, 1, 1, 16, 32, 4, 64, REAL["mamba_chunk_size"])
    z = arch.build_module(REAL, {"remat": "nothing"}).sizes
    assert (z.softmax_scale, z.residual_scale, z.embedding_scale,
            z.logits_divisor, z.tied_head, z.ffn_products_kept) == (
                0.015625, 0.22, 12.0, 8.0, True, False)
    # 0.015625 * sqrt(64) = 0.125: a power of two, exact in bf16
    assert z.softmax_scale * z.head_dim ** 0.5 == 0.125
    assert hybrid.ssm_group_members(z) == 2
    # what a rematerialised layer keeps: its input's successor alone, 33.6
    # MB a layer; the three products would be 302 MB more
    assert hybrid.remat_keeps(z) == (names.MIXER_OUT,)
    assert hybrid.kept_bytes((names.MIXER_OUT,), z, 8192,
                             jnp.bfloat16) == 33_554_432
    assert hybrid.kept_bytes(names.DENSE_FFN_KEEPS, z, 8192,
                             jnp.bfloat16) == 301_989_888
