"""The pattern decoder's ``nemotron_h`` arms (``tpudist/models/hybrid.py``:
layers of one sublayer, the Mamba-2 mixer, plain grouped-query attention,
the expert layer with sigmoid + bias scoring, a scale, relu2 experts in a
latent space and an unscored shared expert, a share of heads, groups and
experts), held to the family's own code where it is on this machine
(``BambaMixer.torch_forward``, ``DeepseekV3TopkRouter``; ``torch`` on the
CPU) and to the plain float32 reference of the benchmark
(``cellbench/archs/nemotron_h.py``) at tiny widths on the CPU: d 64, Mamba
heads of 8 with a state of 16 in chunks of 32, attention heads of 16, 32
experts of 48 in a latent space of 32, top 6, vocabulary 256, 11 layers
``MEMEMEMEM*E``; one of eight head shares and 4 of 32 experts held.

Tolerances, and why.  Float32 against float32 differs only by the order of
sums (the chunked scan against the recurrence, grouped products against
masked ones): 3e-5 of the logits' largest entry (9e-7 read), 1e-4 of a
gradient's norm (under 1e-5 read).  Against ``torch``: 2e-5 (its float32
sums in another order).
"""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import reference
from cellbench.archs import nemotron_h as arch
from tests.decoder_reference import (DATA, dense_products, highest, logits,
                                     reference_logits, reference_pair, rel,
                                     run_steps, seeded, tiny, worst)
from tpudist import telemetry
from tpudist.models import hybrid
from tpudist.models.transformer import lm_loss
from tpudist.parallel import moe
from tpudist.telemetry import names

TINY = json.loads((DATA / "tiny-nemotron-h.json").read_text())
REAL = json.loads((DATA.parents[1] / "configs"
                   / "nemotron-3-super-120b-a12b.json").read_text())
MEMBERS = 8


@pytest.fixture(autouse=True)
def highest_precision():
    with highest():
        yield


# ---------------------------------------------------------------------------
# (a) against the family's own code


def test_the_mamba2_mixer_is_bambas_torch_forward():
    """Shared weights, one group (Bamba's gated norm is the one-group
    case): the mixer's output is ``BambaMixer.torch_forward``'s."""
    torch = pytest.importorskip("torch")
    from transformers.models.bamba.configuration_bamba import BambaConfig
    from transformers.models.bamba.modeling_bamba import BambaMixer

    d, h, p, n, chunk = 64, 4, 8, 16, 32
    their = BambaMixer(BambaConfig(
        hidden_size=d, mamba_n_heads=h, mamba_d_head=p, mamba_n_groups=1,
        mamba_d_state=n, mamba_d_conv=4, mamba_expand=h * p / d,
        mamba_chunk_size=chunk, mamba_conv_bias=True, mamba_proj_bias=False,
        rms_norm_eps=1e-5, num_hidden_layers=1), layer_idx=0)
    sizes = hybrid.HybridSizes(
        d_model=d, n_heads=1, n_kv_heads=1, head_dim=16, rotary_dim=0,
        ssm_heads=h, ssm_groups=1, ssm_head_dim=p, ssm_state=n,
        ssm_chunk=chunk, eps=1e-5)
    ours = hybrid.Mamba2Mixer(sizes, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4 * chunk, d))
    params = jax.jit(ours.init)(jax.random.PRNGKey(1), x)["params"]
    ks = iter(jax.random.split(jax.random.PRNGKey(2), 8))
    draw = lambda a, scale: scale * jax.random.normal(next(ks), a.shape)
    params = {
        "in_proj": {"kernel": draw(params["in_proj"]["kernel"], 0.1)},
        "out_proj": {"kernel": draw(params["out_proj"]["kernel"], 0.1)},
        "conv": draw(params["conv"], 0.5),
        "conv_bias": draw(params["conv_bias"], 0.1),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)),
        "D": 1.0 + draw(params["D"], 0.1),
        "dt_bias": draw(params["dt_bias"], 1.0) - 3.0,
        "norm": 1.0 + draw(params["norm"], 0.1)}
    t = lambda a: torch.tensor(np.asarray(a))
    with torch.no_grad():
        their.in_proj.weight.copy_(t(params["in_proj"]["kernel"].T))
        their.out_proj.weight.copy_(t(params["out_proj"]["kernel"].T))
        their.conv1d.weight.copy_(t(params["conv"][:, None, :]))
        their.conv1d.bias.copy_(t(params["conv_bias"]))
        for name in ("A_log", "D", "dt_bias"):
            getattr(their, name).copy_(t(params[name]))
        their.norm.weight.copy_(t(params["norm"]))
        want = their.torch_forward(t(x)).numpy()
    assert worst(jax.jit(ours.apply)({"params": params}, x), want) < 2e-5


def test_routes_sigmoid_arm_is_deepseek_v3s_router():
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import (
        DeepseekV3TopkRouter)

    d, experts, k, scale = 64, 32, 6, 2.5
    their = DeepseekV3TopkRouter(types.SimpleNamespace(
        num_experts_per_tok=k, n_routed_experts=experts,
        routed_scaling_factor=scale, n_group=1, topk_group=1,
        norm_topk_prob=True, hidden_size=d))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (96, d))
    w = 0.3 * jax.random.normal(ks[1], (d, experts))
    bias = 0.05 * jax.random.normal(ks[2], (experts,))
    with torch.no_grad():
        their.weight.copy_(torch.tensor(np.asarray(w.T)))
        their.e_score_correction_bias.copy_(torch.tensor(np.asarray(bias)))
        picks, weights = their(torch.tensor(np.asarray(x)))
    route = jax.jit(lambda logits, bias: moe.route(
        logits, n_experts=experts, k=k, scoring=names.SIGMOID_BIAS,
        choice_bias=bias, scale=scale))
    routing = route(x @ w, bias)
    # the picks in any order: each token's weights by expert
    dense = lambda p, v: np.asarray(jnp.zeros((96, experts)).at[
        jnp.arange(96)[:, None], jnp.asarray(p)].set(jnp.asarray(v)))
    want = dense(picks.numpy(), weights.numpy())
    got = dense(routing.expert_idx, routing.weights)
    assert (got > 0).sum() == 96 * k and ((got > 0) == (want > 0)).all()
    assert worst(got, want) < 2e-6
    assert np.allclose(got.sum(axis=1), scale, rtol=1e-5)
    # the bias steers the choice and is no part of a weight
    plain = route(x @ w, None)
    assert (np.asarray(plain.expert_idx) != np.asarray(
        routing.expert_idx)).any()
    scores = np.asarray(jax.nn.sigmoid(x @ w))
    np.testing.assert_allclose(
        np.asarray(routing.weights),
        scale * np.take_along_axis(scores, np.asarray(routing.expert_idx), 1)
        / np.take_along_axis(scores, np.asarray(routing.expert_idx),
                             1).sum(1, keepdims=True), rtol=1e-5)


def softmax_route_as_before(logits, *, n_experts, k, held=None,
                            first_expert=0, **_):
    """``moe.route`` as it stood before it took a ``scoring``."""
    held = n_experts if held is None else held
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, expert_idx = jax.lax.top_k(probs, k)
    if k > 1:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    local = expert_idx - first_expert
    local = jnp.where((local >= 0) & (local < held), local, held)
    return moe.Routing(expert_idx, weights, probs, local)


@pytest.mark.parametrize("k", [1, 2, 10])
def test_routes_softmax_arm_is_bit_for_bit_what_it_was(k):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(k), (64, 32))
    got, want = (jax.jit(lambda x, fn=fn: fn(
        x, n_experts=32, k=k, held=4, first_expert=8))(logits)
        for fn in (moe.route, softmax_route_as_before))
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))


def test_the_share_cells_layer_is_bit_for_bit_what_it_was(monkeypatch):
    """The softmax-scored, gated, scored-shared-expert layer of the share
    cell through today's ``ExpertShare`` and ``route``, and through
    ``route`` as it stood: the same bits, values and gradients."""
    from cellbench.archs import qwen3_next

    config = json.loads((DATA / "tiny-hybrid.json").read_text())
    sizes = qwen3_next.build_module(config, {"remat": None}).sizes
    assert (sizes.scoring, sizes.expert_fn, sizes.shared_scored,
            sizes.latent_width, sizes.one_sublayer, sizes.routed_scale) == (
                names.SOFTMAX, names.GATED_SILU, True, None, False, 1.0)
    layer = hybrid.ExpertShare(sizes, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, sizes.d_model))
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
    run = jax.jit(jax.value_and_grad(
        lambda p: jnp.sum(jnp.sin(layer.apply(p, x).astype(jnp.float32)))))
    now = run(params)
    monkeypatch.setattr(moe, "route", softmax_route_as_before)
    jax.clear_caches()
    for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(run(params))):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))


@pytest.mark.parametrize("kw, said", [
    (dict(scoring="entmax"), "sigmoid_bias"),
    (dict(choice_bias=jnp.zeros(8)), "no choice bias"),
    (dict(scale=5.0), "no scale")])
def test_route_names_its_scorings_from_their_table(kw, said):
    with pytest.raises(ValueError, match=said):
        moe.route(jnp.zeros((4, 8)), n_experts=8, k=2, **kw)
    assert set(moe.SCORINGS) == {names.SOFTMAX, names.SIGMOID,
                                 names.SIGMOID_BIAS}
    assert set(moe.EXPERT_FNS) == set(moe.EXPERT_LEAVES) == {
        names.GATED_SILU, names.RELU2}


# ---------------------------------------------------------------------------
# (b) the decoder against the reference


@pytest.fixture(scope="module")
def f32_pair():
    return reference_pair(arch, tiny(TINY))


def test_the_module_takes_the_arms_the_architecture_names(f32_pair):
    module = f32_pair["module"]
    z = module.sizes
    assert module.layer_types == tuple(
        {"M": names.STATE_SPACE, "E": names.EXPERT_LAYER,
         "*": names.FULL}[k] for k in "MEMEMEMEM*E")
    assert (z.one_sublayer, z.attention, z.norm, z.norm_after,
            z.feed_forward, z.heads_axis) == (
                True, names.GROUPED_ATTN, names.PLAIN, False,
                names.EXPERT_SHARE, None)
    assert (z.scoring, z.routed_scale, z.expert_fn, z.latent_width,
            z.shared_scored, z.router_trained) == (
                names.SIGMOID_BIAS, 2.5, names.RELU2, 32, False, True)
    assert (z.ssm_heads, z.ssm_heads_total, z.ssm_groups,
            z.ssm_groups_total, z.ssm_head_dim, z.ssm_state,
            z.ssm_chunk) == (2, 16, 1, 8, 8, 16, 32)
    assert (z.n_heads, z.n_heads_total, z.n_kv_heads, z.head_dim) == (
        2, 16, 1, 16)
    assert (z.n_experts, z.held, z.first_expert, z.top_k) == (32, 4, 0, 6)
    assert hybrid.remat_keeps(z) == (
        names.EXPERT_OUT, names.ROUTER_LOGITS, names.ROUTER_PICKS,
        names.LATENT_IN, names.SHARED_UP)


def test_logits_match_the_reference(f32_pair):
    assert worst(logits(f32_pair), reference_logits(f32_pair)) < 3e-5


def test_loss_matches_the_reference(f32_pair):
    assert abs(float(f32_pair["loss"]) - float(f32_pair["ref_loss"])) < 2e-6


@pytest.mark.parametrize("name", arch.leaf_names(TINY))
def test_every_gradient_matches_the_reference(f32_pair, name):
    p = f32_pair
    leaves = arch.leaf_names(p["config"])
    got = arch.named_leaves(p["config"], p["grads"])[leaves.index(name)]
    assert float(jnp.linalg.norm(p["ref_grads"][name])) > 0
    assert rel(got, p["ref_grads"][name]) < 1e-4


def test_the_choice_bias_is_a_buffer_with_no_gradient(f32_pair):
    p = f32_pair
    buffers = arch.buffer_shapes(p["config"])
    assert set(buffers) == {f"layer_{i}.choice_bias" for i in
                            (1, 3, 5, 7, 10)}
    assert not set(buffers) & set(arch.weight_shapes(p["config"]))
    for name in buffers:
        layer = name.partition(".")[0]
        assert float(jnp.abs(p["weights"][name]).max()) > 0
        assert float(jnp.abs(p["ref_grads"][name]).max()) == 0.0
        assert float(jnp.abs(p["grads"]["params"][layer]["experts"][
            "choice_bias"]).max()) == 0.0


@pytest.mark.parametrize("wrong", [
    dict(norm=names.ZERO_CENTRED), dict(attention=names.NORMED_ATTN)],
    ids=["zero_centred_norm", "normed_attention"])
def test_another_arm_is_not_this_architecture(f32_pair, wrong):
    """Each of the architecture's choices shows in the logits by far more
    than the tolerance: none of them is decoration at these weights."""
    p = f32_pair
    other = p["module"].clone(sizes=dataclasses.replace(
        p["module"].sizes, **wrong))
    # an arm with parameters of its own: init them, keep the rest
    flat = dict(jax.tree_util.tree_flatten_with_path(p["params"])[0])
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: flat.get(path, a),
        jax.jit(other.init)(jax.random.PRNGKey(0), p["tokens"]))
    assert worst(logits(p, other, params), reference_logits(p)) > 1e-2


@pytest.mark.parametrize("wrong", [
    dict(routed_scale=1.0), dict(scoring=names.SOFTMAX, routed_scale=1.0),
    dict(expert_fn=names.GATED_SILU), "choice_bias_dropped"],
    ids=["scale_dropped", "softmax_scores", "gated_experts",
         "choice_bias_dropped"])
def test_another_arm_is_not_this_expert_layer(f32_pair, wrong):
    """The same for the expert layer's choices, read where they act: the
    layer's routed part for its held experts (at this size the shared
    expert, which every arm computes alike, is most of the layer's
    output)."""
    p = f32_pair
    m = arch.dims(p["config"])
    w = arch.of_layer(p["weights"], 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 128, 64), jnp.float32)
    shared_alone, whole_layer = jax.jit(lambda x, w: tuple(
        arch.experts(x, w, m=m, mode="f32", **held)
        for held in (dict(held=0), {})))(x[0], w)
    routed = lambda y: y - shared_alone
    want = routed(whole_layer)
    sizes = p["module"].sizes
    params = dict(p["params"]["params"]["layer_1"]["experts"])
    share = lambda sizes, params: jax.jit(hybrid.ExpertShare(
        sizes, jnp.float32).apply)({"params": params}, x)
    assert worst(routed(share(sizes, params)[0]), want) < 1e-5
    if wrong == "choice_bias_dropped":
        params["choice_bias"] = jnp.zeros_like(params["choice_bias"])
    else:
        sizes = dataclasses.replace(sizes, **wrong)
        if "expert_fn" in wrong:
            params["gate"], params["shared_gate"] = (params["up"],
                                                     params["shared_up"])
    assert worst(routed(share(sizes, params)[0]), want) > 5e-2


def test_a_scored_shared_expert_reads_the_rows_the_experts_read(f32_pair):
    sizes = dataclasses.replace(f32_pair["module"].sizes, shared_scored=True)
    with pytest.raises(ValueError, match="latent"):
        hybrid.ExpertShare(sizes, jnp.float32).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))


def test_a_router_held_fixed_still_hands_its_gradient_to_the_tokens():
    """``router_trained: false`` (the cell's stated fallback): the router's
    own gradient is zero in program and reference alike, every other
    tensor's gradient is still the reference's."""
    config = tiny(TINY)
    config["as_run"]["router_trained"] = False
    p = reference_pair(arch, config, options={"remat": None})
    assert p["module"].sizes.router_trained is False
    ref = p["ref_grads"]
    for name, got in zip(arch.leaf_names(config),
                         arch.named_leaves(config, p["grads"])):
        if name.endswith(".router"):
            assert float(jnp.abs(got).max()) == 0.0 == float(
                jnp.abs(ref[name]).max())
        else:
            assert rel(got, ref[name]) < 1e-4, name


def test_three_adam_steps_follow_the_reference():
    """``make_lm_train_step`` over the float32 program against the
    reference's own Adam: losses to 1e-5, every tensor's change after three
    steps to 2e-3 of its norm; the step built with ``aux=True`` says the
    assignments of each expert layer's held experts."""
    config = tiny(TINY, num_hidden_layers=4, hybrid_override_pattern="ME*E")
    weights = seeded(arch, config, 7)
    module = arch.build_module(config, {"remat": "nothing"})
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 256, (2, 128), dtype=np.int32)
               for _ in range(3)]
    state, losses, aux = run_steps(arch, config, module, weights, batches,
                                   2e-3, aux=True)
    counts = np.asarray(aux["moe_expert_tokens"])
    assert counts.shape == (2, 4)        # [expert layers, held]
    assert 0 < counts.sum() < 2 * 2 * 128 * 6
    ref = reference.train_readings(arch, config, 7, batches, lr=2e-3,
                                   rows_per_block=2)
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    moved = jax.tree.map(jnp.subtract, state.params,
                         arch.program_tree(config, weights))
    norms = np.array([float(jnp.linalg.norm(x))
                      for x in arch.named_leaves(config, moved)])
    # one period's worth of kinds in four layers (``ME*E``), which keeps
    # this test under half a minute and reads 6e-7.  Over the whole
    # eleven-layer pattern a score moves past its neighbour within the
    # three steps at this rate, and whether the chunked or the plain order
    # of sums sees it first moves a held expert's rows: 8 of 93 tensors
    # read over 2e-3 there, the largest 1.5e-2
    np.testing.assert_allclose(norms, ref["update_norms"], rtol=2e-3)
    bias = moved["params"]["layer_1"]["experts"]["choice_bias"]
    assert float(jnp.abs(bias).max()) == 0.0   # Adam leaves a buffer alone


@pytest.mark.parametrize("rigged, windows", [(False, 1), (True, 8)],
                         ids=["seeded_weights", "every_pick_held"])
def test_a_64th_of_the_experts_held_goes_by_windows(rigged, windows):
    """2 of 128 experts held at top 2, 8 rows x 256 tokens: an expert
    layer's 4,096 assignments go through windows of 512 rows (eight even
    shares), at most 8 of them.  At the seeded weights one window holds
    what arrives; with a choice bias that sends every pick to the two held
    experts all eight run, and nothing is dropped: loss and every gradient
    are the reference's either way, under the layers' rematerialisation
    (which keeps the windowed share's result).  The step built with
    ``aux=True`` says the windows taken and the strips of 64 rows
    scattered, a row a layer: the strips the arrivals fill, all 64 where
    every pick is held."""
    config = tiny(TINY, num_hidden_layers=2, hybrid_override_pattern="ME",
                  n_routed_experts=2, num_experts_per_tok=2)
    config["as_run"]["router_experts"] = 128
    assert moe.share_windows(8 * 256, 2, 2, 128) == (512, 8)
    weights = seeded(arch, config, 11)
    if rigged:
        weights["layer_1.choice_bias"] = weights[
            "layer_1.choice_bias"].at[:2].set(100.0)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (8, 256), 0,
                                config["vocab_size"])
    module = arch.build_module(config, {"remat": "nothing"})
    params = arch.program_tree(config, weights)
    # (``reference_pair``'s two programs, on weights rigged behind the seed)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(module.apply(p, tokens), tokens)))(params)
    ref_loss, ref_grads = jax.jit(
        lambda w: arch.loss_and_grads(config, w, tokens))(weights)
    assert abs(float(loss) - float(ref_loss)) < 2e-6
    for name, got in zip(arch.leaf_names(config),
                         arch.named_leaves(config, grads)):
        assert float(jnp.linalg.norm(ref_grads[name])) > 0, name
        assert rel(got, ref_grads[name]) < 1e-4, name
    _, _, aux = run_steps(arch, config, module, weights, [tokens], 0.0,
                          aux=True)
    assert np.asarray(aux["moe_windows"]).tolist() == [[windows]]
    arrived = int(np.asarray(aux["moe_expert_tokens"]).sum())
    assert arrived == 4096 if rigged else 0 < arrived <= 512
    assert moe.share_strip(512) == 64
    assert np.asarray(aux["moe_strips"]).tolist() == [[-(-arrived // 64)]]


# ---------------------------------------------------------------------------
# (c) the share ties to the model


def whole() -> dict:
    """The uncut tiny model: every published head, group and expert."""
    return tiny(TINY, **{k: v for k, v in TINY["published"].items()
                   if k in ("mamba_num_heads", "n_groups",
                            "num_attention_heads", "num_key_value_heads",
                            "n_routed_experts")})


def share_of(m: dict, w: dict, kind: str, member: int) -> dict:
    """Member ``member``'s eighth of one layer's weights: for a mixer its
    heads' (and group's) columns of the input projections, convolution
    taps and per-head numbers, their rows of the output projection; for an
    expert layer its run of the experts, everything else whole."""
    def part(x, start, size, axis=-1):
        return jax.lax.slice_in_dim(x, start + member * size,
                                    start + (member + 1) * size, axis=axis)

    if kind == arch.MAMBA:
        heads, n = m["mh"] // MEMBERS, m["mn"]
        inner, bc, wide = m["mh"] * m["mp"], m["mg"] * n, heads * m["mp"]
        cols = lambda x, at: jnp.concatenate([
            part(x, at, wide), part(x, at + inner, n),
            part(x, at + inner + bc, n)], axis=-1)
        proj = w["in_proj"]
        return {"in_proj": jnp.concatenate(
                    [part(proj, 0, wide), cols(proj, inner),
                     part(proj, 2 * inner + 2 * bc, heads)], axis=-1),
                "conv": cols(w["conv"].T, 0).T,
                "conv_bias": cols(w["conv_bias"], 0),
                **{k: part(w[k], 0, heads) for k in ("A_log", "D",
                                                     "dt_bias")},
                "gated_norm": part(w["gated_norm"], 0, wide),
                "out_proj": part(w["out_proj"], 0, wide, axis=0)}
    if kind == arch.ATTENTION:
        wide, dh = m["heads"] // MEMBERS * m["dh"], m["dh"]
        kv = member // (MEMBERS // m["kv"])   # the head its queries read
        key = lambda x: x[:, kv * dh:(kv + 1) * dh]
        return {"q_proj": part(w["q_proj"], 0, wide),
                "k_proj": key(w["k_proj"]), "v_proj": key(w["v_proj"]),
                "o_proj": part(w["o_proj"], 0, wide, axis=0)}
    held = m["held"] // MEMBERS
    return {**w,
            "experts_up": part(w["experts_up"], 0, held * m["latent"], 0),
            "experts_down": part(w["experts_down"], 0, held * m["width"], 0)}


def sublayer_params(config, layer, share) -> dict:
    tree = arch.program_tree(config, {f"layer_{layer}.{k}": v
                                      for k, v in share.items()})
    (params,) = (v for k, v in tree["params"][f"layer_{layer}"].items()
                 if not k.endswith("_norm"))
    return params


@pytest.mark.parametrize("kind, layer", [(arch.MAMBA, 0),
                                         (arch.ATTENTION, 9)],
                         ids=["mamba_layer", "attention_layer"])
def test_the_eight_head_shares_add_up_to_the_uncut_layer(kind, layer):
    """Section 4's share test.  The uncut reference holds all 16 Mamba
    heads in 8 groups (16 query heads on 2 key/value heads).  Each member
    holds an eighth: alone (no axis: the cell) it gives what the reference
    gives when handed that eighth, and the eight partial outputs add up to
    the uncut layer's (no statistic crosses the cut: the gated norm's is a
    group's).  Told ``heads_axis``, under a ``vmap`` with that axis name,
    the output projections' partial sums are added up and each member's
    output IS the uncut one."""
    full, held = whole(), tiny(TINY)
    m_full, m_held = arch.dims(full), arch.dims(held)
    w = arch.of_layer(seeded(arch, full, 11), layer)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64), jnp.float32)
    reference_of = lambda m: jax.jit(lambda x, w: jnp.stack([arch.sublayer(
        row, w, kind=kind, m=m, mode="f32") for row in x]))
    want = reference_of(m_full)(x, w)
    shares = [share_of(m_full, w, kind, i) for i in range(MEMBERS)]
    sizes = arch.build_module(held, {"remat": None}).sizes
    cls = hybrid.Mamba2Mixer if kind == arch.MAMBA else (
        hybrid.GroupedAttention)
    alone, given_a_share = jax.jit(cls(sizes, jnp.float32).apply), (
        reference_of(m_held))
    parts = []
    for share in shares:
        got = alone({"params": sublayer_params(held, layer, share)}, x)
        given = given_a_share(x, share)
        assert worst(got, given) < 1e-5
        parts.append(got)
    assert worst(sum(parts), want) < 1e-5
    assert worst(parts[0], want) > 1e-1
    shared = cls(dataclasses.replace(sizes, heads_axis="heads"), jnp.float32)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *(
        sublayer_params(held, layer, share) for share in shares))
    every = jax.jit(jax.vmap(lambda p: shared.apply({"params": p}, x),
                             axis_name="heads"))(stacked)
    for member in range(MEMBERS):
        assert worst(every[member], want) < 1e-5


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """Each member holds 4 of the 32 experts and the whole of router,
    latent projections and shared expert.  The eight results, with the
    shared expert (what every member computes alike) counted once, add up
    to what the uncut reference gives for the whole layer."""
    full, held = whole(), tiny(TINY)
    m_full = arch.dims(full)
    layer = 1
    w = arch.of_layer(seeded(arch, full, 11), layer)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64), jnp.float32)
    # (which experts are held is a Python number to reference and program
    # alike: a program a member)
    reference_of = lambda **held: jax.jit(lambda x, w: jnp.stack([
        arch.experts(row, w, m=m_full, mode="f32", **held) for row in x]))(
            x, w)
    want, shared_alone = reference_of(), reference_of(held=0)
    sizes = arch.build_module(held, {"remat": None}).sizes
    per = sizes.held
    total, loads = 0.0, []
    for member in range(MEMBERS):
        module = hybrid.ExpertShare(dataclasses.replace(
            sizes, first_expert=member * per), jnp.float32)
        params = sublayer_params(held, layer,
                                 share_of(m_full, w, arch.EXPERTS, member))
        got, state = jax.jit(lambda p: module.apply(
            p, x, mutable=["intermediates"]))({"params": params})
        given = reference_of(first=member * per, held=per)
        assert worst(got, given) < 1e-5
        total = total + got
        loads.append(np.asarray(jax.tree.leaves(state)[0]))
    assert worst(total - (MEMBERS - 1) * shared_alone, want) < 1e-5
    assert worst(total - MEMBERS * shared_alone, want) > 1e-2
    # every one of a token's 6 picks is some member's
    assert np.concatenate(loads).sum() == 2 * 128 * 6


def test_the_whole_layers_parameters_are_the_eight_shares():
    count = lambda c: {k: int(np.prod(s))
                       for k, s in arch.weight_shapes(c).items()}
    n_whole, n_held = count(whole()), count(tiny(TINY))
    # what a head owns apart from its group's B and C and the key/value
    # head four members read alike; held whole: router, latent projections,
    # shared expert, the norms over d_model, embedding and head
    eighth = ("conv_bias", "A_log", "D", "dt_bias", "gated_norm",
              "out_proj", "q_proj", "o_proj", "experts_up", "experts_down")
    for name, n in n_whole.items():
        leaf = name.rpartition(".")[2]
        if leaf in ("in_proj", "conv"):
            assert MEMBERS * n_held[name] == n, name
        elif leaf in ("k_proj", "v_proj"):
            assert 2 * n_held[name] == n, name
        elif leaf in eighth:
            assert MEMBERS * n_held[name] == n, name
        else:
            assert n_held[name] == n, name


def test_the_real_configurations_parameters_to_the_parameter():
    shapes = arch.weight_shapes(REAL)
    count = lambda kind: sum(
        int(np.prod(s)) for k, s in shapes.items()
        if k.startswith("layer_") and arch.dims(REAL)["kinds"][
            int(k.split(".")[0].split("_")[1])] == kind)
    assert count(arch.MAMBA) == 5 * 13_708_592
    assert count(arch.ATTENTION) == 5_246_976
    assert count(arch.EXPERTS) == 5 * 98_570_240
    assert sum(int(np.prod(s)) for s in shapes.values()) == REAL["as_run"][
        "parameters"] == 700_862_960
    m = arch.dims(REAL)
    assert shapes["layer_0.in_proj"] == (4096, 2320)
    assert shapes["layer_0.conv"] == (1280, 4)
    assert shapes["layer_0.out_proj"] == (1024, 4096)
    assert shapes["layer_9.o_proj"] == (512, 4096)
    assert shapes["layer_1.experts_up"] == (8 * 1024, 2688)
    assert (m["experts"], m["held"], m["top_k"], m["scale"]) == (
        512, 8, 22, 5.0)


# ---------------------------------------------------------------------------
# (d) what the decoder says of itself


def test_an_unknown_layer_kind_is_named_against_the_table():
    sizes = arch.build_module(tiny(TINY), {"remat": None}).sizes
    assert hybrid.layer_kinds(sizes) == (
        names.LINEAR, names.FULL, names.WINDOW, names.STATE_SPACE,
        names.CHANNEL_LINEAR, names.LATENT, names.EXPERT_LAYER)
    paired = dataclasses.replace(sizes, one_sublayer=False)
    assert hybrid.layer_kinds(paired) == tuple(hybrid.MIXERS) == (
        names.LINEAR, names.FULL, names.WINDOW, names.STATE_SPACE,
        names.CHANNEL_LINEAR, names.LATENT)
    tokens = jnp.zeros((1, 32), jnp.int32)
    for z, kinds in ((sizes, ("retention",)),
                     (paired, (names.EXPERT_LAYER,))):
        with pytest.raises(ValueError) as e:
            hybrid.HybridLM(vocab=16, layer_types=kinds, sizes=z).init(
                jax.random.PRNGKey(0), tokens)
        for kind in hybrid.layer_kinds(z):
            assert kind in str(e.value)


def test_the_layout_events_say_the_arms_and_the_share(tmp_path, f32_pair):
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
    # (the name is in the graph where a share takes windows; 4 of 32 held
    # keep one buffer)
    assert e["one_sublayer"] is True
    # the share's result and latent_down's (32 wide), the router's logits
    # (32 experts), the shared expert's ``up`` (96 wide), all float32 here,
    # and the 6 picks and their scores (int32 + float32)
    assert e["remat_keeps"] == [names.EXPERT_OUT, names.ROUTER_LOGITS,
                                names.ROUTER_PICKS, names.LATENT_IN,
                                names.SHARED_UP]
    assert e["remat_kept_bytes_per_layer"] == 2 * 128 * (
        (32 + 32 + 32 + 96) * 4 + 6 * 8)
    assert (e["ssm_heads"], e["ssm_groups"], e["ssm_head_dim"],
            e["ssm_state"], e["ssm_chunk"]) == ([2, 16], [1, 8], 8, 16, 32)
    assert (e["attention"], e["attn_heads"], e["attn_kv_heads"]) == (
        names.GROUPED_ATTN, [2, 16], 1)
    said = [r for r in events if r["name"] == names.MOE_LAYOUT]
    assert len(said) == 5
    for r in said:
        assert (r["scoring"], r["scale"], r["width"], r["experts"],
                r["held"], r["top_k"], r["buffer_rows"], r["window_rows"],
                r["windows_at_most"], r["strip_rows"], r["combine"]) == (
                    names.SIGMOID_BIAS, 2.5, 32, 32, 4, 6, 2 * 128 * 6,
                    2 * 128 * 6, 1, 2 * 128 * 6, names.PICK_MAJOR)


def test_the_real_cells_expert_layers_keep_138_megabytes_a_layer():
    """The cell's sizes, by hand: 8,192 tokens x (the share's result 1,024
    + ``latent_down``'s 1,024 + the shared expert's ``up`` 5,376) x 2 bytes
    + 8,192 x 512 logits x 4 bytes = 16.8 + 16.8 + 88.1 + 16.8 MB, and
    since PR 44 the 22 picks and their scores, 8,192 x 22 x 8 bytes = 1.4
    MB: 139.9 MB."""
    z = arch.build_module(REAL, {"remat": "nothing"}).sizes
    keep = hybrid.remat_keeps(z)
    assert keep == (names.EXPERT_OUT, names.ROUTER_LOGITS,
                    names.ROUTER_PICKS, names.LATENT_IN, names.SHARED_UP)
    each = [hybrid.kept_bytes((name,), z, 8192, jnp.bfloat16)
            for name in keep]
    assert each == [16_777_216, 16_777_216, 1_441_792, 16_777_216,
                    88_080_384]
    assert hybrid.kept_bytes(keep, z, 8192, jnp.bfloat16) == sum(each) == (
        139_853_824)
    assert hybrid.kept_bytes((), z, 8192, jnp.bfloat16) == 0


@pytest.mark.parametrize("kept", [
    (names.SHARED_UP,), (names.ROUTER_LOGITS,), (names.LATENT_IN,),
    (names.ROUTER_LOGITS, names.LATENT_IN, names.SHARED_UP)],
    ids=["shared_up", "router_logits", "latent_in", "all_three"])
def test_a_kept_product_is_one_fewer_a_layer_in_the_gradient(kept,
                                                             monkeypatch):
    """The tiny architecture, rematerialised (``nothing``), in bf16: for
    each name a layer keeps beside ``EXPERT_OUT`` the gradient's jaxpr
    holds one ``dot_general`` fewer in each of the five expert layers (the
    shared expert's ``up``, the router's float32 product, ``latent_down``
    run once, not twice); ``down`` and ``latent_up`` never ran twice."""
    config = tiny(TINY, "bfloat16")
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 128), 0,
                                config["vocab_size"])
    module = arch.build_module(config, {"remat": "nothing"})
    params = arch.program_tree(config, seeded(arch, config, 7))
    loss = lambda p: lm_loss(module.apply(p, tokens), tokens)

    def products(keep):
        monkeypatch.setattr(hybrid, "remat_keeps", lambda z, kind: keep)
        return dense_products(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)

    layers = module.layer_types.count(names.EXPERT_LAYER)
    assert layers == 5
    alone = products((names.EXPERT_OUT,))
    assert products((names.EXPERT_OUT,) + kept) == alone - layers * len(kept)
    # nothing else of an expert layer's dense products runs twice: naming
    # what is not in the graph keeps nothing
    assert products((names.EXPERT_OUT, names.SHARED_GATE)) == alone


def test_the_gradient_with_the_keeps_is_the_gradient_without_remat(f32_pair):
    """Bit for bit, in float32 (in bf16 the CPU's compiler widens the
    elementwise chains inside a fusion, so there remat alone moves the
    last bit): a kept tensor is the one the rematerialised forward would
    compute.  ``f32_pair``'s module is rematerialised with the keeps."""
    p = f32_pair
    assert p["module"].remat and len(hybrid.remat_keeps(
        p["module"].sizes)) == 5
    plain = arch.build_module(p["config"], {"remat": None})
    assert not plain.remat
    loss, grads = jax.jit(jax.value_and_grad(
        lambda q: lm_loss(plain.apply(q, p["tokens"]), p["tokens"])))(
            p["params"])
    assert float(loss) == float(p["loss"])
    for got, want in zip(jax.tree.leaves(p["grads"]),
                         jax.tree.leaves(grads)):
        assert bool(jnp.all(got == want))


def test_the_layers_names_carry_what_the_readers_look_for(f32_pair):
    import re

    p = f32_pair
    text = jax.jit(p["module"].apply).lower(
        p["params"], p["tokens"]).as_text(debug_info=True)
    found = set(re.findall(r'loc\("([^"]+)"', text))
    layer = rf"{names.PATTERN_LAYER}_\d+"
    assert any(re.search(rf"/{layer}/{names.SSM}/.*{names.SSD_SCAN}", f)
               for f in found)
    assert any(re.search(rf"/{layer}/{names.ATTN}/", f) for f in found)
    # (``experts`` and ``moe_combine`` lie inside the share layer's loop
    # over blocks, where the lowered names start afresh:
    # tests/test_tracing_names.py reads their nesting off the compiled text)
    for nested in (names.LATENT_PROJ, names.SHARED_EXPERT):
        assert any(re.search(rf"(^|/){names.MOE}/(.*/)?{nested}(/|$)", f)
                   for f in found), nested
