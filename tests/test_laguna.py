"""The pattern decoder's ``laguna`` arms (``tpudist/models/hybrid.py``: two
softmax kinds in one decoder, causal to everything and inside a sliding
window, each with its own heads and rotary positions, YaRN among them, a
gate a head from its own projection, the feed-forward arm said a layer: a
leading dense layer before expert layers scored by sigmoid without a bias;
a share of heads and experts), held to ``transformers``' YaRN where it is
on this machine (``torch`` on the CPU) and to the plain float32 reference of
the benchmark (``cellbench/archs/laguna.py``) at tiny widths on the CPU:
d 64, heads of 16, 4 (full) and 6 (sliding) query heads on 2 key/value
heads, a window of 32, YaRN on 8 of a full layer's 16 dims, a dense
feed-forward of 160, 64 experts of 48, top 8, vocabulary 256, 5 layers;
one of two head shares and 2 of 64 experts held.

Tolerances, and why.  Float32 against float32 differs only by the order of
sums (the dispatch's blocks against dense masked scores, grouped products
against masked ones): 3e-5 of the logits' largest entry (2e-6 read), 1e-4
of a gradient's norm.  Against ``torch``: 1e-6 of a frequency (float32
``pow`` in another library).
"""

import dataclasses
import json
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.archs import laguna as arch
from tests.decoder_reference import (DATA, highest, logits, reference_logits,
                                     reference_pair, rel, seeded, tiny, worst)
from tpudist import telemetry
from tpudist.models import hybrid
from tpudist.models.transformer import lm_loss
from tpudist.ops import attention, rope
from tpudist.ops.flash_attention import (_diagonal_strips, _far_edge_strips,
                                         _first_live_kv, _first_live_q,
                                         _last_live_kv, _tile_interior,
                                         _tile_live, band_grid, diag_sub)
from tpudist.parallel import moe
from tpudist.telemetry import names

TINY = json.loads((DATA / "tiny-laguna.json").read_text())
REAL = json.loads((DATA.parents[1] / "configs"
                   / "laguna-s-2.1.json").read_text())
HEAD_MEMBERS, EXPERT_MEMBERS = 2, 32


def whole() -> dict:
    """The tiny configuration uncut: every head and every expert here."""
    config = tiny(TINY)
    for key in ("num_attention_heads", "num_attention_heads_per_layer",
                "num_key_value_heads", "num_experts"):
        config[key] = config["published"][key]
    return config


@pytest.fixture(autouse=True)
def highest_precision():
    with highest():
        yield


def softmax_sizes(config: dict, kind: str) -> hybrid.SoftmaxSizes:
    return arch.build_module(config, {"remat": None}).sizes.softmax(kind)


# ---------------------------------------------------------------------------
# (a) rotary positions by kind


@pytest.mark.parametrize("config", [REAL, TINY], ids=["real", "tiny"])
def test_yarn_frequencies_and_scale_are_transformers(config):
    torch = pytest.importorskip("torch")
    from transformers.modeling_rope_utils import _compute_yarn_parameters

    r = config["rope_parameters"]["full_attention"]
    theirs, scale = _compute_yarn_parameters(types.SimpleNamespace(
        rope_theta=r["rope_theta"], head_dim=config["head_dim"],
        partial_rotary_factor=r["partial_rotary_factor"],
        hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rope_scaling=r), torch.device("cpu"))
    ours, our_scale = softmax_sizes(config, names.FULL).rotary()
    assert ours.shape == (config["head_dim"] // 4,) == tuple(theirs.shape)
    assert worst(ours, theirs.numpy()) < 1e-6
    assert our_scale == scale == r["attention_factor"]
    # the reference's, from the formula, is the same
    mine, my_scale = arch.inv_freq(arch.dims(config)["rope"][arch.FULL])
    assert worst(mine, theirs.numpy()) < 1e-6 and my_scale == scale
    # some pairs keep their frequency, some take it over the factor
    plain = np.asarray(rope.rope_inv_freq(ours.shape[0], r["rope_theta"]))
    ratio = np.asarray(ours) / plain
    assert ratio[0] == 1.0 and ratio[-1] == pytest.approx(1 / r["factor"])
    assert np.all(np.diff(ratio) <= 1e-7)


def test_the_real_scale_is_a_tenth_of_ln_128_and_one():
    scale = REAL["rope_parameters"]["full_attention"]["attention_factor"]
    assert scale == 0.1 * math.log(128) + 1 == 1.4852030263919618


def test_plain_frequencies_are_what_rope_angles_always_gave():
    got = rope.rope_angles(3, 16, 8, 10000.0)
    want = (3.0 + np.arange(16.0))[:, None] * 10000.0 ** (
        -np.arange(8.0) / 8)
    assert worst(got, want) < 1e-6
    assert np.array_equal(got, rope.rope_angles_at(
        3, 16, rope.rope_inv_freq(8, 10000.0)))


@pytest.mark.parametrize("kind", [names.FULL, names.WINDOW])
def test_rotary_by_kind_is_the_position_at_a_time_form(kind):
    """Every pair ``(i, i + half)`` of a head's first ``rotary_dim`` dims
    turns by ``position * inv_freq_i``, cos and sin times the kind's scale;
    the other dims pass through: spelled out a position and a pair at a
    time in float64."""
    a = softmax_sizes(TINY, kind)
    inv_freq, scale = a.rotary()
    half, dh = a.rotary_dim // 2, TINY["head_dim"]
    assert (a.rotary_dim, scale != 1.0) == {
        names.FULL: (dh // 2, True), names.WINDOW: (dh, False)}[kind]
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (1, 40, 3, dh)))
    got = np.asarray(jax.jit(lambda x: hybrid.rotate_partial(
        x, inv_freq, scale))(jnp.asarray(x)))
    freq = np.asarray(inv_freq, np.float64)
    want = x.astype(np.float64).copy()
    for p in range(x.shape[1]):
        for i in range(half):
            c, s = (scale * f(p * freq[i]) for f in (math.cos, math.sin))
            lo, hi = x[0, p, :, i], x[0, p, :, i + half]
            want[0, p, :, i] = lo * c - hi * s
            want[0, p, :, i + half] = lo * s + hi * c
    assert worst(got, want) < 2e-6
    assert np.array_equal(got[..., 2 * half:], x[..., 2 * half:])
    # and the reference's own rotation says the same
    rope_of_kind = arch.dims(TINY)["rope"][
        {names.FULL: arch.FULL, names.WINDOW: arch.SLIDING}[kind]]
    assert worst(jax.jit(lambda x: arch.rotate(x, rope_of_kind))(
        jnp.asarray(x[0])), want[0]) < 2e-6


# ---------------------------------------------------------------------------
# (b) the gated attention arm against a dense masked softmax


def dense_masked_attention(x, p, a, dh, window, gate=True):
    """Plain ``jnp``, all heads at once over ``[s, s]`` scores."""
    s = x.shape[0]
    h, kv = a.n_heads, a.n_kv_heads
    inv_freq, scale = a.rotary()
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = scale * jnp.cos(angle)[:, None], scale * jnp.sin(angle)[:, None]
    half = inv_freq.shape[0]

    def turn(t):
        lo, hi = t[..., :half], t[..., half:2 * half]
        return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos,
                                t[..., 2 * half:]], axis=-1)

    q = turn((x @ p["q_proj"]["kernel"]).reshape(s, h, dh))
    k = turn((x @ p["k_proj"]["kernel"]).reshape(s, kv, dh))
    v = (x @ p["v_proj"]["kernel"]).reshape(s, kv, dh)
    k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    apart = jnp.arange(s)[:, None] - jnp.arange(s)[None]
    seen = apart >= 0
    if window is not None:
        seen &= apart < window
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), v)
    if gate:
        out = out * jax.nn.sigmoid(x @ p["g_proj"]["kernel"])[..., None]
    return out.reshape(s, h * dh) @ p["o_proj"]["kernel"]


@pytest.mark.parametrize("kind", [names.FULL, names.WINDOW])
def test_the_head_gated_arm_is_a_dense_masked_softmax(kind):
    sizes = arch.build_module(tiny(TINY), {"remat": None}).sizes
    a, dh = sizes.softmax(kind), sizes.head_dim
    assert a.window == {names.FULL: None, names.WINDOW: 32}[kind]
    module = hybrid.HeadGatedAttention(sizes, jnp.float32, kind)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64), jnp.float32)
    params = jax.tree.map(
        lambda w: 0.2 * jax.random.normal(jax.random.PRNGKey(w.size), w.shape),
        jax.jit(module.init)(jax.random.PRNGKey(2), x)["params"])
    assert params["g_proj"]["kernel"].shape == (64, a.n_heads)
    assert "q_norm" not in params
    got = jax.jit(module.apply)({"params": params}, x)
    dense = lambda window, **gate: jax.jit(lambda x, params: jnp.stack([
        dense_masked_attention(row, params, a, dh, window, **gate)
        for row in x]))(x, params)
    assert worst(got, dense(a.window)) < 1e-5
    # a dropped gate, and on a sliding layer a dropped window, are another
    # result
    assert worst(got, dense(a.window, gate=False)) > 0.1
    if kind == names.WINDOW:
        assert worst(got, dense(None)) > 0.1


def test_a_window_has_one_attention_instance_and_no_window_the_default():
    assert attention.attention_within(None) is attention.default_attention
    one = attention.attention_within(512)
    assert one is attention.attention_within(512) and one.window == 512
    assert attention.attention_within(64) is not one


# (seq, block_q, block_k, window) -> score entries computed over live pairs,
# every tile whole and, where the window is as wide as whole tiles, its two
# edge tiles by squares a quarter of the tile wide
BANDS = {
    (8192, 1024, 1024, 512): (3.871, None),
    (8192, 512, 512, 512): (2.0, 1.25),   # the cell's sliding layers
    (8192, 256, 256, 512): (1.5, 1.125),
    (8192, 1024, 512, 512): (2.968, None),
    (8192, 1024, 1024, 4096): (1.25, 1.0624),
    (4096, 1024, 1024, 512): (3.733, None),
    (2048, 1024, 1024, 4096): (1.5, 1.125),   # a window past the end: causal
}


@pytest.mark.parametrize("seq, bq, bk, window", sorted(BANDS))
def test_what_a_windows_tiles_compute(seq, bq, bk, window):
    """``computed_over_live`` of a windowed call counts the tiles the
    kernels' own predicate (``_tile_live``) keeps over the band's live
    pairs: whole, or a tile an edge crosses by the squares on its live side
    where ``diag_sub`` says the kernels take it so."""
    whole, by_squares = BANDS[seq, bq, bk, window]
    got = attention.computed_over_live(seq, bq, bk, 0, window)
    assert got == pytest.approx(whole, rel=1e-3)
    live = [(i, j) for i in range(seq // bq) for j in range(seq // bk)
            if _tile_live(i, j, bq, bk, 0, window)]
    q = np.arange(seq)
    pairs = np.minimum(q + 1, window).sum()
    assert got == len(live) * bq * bk / pairs
    sub = diag_sub(bq, bk, 0, window, bq // 4)
    assert sub == (0 if by_squares is None else bq // 4)
    if sub:
        got = attention.computed_over_live(seq, bq, bk, sub, window)
        assert got == pytest.approx(by_squares, rel=1e-3)
        # entry by entry from the strips the kernels unroll: the diagonal
        # tile's staircase and the far edge tile's, every other tile whole
        computed = 0
        for i, j in live:
            strips = (_diagonal_strips(bq, sub) if i == j else
                      _far_edge_strips(bq, sub) if i - j == window // bq
                      else [(slice(0, bq), slice(0, bk))])
            tile = np.zeros((bq, bk), bool)
            for rows, cols in strips:
                assert not tile[rows, cols].any()
                tile[rows, cols] = True
            r, c = np.indices((bq, bk))
            apart = (i * bq + r) - (j * bk + c)
            assert tile[(apart >= 0) & (apart < window)].all()
            computed += tile.sum()
        assert got == computed / pairs


@pytest.mark.parametrize("seq, bq, bk, window", sorted(BANDS))
def test_a_sweep_is_the_run_of_a_tiles_live_tiles(seq, bq, bk, window):
    """For every band of the table: a query tile's first to last live key
    tile is exactly the set ``_tile_live`` keeps, a key tile's sweep over
    query tiles starts at its first live one, and the two grid axes are the
    longest runs (the window's width in tiles plus one, or the sequence)."""
    nq, nkv = seq // bq, seq // bk
    kv_steps, q_steps, n_live, n_edge = band_grid(nq, nkv, bq, bk, 0, window)
    live = np.array([[bool(_tile_live(i, j, bq, bk, 0, window))
                      for j in range(nkv)] for i in range(nq)])
    assert n_live == live.sum() and live.any(1).all() and live.any(0).all()
    assert n_edge == sum(
        not bool(_tile_interior(i, j, bq, bk, 0, window))
        for i, j in zip(*np.nonzero(live)))
    for i in range(nq):
        run = range(int(_first_live_kv(i, nkv, bq, bk, window)),
                    int(_last_live_kv(i, nkv, bq, bk, 0)) + 1)
        assert list(np.flatnonzero(live[i])) == list(run)
    for j in range(nkv):
        assert int(_first_live_q(j, nq, bq, bk, 0, window)) == (
            np.flatnonzero(live[:, j])[0])
    assert kv_steps == live.sum(1).max() and q_steps == live.sum(0).max()
    if bq == bk:
        assert kv_steps == q_steps == min(nkv, -(-window // bk) + 1)


def test_a_windowed_call_runs_tiles_of_the_windows_width():
    """A window narrower than the row's tiles runs square tiles of its own
    width, its two edge tiles by squares of 256 (PR 42: with the grid cut
    to the band they beat every other tile of the sweep; before it they ran
    20% longer than the row's, PR 41; squares of 128 run 5% faster still
    and cost a tenth of the cell's set-up in trace and load).  At 512 over
    8,192 keys that is 31 x 3 squares of 256 a head for 4,063,488 live
    pairs, 32 grid steps a head for 31 live; a window at or over the row's
    tiles, one the lanes do not
    divide, or none keeps the row's, and the causal figures are what they
    were."""
    r = attention.route("TPU v5 lite", 8192, 128, 512)
    assert (r.kernel, r.block_q, r.block_k, r.sub) == (
        attention.FLASH, 512, 512, 256)
    assert attention.computed_over_live(8192, 512, 512, 256, 512) == (
        31 * 3 * 256 ** 2 / 4_063_488) <= 2.1
    # (every live tile of these two windows is one an edge crosses)
    assert band_grid(16, 16, 512, 512, 0, 512) == (2, 2, 31, 31)
    assert band_grid(8, 8, 1024, 1024, 0, 512) == (2, 2, 15, 15)
    assert band_grid(8, 8, 1024, 1024, 0, None) == (8, 8, 36, 8)
    # (384 is whole lanes wide and does not divide 8,192)
    for window in (None, 1024, 4096, 100, 384, 384 + 64):
        r = attention.route("TPU v5 lite", 8192, 128, window)
        assert (r.kernel, r.block_q, r.block_k, r.sub) == (
            attention.FLASH, 1024, 1024, 256)
    assert attention.route("TPU v6 lite", 8192, 128, 256)[1:3] == (512, 1024)
    assert attention.computed_over_live(8192, 1024, 1024, 0, 512) == (
        15 * 1024 ** 2 / 4_063_488)
    assert attention.computed_over_live(
        8192, 1024, 1024, 256) == pytest.approx(1.0311, abs=1e-4)


# ---------------------------------------------------------------------------
# (c) the decoder against the reference


@pytest.fixture(scope="module")
def f32_pair():
    return reference_pair(arch, tiny(TINY))


def test_the_module_takes_the_arms_the_architecture_names(f32_pair):
    module = f32_pair["module"]
    z = module.sizes
    assert module.layer_types == (names.FULL, names.WINDOW, names.WINDOW,
                                  names.WINDOW, names.FULL)
    assert module.feed_forwards == (names.DENSE_FFN,) + (
        names.EXPERT_SHARE,) * 4
    assert (z.attention, z.norm, z.norm_after, z.one_sublayer) == (
        names.HEAD_GATED_ATTN, names.PLAIN, False, False)
    full, window = z.softmax(names.FULL), z.softmax(names.WINDOW)
    # unequal head counts on the same key/value heads, held of in all
    assert (full.n_heads, full.n_heads_total, window.n_heads,
            window.n_heads_total) == (4, 8, 6, 12)
    assert full.n_kv_heads == window.n_kv_heads == 2
    assert (full.window, window.window) == (None, 32)
    assert (full.rotary_dim, window.rotary_dim) == (8, 16)
    assert (full.rope_theta, window.rope_theta) == (500000.0, 10000.0)
    assert full.yarn == hybrid.Yarn(16.0, 64, 4.0, 1.0, 1.2772588722239782)
    assert window.yarn is None
    assert (z.scoring, z.routed_scale, z.top_k, z.n_experts, z.held,
            z.expert_fn, z.shared_scored, z.latent_width) == (
        names.SIGMOID, 2.5, 8, 64, 2, names.GATED_SILU, False, None)
    # the kinds' sizes are said once: the decoder's one set is not filled,
    # and a kind that is not named has none
    assert (z.n_heads, z.n_kv_heads, z.n_heads_total, z.rotary_dim) == (
        None, None, None, 0)
    with pytest.raises(ValueError, match="softmax_kinds names"):
        dataclasses.replace(z, softmax_kinds=z.softmax_kinds[:1]).softmax(
            names.WINDOW)


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
    want = p["ref_grads"][name]
    assert float(jnp.linalg.norm(want)) > 0, name
    assert rel(got, want.reshape(got.shape)) < 1e-4, name


def other_module(p, **changed):
    """The pair's module with its sizes changed."""
    return dataclasses.replace(p["module"], sizes=dataclasses.replace(
        p["module"].sizes, **changed))


def kinds_with(p, kind: str, **changed) -> dict:
    """``softmax_kinds=`` with one kind's sizes changed."""
    z = p["module"].sizes
    return dict(softmax_kinds=tuple(
        (k, dataclasses.replace(a, **changed) if k == kind else a)
        for k, a in z.softmax_kinds))


@pytest.mark.parametrize("wrong", [
    "window_dropped", "yarn_scale_dropped", "yarn_factor_dropped",
    "routed_scale_dropped", "choice_of_bias", "softmax_scores"])
def test_another_arm_is_not_this_architecture(f32_pair, wrong):
    p = f32_pair
    full = p["module"].sizes.softmax(names.FULL)
    changed = {
        "window_dropped": kinds_with(p, names.WINDOW, window=None),
        "yarn_scale_dropped": kinds_with(
            p, names.FULL, yarn=dataclasses.replace(full.yarn, scale=1.0)),
        "yarn_factor_dropped": kinds_with(p, names.FULL, yarn=None),
        "routed_scale_dropped": dict(routed_scale=1.0),
        "choice_of_bias": dict(scoring=names.SIGMOID_BIAS),
        "softmax_scores": dict(scoring=names.SOFTMAX, routed_scale=1.0),
    }[wrong]
    module = other_module(p, **changed)
    params = p["params"]
    if wrong == "choice_of_bias":
        # a bias that steers the choice: another layer, other picks
        params = jax.tree.map(lambda x: x, params)
        for i in range(1, 5):
            params["params"][f"layer_{i}"]["experts"]["choice_bias"] = (
                jnp.linspace(-0.3, 0.3, 64))
    assert worst(logits(p, module, params), reference_logits(p)) > 1e-3


def test_the_gate_is_in_the_result(f32_pair):
    """With every gate's projection zeroed the gates are one half: another
    result, and exactly the reference's with its gate dropped, halved."""
    p = f32_pair
    weights = {k: jnp.zeros_like(v) if k.endswith("g_proj") else v
               for k, v in p["weights"].items()}
    got = logits(p, params=arch.program_tree(p["config"], weights))
    assert worst(got, reference_logits(p, weights)) < 3e-5
    assert worst(got, reference_logits(p)) > 1e-2
    m = arch.dims(p["config"])
    x = jax.random.normal(jax.random.PRNGKey(5), (128, 64))
    halved, ungated = jax.jit(lambda x, w: tuple(
        arch.attention(x, w, kind=arch.SLIDING, m=m, mode="f32", **gate)
        for gate in ({}, dict(gate=False))))(x, arch.of_layer(weights, 1))
    assert worst(halved, 0.5 * ungated) < 1e-6


def test_the_reference_takes_a_lone_row_without_the_loop_over_rows(f32_pair):
    """The real cell's block is one row, which the reference takes as it is
    (no loop over rows, no checkpoint round it: a level of recomputed code
    less); the mean of the rows' own losses and gradients is the block's."""
    p = f32_pair
    assert p["tokens"].shape[0] == 2
    a_row = jax.jit(lambda w, row: arch.loss_and_grads(p["config"], w, row))
    alone = [a_row(p["weights"], p["tokens"][i:i + 1]) for i in range(2)]
    assert (alone[0][0] + alone[1][0]) / 2 == pytest.approx(
        float(p["ref_loss"]), rel=1e-6)
    for name, want in p["ref_grads"].items():
        got = (alone[0][1][name] + alone[1][1][name]) / 2
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * scale + 1e-9, name


@pytest.mark.parametrize("kind", [arch.FULL, arch.SLIDING])
def test_the_references_query_blocks_are_the_whole_rows_attention(
        kind, f32_pair, monkeypatch):
    """At the real size the reference takes a head's queries 2,048 at a
    time, every block against the same number of keys (all of them, or its
    own and the 31 before them here, rows of zeros before the first); the
    tiny rows are one block.  Four blocks of 64 give the one block's output
    and gradients."""
    m = arch.dims(f32_pair["config"])
    w = {k: v for k, v in arch.of_layer(
        f32_pair["weights"], 0 if kind == arch.FULL else 1).items()
        if k.endswith("_proj")}
    assert len(w) == 5
    x = jax.random.normal(jax.random.PRNGKey(11), (256, 64))

    def out_and_grads():
        f = lambda x, w: jnp.sum(jnp.sin(arch.attention(
            x, w, kind=kind, m=m, mode="f32")))
        # (a new program a call: ``QUERY_BLOCK`` is read while tracing)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(x, w)

    whole = out_and_grads()
    monkeypatch.setattr(arch, "QUERY_BLOCK", 64)
    blocks = out_and_grads()
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocks)):
        assert worst(a, b) < 1e-5


def test_a_router_held_fixed_still_hands_its_gradient_to_the_tokens():
    config = tiny(TINY)
    config["as_run"]["router_trained"] = False
    p = reference_pair(arch, config, options={"remat": None})
    assert not p["module"].sizes.router_trained
    want = p["ref_grads"]
    got = dict(zip(arch.leaf_names(config),
                   arch.named_leaves(config, p["grads"])))
    for name, g in got.items():
        if name.endswith(".router"):
            assert not np.any(np.asarray(g)) and not np.any(
                np.asarray(want[name])), name
        else:
            assert rel(g, want[name].reshape(g.shape)) < 1e-4, name


def test_the_gradient_under_remat_is_the_gradient_without(f32_pair):
    """What a layer keeps changes no number: layer 0 keeps its dense
    feed-forward's three products beside ``mixer_out``, the expert layers
    ``mixer_out`` and what their router computed: its logits, its picks and
    their scores."""
    p = f32_pair
    z = p["module"].sizes
    dense = dataclasses.replace(z, feed_forward=names.DENSE_FFN)
    assert hybrid.remat_keeps(dense) == (
        names.MIXER_OUT,) + names.DENSE_FFN_KEEPS
    assert hybrid.remat_keeps(z) == (
        names.MIXER_OUT, names.ROUTER_LOGITS, names.ROUTER_PICKS)
    plain = dataclasses.replace(p["module"], remat=False)
    grads = jax.jit(jax.grad(lambda q: lm_loss(
        plain.apply(q, p["tokens"]), p["tokens"])))(p["params"])
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(p["grads"])):
        assert bool(jnp.all(a == b))


def test_the_real_cells_layers_keep_their_bytes():
    """Layer 0: ``mixer_out`` and the dense arm's three products over 8,192
    tokens in bf16, 8192 x (3072 + 2 x 12288 + 3072) x 2 = 503.3 MB; the
    expert layers ``mixer_out``, 50.3 MB, the router's float32 logits over
    256 experts, 8.4 MB, and its 10 picks and their scores (int32 and
    float32), 0.66 MB."""
    z = arch.build_module(REAL, {"remat": "nothing"}).sizes
    dense = dataclasses.replace(z, feed_forward=names.DENSE_FFN)
    assert hybrid.kept_bytes(hybrid.remat_keeps(dense), dense, 8192,
                             jnp.bfloat16) == 503_316_480
    assert hybrid.kept_bytes(hybrid.remat_keeps(z), z, 8192,
                             jnp.bfloat16) == (
        50_331_648 + 8_388_608 + 655_360)


# ---------------------------------------------------------------------------
# (d) the share ties to the model


def head_share(m_full: dict, w: dict, layer: int, member: int) -> dict:
    """Member ``member``'s half of an uncut attention layer's weights: its
    key/value heads and the query heads that read them."""
    dh, kv = m_full["dh"], m_full["kv"] // HEAD_MEMBERS
    h = m_full["heads"][layer] // HEAD_MEMBERS
    q = slice(member * h * dh, (member + 1) * h * dh)
    k = slice(member * kv * dh, (member + 1) * kv * dh)
    return {**w, "q_proj": w["q_proj"][:, q], "k_proj": w["k_proj"][:, k],
            "v_proj": w["v_proj"][:, k],
            "g_proj": w["g_proj"][:, member * h:(member + 1) * h],
            "o_proj": w["o_proj"][q]}


def attention_params(share: dict) -> dict:
    return {name: {"kernel": share[name]}
            for name in ("q_proj", "k_proj", "v_proj", "g_proj", "o_proj")}


@pytest.mark.parametrize("kind, layer", [(arch.SLIDING, 1), (arch.FULL, 0)],
                         ids=["sliding", "full"])
def test_the_two_head_shares_add_up_to_the_uncut_layer(kind, layer):
    """Section 4's share test.  The uncut reference holds 12 (sliding) or 8
    (full) query heads on 4 key/value heads.  Each member holds a half:
    alone (no axis: the cell) it gives what the reference gives when handed
    that half, and the two partial outputs add up to the uncut layer's (the
    gate is a head's own: no statistic crosses the cut).  Told
    ``heads_axis``, under a ``vmap`` with that axis name, ``o_proj``'s
    partial sums are added up and each member's output IS the uncut one."""
    full, held = whole(), tiny(TINY)
    m_full, m_held = arch.dims(full), arch.dims(held)
    assert m_full["heads"][layer] == 2 * m_held["heads"][layer]
    w = arch.of_layer(seeded(arch, full, 11), layer)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64), jnp.float32)
    reference_of = lambda m: jax.jit(lambda x, w: jnp.stack([arch.attention(
        row, w, kind=kind, m=m, mode="f32") for row in x]))
    want = reference_of(m_full)(x, w)
    shares = [head_share(m_full, w, layer, i) for i in range(HEAD_MEMBERS)]
    sizes = arch.build_module(held, {"remat": None}).sizes
    program_kind = {arch.FULL: names.FULL, arch.SLIDING: names.WINDOW}[kind]
    alone, given_a_share = jax.jit(hybrid.HeadGatedAttention(
        sizes, jnp.float32, program_kind).apply), reference_of(m_held)
    parts = []
    for share in shares:
        got = alone({"params": attention_params(share)}, x)
        given = given_a_share(x, share)
        assert worst(got, given) < 1e-5
        parts.append(got)
    assert worst(sum(parts), want) < 1e-5
    assert worst(parts[0], want) > 1e-1
    shared = hybrid.HeadGatedAttention(
        dataclasses.replace(sizes, heads_axis="heads"), jnp.float32,
        program_kind)
    stacked = jax.tree.map(lambda *a: jnp.stack(a),
                           *map(attention_params, shares))
    every = jax.jit(jax.vmap(lambda p: shared.apply({"params": p}, x),
                             axis_name="heads"))(stacked)
    for member in range(HEAD_MEMBERS):
        assert worst(every[member], want) < 1e-5


def expert_params(m: dict, w: dict, first: int, held: int) -> dict:
    d, width = m["d"], m["width"]
    at = slice(first, first + held)
    return {"router": w["router"],
            "gate": w["experts_gate"].reshape(-1, d, width)[at],
            "up": w["experts_up"].reshape(-1, d, width)[at],
            "down": w["experts_down"].reshape(-1, width, d)[at],
            **{f"shared_{n}": w[f"shared_{n}"] for n in ("gate", "up",
                                                         "down")}}


def test_the_32_expert_shares_add_up_to_the_uncut_layer():
    """Each member holds 2 of the 64 experts and the whole of router and
    shared expert.  The 32 results, with the shared expert (what every
    member computes alike) counted once, add up to what the uncut reference
    gives for the whole layer."""
    full, held = whole(), tiny(TINY)
    m_full = arch.dims(full)
    w = arch.of_layer(seeded(arch, full, 11), 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64), jnp.float32)
    # (which experts are held is a Python number to reference and program
    # alike: a program a member)
    reference_of = lambda **held: jax.jit(lambda x, w: jnp.stack([
        arch.experts(row, w, m=m_full, mode="f32", **held) for row in x]))(
            x, w)
    want, shared_alone = reference_of(), reference_of(held=0)
    sizes = arch.build_module(held, {"remat": None}).sizes
    per = sizes.held
    assert per * EXPERT_MEMBERS == sizes.n_experts == 64
    total, loads = 0.0, []
    for member in range(EXPERT_MEMBERS):
        module = hybrid.ExpertShare(dataclasses.replace(
            sizes, first_expert=member * per), jnp.float32)
        got, state = jax.jit(lambda p: module.apply(
            p, x, mutable=["intermediates"]))(
                {"params": expert_params(m_full, w, member * per, per)})
        if member in (0, 17):
            given = reference_of(first=member * per, held=per)
            assert worst(got, given) < 1e-5
        total = total + got
        loads.append(np.asarray(
            state["intermediates"]["moe_expert_tokens"][0]))
    assert worst(total - (EXPERT_MEMBERS - 1) * shared_alone, want) < 1e-5
    assert worst(total - EXPERT_MEMBERS * shared_alone, want) > 1e-2
    # every one of a token's 8 picks is some member's
    assert np.concatenate(loads).sum() == 2 * 128 * 8


def test_the_tiny_share_goes_by_windows_as_the_real_one_does():
    for config, tokens in ((TINY, 2 * 128), (REAL, 8192)):
        m = arch.dims(config)
        rows, at_most = moe.share_windows(tokens, m["top_k"], m["held"],
                                          m["experts"])
        assert at_most == 4 and 4 * rows == tokens * m["top_k"]
    assert moe.share_windows(8192, 10, 8, 256) == (20480, 4)


# ---------------------------------------------------------------------------
# (e) the router's scorings


def test_routes_sigmoid_arm_is_plain_sigmoid_top_k_renormalised():
    logits = 1.5 * jax.random.normal(jax.random.PRNGKey(0), (96, 64))
    got = jax.jit(lambda x: moe.route(
        x, n_experts=64, k=8, held=2, first_expert=6, scoring=names.SIGMOID,
        scale=2.5))(logits)

    @jax.jit
    def plainly(logits):
        scores = jax.nn.sigmoid(logits)
        order = jnp.argsort(-scores, axis=-1)[:, :8]
        return scores, order, jnp.take_along_axis(scores, order, axis=-1)

    scores, order, picked = plainly(logits)
    assert np.array_equal(got.expert_idx, order)
    assert worst(got.weights,
                 2.5 * picked / picked.sum(-1, keepdims=True)) < 1e-6
    assert np.allclose(np.asarray(got.weights).sum(-1), 2.5, rtol=1e-6)
    assert np.array_equal(got.probs, scores)
    local = np.asarray(order) - 6
    assert np.array_equal(got.local, np.where((local >= 0) & (local < 2),
                                              local, 2))
    # the same picks and weights as the biased arm with a bias of zero
    biased = jax.jit(lambda x: moe.route(
        x, n_experts=64, k=8, scoring=names.SIGMOID_BIAS,
        choice_bias=jnp.zeros(64), scale=2.5))(logits)
    assert np.array_equal(biased.expert_idx, got.expert_idx)
    assert np.array_equal(biased.weights, got.weights)


def test_the_sigmoid_scorings_are_one_function_and_the_layer_gives_the_bias(
        f32_pair):
    """``sigmoid`` and ``sigmoid_bias`` score alike; what differs is whether
    the layer holds a bias for the picks to go by: this decoder holds none."""
    assert moe.SCORINGS[names.SIGMOID] is moe.SCORINGS[names.SIGMOID_BIAS]
    leaves = [jax.tree_util.keystr(path) for path, _ in
              jax.tree_util.tree_flatten_with_path(f32_pair["params"])[0]]
    assert [l for l in leaves if "router" in l]
    assert not [l for l in leaves if "choice_bias" in l]


@pytest.mark.parametrize("scoring", [names.SOFTMAX, names.SIGMOID_BIAS])
def test_the_other_scorings_are_bit_for_bit_what_they_were(scoring):
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    if scoring == names.SOFTMAX:
        now = lambda x: moe.route(x, n_experts=32, k=4)

        def before(logits):
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            weights, picks = jax.lax.top_k(probs, 4)
            return probs, picks, weights / jnp.sum(weights, axis=-1,
                                                   keepdims=True)
    else:
        now = lambda x: moe.route(x, n_experts=32, k=4, scoring=scoring,
                                  choice_bias=bias, scale=5.0)

        def before(logits):
            probs = jax.nn.sigmoid(logits.astype(jnp.float32))
            _, picks = jax.lax.top_k(probs + bias, 4)
            weights = jnp.take_along_axis(probs, picks, axis=-1)
            return probs, picks, weights / (jnp.sum(
                weights, axis=-1, keepdims=True) + 1e-20) * 5.0
    got = jax.jit(now)(logits)
    probs, picks, weights = jax.jit(before)(logits)
    assert np.array_equal(got.expert_idx, picks)
    assert np.array_equal(got.weights, weights)
    assert np.array_equal(got.probs, probs)


# ---------------------------------------------------------------------------
# (f) counts, errors, events


def test_the_real_configurations_parameters_to_the_parameter():
    shapes = arch.weight_shapes(REAL)
    count = lambda names_: sum(int(np.prod(shapes[n])) for n in names_)
    by_layer = [count(n for n in shapes if n.startswith(f"layer_{i}."))
                for i in range(5)]
    assert by_layer == [135_346_176, 117_295_104, 117_295_104, 117_295_104,
                        107_821_056]
    assert count(["embed", "head", "final_norm"]) == 77_073_408
    assert count(shapes) == REAL["as_run"]["parameters"] == 672_125_952
    attn = lambda i: count(f"layer_{i}.{n}_proj" for n in "qkvgo")
    assert (attn(0), attn(1)) == (22_093_824, 31_567_872)
    assert count(n for n in shapes if n.startswith("layer_1.") and (
        "router" in n or "shared" in n or "experts" in n)) == 85_721_088
    assert count(f"layer_0.ffn_{n}" for n in ("gate", "up", "down")) == (
        113_246_208)
    # the whole layers are twice the attention shares and 32 times the
    # experts
    published = dict(REAL, **REAL["published"])
    whole_shapes = arch.weight_shapes(published)
    assert sum(int(np.prod(whole_shapes[f"layer_0.{n}_proj"]))
               for n in "qkvgo") == 44_187_648
    assert sum(int(np.prod(whole_shapes[f"layer_1.{n}_proj"]))
               for n in "qkvgo") == 63_135_744


def test_unknown_arms_are_named_against_their_tables(f32_pair):
    p = f32_pair
    with pytest.raises(ValueError) as e:
        other_module(p, attention="alibi").init(jax.random.PRNGKey(0),
                                                p["tokens"])
    for arm in hybrid.ATTENTIONS:
        assert arm in str(e.value)
    assert set(hybrid.ATTENTIONS) == {
        names.GATED_ATTN, names.NORMED_ATTN, names.GROUPED_ATTN,
        names.HEAD_GATED_ATTN}
    for arms in ((names.DENSE_FFN,) * 4, (names.DENSE_FFN,) * 4 + ("moe",)):
        with pytest.raises(ValueError, match="feed_forwards"):
            dataclasses.replace(p["module"], feed_forwards=arms).init(
                jax.random.PRNGKey(0), p["tokens"])
    with pytest.raises(ValueError) as e:
        dataclasses.replace(p["module"], layer_types=("retention",) * 5).init(
            jax.random.PRNGKey(0), p["tokens"])
    for kind in hybrid.MIXERS:
        assert kind in str(e.value)


def test_the_layout_events_say_the_kinds_the_arms_and_the_share(tmp_path,
                                                                f32_pair):
    p = f32_pair
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        jax.jit(p["module"].apply)(p["params"],
                                   p["tokens"]).block_until_ready()
        ring = list(session.ring)
    finally:
        telemetry.finish(write_report=False)
    (mixer,) = [r for r in ring if r["name"] == names.MIXER_LAYOUT]
    assert mixer["kinds"] == [names.FULL] + [names.WINDOW] * 3 + [names.FULL]
    assert mixer["attention"] == names.HEAD_GATED_ATTN
    assert "attn_heads" not in mixer and "attn_kv_heads" not in mixer
    assert mixer["softmax_kinds"] == {
        names.FULL: dict(heads=[4, 8], kv_heads=2, window=None,
                         rotary_dim=8, rope_theta=500000.0, yarn_factor=16.0,
                         rope_scale=1.2772588722239782),
        names.WINDOW: dict(heads=[6, 12], kv_heads=2, window=32,
                           rotary_dim=16, rope_theta=10000.0,
                           yarn_factor=None, rope_scale=1.0)}
    assert mixer["feed_forwards"] == [names.DENSE_FFN] + [
        names.EXPERT_SHARE] * 4
    assert mixer["remat_keeps"] == [
        [names.MIXER_OUT, *names.DENSE_FFN_KEEPS]] + [
        [names.MIXER_OUT, names.ROUTER_LOGITS, names.ROUTER_PICKS]] * 4
    itemsize = 4    # the pair computes in float32
    # an expert layer: ``mixer_out``, 64 experts' logits, 8 picks and scores
    assert mixer["remat_kept_bytes_per_layer"] == [
        256 * (64 + 2 * 160 + 64) * itemsize] + [
        256 * (64 * itemsize + 64 * 4 + 8 * 8)] * 4
    layouts = [r for r in ring if r["name"] == names.MOE_LAYOUT]
    assert len(layouts) == 4
    for said in layouts:
        assert (said["scoring"], said["scale"], said["experts"],
                said["held"], said["top_k"], said["width"]) == (
            names.SIGMOID, 2.5, 64, 2, 8, 64)
        assert (said["window_rows"], said["windows_at_most"],
                said["strip_rows"], said["combine"]) == (
            512, 4, 64, names.SCATTER_ADD)


def test_a_decoder_of_one_arm_says_its_keeps_as_before(tmp_path):
    """Where every layer takes the same arm the event's ``remat_keeps`` and
    ``remat_kept_bytes_per_layer`` are one list and one number."""
    module = hybrid.HybridLM(
        vocab=64, layer_types=(names.FULL,) * 2, remat=True,
        sizes=hybrid.HybridSizes(
            d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, rotary_dim=4,
            n_experts=4, held=2, top_k=2, expert_width=16, shared_width=16))
    tokens = jnp.zeros((1, 64), jnp.int32)
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        module.init(jax.random.PRNGKey(0), tokens)
        (said,) = [r for r in session.ring
                   if r["name"] == names.MIXER_LAYOUT][:1]
    finally:
        telemetry.finish(write_report=False)
    assert said["remat_keeps"] == [names.MIXER_OUT, names.ROUTER_LOGITS,
                                   names.ROUTER_PICKS]
    assert said["remat_kept_bytes_per_layer"] == 64 * (32 * 4 + 4 * 4 + 2 * 8)
    assert said["feed_forwards"] == [names.EXPERT_SHARE] * 2
    assert (said["attn_heads"], said["attn_kv_heads"]) == ([2, 2], 1)
    assert "softmax_kinds" not in said


def test_a_window_layer_takes_the_head_gated_arm():
    """Only the head-gated arm goes by its layer's kind: a sliding layer in
    a decoder of another arm is refused, not run causally."""
    module = hybrid.HybridLM(
        vocab=64, layer_types=(names.WINDOW,),
        sizes=hybrid.HybridSizes(
            d_model=32, n_heads=2, n_kv_heads=1, head_dim=16,
            attention=names.GROUPED_ATTN, n_experts=4, held=2, top_k=2,
            expert_width=16, shared_width=16))
    with pytest.raises(ValueError, match="has no window"):
        module.init(jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))


def test_the_layers_names_carry_what_the_readers_look_for(f32_pair):
    """A sliding layer's ops lie under ``window_attn`` and under no
    ``attn``; the gate's under ``head_gate`` in both kinds; layer 0's
    feed-forward under ``mlp``, the others' under ``moe``."""
    import re

    p = f32_pair
    text = jax.jit(jax.grad(lambda q: lm_loss(
        p["module"].apply(q, p["tokens"]), p["tokens"]))).lower(
            p["params"]).as_text(debug_info=True)
    found = set(re.findall(r'loc\("([^"]+)"', text))
    under = lambda scope, n: re.search(
        rf"(^|[/(]){scope}([/)]|$)", n) is not None
    of_layer = lambda i: [n for n in found if under(f"layer_{i}", n)]
    for i, (mixer, other) in enumerate([
            (names.ATTN, names.WINDOW_ATTN), (names.WINDOW_ATTN, names.ATTN),
            (names.WINDOW_ATTN, names.ATTN), (names.WINDOW_ATTN, names.ATTN),
            (names.ATTN, names.WINDOW_ATTN)]):
        ops = of_layer(i)
        assert [n for n in ops if under(mixer, n)
                and under(names.HEAD_GATE, n)], i
        assert [n for n in ops if under(mixer, n)
                and names.BACKWARD_MARK in n], i
        assert not [n for n in ops if under(other, n)], i
        ffn, not_ffn = (names.MLP, names.MOE) if i == 0 else (
            names.MOE, names.MLP)
        assert [n for n in ops if under(ffn, n)], i
        assert not [n for n in ops if under(not_ffn, n)], i
    assert [n for n in found if under(names.SHARED_EXPERT, n)
            and under(names.MOE, n)]
