"""The pattern decoder (``tpudist/models/hybrid.py``), its chunked
delta-rule scan (``tpudist/ops/gated_delta.py``) and the dropless expert
share (``tpudist/parallel/moe.py``), held to the plain float32 reference of
the benchmark (``cellbench/archs/qwen3_next.py``) at tiny widths on the CPU:
d 64, 2 key / 4 value heads of 16, attention 4 x 32 over 1 kv head, 16
experts top-4 with 4 held, vocabulary 256, 4 layers in the 3:1 pattern.

Tolerances, and why.  Float32 against float32 differs only by the order of
sums (the chunked form against the recurrence; grouped products against
masked dense ones): 1e-5 of a tensor's largest entry for values, 1e-4 of a
gradient's norm.  A bf16 program rounds every matmul operand to 8 bits of
mantissa (2^-9 relative), which reads 3e-3..8e-3 on the scan's outputs and
2e-2..3e-2 on a gradient's direction: 100x over the float32 tolerances, so
bf16 where float32 is stated fails them (a test shows it) and the bf16
bounds sit 3x over what was read.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import reference
from cellbench.archs import qwen3_next as arch
from tests.decoder_reference import (DATA, highest, logits, reference_logits,
                                     reference_pair, rel, run_steps, seeded,
                                     tiny, worst)
from tpudist.models import hybrid
from tpudist.models.transformer import lm_loss
from tpudist.ops.gated_delta import (chunked_gated_delta_rule,
                                     gated_delta_rule_reference)
from tpudist.parallel import moe
from tpudist.telemetry import names
from tpudist.telemetry.names import MIXER_OUT

TINY = json.loads((DATA / "tiny-hybrid.json").read_text())


@pytest.fixture(autouse=True)
def highest_precision():
    with highest():
        yield


# ---------------------------------------------------------------------------
# (a) the chunked scan against the per-position recurrence


def scan_inputs(chunks: int, dtype, heads=3, dk=16, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (2, chunks * 64, heads)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], shape + (dk,))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], shape + (dk,)))
    v = jax.random.normal(ks[2], shape + (dv,))
    # per-position decays from exp(-1) = 0.37 down to exp(-0.001)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=-7.0, maxval=0.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape))
    return [x.astype(dtype) for x in (q, k, v)] + [g, beta]


SCAN_BOUNDS = {jnp.float32: 1e-5, jnp.bfloat16: 2.5e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_scan_gives_the_recurrences_values(chunks, dtype):
    args = scan_inputs(chunks, dtype)
    got = jax.jit(chunked_gated_delta_rule)(*args)
    assert got.dtype == dtype
    assert worst(got.astype(jnp.float32), jax.jit(
        gated_delta_rule_reference)(*args)) < SCAN_BOUNDS[dtype]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_chunked_scan_gives_the_recurrences_gradients(chunks, dtype):
    args = scan_inputs(chunks, dtype, seed=1)

    def through(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            fn(*a).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4)))(*args)

    for name, got, want in zip("q k v g beta".split(),
                               through(chunked_gated_delta_rule),
                               through(gated_delta_rule_reference)):
        assert worst(got.astype(jnp.float32), want.astype(
            jnp.float32)) < SCAN_BOUNDS[dtype], name


def test_bf16_scan_fails_the_float32_bound():
    args = scan_inputs(2, jnp.bfloat16)
    got = jax.jit(chunked_gated_delta_rule)(*args).astype(jnp.float32)
    assert worst(got, jax.jit(gated_delta_rule_reference)(
        *args)) > 100 * SCAN_BOUNDS[jnp.float32]


def test_strong_forgetting_does_not_overflow():
    """``g`` of -30 a position: a cumulative sum of -1920 inside a chunk;
    no decay is ever the ``exp`` of a positive number."""
    q, k, v, g, beta = scan_inputs(2, jnp.float32)
    g = jnp.full_like(g, -30.0)
    got = jax.jit(chunked_gated_delta_rule)(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert worst(got, jax.jit(gated_delta_rule_reference)(
        q, k, v, g, beta)) < 1e-5


def test_scan_refuses_a_ragged_last_chunk():
    q, k, v, g, beta = scan_inputs(1, jnp.float32)
    with pytest.raises(ValueError, match="whole number of chunks"):
        chunked_gated_delta_rule(q[:, :50], k[:, :50], v[:, :50], g[:, :50],
                                 beta[:, :50])


# ---------------------------------------------------------------------------
# (b) the decoder against the reference


@pytest.fixture(scope="module")
def f32_pair():
    return reference_pair(arch, tiny(TINY))


def test_logits_match_the_reference(f32_pair):
    assert worst(logits(f32_pair), reference_logits(f32_pair)) < 1e-5


def test_loss_matches_the_reference(f32_pair):
    assert abs(float(f32_pair["loss"]) - float(f32_pair["ref_loss"])) < 2e-6


def test_remat_keeps_each_layers_activation_between_mixer_and_experts(
        f32_pair):
    """Under remat a layer's activation between its mixer and its experts
    is named and kept (one a layer), so the experts' backward pass needs
    nothing of the mixer's forward; the gradients above are this
    program's."""
    p = f32_pair
    # (the trace is the subject: nothing runs)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q: lm_loss(p["module"].apply(q, p["tokens"]), p["tokens"])))(
            p["params"]))
    assert "checkpoint" in text or "remat" in text
    assert text.count(f"name={MIXER_OUT}") >= (
        p["config"]["num_hidden_layers"])


@pytest.mark.parametrize("name", arch.leaf_names(TINY))
def test_every_gradient_matches_the_reference(f32_pair, name):
    p = f32_pair
    names = arch.leaf_names(p["config"])
    got = arch.named_leaves(p["config"], p["grads"])[names.index(name)]
    assert rel(got, p["ref_grads"][name]) < 1e-4


def test_the_program_in_bf16_fails_the_float32_tolerances():
    p = reference_pair(arch, tiny(TINY, "bfloat16"))
    gaps = {n: rel(g, p["ref_grads"][n]) for n, g in zip(
        arch.leaf_names(p["config"]),
        arch.named_leaves(p["config"], p["grads"]))}
    assert worst(logits(p).astype(jnp.float32), reference_logits(p)) > 1e-3
    assert min(gaps.values()) > 100 * 1e-4
    # and yet it is the same mathematics: a matrix's gradient is within
    # 3x of bf16's 3e-2; the routed tensors of the deeper layers read
    # higher (a pick whose 4th and 5th scores tie goes to another expert)
    dense = [n for n in gaps if "experts_" not in n and "router" not in n
             and "A_log" not in n and "dt_bias" not in n]
    assert max(gaps[n] for n in dense) < 0.1


def program_and_weights():
    """The float32 program and the reference's weights at seed 7."""
    config = tiny(TINY)
    return (config, seeded(arch, config, 7),
            arch.build_module(config, {"remat": "nothing"}))


def test_three_adam_steps_follow_the_reference():
    """``make_lm_train_step`` over the float32 program against the
    reference's own Adam: losses to 1e-5, every tensor's change after three
    steps to 2e-3 of its norm (Adam divides by the root of the second
    moment, which magnifies the 1e-4 of a gradient where it is small)."""
    config, weights, module = program_and_weights()
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


def test_the_step_returns_the_assignments_a_held_expert_a_layer():
    config, weights, module = program_and_weights()
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 128), 0,
                                config["vocab_size"])
    _, _, aux = run_steps(arch, config, module, weights, [tokens], 1e-3,
                          aux=True)
    counts = np.asarray(aux["moe_expert_tokens"])
    assert counts.shape == (4, 4)      # layers x held experts
    # the reference, routing the first layer's expert input for itself
    m = arch.dims(config)

    @jax.jit
    def first_layers_picks(weights, row):
        w = arch._of_layer(weights, 0)
        x = weights["embed"][row]
        x = x + arch._linear_attention(
            arch._norm(x, w["mixer_norm"], m["eps"]), w, m=m, mode="f32")
        return arch.route(arch._norm(x, w["experts_norm"], m["eps"]),
                          w["router"], m=m)[0]

    want = np.zeros(4, np.int64)
    for row in tokens:
        picks = np.asarray(first_layers_picks(weights, row))
        want += [(picks == m["first"] + e).sum() for e in range(4)]
    np.testing.assert_array_equal(counts[0], want)


# ---------------------------------------------------------------------------
# (c) the shares add up to the uncut layer; (d) nothing is dropped


def expert_layer(seed=3, tokens=96):
    """An uncut reference layer of 16 experts and its weights."""
    config = tiny(TINY, num_experts=16)
    config["as_run"].update(router_experts=16, first_expert=0)
    m = arch.dims(config)
    weights = seeded(arch, config, seed)
    w = {k: 10.0 * v for k, v in arch._of_layer(weights, 0).items()}
    x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, m["d"]))
    return m, w, x


def share_of(m, w, first, held, shared):
    d, width = m["d"], m["width"]
    cut = lambda name, a, b: w[name].reshape(16, a, b)[first:first + held]
    params = {"router": w["router"], "experts": {
        "gate": cut("experts_gate", d, width),
        "up": cut("experts_up", d, width),
        "down": cut("experts_down", width, d)}}
    if shared:
        params["shared"] = {k: w[f"shared_{k}"]
                            for k in ("gate", "up", "down", "score")}
    return params


def assert_share_follows(program, ref, params, w, x):
    """``program(params, x)`` and every gradient of it against the masked
    dense ``ref(w, x)`` of held experts 4..7, float32 against float32."""
    assert worst(jax.jit(program)(params, x), jax.jit(ref)(w, x)) < 1e-5
    loss = lambda fn: lambda p, x: jnp.sum(jnp.sin(fn(p, x)))
    gp, gx = jax.jit(jax.grad(loss(program), argnums=(0, 1)))(params, x)
    rw, rx = jax.jit(jax.grad(loss(ref), argnums=(0, 1)))(w, x)
    assert rel(gx, rx) < 1e-4
    assert rel(gp["router"], rw["router"]) < 1e-4
    for name in ("gate", "up", "down"):
        full = rw[f"experts_{name}"].reshape(
            16, *gp["experts"][name].shape[1:])[4:8]
        assert rel(gp["experts"][name], full) < 1e-4, name


def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    m, w, x = expert_layer()
    whole = jax.jit(lambda x, w: arch._experts(x, w, m=m, mode="f32"))(x, w)

    def share(first, shared):
        # (``first_expert`` is a Python number to the router's checks: a
        # program a share)
        return jax.jit(lambda p, x: moe.expert_share(
            p, x, n_experts=16, held=4, first_expert=first, k=m["top_k"]))(
                share_of(m, w, first, 4, shared=shared), x)

    parts = [share(first, first == 0)[0] for first in range(0, 16, 4)]
    assert worst(sum(parts), whole) < 1e-5
    # a share alone is the reference's share: the absent experts' part is
    # left out in both, and the shared expert is every member's
    alone = jax.jit(lambda x, w: arch._experts(
        x, w, m=m, mode="f32", first=8, held=4))(x, w)
    got, counts, _, _ = share(8, True)
    assert worst(got, alone) < 1e-5
    picks, _ = jax.jit(lambda x, w: arch.route(x, w["router"], m=m))(x, w)
    np.testing.assert_array_equal(
        counts, [(np.asarray(picks) == e).sum() for e in range(8, 12)])


@pytest.mark.parametrize("block", [8192, 256])
@pytest.mark.parametrize("rigged", ["one_expert", "all_held"])
def test_rigged_imbalance_drops_nothing(rigged, block):
    """1,024 tokens x top 4 = 4,096 assignments, all of them through the
    layer's buffer in one block, and in four blocks of 256 tokens.
    ``one_expert``: every token picks held expert 5 (1,024 rows there, the
    most one expert can get); ``all_held``: every token's four picks are
    the four held experts, the full bound.  There is no capacity and no
    buffer to outgrow: the result and the gradients are those of the
    masked dense reference."""
    m, w, x = expert_layer(tokens=1024)
    bias = np.zeros(16, np.float32)
    bias[[5] if rigged == "one_expert" else [4, 5, 6, 7]] = 50.0
    # a constant input feature that the router reads as a bias
    x = x.at[:, 0].set(1.0)
    w["router"] = w["router"].at[0].set(bias)
    params = share_of(m, w, 4, 4, shared=False)

    def program(params, x):
        return moe.expert_share(params, x, n_experts=16, held=4,
                                first_expert=4, k=4, block_tokens=block)

    def ref(w, x):
        return arch._experts(x, w, m=m, mode="f32", first=4, held=4,
                             shared=False)

    _, counts, _, _ = jax.jit(program)(params, x)
    if rigged == "all_held":
        np.testing.assert_array_equal(counts, [1024] * 4)
    else:
        assert int(counts[1]) == 1024
    assert_share_follows(lambda *a: program(*a)[0], ref, params, w, x)


@pytest.mark.parametrize("block", [8192, 256])
@pytest.mark.parametrize("k", [10, 1])
def test_any_top_k_adds_up_each_tokens_held_rows(k, block):
    """The router as seeded, so a token's picks are held and absent side by
    side, at the benchmark cell's ``k = 10`` (which a ``[tokens, k, d]``
    layout would pad to sixteen) and at ``k = 1``, in one block and in
    four: the result and every gradient are the masked dense
    reference's."""
    m, w, x = expert_layer(tokens=1024)
    params = share_of(m, w, 4, 4, shared=False)

    def program(params, x):
        return moe.expert_share(params, x, n_experts=16, held=4,
                                first_expert=4, k=k, block_tokens=block)[0]

    def ref(w, x):
        y = arch._experts(x, w, m=dict(m, top_k=k), mode="f32", first=4,
                          held=4, shared=False)
        if k == 1:
            # a lone pick is gated by its raw probability (``moe.route``);
            # the reference renormalises it to 1
            y = y * jnp.max(jax.nn.softmax(x @ w["router"]), axis=-1,
                            keepdims=True)
        return y

    picks, _ = jax.jit(lambda x, w: arch.route(
        x, w["router"], m=dict(m, top_k=k)))(x, w)
    here = (np.asarray(picks) >= 4) & (np.asarray(picks) < 8)
    assert here.any() and not here.all()
    assert_share_follows(program, ref, params, w, x)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for inner in (param if isinstance(param, (tuple, list))
                          else [param]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _avals(jaxpr):
    """Every value of a jaxpr and of the jaxprs nested in its equations."""
    for eqn in _equations(jaxpr):
        yield from (v.aval for v in (*eqn.invars, *eqn.outvars))


def test_the_combine_builds_no_token_major_tensor():
    """A block of ``_held_experts`` forward and backward: each token's ``k``
    rows come back as ``k`` slabs ``[k, tokens, d]``; no ``[tokens, k, d]``
    value in any dtype, whose ``k`` the TPU's ``(8, 128)`` tile would pad."""
    t, k, d, width, held = 24, 10, 16, 8, 4
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    experts = {"gate": jax.random.normal(key[0], (held, d, width)),
               "up": jax.random.normal(key[1], (held, d, width)),
               "down": jax.random.normal(key[2], (held, width, d))}
    x = jax.random.normal(key[3], (t, d))
    weights = jnp.full((t, k), 1.0 / k)
    local = jax.random.randint(key[4], (t, k), 0, held + 1)

    def block(experts, x, weights, dy):
        y, pull = jax.vjp(
            lambda *a: moe._held_experts(*a, local, held, moe.gated_ffn),
            experts, x, weights)
        return y, pull(dy)

    shapes = {a.shape for a in _avals(
        jax.make_jaxpr(block)(experts, x, weights, x).jaxpr)
        if hasattr(a, "shape")}
    assert (k, t, d) in shapes
    assert (t, k, d) not in shapes


def _poison_the_rows_behind_the_last_group(monkeypatch):
    """``lax.ragged_dot`` as the chip leaves it: the rows behind its last
    group are NaN in its result and in its cotangents."""
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def poison_cotangent(x, live):
        return x

    poison_cotangent.defvjp(
        lambda x, live: (x, live),
        lambda live, g: (jnp.where(live, g, jnp.nan), None))

    def poisoned(lhs, rhs, group_sizes, **kw):
        live = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
        out = real(poison_cotangent(lhs, live), rhs, group_sizes, **kw)
        return jnp.where(live, out, jnp.nan)

    monkeypatch.setattr(moe.lax, "ragged_dot", poisoned)


def test_rows_the_grouped_products_leave_undefined_reach_nothing(
        monkeypatch):
    """On the chip a grouped product's rows behind its last group, and the
    same rows of its cotangents, hold whatever the buffer held (the CPU's
    reference lowering zeroes them, so no other test here sees it).  With
    those rows poisoned, forward and backward, the layer's result and
    every gradient are still the masked dense reference's."""
    _poison_the_rows_behind_the_last_group(monkeypatch)
    m, w, x = expert_layer(tokens=1024)
    params = share_of(m, w, 4, 4, shared=False)

    def program(params, x):
        return moe.expert_share(params, x, n_experts=16, held=4,
                                first_expert=4, k=4)[0]

    def ref(w, x):
        return arch._experts(x, w, m=m, mode="f32", first=4, held=4,
                             shared=False)

    assert_share_follows(program, ref, params, w, x)


#: a block of 64 tokens x top 6 = 384 assignments at 2 held experts, taken
#: through windows of 32 rows and strips of 4 (100 and 12 in the ``ragged``
#: loads: neither the bound's windows nor a window's strips come out even):
#: load -> (the block's picks from a generator, the windows the arrivals
#: fill, the strips they touch)
def _even(rng):
    # a quarter of a window arrives in the mean
    picks = np.where(rng.random((64, 6)) < 0.02, rng.integers(0, 2, (64, 6)),
                     2)
    return picks, 1, -(-int((picks < 2).sum()) // 4)


def _arrivals(n, first=None):
    """``n`` arrivals at random places, the first expert's ``first`` (half
    of them) ahead of the second's."""
    first = n // 2 if first is None else first

    def load(rng):
        picks = np.full(384, 2)
        picks[rng.permutation(384)[:n]] = [0] * first + [1] * (n - first)
        return picks.reshape(64, 6)
    return load


def _every_pick(rng):
    return rng.integers(0, 2, (64, 6))


WINDOW_LOADS = {
    "even": _even,
    "no_arrival": lambda rng: (np.full((64, 6), 2), 0, 0),
    "every_pick_held": lambda rng: (_every_pick(rng), 12, 96),
    # 33 arrivals, one row past the first window's edge; the second
    # expert's run of 13 lies over the edge
    "straddle": lambda rng: (_arrivals(33, 20)(rng), 2, 9),
    # three whole windows of nine strips (the ninth starts early, at row
    # 88 for its rows 96..99), and a last window that starts at row 284
    # for its rows 300..383: strips 1..8, strip 1 from its fifth row on
    "ragged_bound": lambda rng: (_every_pick(rng), 4, 35),
    # the last arrival inside a strip, on a strip's edge, and in the last
    # strip of a window
    "ends_inside_a_strip": lambda rng: (_arrivals(6)(rng), 1, 2),
    "ends_on_a_strips_edge": lambda rng: (_arrivals(8)(rng), 1, 2),
    "ends_in_the_last_strip": lambda rng: (_arrivals(30)(rng), 1, 8),
    "second_window_ends_on_an_edge": lambda rng: (_arrivals(44)(rng), 2, 11),
    # the overlapping last window's rows 300..329: strips 1..3 of it, and
    # none of the strips before ``lo`` a second time
    "ragged_last_window_in_part": lambda rng: (_arrivals(330)(rng), 4, 30),
    # the overlapping last window's first own row alone
    "ragged_one_row_past_the_third_window":
        lambda rng: (_arrivals(301)(rng), 4, 28),
}
# the same loads with the rows behind the last arrival poisoned
WINDOW_LOADS.update({f"{load}_poisoned": WINDOW_LOADS[load] for load in (
    "even", "straddle", "ends_inside_a_strip", "ends_in_the_last_strip",
    "ragged_last_window_in_part")})


def _strips_by_row(arrived, bound, window):
    """The strips the loops scatter, counted a row at a time: every arrival
    lies in one window (the last one starts early) and there in one strip
    of ``window // 8`` rows."""
    strip = window // 8
    touched = set()
    for row in range(arrived):
        i = row // window
        touched.add((i, (row - min(i * window, bound - window)) // strip))
    return len(touched)


@pytest.mark.parametrize("expert", [names.RELU2, names.GATED_SILU])
@pytest.mark.parametrize("load", list(WINDOW_LOADS))
def test_windows_give_the_one_buffers_block_and_the_dense_sum(
        load, expert, monkeypatch):
    """A block of ``_held_experts_windowed``, forward and all four
    cotangents (the experts' projections, the tokens, the weights), against
    the one buffer of the bound and against the dense sum over each token's
    picks: one window at an even load; no trip, zeros out and zero
    gradients where nothing arrived; all twelve windows where every pick is
    held (dropless); a run that lies over a window's edge; the rows behind
    the last arrival poisoned with NaN in the window's buffer and in its
    cotangents; a bound that is no multiple of the window (the last window
    overlaps the one before it, and the strips before its own rows are not
    scattered twice); a window that is no multiple of its strips; a last
    arrival inside a strip, on a strip's edge and in a window's last strip.
    The strips the layer counts are the ones the arrivals lie in."""
    t, k, d, width, held = 64, 6, 16, 8, 2
    window = 100 if load.startswith("ragged") else 32
    picks, windows, strips = WINDOW_LOADS[load](np.random.default_rng(0))
    local = jnp.asarray(picks, jnp.int32)
    arrived = int((picks < held).sum())
    assert -(-arrived // window) == windows
    assert moe.share_strip(window) == window // 8
    assert _strips_by_row(arrived, t * k, window) == strips
    first, end = moe._window_run(jnp.arange(-(-t * k // window)), arrived,
                                 t * k, window)[2:]
    assert int(jnp.sum(end - first)) == strips
    if load.startswith("even"):
        assert 0 < arrived < window // 2
    key = jax.random.split(jax.random.PRNGKey(1), 6)
    expert_fn = moe.EXPERT_FNS[expert]
    experts = {name: jax.random.normal(
        key[i], (held, width, d) if name == "down" else (held, d, width))
        for i, name in enumerate(moe.EXPERT_LEAVES[expert])}
    x = jax.random.normal(key[3], (t, d))
    weights = jax.random.uniform(key[4], (t, k))
    dy = jax.random.normal(key[5], (t, d))

    def dense(experts, x, weights):
        every = jax.vmap(expert_fn, in_axes=(0, None))(experts, x)
        rows = every[jnp.minimum(local, held - 1), jnp.arange(t)[:, None]]
        return jnp.sum(jnp.where((local < held)[..., None],
                                 weights[..., None] * rows, 0.0), axis=1)

    def one_buffer(*a):
        return moe._held_experts(*a, local, held, expert_fn)

    def windowed(*a):
        return moe._held_experts_windowed(*a, local, held, expert_fn, window)

    def with_cotangents(fn):
        """``fn``'s block and the four cotangents of ``dy``, compiled."""
        def both(experts, x, weights, dy):
            y, pull = jax.vjp(fn, experts, x, weights)
            return y, pull(dy)
        return jax.jit(both)(experts, x, weights, dy)

    if load.endswith("poisoned"):
        _poison_the_rows_behind_the_last_group(monkeypatch)
    got, grads = with_cotangents(windowed)
    assert got.dtype == jnp.float32 and got.shape == (t, d)
    for other in (one_buffer, dense):
        want, wanted = with_cotangents(other)
        assert worst(got, want) < 1e-5 if arrived else not want.any()
        for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(wanted)):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.isfinite(np.asarray(g)).all()
            assert rel(g, w) < 1e-5 if arrived else not np.asarray(g).any()
    if not arrived:
        assert not np.asarray(got).any()


@pytest.mark.parametrize("expert, products", [(names.RELU2, 2),
                                              (names.GATED_SILU, 3)])
def test_a_trip_scatters_strips_and_its_products_take_the_window(
        expert, products):
    """The windowed block's jaxpr, forward and with its backward rule: every
    scatter-add carries a strip's rows (12 of the window's 100) and none the
    window's, and the grouped products are as many equations as before
    there were strips, one a projection forward, and behind the forward one
    again and two transposes a projection: the strips add no call site."""
    t, k, d, width, held, window = 64, 6, 16, 8, 2, 100
    experts = {name: jnp.ones(
        (held, width, d) if name == "down" else (held, d, width))
        for name in moe.EXPERT_LEAVES[expert]}
    x, weights = jnp.ones((t, d)), jnp.ones((t, k))
    local = jnp.zeros((t, k), jnp.int32)

    def windowed(*a):
        return moe._held_experts_windowed(*a, local, held,
                                          moe.EXPERT_FNS[expert], window)

    def both(experts, x, weights, dy):
        y, pull = jax.vjp(windowed, experts, x, weights)
        return y, pull(dy)

    for program, args, passes in ((windowed, (experts, x, weights), 1),
                                  (both, (experts, x, weights, x), 4)):
        eqns = list(_equations(jax.make_jaxpr(program)(*args).jaxpr))
        updates = [eqn.invars[2].aval.shape for eqn in eqns
                   if eqn.primitive.name == "scatter-add"]
        assert sorted(updates) == sorted(
            [(12, d)] if passes == 1 else [(12,), (12, d), (12, d)])
        assert sum(eqn.primitive.name.startswith("ragged_dot")
                   for eqn in eqns) == passes * products
        # a trip's loop over its strips inside the loop over the windows
        assert sum(eqn.primitive.name == "while" for eqn in eqns) == (
            2 if passes == 1 else 4)


def _primitives(jaxpr):
    """Every equation's primitive, of a jaxpr and of those nested in it."""
    return (eqn.primitive.name for eqn in _equations(jaxpr))


@pytest.mark.parametrize("held, windows", [(4, False), (1, True)],
                         ids=["a_16th_held", "a_64th_held"])
def test_a_share_of_a_32nd_or_less_takes_windows_and_no_other(held, windows):
    """512 tokens x top 8 of 64 experts, forward and backward.  4 held (a
    16th): a window of eight even shares would be half the bound, so the
    layer keeps ONE buffer of the bound's 4,096 rows and no loop but
    ``lax.map``'s own over the blocks (a ``scan``), as before there were
    windows.  1 held (a 64th): windows of 512 rows in a ``while``, and no
    tensor of 4,096 rows by a row's width or an expert's."""
    t, k, d, width, n = 512, 8, 16, 24, 64
    assert moe.share_windows(t, k, held, n) == (
        (512, 8) if windows else (t * k, 1))
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {"router": jax.random.normal(key[0], (d, n)), "experts": {
        "gate": jax.random.normal(key[1], (held, d, width)),
        "up": jax.random.normal(key[2], (held, d, width)),
        "down": jax.random.normal(key[3], (held, width, d))}}
    x = jax.random.normal(key[4], (t, d))
    loss = lambda p, x: jnp.sum(jnp.sin(moe.expert_share(
        p, x, n_experts=n, held=held, first_expert=8, k=k)[0]))
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr
    loops = {p for p in _primitives(jaxpr) if p in ("while", "scan")}
    shapes = {a.shape for a in _avals(jaxpr) if hasattr(a, "shape")}
    if windows:
        assert loops == {"while", "scan"}
        assert {(512, d), (512, width)} <= shapes
        assert not {(t * k, d), (t * k, width), (k, t, d)} & shapes
    else:
        assert loops == {"scan"}
        assert {(t * k, d), (t * k, width), (k, t, d)} <= shapes


# ---------------------------------------------------------------------------
# what a rematerialised expert layer of one sublayer keeps


def _sizes(**arms) -> hybrid.HybridSizes:
    return hybrid.HybridSizes(
        d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, rotary_dim=4,
        linear_key_heads=1, linear_value_heads=2, linear_key_dim=8,
        linear_value_dim=8, ffn_width=48, n_experts=8, held=2, top_k=2,
        expert_width=16, shared_width=40, **arms)


ONE_EXPERT_LAYER = dict(one_sublayer=True, shared_scored=False)
ROUTERS_KEEPS = (names.ROUTER_LOGITS, names.ROUTER_PICKS)


@pytest.mark.parametrize("arms, keeps, columns", [
    (dict(ONE_EXPERT_LAYER, latent_width=24, expert_fn=names.RELU2),
     (names.EXPERT_OUT, *ROUTERS_KEEPS, names.LATENT_IN, names.SHARED_UP),
     24 + 16 + 8 + 24 + 40),
    (dict(ONE_EXPERT_LAYER, expert_fn=names.RELU2),
     (names.EXPERT_OUT, *ROUTERS_KEEPS, names.SHARED_UP), 32 + 16 + 8 + 40),
    (dict(ONE_EXPERT_LAYER, latent_width=24, expert_fn=names.GATED_SILU),
     (names.EXPERT_OUT, *ROUTERS_KEEPS, names.LATENT_IN, names.SHARED_GATE,
      names.SHARED_UP), 24 + 16 + 8 + 24 + 2 * 40),
    (dict(ONE_EXPERT_LAYER, expert_fn=names.GATED_SILU),
     (names.EXPERT_OUT, *ROUTERS_KEEPS, names.SHARED_GATE, names.SHARED_UP),
     32 + 16 + 8 + 2 * 40),
    # a scored shared expert is computed inside ``expert_share``, unnamed
    (dict(one_sublayer=True), (names.EXPERT_OUT, *ROUTERS_KEEPS),
     32 + 16 + 8),
    # the expert-share arm of a two-sublayer layer: what its router computed
    (dict(), (MIXER_OUT, *ROUTERS_KEEPS), 32 + 16 + 8),
    (dict(expert_fn=names.RELU2), (MIXER_OUT, *ROUTERS_KEEPS), 32 + 16 + 8),
    # and what was returned before for every other layer
    (dict(feed_forward=names.DENSE_FFN),
     (MIXER_OUT,) + names.DENSE_FFN_KEEPS, 32 + 2 * 48 + 32),
    (dict(one_sublayer=True, feed_forward=names.DENSE_FFN), (), 0),
], ids=["relu2_latent", "relu2", "gated_latent", "gated", "scored_shared",
        "two_sublayer_share_arm", "two_sublayer_share_arm_relu2",
        "dense_arm", "one_sublayer_mixers_only"])
def test_remat_keeps_by_the_layers_shape(arms, keeps, columns):
    """A one-sublayer expert layer keeps, beside its share's result, the
    outputs of the dense products its backward pass reads: the router's
    logits (float32: 8 experts are 16 bf16 columns) and its 2 picks and
    their scores (int32 and float32: 8 bf16 columns), ``latent_down``'s
    where there is a latent space, an unscored shared expert's first
    products'.  The expert-share arm of a two-sublayer layer keeps the
    router's two beside ``MIXER_OUT`` (since PR 44); every other layer
    keeps what it kept.  ``kept_bytes`` over 64 tokens in bf16, by hand."""
    z = _sizes(**arms)
    assert hybrid.remat_keeps(z) == keeps
    assert hybrid.kept_bytes(keeps, z, 64, jnp.bfloat16) == 64 * 2 * columns


def _dense_products(jaxpr) -> int:
    return sum(p == "dot_general" for p in _primitives(jaxpr))


@pytest.mark.parametrize("kind", [*hybrid.MIXERS, None], ids=str)
def test_a_delta_rule_layer_keeps_its_chunks_inverse_and_no_other(kind):
    """Since PR 48 a layer whose mixer scans by the delta rule (a decay a
    head or a channel) keeps ``DELTA_INVERSE`` behind whatever its
    feed-forward arm keeps: ``tokens x heads x 64 x 4`` bytes, float32
    whatever the compute dtype (2 heads: 256 bf16 columns).  Since PR 49 a
    decay a channel keeps ``names.KDA_KEEPS`` before it, what its mixer
    computes on the way to the scan: ``heads x 8`` columns each here, in
    the compute dtype.  Every other kind of layer, and a call that names
    no kind, keeps what it kept: a decay a head the inverse alone."""
    delta = kind in (names.LINEAR, names.CHANNEL_LINEAR)
    assert delta == (kind in hybrid.DELTA_RULE_KINDS)
    channel = names.KDA_KEEPS if kind == names.CHANNEL_LINEAR else ()
    for arms in (dict(), dict(feed_forward=names.DENSE_FFN),
                 dict(feed_forward=names.DENSE_FFN, ffn_products_kept=False),
                 dict(one_sublayer=True),
                 dict(one_sublayer=True, feed_forward=names.DENSE_FFN)):
        z = _sizes(**arms)
        keeps, arm = hybrid.remat_keeps(z, kind), hybrid.remat_keeps(z)
        assert keeps == arm + channel + (
            (names.DELTA_INVERSE,) if delta else ())
        for dtype in (jnp.bfloat16, jnp.float32):
            assert hybrid.kept_bytes(keeps, z, 64, dtype) == hybrid.kept_bytes(
                arm, z, 64, dtype) + (64 * 2 * 64 * 4 if delta else 0) + (
                    len(channel) * 64 * 2 * 8 * jnp.dtype(dtype).itemsize)


def _delta_rule_call(decay: str, beta_max: float, dk: int, dv: int):
    """``(args, module class)``: float32 operands over two chunks of two
    heads, and a module that is the scan alone, for ``remat_module``."""
    import flax.linen as nn

    q, k, v, g, beta = scan_inputs(2, jnp.float32, heads=2, dk=dk, dv=dv)
    if decay == names.CHANNEL:
        g = g[..., None] * jax.random.uniform(
            jax.random.PRNGKey(5), (dk,), minval=0.5, maxval=1.0)

    class Scan(nn.Module):
        @nn.compact
        def __call__(self, *args):
            return chunked_gated_delta_rule(*args, beta_max=beta_max)

    return [q, k, v, g, beta_max * beta], Scan


@pytest.mark.parametrize("kind, decay, beta_max, dk, dv, solved", [
    (names.LINEAR, "head", 1.0, 16, 16, 10),
    (names.LINEAR, "head", 2.0, 12, 24, 10),
    (names.CHANNEL_LINEAR, names.CHANNEL, 1.0, 16, 16, 11)],
    ids=["a_head_beta_1", "a_head_beta_2_unequal_widths", "a_channel"])
def test_a_rematerialised_delta_rule_solves_for_its_inverse_once(
        kind, decay, beta_max, dk, dv, solved):
    """The scan under ``remat_module(..., "nothing", remat_keeps(...))``: with
    ``DELTA_INVERSE`` among the names the gradient holds the inverse's ten
    products fewer (by halves from a base of 16 rows or of 4: two a level
    and two a doubling of the base's span, ten either way) than under the
    same policy without the name: the rematerialised forward no longer
    solves.  A decay a channel loses an eleventh, the product that makes
    ``A``: there ``A`` is that product masked and nothing else reads it,
    where a decay a head multiplies it by the decays, whose gradient wants
    it again.  The gradients are the same bits either way: the kept ``T`` is
    the float32 one the rematerialised forward computed."""
    from tpudist.models.transformer import remat_module

    args, scan = _delta_rule_call(decay, beta_max, dk, dv)
    keeps = hybrid.remat_keeps(_sizes(), kind)
    assert keeps[-1] == names.DELTA_INVERSE

    def gradient(keep):
        module = remat_module(scan, "nothing", keep)()
        return jax.grad(lambda *a: jnp.sum(jnp.sin(module.apply({}, *a))),
                        argnums=(0, 1, 2, 3, 4))

    kept, solved_again = gradient(keeps), gradient(keeps[:-1])
    count = lambda fn: _dense_products(jax.make_jaxpr(fn)(*args).jaxpr)
    assert count(solved_again) - count(kept) == solved
    assert str(jax.make_jaxpr(kept)(*args)).count(
        f"name={names.DELTA_INVERSE}") >= 1
    for name, got, want in zip("q k v g beta".split(), jax.jit(kept)(*args),
                               jax.jit(solved_again)(*args)):
        assert bool(jnp.any(want != 0)) and bool(jnp.all(got == want)), name


@pytest.mark.parametrize("decay, beta_max", [
    ("head", 1.0), ("head", 2.0), (names.CHANNEL, 1.0)],
    ids=["a_head_beta_1", "a_head_beta_2", "a_channel"])
def test_outside_a_policy_the_inverses_name_is_an_identity(decay, beta_max,
                                                           monkeypatch):
    """No policy names it: the call lowers to the text it lowers to with the
    name taken out (a name lowers to nothing), forward and gradient, and
    under a rematerialisation that names nothing the inverse is solved for
    again as it was."""
    from tpudist.ops import gated_delta

    args, _ = _delta_rule_call(decay, beta_max, 16, 16)

    def read():
        # (new functions a reading: nothing traced before is found again)
        call = lambda *a: chunked_gated_delta_rule(*a, beta_max=beta_max)
        loss = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                   argnums=(0, 1, 2, 3, 4))
        again = loss(jax.checkpoint(call))
        return ([jax.jit(fn).lower(*args).as_text()
                 for fn in (call, loss(call), again)],
                _dense_products(jax.make_jaxpr(again)(*args).jaxpr))

    named = read()
    monkeypatch.setattr(gated_delta, "checkpoint_name", lambda x, name: x)
    assert named == read()


@pytest.mark.parametrize("arms", [
    dict(expert_fn=names.GATED_SILU),
    dict(expert_fn=names.GATED_SILU, latent_width=24),
    dict(expert_fn=names.RELU2)], ids=["gated", "gated_latent", "relu2"])
def test_a_rematerialised_expert_layer_runs_its_dense_products_once(
        arms, monkeypatch):
    """Two expert layers of one sublayer behind an attention layer, remat
    ``nothing``: the gradient holds one ``dot_general`` fewer a layer for
    each name kept than the program that keeps ``EXPERT_OUT`` alone, and
    its bits are those of that program and of the program without remat (a
    kept tensor is the one the rematerialised forward computes).  Bits in
    float32: in bf16 the CPU's compiler widens the elementwise chains
    inside a fusion, so there even remat alone, with nothing kept, moves
    the last bit."""
    z = _sizes(**ONE_EXPERT_LAYER, **arms)
    kinds = (names.FULL, names.EXPERT_LAYER, names.EXPERT_LAYER)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, 64)

    def program(remat):
        module = hybrid.HybridLM(vocab=64, layer_types=kinds, sizes=z,
                                 remat=remat)
        return lambda p: lm_loss(module.apply(p, tokens), tokens)

    params = jax.jit(hybrid.HybridLM(
        vocab=64, layer_types=kinds, sizes=z).init)(jax.random.PRNGKey(0),
                                                    tokens)
    keeps = hybrid.remat_keeps(z)
    # (the router's picks are no product: the test of the sorts counts them)
    new = [k for k in keeps
           if k not in (names.EXPERT_OUT, names.ROUTER_PICKS)]
    assert len(new) == (2 if z.expert_fn == names.RELU2 else 3) + bool(
        z.latent_width)
    jaxpr = jax.make_jaxpr(jax.grad(program(True)))(params)
    for name in new:
        assert str(jaxpr).count(f"name={name}") >= 2
    kept = jax.jit(jax.grad(program(True)))(params)
    plain = jax.jit(jax.grad(program(False)))(params)
    monkeypatch.setattr(hybrid, "remat_keeps",
                        lambda z, kind: (names.EXPERT_OUT,))
    before = _dense_products(
        jax.make_jaxpr(jax.grad(program(True)))(params).jaxpr)
    assert before - _dense_products(jaxpr.jaxpr) == 2 * len(new)
    recomputed = jax.jit(jax.grad(program(True)))(params)
    for name in new:
        # one at a time: each name is one product a layer
        monkeypatch.setattr(
            hybrid, "remat_keeps",
            lambda z, kind, name=name: (names.EXPERT_OUT, name))
        one = jax.make_jaxpr(jax.grad(program(True)))(params)
        assert _dense_products(one.jaxpr) == before - 2
    for got, again, want in zip(*(jax.tree.leaves(t) for t in (
            kept, recomputed, plain))):
        assert got.dtype == want.dtype
        assert bool(jnp.all(got == again)) and bool(jnp.all(got == want))


def _router_before_pr44(logits, k, choice_bias, scale, scoring):
    """The yardstick: ``route``'s scorings as they stood before PR 44,
    ``lax.top_k`` and, for the sigmoids, ``take_along_axis`` behind it."""
    if scoring == names.SOFTMAX:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, picks = jax.lax.top_k(probs, k)
        return picks, weights / jnp.sum(weights, axis=-1,
                                        keepdims=True), probs
    scores = jax.nn.sigmoid(logits)
    chosen = scores if choice_bias is None else (
        scores + jax.lax.stop_gradient(choice_bias.astype(jnp.float32)))
    _, picks = jax.lax.top_k(chosen, k)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return picks, weights * scale, scores


def _bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("scoring", [names.SIGMOID, names.SIGMOID_BIAS,
                                     names.SOFTMAX])
@pytest.mark.parametrize("tokens, n, k", [(96, 512, 22), (96, 256, 10),
                                          (16, 8, 3)],
                         ids=["22_of_512", "10_of_256", "3_of_8"])
def test_route_is_top_k_and_take_along_axis_bit_for_bit(tokens, n, k,
                                                        scoring):
    """The picks (ties to the lower index), their weights and the gradient
    with respect to the logits are those of ``lax.top_k`` +
    ``take_along_axis``, to the last bit, whether the scores ride the sort
    that picks them (a choice bias) or are ``top_k``'s own values (none).
    Logits and bias are rounded to halves and eighths so that scores tie
    within a row, biased and unbiased; the gradient comes through weights
    mixed with both signs, and through the scores themselves."""
    key = jax.random.split(jax.random.PRNGKey(n + k), 3)
    logits = jnp.round(2 * jax.random.normal(key[0], (tokens, n))) / 2
    bias = (jnp.round(4 * jax.random.normal(key[1], (n,))) / 8
            if scoring == names.SIGMOID_BIAS else None)
    scale = 1.0 if scoring == names.SOFTMAX else 2.5
    mix = jax.random.normal(key[2], (tokens, k))
    ties = jnp.sort(logits if bias is None
                    else jax.nn.sigmoid(logits) + bias, axis=-1)
    assert bool(jnp.any(ties[:, 1:] == ties[:, :-1]))

    def now(x):
        r = moe.route(x, n_experts=n, k=k, scoring=scoring,
                      choice_bias=bias, scale=scale)
        return jnp.sum(r.weights * mix) + 1e-3 * jnp.sum(r.probs ** 2), (
            r.expert_idx, r.weights)

    def before(x):
        picks, weights, probs = _router_before_pr44(x, k, bias, scale,
                                                    scoring)
        return jnp.sum(weights * mix) + 1e-3 * jnp.sum(probs ** 2), (
            picks, weights)

    # (op by op, both: compiled, each program fuses its own way and one
    # gradient entry in 128 reads another last bit, 3.2e-10 of it, PR 46;
    # the bits are the claim, so the dispatch stays the one that gives them)
    (_, (picks, weights)), grad = jax.value_and_grad(now, has_aux=True)(
        logits)
    (_, (want_picks, want_weights)), want_grad = jax.value_and_grad(
        before, has_aux=True)(logits)
    np.testing.assert_array_equal(picks, want_picks)
    np.testing.assert_array_equal(_bits(weights), _bits(want_weights))
    np.testing.assert_array_equal(_bits(grad), _bits(want_grad))
    assert bool(jnp.any(grad != 0))


def _sorts_over(jaxpr, n: int) -> int:
    """The ``sort`` and ``top_k`` equations of a jaxpr, nested ones too,
    whose operand is ``n`` wide along the sorted dimension (a ``top_k`` is a
    sort of the whole row on the chip)."""
    sorted_axis = {"sort": "dimension", "top_k": "axis"}
    return sum(
        eqn.invars[0].aval.shape[eqn.params[sorted_axis[name]]] == n
        for eqn in _equations(jaxpr)
        if (name := eqn.primitive.name) in sorted_axis)


@pytest.mark.parametrize("scoring", [names.SIGMOID_BIAS, names.SIGMOID,
                                     names.SOFTMAX])
@pytest.mark.parametrize("arms, kinds", [
    (ONE_EXPERT_LAYER, (names.FULL, names.EXPERT_LAYER, names.EXPERT_LAYER)),
    (dict(), (names.FULL, names.FULL))],
    ids=["one_sublayer", "two_sublayers"])
def test_a_rematerialised_expert_layer_sorts_its_scores_once(arms, kinds,
                                                            scoring,
                                                            monkeypatch):
    """Two expert layers behind (or inside) attention layers, remat
    ``nothing``: the gradient's jaxpr holds ONE sort over the router's 8
    scores a layer, as the program without remat does, because the layer
    keeps ``names.ROUTER_PICKS`` and the picks' gradient reads the NAMED
    picks; with the picks' name taken out of what the layer keeps the sort
    runs again in the rematerialised forward, twice a layer (counted in the
    jaxpr, before any chip run: PR 40's lesson (1)).  No gather picks the
    scores in either: the only tensors of ``[tokens, top_k]`` gathered are
    the dispatch's."""
    z = _sizes(scoring=scoring, **arms)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, 64)

    def sorts(remat):
        module = hybrid.HybridLM(vocab=64, layer_types=kinds, sizes=z,
                                 remat=remat)
        params = jax.eval_shape(module.init, jax.random.PRNGKey(0), tokens)
        return _sorts_over(jax.make_jaxpr(jax.grad(
            lambda p: lm_loss(module.apply(p, tokens), tokens)))(
                params).jaxpr, z.n_experts)

    assert names.ROUTER_PICKS in hybrid.remat_keeps(z)
    assert sorts(True) == sorts(False) == 2
    kept = hybrid.remat_keeps
    monkeypatch.setattr(hybrid, "remat_keeps", lambda z, kind: tuple(
        name for name in kept(z, kind) if name != names.ROUTER_PICKS))
    assert sorts(True) == 4


def test_the_capacity_arm_routes_by_the_same_router():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    routing = jax.jit(lambda x: moe.route(
        x, n_experts=8, k=2, held=2, first_expert=4))(logits)
    np.testing.assert_allclose(routing.weights.sum(-1), 1.0, rtol=1e-6)
    held_here = (routing.expert_idx >= 4) & (routing.expert_idx < 6)
    np.testing.assert_array_equal(routing.local < 2, held_here)
    _, combine, _ = jax.jit(lambda x: moe._topk_dispatch(
        x, 8, capacity=32, k=2))(logits)
    # the capacity arm's combine weights are the router's, expert by expert
    by_expert = np.zeros((32, 8), np.float32)
    np.put_along_axis(by_expert, np.asarray(routing.expert_idx),
                      np.asarray(routing.weights), axis=1)
    np.testing.assert_allclose(combine.sum(-1), by_expert, rtol=1e-6)
    with pytest.raises(ValueError, match="not a run"):
        moe.route(logits, n_experts=8, k=2, held=4, first_expert=6)


# ---------------------------------------------------------------------------
# (e) the flash kernels at head_dim 256, 8 query heads a kv head (kernels in
# interpret mode, as ``tests/test_ops.py`` runs them: their time is the
# interpreter's, not dispatch, and they are called as they were)


@pytest.fixture(scope="module")
def gqa_256():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 8, 256, 256), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1, 256, 256), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1, 256, 256), jnp.float32)
    return q, k, v


def _blockwise(q, k, v):
    from tpudist.ops import blockwise_attention

    group = q.shape[1] // k.shape[1]
    return blockwise_attention(q, jnp.repeat(k, group, axis=1),
                               jnp.repeat(v, group, axis=1), causal=True,
                               block_k=128)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_at_head_dim_256_grouped_8_to_1(gqa_256, grad):
    from tpudist.ops import flash_attention

    flash = lambda q, k, v: flash_attention(q, k, v, True, 128, 128, True)
    if not grad:
        assert worst(flash(*gqa_256), _blockwise(*gqa_256)) < 1e-5
        return
    through = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                  argnums=(0, 1, 2))(*gqa_256)
    for got, want in zip(through(flash), through(_blockwise)):
        assert worst(got, want) < 1e-4


def test_packed_flash_at_head_dim_256_grouped_8_to_1(gqa_256):
    from tpudist.ops import flash_attention_packed

    q, k, v = gqa_256
    flat = lambda t: t.transpose(0, 2, 1, 3).reshape(1, 256, -1)
    qkv = jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1)
    got = flash_attention_packed(qkv, 8, 1, True, 128, 128, True)
    assert worst(got, flat(_blockwise(q, k, v))) < 1e-5
