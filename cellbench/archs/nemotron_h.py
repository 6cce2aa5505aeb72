"""``model_type`` ``nemotron_h``: a decoder whose layers are ONE sublayer
each, ``y = x + Sub(RMSNorm(x))``, by the letters of its
``hybrid_override_pattern``: ``M`` a Mamba-2 state-space mixer, ``E`` routed
experts in a latent space beside a shared expert (LatentMoE), ``*`` plain
grouped-query softmax attention; by the keys of the model's own
``config.json`` (``mamba_num_heads``, ``n_groups``, ``moe_latent_size``,
``n_routed_experts``, ``routed_scaling_factor``, ...).

**A share.**  The configuration file may hold a chip's share of a stated
deployment in which the chips that share a layer hold its heads and its
experts between them: ``mamba_num_heads`` / ``n_groups`` and
``num_attention_heads`` / ``num_key_value_heads`` are then the heads and
groups HELD here, ``n_routed_experts`` the experts held
(``as_run.first_expert`` on) while the router keeps its published width
(``as_run.router_experts``), its ``num_experts_per_tok`` picks, its
renormalisation over all of them and its scale; ``vocab_size`` is the slice
of the vocabulary held; the published counts stand under ``published``.
Router, latent projections and shared expert are whole.  What the absent
heads would add to a mixer's output and the absent experts to the routed
sum is left out, here as in the program, and that partial result goes on.
No statistic crosses the cut: the gated norm's mean square runs over one
GROUP's channels.

The reference is written from the equations (float32 ``jax.numpy``),
importing nothing of the program:

- ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w`` (plain, ``w`` round 1);
  embedding, final norm, untied head, mean next-token cross entropy;
- ``M``: ``[z, xBC, dt] = W_in u``; ``xBC = SiLU(conv(xBC) + b)``
  (depthwise, causal, left-padded), split into ``x [heads, p]``, ``B, C
  [groups, n]`` (head ``j`` reads group ``j // (heads / groups)``);
  ``dt = softplus(dt + dt_bias)``, ``a_t = exp(-exp(A_log) * dt_t)``;
  ``h_t = a_t h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``,
  **one position at a time** (the program computes it in chunks);
  ``W_out (RMSNorm_group(y * SiLU(z)) * w)``;
- ``E``: scores ``s = sigmoid(W_r u)`` over all the router's experts; the
  picks are the ``k`` largest of ``s + b`` (``choice_bias``, a buffer that
  takes no gradient), their weights ``scale * s_i / (sum of the picked s +
  1e-20)`` (``DeepseekV3TopkRouter`` with one group); routed part
  ``W_up_lat (sum_i w_i E_i(W_down_lat u))`` over the picks held here,
  ``E_i(v) = W2_i relu(W1_i v)^2``; plus the shared expert
  ``S2 relu(S1 u)^2`` on the full width, unscored;
- ``*``: causal softmax at ``dh^-0.5``, each key/value head serving
  ``heads / kv`` query heads; no bias, gate, q/k norm or rotary positions.

``choice_bias`` is a buffer: ``init_weights`` seeds it (``layer_<i>.
choice_bias``) and ``program_tree`` hands it on, but it is no entry of
``weight_shapes`` / ``leaf_names`` (not a parameter, not compared); its
gradient is zero, so Adam leaves it where it was, here and in the program.

**Memory** is what shapes the code (weights + Adam + one gradient are
16 bytes a parameter, 11.21 GB of the chip's 16.9 at the real size, before
any activation): one entry a tensor (``STACKED = ()``), rows one at a time
(``lax.map``), every layer and every piece of a layer under
``jax.checkpoint``; attention a head and a block of ``QUERY_BLOCK`` queries
at a time (``lax.map`` over heads); the shared expert and the loss a block
of positions at a time (``lax.map``), the held experts one after another
(``lax.scan``: loops, not unrolled copies, which the compiler would take
minutes over); the recurrence as a two-level
``lax.scan`` whose inner level (``SCAN_CHUNK`` positions) is rematerialised,
so that one state a chunk is kept and not one a position.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from cellbench import flops
from cellbench.reference import matmul, seed_key, t_last

#: every tensor is an entry of its own; none is stacked over layers
STACKED = ()
#: the pattern's letters
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
#: positions of the recurrence's rematerialised inner scan; queries of one
#: attention block; positions of a block of the shared expert and of the loss
SCAN_CHUNK = 64
QUERY_BLOCK = 2048
FFN_BLOCK = 2048
LOSS_BLOCK = 1024


def dims(config: dict) -> dict:
    """Sizes under short names.  ``layers`` is 1 for the runner's count of
    custom calls (``custom_calls_per_layer`` is then the step's total: a
    pattern's layers do not run the same kernels); ``depth`` is the number
    of layers.  Head, group and expert counts are those HELD; ``*_all``
    the published ones."""
    run = config["as_run"]
    whole = config.get("published", {})
    pattern = config["hybrid_override_pattern"]
    depth = config["num_hidden_layers"]
    if len(pattern) != depth or set(pattern) - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError(f"hybrid_override_pattern {pattern!r} is not "
                         f"{depth} letters of M, E and *")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("group-limited routing is not written down here: "
                         "n_group and topk_group are 1")
    if config["mlp_hidden_act"] != "relu2" or not config["norm_topk_prob"]:
        raise ValueError("the experts are relu2 and the picks' weights "
                         "renormalised")
    if config.get("num_nextn_predict_layers"):
        raise ValueError("no multi-token-prediction module is written down "
                         "here (departures)")
    mh, mg = config["mamba_num_heads"], config["n_groups"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return dict(
        vocab=config["vocab_size"], seq=config["max_position_embeddings"],
        d=config["hidden_size"], layers=1, depth=depth, kinds=tuple(pattern),
        eps=config["layer_norm_epsilon"],
        heads=heads, kv=kv, dh=config["head_dim"],
        heads_all=whole.get("num_attention_heads", heads),
        mh=mh, mg=mg, mp=config["mamba_head_dim"],
        mn=config["ssm_state_size"], conv=config["conv_kernel"],
        chunk=config["chunk_size"],
        mh_all=whole.get("mamba_num_heads", mh),
        mg_all=whole.get("n_groups", mg),
        held=config["n_routed_experts"], experts=run["router_experts"],
        first=run["first_expert"], top_k=config["num_experts_per_tok"],
        latent=config["moe_latent_size"],
        width=config["moe_intermediate_size"],
        shared=config["moe_shared_expert_intermediate_size"],
        scale=float(config["routed_scaling_factor"]),
        router_trained=run["router_trained"])


def _layer_shapes(m: dict, kind: str) -> dict:
    d = m["d"]
    if kind == MAMBA:
        inner, bc = m["mh"] * m["mp"], m["mg"] * m["mn"]
        return {"norm": (d,), "in_proj": (d, 2 * inner + 2 * bc + m["mh"]),
                "conv": (inner + 2 * bc, m["conv"]),
                "conv_bias": (inner + 2 * bc,), "A_log": (m["mh"],),
                "D": (m["mh"],), "dt_bias": (m["mh"],),
                "gated_norm": (inner,), "out_proj": (inner, d)}
    if kind == ATTENTION:
        h, kv, dh = m["heads"], m["kv"], m["dh"]
        return {"norm": (d,), "q_proj": (d, h * dh), "k_proj": (d, kv * dh),
                "v_proj": (d, kv * dh), "o_proj": (h * dh, d)}
    e, lat, w, sw = m["held"], m["latent"], m["width"], m["shared"]
    return {"norm": (d,), "router": (d, m["experts"]),
            "latent_down": (d, lat), "latent_up": (lat, d),
            # a layer's held experts as ONE two-axis tensor a projection
            "experts_up": (e * lat, w), "experts_down": (e * w, lat),
            "shared_up": (d, sw), "shared_down": (sw, d)}


def weight_shapes(config: dict) -> dict:
    m = dims(config)
    shapes = {"embed": (m["vocab"], m["d"]), "final_norm": (m["d"],),
              "head": (m["d"], m["vocab"])}
    for i, kind in enumerate(m["kinds"]):
        shapes.update({f"layer_{i}.{name}": shape
                       for name, shape in _layer_shapes(m, kind).items()})
    return shapes


def buffer_shapes(config: dict) -> dict:
    """What a layer holds that is no parameter: each expert layer's
    ``choice_bias`` over the router's experts."""
    m = dims(config)
    return {f"layer_{i}.choice_bias": (m["experts"],)
            for i, kind in enumerate(m["kinds"]) if kind == EXPERTS}


def leaf_names(config: dict) -> list:
    return sorted(weight_shapes(config))


def init_weights(config: dict, seed_words) -> dict:
    """Seeded weights and buffers (``assumed`` in the configuration file):
    matrices normal(0, ``as_run.init_std``); norm weights, the gated norm's
    and ``D`` normal(1, ``norm_std``), so that one left out shows; the
    convolution's taps normal(0, 1 / sqrt(width)), its bias normal(0,
    ``conv_bias_std``); over the held heads ``A = exp(A_log)`` evenly
    spaced from ``A_min`` to ``A_max`` and ``softplus(dt_bias)`` spaced in
    ratio from the config's ``time_step_min`` to ``time_step_max``, both
    rising, so that a head's state fades by ``1 / e`` in between
    ``1 / (A_max * time_step_max)`` and ``1 / (A_min * time_step_min)``
    positions (0.6 to 1,000 at the published steps) and a wrong carried
    state shows; ``choice_bias`` normal(0, ``choice_bias_std``)."""
    run = config["as_run"]
    m = dims(config)
    key = seed_key(seed_words)
    shapes = {**weight_shapes(config), **buffer_shapes(config)}
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        leaf = name.rpartition(".")[2]
        draw = jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)
        if leaf == "A_log":
            out[name] = jnp.log(jnp.linspace(run["A_min"], run["A_max"],
                                             m["mh"], dtype=jnp.float32))
        elif leaf == "dt_bias":
            step = jnp.exp(jnp.linspace(
                math.log(config["time_step_min"]),
                math.log(config["time_step_max"]), m["mh"],
                dtype=jnp.float32))
            out[name] = step + jnp.log(-jnp.expm1(-step))   # softplus^-1
        elif leaf.endswith("norm") or leaf == "D":
            out[name] = 1.0 + run["norm_std"] * draw
        elif leaf == "conv":
            out[name] = draw / math.sqrt(m["conv"])
        elif leaf == "conv_bias":
            out[name] = run["conv_bias_std"] * draw
        elif leaf == "choice_bias":
            out[name] = run["choice_bias_std"] * draw
        else:
            out[name] = run["init_std"] * draw
    return out


# ---------------------------------------------------------------------------
# the reference: one row at a time


def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _blocks(n: int, block: int) -> list:
    return [(i, min(i + block, n)) for i in range(0, n, block)]


def _by_blocks(fn, x, block: int):
    """``fn`` over ``x [s, ...]`` a block of positions at a time (one after
    another; whole where the blocks do not divide ``s``)."""
    s = x.shape[0]
    if s % block:
        return fn(x)
    out = lax.map(fn, x.reshape(s // block, block, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def state_space(x, dt, a_log, b, c, d):
    """The recurrence as written: ``x [s, heads, p]``, ``dt [s, heads]``,
    ``a_log, d [heads]``, ``b, c [s, groups, n]`` -> ``y [s, heads, p]``.
    Elementwise float32 (no matmul unit, so no precision to state)."""
    s, heads, p = x.shape
    r = heads // b.shape[1]
    chunk = SCAN_CHUNK if s % SCAN_CHUNK == 0 else 1

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(t, r, axis=0) for t in (b_t, c_t))
        state = (state * jnp.exp(-jnp.exp(a_log) * dt_t)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + (
            d[:, None] * x_t)

    @jax.checkpoint
    def inner(state, xs):
        return lax.scan(step, state, xs)

    by_chunk = lambda t: t.reshape(s // chunk, chunk, *t.shape[1:])
    state0 = jnp.zeros((heads, p, b.shape[-1]), jnp.float32)
    _, y = lax.scan(inner, state0, tuple(map(by_chunk, (x, dt, b, c))))
    return y.reshape(s, heads, p)


def _mamba(x, w, *, m, mode, carry=True):
    """``x [s, d]`` (normed) -> ``[s, d]``.  ``carry=False`` is the planted
    fault of the tests (the state zeroed at every ``chunk_size``)."""
    s = x.shape[0]
    h, g, p, n = m["mh"], m["mg"], m["mp"], m["mn"]
    inner, bc = h * p, g * n

    @jax.checkpoint
    def project(x, w_in, w_conv, b_conv):
        z, mixed, dt = jnp.split(matmul(x, w_in, mode),
                                 [inner, 2 * inner + 2 * bc], axis=-1)
        width = w_conv.shape[1]
        padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(padded[j:j + s] * w_conv[:, j]
                                for j in range(width)) + b_conv)
        u, b, c = jnp.split(mixed, [inner, inner + bc], axis=-1)
        return (z, u.reshape(s, h, p), b.reshape(s, g, n),
                c.reshape(s, g, n), dt)

    z, u, b, c, dt = project(x, w["in_proj"], w["conv"], w["conv_bias"])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    scan = functools.partial(state_space, a_log=w["A_log"], d=w["D"])
    if carry:
        y = scan(u, dt, b=b, c=c)
    else:
        cut = lambda t: t.reshape(s // m["chunk"], m["chunk"], *t.shape[1:])
        y = jax.vmap(lambda u, dt, b, c: scan(u, dt, b=b, c=c))(
            *map(cut, (u, dt, b, c))).reshape(s, h, p)

    @jax.checkpoint
    def close(y, z, w_n, w_out):
        y = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, g, inner // g)
        return matmul(_rms(y, m["eps"]).reshape(s, inner) * w_n, w_out, mode)

    return close(y, z, w["gated_norm"], w["out_proj"])


def _attention(x, w, *, m, mode):
    """``x [s, d]`` (normed) -> ``[s, d]``: a head and a block of queries
    at a time, each against the keys up to the block's end."""
    s = x.shape[0]
    h, kv, dh = m["heads"], m["kv"], m["dh"]
    q = matmul(x, w["q_proj"], mode).reshape(s, h, dh)
    k = matmul(x, w["k_proj"], mode).reshape(s, kv, dh)
    v = matmul(x, w["v_proj"], mode).reshape(s, kv, dh)

    @functools.partial(jax.checkpoint, static_argnums=3)
    def attend(q_b, k_h, v_h, start):
        scores = matmul(q_b, t_last(k_h), mode) / math.sqrt(dh)
        seen = (start + jnp.arange(q_b.shape[0])[:, None]
                >= jnp.arange(k_h.shape[0])[None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v_h, mode)

    def head(qkv):
        q_h, k_h, v_h = qkv
        return jnp.concatenate([attend(q_h[lo:hi], k_h[:hi], v_h[:hi], lo)
                                for lo, hi in _blocks(s, QUERY_BLOCK)])

    # head-major, each key/value head beside the ``h / kv`` queries it serves
    by_head = lambda t, r: jnp.repeat(jnp.moveaxis(t, 1, 0), r, axis=0)
    attn = lax.map(head, (by_head(q, 1), by_head(k, h // kv),
                          by_head(v, h // kv)))
    return matmul(jnp.moveaxis(attn, 0, 1).reshape(s, h * dh), w["o_proj"],
                  mode)


def route(x, router, choice_bias, *, m):
    """``(picks [s, k], weights [s, k])`` over all the router's experts,
    as ``DeepseekV3TopkRouter`` states it with one group: the reference
    routes for itself, at the highest precision whatever ``mode`` (a pick
    is no matmul operand to round)."""
    scores = jax.nn.sigmoid(
        jnp.matmul(x, router, precision=lax.Precision.HIGHEST))
    _, picks = lax.top_k(scores + lax.stop_gradient(choice_bias), m["top_k"])
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return picks, weights * m["scale"]


def _relu2_ffn(x, up, down, mode):
    return matmul(jnp.square(jax.nn.relu(matmul(x, up, mode))), down, mode)


def experts(x, w, *, m, mode, first=None, held=None, shared=True):
    """``x [s, d]`` (normed) -> ``[s, d]``: the picks held here, every held
    expert over every position's latent row with its weight (0 where it
    was not picked), the sum back out of the latent space, plus the shared
    expert a block of positions at a time.  ``first`` / ``held`` (the
    tests') take a narrower run of the experts whose weights are here."""
    first = m["first"] if first is None else first
    held = m["held"] if held is None else held
    lat, width = m["latent"], m["width"]
    router = w["router"] if m["router_trained"] else lax.stop_gradient(
        w["router"])
    picks, weights = route(x, router, w["choice_bias"], m=m)
    at = first - m["first"]        # where the weights here hold expert e
    up = w["experts_up"].reshape(-1, lat, width)[at:at + held]
    down = w["experts_down"].reshape(-1, width, lat)[at:at + held]
    one = jax.checkpoint(functools.partial(_relu2_ffn, mode=mode))
    rows = matmul(x, w["latent_down"], mode)

    def add(y, expert):
        e, up_e, down_e = expert
        weight = jnp.sum(jnp.where(picks == e, weights, 0.0), axis=-1)
        return y + weight[:, None] * one(rows, up_e, down_e), None

    y, _ = lax.scan(add, jnp.zeros_like(rows),
                    (first + jnp.arange(held), up, down))
    y = matmul(y, w["latent_up"], mode)
    if shared:
        y = y + _by_blocks(
            lambda block: one(block, w["shared_up"], w["shared_down"]),
            x, FFN_BLOCK)
    return y


def sublayer(x, w, *, kind, m, mode, carry=True):
    """One layer's sublayer alone, ``x [s, d]`` (normed) -> ``[s, d]`` (the
    share tests compare it)."""
    if kind == MAMBA:
        return _mamba(x, w, m=m, mode=mode, carry=carry)
    if kind == ATTENTION:
        return _attention(x, w, m=m, mode=mode)
    return experts(x, w, m=m, mode=mode)


def _layer(x, w, *, kind, m, mode, carry=True):
    sub = jax.checkpoint(functools.partial(sublayer, kind=kind, m=m,
                                           mode=mode, carry=carry))
    return x + sub(_rms(x, m["eps"]) * w["norm"], w)


def of_layer(weights: dict, i: int) -> dict:
    prefix = f"layer_{i}."
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def hidden(config: dict, weights: dict, row, mode: str = "f32",
           carry: bool = True):
    """One row ``[s] int`` -> the last layer's output ``[s, d]``."""
    m = dims(config)
    x = weights["embed"][row]
    for i, kind in enumerate(m["kinds"]):
        layer = jax.checkpoint(functools.partial(
            _layer, kind=kind, m=m, mode=mode, carry=carry))
        x = layer(x, of_layer(weights, i))
    return x


def forward(config: dict, weights: dict, tokens, mode: str = "f32"):
    """``tokens [rows, s]`` -> logits ``[rows, s, vocab]`` (tests; the loss
    below never holds them whole)."""
    m = dims(config)

    def row(r):
        x = _rms(hidden(config, weights, r, mode), m["eps"])
        return matmul(x * weights["final_norm"], weights["head"], mode)

    return jnp.stack([row(r) for r in tokens])


def _row_loss(config, weights, row, mode, carry):
    """Sum over the row's predicted positions of the next token's negative
    log-likelihood, a block of positions at a time."""
    m = dims(config)
    x = hidden(config, weights, row, mode, carry)[:-1]
    targets = row[1:]
    n = x.shape[0]
    block = min(LOSS_BLOCK, n)
    pad = -n % block
    x = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, block)
    live = (jnp.arange(n + pad) < n).reshape(-1, block)

    @jax.checkpoint
    def one(xs):
        x, targets, live = xs
        logits = matmul(_rms(x, m["eps"]) * weights["final_norm"],
                        weights["head"], mode)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(live, picked, 0.0))

    return jnp.sum(lax.map(one, (x, targets, live)))


def loss_and_grads(config: dict, weights: dict, tokens, mode: str = "f32",
                   carry: bool = True):
    """Mean next-token cross entropy over every predicted position of the
    block's rows, and its gradient; the rows one after another."""
    rows, s = tokens.shape

    def loss(w):
        per_row = lax.map(jax.checkpoint(
            lambda r: _row_loss(config, w, r, mode, carry)), tokens)
        return jnp.sum(per_row) / (rows * (s - 1))

    return jax.value_and_grad(loss)(weights)


# ---------------------------------------------------------------------------
# the program side: tpudist's HybridLM, and the benchmark's weights in its
# tree and back

#: reference leaf -> path under a layer of the program's tree, by the
#: layer's kind
_LAYER_PATHS = {
    MAMBA: {
        "norm": ("mixer_norm", "scale"),
        "in_proj": ("ssm", "in_proj", "kernel"), "conv": ("ssm", "conv"),
        "conv_bias": ("ssm", "conv_bias"), "A_log": ("ssm", "A_log"),
        "D": ("ssm", "D"), "dt_bias": ("ssm", "dt_bias"),
        "gated_norm": ("ssm", "norm"),
        "out_proj": ("ssm", "out_proj", "kernel")},
    ATTENTION: {
        "norm": ("mixer_norm", "scale"),
        **{f"{n}_proj": ("attn", f"{n}_proj", "kernel") for n in "qkvo"}},
    EXPERTS: {
        "norm": ("experts_norm", "scale"), "router": ("experts", "router"),
        "choice_bias": ("experts", "choice_bias"),
        "latent_down": ("experts", "latent_down", "kernel"),
        "latent_up": ("experts", "latent_up", "kernel"),
        "experts_up": ("experts", "up"), "experts_down": ("experts", "down"),
        "shared_up": ("experts", "shared_up"),
        "shared_down": ("experts", "shared_down")}}
_TOP_PATHS = {"embed": ("tok_embed", "embedding"),
              "final_norm": ("final_norm", "scale"),
              "head": ("head", "kernel")}


def _path(name: str, kinds: tuple) -> tuple:
    layer, _, leaf = name.rpartition(".")
    if not layer:
        return _TOP_PATHS[name]
    return (layer,) + _LAYER_PATHS[kinds[int(layer.rpartition("_")[2])]][leaf]


def _per_expert(m: dict, leaf: str):
    """The three-axis shape the program holds a layer's experts in, or
    ``None`` for any other tensor."""
    if leaf == "experts_up":
        return (m["held"], m["latent"], m["width"])
    if leaf == "experts_down":
        return (m["held"], m["width"], m["latent"])
    return None


def build_module(config: dict, job: dict):
    from tpudist.models.hybrid import HybridLM, HybridSizes
    from tpudist.telemetry import names

    m = dims(config)
    kind = {MAMBA: names.STATE_SPACE, EXPERTS: names.EXPERT_LAYER,
            ATTENTION: names.FULL}
    sizes = HybridSizes(
        d_model=m["d"], n_heads=m["heads"], n_kv_heads=m["kv"],
        head_dim=m["dh"], rotary_dim=0, n_heads_total=m["heads_all"],
        attention=names.GROUPED_ATTN,
        ssm_heads=m["mh"], ssm_groups=m["mg"], ssm_head_dim=m["mp"],
        ssm_state=m["mn"], ssm_conv_width=m["conv"], ssm_chunk=m["chunk"],
        ssm_heads_total=m["mh_all"], ssm_groups_total=m["mg_all"],
        norm=names.PLAIN, one_sublayer=True,
        feed_forward=names.EXPERT_SHARE, n_experts=m["experts"],
        held=m["held"], first_expert=m["first"], top_k=m["top_k"],
        expert_width=m["width"], shared_width=m["shared"],
        scoring=names.SIGMOID_BIAS, routed_scale=m["scale"],
        router_trained=m["router_trained"], expert_fn=names.RELU2,
        latent_width=m["latent"], shared_scored=False, eps=m["eps"])
    return HybridLM(
        vocab=m["vocab"], layer_types=tuple(kind[k] for k in m["kinds"]),
        sizes=sizes, dtype=jnp.dtype(config["as_run"]["compute_dtype"]),
        remat=job["remat"] is not None,
        remat_policy=job["remat"] or "nothing")


def program_tree(config: dict, weights: dict) -> dict:
    m = dims(config)
    params: dict = {}
    for name, value in weights.items():
        shape = _per_expert(m, name.rpartition(".")[2])
        node = params
        *parents, last = _path(name, m["kinds"])
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value if shape is None else value.reshape(shape)
    return {"params": params}


def named_leaves(config: dict, params: dict) -> list:
    kinds = dims(config)["kinds"]
    out = []
    for name in leaf_names(config):
        node = params["params"]
        for p in _path(name, kinds):
            node = node[p]
        out.append(node.reshape(-1, node.shape[-1]) if node.ndim == 3
                   else node)
    return out


# ---------------------------------------------------------------------------
# the yardstick: what the architecture's algorithm needs, from shapes


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """Model FLOPs of one forward pass, a token, by part, for the heads
    and experts held (one multiply-add is 2; norms, softmax, gates, the
    convolution and other vector work are left out as ``flops.py`` leaves
    them out)."""
    m = dims(config)
    d = m["d"]
    inner, bc = m["mh"] * m["mp"], m["mg"] * m["mn"]
    return {
        "mamba_matmuls": 2.0 * d * (2 * inner + 2 * bc + m["mh"])
        + 2.0 * inner * d,
        # a state entry a position: a * h and dt x (x) B, added (the
        # update), and h . C (the read): 3 multiply-adds
        "ssd": 3 * 2.0 * m["mh"] * m["mp"] * m["mn"],
        "attn_matmuls": 2.0 * d * (m["heads"] + 2 * m["kv"]) * m["dh"]
        + 2.0 * m["heads"] * m["dh"] * d,
        "attn_pairs": flops.attention_forward_flops(
            batch=1, seq=seq, d_model=m["heads"] * m["dh"]) / seq,
        "router": 2.0 * d * m["experts"],
        "latent_proj": 2 * 2.0 * d * m["latent"],
        # a token's top_k picks fall on the held experts held / experts of
        # the time when the router is even
        "held_experts": 2 * 2.0 * m["latent"] * m["width"] * m["top_k"]
        * m["held"] / m["experts"],
        "shared_expert": 2 * 2.0 * d * m["shared"],
        "head": 2.0 * d * m["vocab"],
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    m = dims(config)
    f = forward_flops_per_token(config, seq)
    per_kind = {
        MAMBA: f["mamba_matmuls"] + f["ssd"],
        ATTENTION: f["attn_matmuls"] + f["attn_pairs"],
        EXPERTS: f["router"] + f["latent_proj"] + f["held_experts"]
        + f["shared_expert"]}
    return 3.0 * (sum(per_kind[k] for k in m["kinds"]) + f["head"])


def kernel_work(config: dict, per_chip_batch: int, seq: int) -> dict:
    """The three flash kernels of the attention layers.  Operations: two of
    the six matmuls each at ``heads * dh`` (``flops.py``).  Bytes: grouped
    key/value heads make k, v, dk, dv ``kv * dh`` wide where q, o, do, dq
    are ``heads * dh``; split over the kernels as
    ``flops.flash_kernel_work`` splits them (each backward kernel its own
    outputs and half of the five reads they share)."""
    m = dims(config)
    n = m["kinds"].count(ATTENTION)
    f = n * flops.attention_forward_flops(
        batch=per_chip_batch, seq=seq, d_model=m["heads"] * m["dh"])
    wide = n * float(per_chip_batch * seq * m["heads"] * m["dh"] * 2)
    narrow = wide * m["kv"] / m["heads"]
    shared_reads = 3 * wide + 2 * narrow      # q, o, do; k, v
    return {flops.FLASH_FWD: (f, 2 * wide + 2 * narrow),
            flops.FLASH_BWD_DQ: (f, wide + shared_reads / 2),
            flops.FLASH_BWD_DKV: (f, 2 * narrow + shared_reads / 2)}


def ssd_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` the RECURRENCE needs in one training step on
    one chip, all state-space layers, forward + backward (twice the
    forward): 3 multiply-adds a state entry a position; x, z and y in bf16,
    B and C a group in bf16 and dt a head in float32 across HBM once
    forward and twice backward.  The chunked form's extra products and
    every recomputation are the program's and not counted."""
    m = dims(config)
    n = m["kinds"].count(MAMBA) * per_chip_batch * seq
    ops = 3.0 * n * forward_flops_per_token(config, seq)["ssd"]
    forward_bytes = n * (2.0 * (3 * m["mh"] * m["mp"] + 2 * m["mg"] * m["mn"])
                         + 4.0 * m["mh"])
    return ops, 3.0 * forward_bytes


def expert_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` of the held experts' grouped products in one
    training step on one chip, all expert layers, forward + backward, at
    the rows that arrive in the mean (``top_k * held / experts`` of a
    token's picks): the two projections' multiply-adds; every held expert's
    weights read once forward and once backward and their gradient written
    (bf16 in, float32 out), the rows in and out of each product in bf16."""
    m = dims(config)
    layers = m["kinds"].count(EXPERTS)
    rows = per_chip_batch * seq * m["top_k"] * m["held"] / m["experts"]
    per_layer_ops = 3.0 * rows * 2 * 2.0 * m["latent"] * m["width"]
    weights = m["held"] * 2.0 * m["latent"] * m["width"]
    row_bytes = rows * 2.0 * (2 * m["latent"] + 2 * m["width"])
    return layers * per_layer_ops, layers * (weights * (2 + 2 + 4)
                                             + 3.0 * row_bytes)


def shared_expert_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` of the shared expert's two products in one
    training step on one chip, all expert layers, forward + backward (twice
    the forward): 6 x 2 x d x width FLOPs a token a layer; the weights
    read once forward and once backward in bf16 and their gradient written
    in float32, the rows in and out of each product in bf16.  The
    rematerialised forward is the program's choice, not counted."""
    m = dims(config)
    layers = m["kinds"].count(EXPERTS)
    rows = per_chip_batch * seq
    weights = 2.0 * m["d"] * m["shared"]
    ops = 3.0 * rows * forward_flops_per_token(config, seq)["shared_expert"]
    row_bytes = rows * 2.0 * (2 * m["d"] + 2 * m["shared"])
    return layers * ops, layers * (weights * (2 + 2 + 4) + 3.0 * row_bytes)
