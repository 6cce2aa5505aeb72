"""``model_type`` ``laguna``: a decoder whose layers are softmax attention
followed by a feed-forward, ``h = x + Attn_l(RMSNorm(x))``, ``y = h +
FFN_l(RMSNorm(h))``, where both halves go by the LAYER: ``layer_types`` says
whether a layer attends causally to everything (``full_attention``) or
inside a sliding window (``sliding_attention``), with
``num_attention_heads_per_layer`` query heads and rotary positions of its
kind's own (``rope_parameters``); ``mlp_layer_types`` says whether its
feed-forward is dense or routed experts beside a shared expert; by the keys
of the model's own ``config.json``.

**A share.**  The configuration file may hold a chip's share of a stated
deployment in which the chips that share a layer hold its heads and its
experts between them: ``num_attention_heads_per_layer`` /
``num_key_value_heads`` are then the heads HELD here (key/value heads with
the query heads that read them), ``num_experts`` the experts held
(``as_run.first_expert`` on) while the router keeps its published width
(``as_run.router_experts``), its ``num_experts_per_tok`` picks, its
renormalisation and its scale; ``vocab_size`` is the slice of the vocabulary
held; the published counts stand under ``published``.  Router, shared expert
and the dense feed-forward are whole.  What the absent heads would add to an
attention's output and the absent experts to the routed sum is left out,
here as in the program, and that partial result goes on.  No statistic
crosses the cut: the gate is a head's own.

The reference is written from the equations (float32 ``jax.numpy``, no
biases anywhere), importing nothing of the program:

- ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w`` (plain, ``w`` round 1)
  before each sublayer; embedding, final norm, untied head, mean next-token
  cross entropy;
- *attention*: ``q, k, v = W_q u, W_k u, W_v u``; query head ``j`` reads
  key/value head ``j // (heads / kv)``; no q/k norm; rotary positions by the
  layer's kind on the first ``partial_rotary_factor * head_dim`` dims of a
  head (pairs ``(i, i + half)``, the rest passes through): ``default``
  ``theta_i = base^(-i / half)``; ``yarn`` ``inv_freq_i = (1 - m_i) *
  theta_i / factor + m_i * theta_i`` with ``m`` 1 for the pairs that turn
  more than ``beta_fast`` times in ``original_max_position_embeddings``
  positions, 0 for those that turn fewer than ``beta_slow`` times and
  linear between (the bounds cut off to whole pairs, outwards), and cos and
  sin both times ``attention_factor``; scores ``q . k / sqrt(head_dim)``,
  causal and, in a sliding layer, ``q_pos - k_pos < sliding_window``;
  softmax; the gate ``g = sigmoid(W_g u)``, one number a head a token,
  multiplies the head's output before ``W_o``;
- *dense feed-forward*: ``W_down (SiLU(W_gate v) * W_up v)``;
- *experts*: ``s = sigmoid(W_r v)`` over all the router's experts; the picks
  are the ``k`` largest ``s``, their weights ``scale * s_i / (sum of the
  picked s + 1e-20)``; the routed part is ``sum_i w_i E_i(v)`` over the
  picks held here, ``E_i(v) = W2_i (SiLU(W1g_i v) * W1u_i v)``; plus the
  shared expert of the same form, unscored, once.

**Memory** is what shapes the code (weights + Adam + one gradient are
16 bytes a parameter, 10.75 GB of the chip's 16.9 at the real size, before
any activation): one entry a tensor (``STACKED = ()``: the layers are of
three shapes), rows one at a time (``lax.map``; a lone row as it is), every
layer and every piece
of a layer under ``jax.checkpoint``; attention a head and a block of
``QUERY_BLOCK`` queries at a time over dense masked scores (``lax.map``
over heads and over a head's blocks; the window is a mask, and a sliding
layer's block reads its own keys and the ``window - 1`` before them); the
feed-forwards and the loss a block of positions at a
time (``lax.map``), the held experts one after another (``lax.scan``):
loops, not unrolled copies, which the compiler would take minutes over.
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
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
#: queries of one attention block; positions of a block of a feed-forward and
#: of the loss
QUERY_BLOCK = 2048
FFN_BLOCK = 2048
LOSS_BLOCK = 1024


def _rope(config: dict, kind: str) -> dict:
    """A layer kind's rotary positions: the dims rotated, the base and,
    for ``yarn``, its constants."""
    r = config["rope_parameters"][kind]
    out = dict(rotary=int(r["partial_rotary_factor"] * config["head_dim"]),
               base=float(r["rope_theta"]), yarn=None)
    if r["rope_type"] == "yarn":
        out["yarn"] = dict(
            factor=float(r["factor"]),
            original=r["original_max_position_embeddings"],
            beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
            scale=float(r["attention_factor"]))
    elif r["rope_type"] != "default":
        raise ValueError(f"rope_type {r['rope_type']!r} is not written down "
                         f"here: default and yarn are")
    return out


def dims(config: dict) -> dict:
    """Sizes under short names.  ``layers`` is 1 for the runner's count of
    custom calls (``custom_calls_per_layer`` is then the step's total: the
    layers do not run the same kernels); ``depth`` is the number of layers.
    Head and expert counts are those HELD; ``heads_all`` the published
    query heads a layer."""
    run = config["as_run"]
    whole = config.get("published", {})
    depth = config["num_hidden_layers"]
    kinds = tuple(config["layer_types"])
    ffns = tuple(config["mlp_layer_types"])
    heads = tuple(config["num_attention_heads_per_layer"])
    if not (len(kinds) == len(ffns) == len(heads) == depth):
        raise ValueError(f"layer_types, mlp_layer_types and "
                         f"num_attention_heads_per_layer say a layer each: "
                         f"{depth} of them")
    if set(kinds) - {FULL, SLIDING} or set(ffns) - {DENSE, SPARSE}:
        raise ValueError(f"a layer is {FULL} or {SLIDING} with a {DENSE} or "
                         f"{SPARSE} feed-forward")
    if [i for i, f in enumerate(ffns) if f == DENSE] != list(
            config["mlp_only_layers"]):
        raise ValueError("mlp_only_layers names the dense layers of "
                         "mlp_layer_types")
    if config["gating"] != "per-head" or set(
            config["gating_types"][:depth]) != {"per_head"}:
        raise ValueError("the gate written down here is one a head")
    if (not config["norm_topk_prob"] or config["moe_router_logit_softcapping"]
            or config["moe_apply_router_weight_on_input"]
            or config["attention_bias"]):
        raise ValueError("the picks' weights are renormalised and multiply "
                         "the experts' outputs, the router's logits are not "
                         "capped, attention has no bias")
    kv = config["num_key_value_heads"]
    if any(h % kv for h in heads):
        raise ValueError(f"{heads} query heads do not group over {kv} "
                         f"key/value heads")
    return dict(
        vocab=config["vocab_size"], seq=config["max_position_embeddings"],
        d=config["hidden_size"], layers=1, depth=depth, kinds=kinds,
        ffns=ffns, eps=config["rms_norm_eps"],
        heads=heads, kv=kv, dh=config["head_dim"],
        heads_all=tuple(whole.get("num_attention_heads_per_layer",
                                  heads)[:depth]),
        window=config["sliding_window"],
        rope={kind: _rope(config, kind) for kind in (FULL, SLIDING)},
        ffn_width=config["intermediate_size"],
        held=config["num_experts"], experts=run["router_experts"],
        first=run["first_expert"], top_k=config["num_experts_per_tok"],
        width=config["moe_intermediate_size"],
        shared=config["shared_expert_intermediate_size"],
        scale=float(config["moe_routed_scaling_factor"]),
        router_trained=run["router_trained"])


def _layer_shapes(m: dict, i: int) -> dict:
    d, h, kv, dh = m["d"], m["heads"][i], m["kv"], m["dh"]
    shapes = {"mixer_norm": (d,), "ffn_norm": (d,),
              "q_proj": (d, h * dh), "k_proj": (d, kv * dh),
              "v_proj": (d, kv * dh), "g_proj": (d, h), "o_proj": (h * dh, d)}
    if m["ffns"][i] == DENSE:
        f = m["ffn_width"]
        shapes.update({"ffn_gate": (d, f), "ffn_up": (d, f),
                       "ffn_down": (f, d)})
    else:
        e, w, sw = m["held"], m["width"], m["shared"]
        shapes.update({
            "router": (d, m["experts"]),
            # a layer's held experts as ONE two-axis tensor a projection
            "experts_gate": (e * d, w), "experts_up": (e * d, w),
            "experts_down": (e * w, d),
            "shared_gate": (d, sw), "shared_up": (d, sw),
            "shared_down": (sw, d)})
    return shapes


def weight_shapes(config: dict) -> dict:
    m = dims(config)
    shapes = {"embed": (m["vocab"], m["d"]), "final_norm": (m["d"],),
              "head": (m["d"], m["vocab"])}
    for i in range(m["depth"]):
        shapes.update({f"layer_{i}.{name}": shape
                       for name, shape in _layer_shapes(m, i).items()})
    return shapes


def leaf_names(config: dict) -> list:
    return sorted(weight_shapes(config))


def init_weights(config: dict, seed_words) -> dict:
    """Seeded weights (``assumed`` in the configuration file): matrices
    normal(0, ``as_run.init_std``); norm weights normal(1, ``norm_std``), so
    that one left out shows; the gate's projection normal(0,
    ``gate_init_std``): over a normed input of ``hidden_size`` dims the
    gate's logit has a deviation of ``gate_init_std * sqrt(hidden_size)``
    (1.1 at 0.02 x 3072), so the gates spread over (0.1, 0.9) and one
    dropped, or stuck at a half, shows; the router's normal(0,
    ``router_init_std``)."""
    run = config["as_run"]
    key = seed_key(seed_words)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(config).items())):
        leaf = name.rpartition(".")[2]
        draw = jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)
        if leaf.endswith("norm"):
            out[name] = 1.0 + run["norm_std"] * draw
        elif leaf == "g_proj":
            out[name] = run["gate_init_std"] * draw
        elif leaf == "router":
            out[name] = run["router_init_std"] * draw
        else:
            out[name] = run["init_std"] * draw
    return out


# ---------------------------------------------------------------------------
# the reference: one row at a time


def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _by_blocks(fn, x, block: int):
    """``fn`` over ``x [s, ...]`` a block of positions at a time (one after
    another; whole where the blocks do not divide ``s``)."""
    s = x.shape[0]
    if s % block:
        return fn(x)
    out = lax.map(fn, x.reshape(s // block, block, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def inv_freq(rope: dict):
    """``(inverse frequencies [rotary / 2], what multiplies cos and sin)``
    of one layer kind, from the formulas above."""
    half = rope["rotary"] // 2
    theta = [rope["base"] ** (-i / half) for i in range(half)]
    y = rope["yarn"]
    if y is None:
        return jnp.asarray(theta, jnp.float32), 1.0

    def pair_that_turns(times):
        # theta_i * original / (2 pi) = times, solved for i
        return half * math.log(y["original"] / (times * 2 * math.pi)) / (
            math.log(rope["base"]))

    low = max(math.floor(pair_that_turns(y["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(y["beta_slow"])),
               rope["rotary"] - 1)
    if low == high:
        high += 0.001
    m = [1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
         for i in range(half)]
    return jnp.asarray([(1.0 - m_i) * t / y["factor"] + m_i * t
                        for m_i, t in zip(m, theta)], jnp.float32), y["scale"]


def rotate(x, rope: dict):
    """Rotary positions on the first ``rope['rotary']`` dims of ``x [s,
    heads, dh]``, pairs ``(i, i + rotary / 2)``; the rest passes through."""
    freq, scale = inv_freq(rope)
    half = freq.shape[0]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = scale * jnp.cos(angle)[:, None], scale * jnp.sin(angle)[:, None]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., 2 * half:]], axis=-1)


def attention(x, w, *, kind, m, mode, windowed=True, gate=True):
    """``x [s, d]`` (normed) -> ``[s, d]``: a head and a block of queries
    at a time, each against the keys its band can reach, dense scores
    under the mask.  ``windowed=False`` and ``gate=False`` are planted
    faults of the tests (a sliding layer that attends causally to
    everything; the gate dropped)."""
    s = x.shape[0]
    kv, dh = m["kv"], m["dh"]
    h = w["q_proj"].shape[1] // dh
    window = m["window"] if kind == SLIDING and windowed else None
    rope = m["rope"][kind]
    q = rotate(matmul(x, w["q_proj"], mode).reshape(s, h, dh), rope)
    k = rotate(matmul(x, w["k_proj"], mode).reshape(s, kv, dh), rope)
    v = matmul(x, w["v_proj"], mode).reshape(s, kv, dh)

    # every block of queries against the same NUMBER of keys, so that the
    # blocks are one loop's trips and not copies of its body: all the keys
    # (the causal mask hides what lies ahead), or in a sliding layer the
    # block's own and the ``window - 1`` before them (rows of zeros stand
    # before the first key, hidden by their positions)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    before = 0 if window is None else window - 1
    span = s if window is None else block + before

    @jax.checkpoint
    def attend(q_b, k_b, v_b, q_at, k_at):
        scores = matmul(q_b, t_last(k_b), mode) / math.sqrt(dh)
        k_pos = k_at + jnp.arange(span)[None]
        apart = q_at + jnp.arange(block)[:, None] - k_pos
        seen = (apart >= 0) & (k_pos >= 0)
        if window is not None:
            seen &= apart < window
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v_b, mode)

    def head(qkv):
        q_h, k_h, v_h = qkv
        k_h, v_h = (jnp.pad(t, ((before, 0), (0, 0))) for t in (k_h, v_h))

        def of_block(lo):
            # the first key a query of the block can see lies at position
            # ``first - before``
            first = 0 if window is None else lo
            return attend(lax.dynamic_slice_in_dim(q_h, lo, block),
                          lax.dynamic_slice_in_dim(k_h, first, span),
                          lax.dynamic_slice_in_dim(v_h, first, span), lo,
                          first - before)

        return lax.map(of_block, jnp.arange(0, s, block)).reshape(s, dh)

    # head-major, each key/value head beside the ``h / kv`` queries it serves
    by_head = lambda t, r: jnp.repeat(jnp.moveaxis(t, 1, 0), r, axis=0)
    attn = lax.map(head, (by_head(q, 1), by_head(k, h // kv),
                          by_head(v, h // kv)))
    attn = jnp.moveaxis(attn, 0, 1)                      # [s, h, dh]
    if gate:
        attn = attn * jax.nn.sigmoid(matmul(x, w["g_proj"], mode))[..., None]
    return matmul(attn.reshape(s, h * dh), w["o_proj"], mode)


def _gated_ffn(x, gate, up, down, mode):
    return matmul(jax.nn.silu(matmul(x, gate, mode)) * matmul(x, up, mode),
                  down, mode)


def dense_ffn(x, w, *, mode):
    """``x [s, d]`` (normed) -> ``[s, d]``, a block of positions at a
    time."""
    one = jax.checkpoint(functools.partial(_gated_ffn, mode=mode))
    return _by_blocks(
        lambda block: one(block, w["ffn_gate"], w["ffn_up"], w["ffn_down"]),
        x, FFN_BLOCK)


def route(x, router, *, m, scale=None):
    """``(picks [s, k], weights [s, k])`` over all the router's experts: the
    reference routes for itself, at the highest precision whatever ``mode``
    (a pick is no matmul operand to round)."""
    scores = jax.nn.sigmoid(
        jnp.matmul(x, router, precision=lax.Precision.HIGHEST))
    weights, picks = lax.top_k(scores, m["top_k"])
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return picks, weights * (m["scale"] if scale is None else scale)


def experts(x, w, *, m, mode, first=None, held=None, shared=True, scale=None):
    """``x [s, d]`` (normed) -> ``[s, d]``: the picks held here, every held
    expert over every position with its weight (0 where it was not
    picked), one expert after another, plus the shared expert a block of
    positions at a time.  ``first`` / ``held`` (the tests') take a narrower
    run of the experts whose weights are here; ``scale`` (a planted fault)
    another routed scale than the configuration's."""
    first = m["first"] if first is None else first
    held = m["held"] if held is None else held
    d, width = m["d"], m["width"]
    router = w["router"] if m["router_trained"] else lax.stop_gradient(
        w["router"])
    picks, weights = route(x, router, m=m, scale=scale)
    at = first - m["first"]        # where the weights here hold expert e
    gate = w["experts_gate"].reshape(-1, d, width)[at:at + held]
    up = w["experts_up"].reshape(-1, d, width)[at:at + held]
    down = w["experts_down"].reshape(-1, width, d)[at:at + held]
    one = jax.checkpoint(functools.partial(_gated_ffn, mode=mode))

    def add(y, expert):
        e, gate_e, up_e, down_e = expert
        weight = jnp.sum(jnp.where(picks == e, weights, 0.0), axis=-1)
        return y + weight[:, None] * one(x, gate_e, up_e, down_e), None

    y, _ = lax.scan(add, jnp.zeros_like(x),
                    (first + jnp.arange(held), gate, up, down))
    if shared:
        y = y + _by_blocks(
            lambda block: one(block, w["shared_gate"], w["shared_up"],
                              w["shared_down"]), x, FFN_BLOCK)
    return y


def feed_forward(x, w, *, ffn, m, mode):
    if ffn == DENSE:
        return dense_ffn(x, w, mode=mode)
    return experts(x, w, m=m, mode=mode)


def _layer(x, w, *, kind, ffn, m, mode):
    mixer = jax.checkpoint(functools.partial(attention, kind=kind, m=m,
                                             mode=mode))
    x = x + mixer(_rms(x, m["eps"]) * w["mixer_norm"], w)
    arm = jax.checkpoint(functools.partial(feed_forward, ffn=ffn, m=m,
                                           mode=mode))
    return x + arm(_rms(x, m["eps"]) * w["ffn_norm"], w)


def of_layer(weights: dict, i: int) -> dict:
    prefix = f"layer_{i}."
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def hidden(config: dict, weights: dict, row, mode: str = "f32"):
    """One row ``[s] int`` -> the last layer's output ``[s, d]``."""
    m = dims(config)
    x = weights["embed"][row]
    for i, (kind, ffn) in enumerate(zip(m["kinds"], m["ffns"])):
        layer = jax.checkpoint(functools.partial(
            _layer, kind=kind, ffn=ffn, m=m, mode=mode))
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


def _row_loss(config, weights, row, mode):
    """Sum over the row's predicted positions of the next token's negative
    log-likelihood, a block of positions at a time."""
    m = dims(config)
    x = hidden(config, weights, row, mode)[:-1]
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


def loss_and_grads(config: dict, weights: dict, tokens, mode: str = "f32"):
    """Mean next-token cross entropy over every predicted position of the
    block's rows, and its gradient; the rows one after another."""
    rows, s = tokens.shape

    def loss(w):
        if rows == 1:   # nothing to loop over, or to save memory against
            return _row_loss(config, w, tokens[0], mode) / (s - 1)
        per_row = lax.map(jax.checkpoint(
            lambda r: _row_loss(config, w, r, mode)), tokens)
        return jnp.sum(per_row) / (rows * (s - 1))

    return jax.value_and_grad(loss)(weights)


# ---------------------------------------------------------------------------
# the program side: tpudist's HybridLM, and the benchmark's weights in its
# tree and back

_TOP_PATHS = {"embed": ("tok_embed", "embedding"),
              "final_norm": ("final_norm", "scale"),
              "head": ("head", "kernel")}
_EXPERT_PATHS = {
    "ffn_norm": ("experts_norm", "scale"), "router": ("experts", "router"),
    "experts_gate": ("experts", "gate"), "experts_up": ("experts", "up"),
    "experts_down": ("experts", "down"),
    "shared_gate": ("experts", "shared_gate"),
    "shared_up": ("experts", "shared_up"),
    "shared_down": ("experts", "shared_down")}
_DENSE_PATHS = {
    "ffn_norm": ("mlp_norm", "scale"),
    **{f"ffn_{n}": ("mlp", f"{n}_proj", "kernel")
       for n in ("gate", "up", "down")}}
#: the attention sublayer's name in the program's tree, by the layer's kind
_MIXER = {FULL: "attn", SLIDING: "window_attn"}


def _path(name: str, m: dict) -> tuple:
    layer, _, leaf = name.rpartition(".")
    if not layer:
        return _TOP_PATHS[name]
    i = int(layer.rpartition("_")[2])
    if leaf == "mixer_norm":
        return (layer, "mixer_norm", "scale")
    if leaf.endswith("_proj"):
        return (layer, _MIXER[m["kinds"][i]], leaf, "kernel")
    paths = _DENSE_PATHS if m["ffns"][i] == DENSE else _EXPERT_PATHS
    return (layer,) + paths[leaf]


def _per_expert(m: dict, leaf: str):
    """The three-axis shape the program holds a layer's experts in, or
    ``None`` for any other tensor."""
    if leaf in ("experts_gate", "experts_up"):
        return (m["held"], m["d"], m["width"])
    if leaf == "experts_down":
        return (m["held"], m["width"], m["d"])
    return None


def build_module(config: dict, job: dict):
    from tpudist.models.hybrid import (HybridLM, HybridSizes, SoftmaxSizes,
                                       Yarn)
    from tpudist.telemetry import names

    m = dims(config)
    kind = {FULL: names.FULL, SLIDING: names.WINDOW}
    arm = {DENSE: names.DENSE_FFN, SPARSE: names.EXPERT_SHARE}

    def softmax(k):
        """The sizes of the layers of kind ``k``: they share a head count."""
        at = [i for i, x in enumerate(m["kinds"]) if x == k]
        held = {m["heads"][i] for i in at}
        in_all = {m["heads_all"][i] for i in at}
        if len(held) != 1 or len(in_all) != 1:
            raise ValueError(f"the {k} layers hold {sorted(held)} of "
                             f"{sorted(in_all)} query heads: one count a kind")
        rope = m["rope"][k]
        y = rope["yarn"]
        return SoftmaxSizes(
            n_heads=held.pop(), n_kv_heads=m["kv"],
            n_heads_total=in_all.pop(),
            window=m["window"] if k == SLIDING else None,
            rotary_dim=rope["rotary"], rope_theta=rope["base"],
            yarn=y and Yarn(factor=y["factor"],
                            original_positions=y["original"],
                            beta_fast=y["beta_fast"],
                            beta_slow=y["beta_slow"], scale=y["scale"]))

    kinds = tuple((kind[k], softmax(k)) for k in (FULL, SLIDING)
                  if k in m["kinds"])
    sizes = HybridSizes(
        d_model=m["d"], head_dim=m["dh"],
        attention=names.HEAD_GATED_ATTN, softmax_kinds=kinds,
        norm=names.PLAIN, feed_forward=names.EXPERT_SHARE,
        ffn_width=m["ffn_width"], n_experts=m["experts"], held=m["held"],
        first_expert=m["first"], top_k=m["top_k"], expert_width=m["width"],
        shared_width=m["shared"], scoring=names.SIGMOID,
        routed_scale=m["scale"], router_trained=m["router_trained"],
        expert_fn=names.GATED_SILU, shared_scored=False, eps=m["eps"])
    return HybridLM(
        vocab=m["vocab"], layer_types=tuple(kind[k] for k in m["kinds"]),
        sizes=sizes, dtype=jnp.dtype(config["as_run"]["compute_dtype"]),
        remat=job["remat"] is not None,
        remat_policy=job["remat"] or "nothing",
        feed_forwards=tuple(arm[f] for f in m["ffns"]))


def program_tree(config: dict, weights: dict) -> dict:
    m = dims(config)
    params: dict = {}
    for name, value in weights.items():
        shape = _per_expert(m, name.rpartition(".")[2])
        node = params
        *parents, last = _path(name, m)
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value if shape is None else value.reshape(shape)
    return {"params": params}


def named_leaves(config: dict, params: dict) -> list:
    m = dims(config)
    out = []
    for name in leaf_names(config):
        node = params["params"]
        for p in _path(name, m):
            node = node[p]
        out.append(node.reshape(-1, node.shape[-1]) if node.ndim == 3
                   else node)
    return out


# ---------------------------------------------------------------------------
# the yardstick: what the architecture's algorithm needs, from shapes


def live_pairs(seq: int, window=None) -> float:
    """Attended (query, key) pairs of one sequence: ``flops.causal_pairs``,
    or inside a sliding window position ``q``'s ``min(q + 1, window)``."""
    if window is None or window >= seq:
        return flops.causal_pairs(seq)
    return flops.causal_pairs(window) + float(seq - window) * window


def _windows(m: dict) -> list:
    """A layer's window (None: causal to everything), by layer."""
    return [m["window"] if k == SLIDING else None for k in m["kinds"]]


def forward_flops_per_token(config: dict, seq: int) -> list:
    """Model FLOPs of one forward pass, a token, by part, a LAYER, for the
    heads and experts held, and ``head`` last (one multiply-add is 2; norms,
    softmax, rotary positions, gates' sigmoids and other vector work are
    left out as ``flops.py`` leaves them out)."""
    m = dims(config)
    d, dh = m["d"], m["dh"]
    out = []
    for h, window, ffn in zip(m["heads"], _windows(m), m["ffns"]):
        part = {
            # q, k, v, the gate a head, and o
            "attn_matmuls": 2.0 * d * ((h + 2 * m["kv"]) * dh + h)
            + 2.0 * h * dh * d,
            "attn_pairs": 4.0 * live_pairs(seq, window) * h * dh / seq}
        if ffn == DENSE:
            part["dense_ffn"] = 3 * 2.0 * d * m["ffn_width"]
        else:
            part["router"] = 2.0 * d * m["experts"]
            # a token's top_k picks fall on the held experts held / experts
            # of the time when the router is even
            part["held_experts"] = (3 * 2.0 * d * m["width"] * m["top_k"]
                                    * m["held"] / m["experts"])
            part["shared_expert"] = 3 * 2.0 * d * m["shared"]
        out.append(part)
    return out + [{"head": 2.0 * d * m["vocab"]}]


def train_flops_per_token(config: dict, seq: int) -> float:
    return 3.0 * sum(sum(part.values())
                     for part in forward_flops_per_token(config, seq))


def _flash_work(m: dict, layers: list, per_chip_batch: int, seq: int) -> dict:
    """The three flash kernels over ``layers`` (indices), at their LIVE
    pairs.  Operations: two of the six matmuls each (``flops.py``).  Bytes:
    grouped key/value heads make k, v, dk, dv ``kv * dh`` wide where q, o,
    do, dq are ``heads * dh``; split over the kernels as
    ``flops.flash_kernel_work`` splits them (each backward kernel its own
    outputs and half of the five reads they share)."""
    f = wide = narrow = 0.0
    windows = _windows(m)
    for i in layers:
        f += 4.0 * per_chip_batch * live_pairs(seq, windows[i]) * (
            m["heads"][i] * m["dh"])
        wide += float(per_chip_batch * seq * m["heads"][i] * m["dh"] * 2)
        narrow += float(per_chip_batch * seq * m["kv"] * m["dh"] * 2)
    shared_reads = 3 * wide + 2 * narrow      # q, o, do; k, v
    return {flops.FLASH_FWD: (f, 2 * wide + 2 * narrow),
            flops.FLASH_BWD_DQ: (f, wide + shared_reads / 2),
            flops.FLASH_BWD_DKV: (f, 2 * narrow + shared_reads / 2)}


def kernel_work(config: dict, per_chip_batch: int, seq: int) -> dict:
    """The three flash kernels of every layer, both kinds together, each
    layer at its own live pairs (a sliding layer's band, not the causal
    triangle): a share of this roofline cannot pass 100%."""
    m = dims(config)
    return _flash_work(m, range(m["depth"]), per_chip_batch, seq)


def window_kernel_work(config: dict, per_chip_batch: int, seq: int) -> dict:
    """The same of the sliding layers alone."""
    m = dims(config)
    return _flash_work(m, [i for i, k in enumerate(m["kinds"])
                           if k == SLIDING], per_chip_batch, seq)


def expert_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` of the held experts' grouped products in one
    training step on one chip, all expert layers, forward + backward, at
    the rows that arrive in the mean (``top_k * held / experts`` of a
    token's picks): the three projections' multiply-adds; every held
    expert's weights read once forward and once backward and their gradient
    written (bf16 in, float32 out), the rows in and out of each product in
    bf16."""
    m = dims(config)
    layers = m["ffns"].count(SPARSE)
    rows = per_chip_batch * seq * m["top_k"] * m["held"] / m["experts"]
    per_layer_ops = 3.0 * rows * 3 * 2.0 * m["d"] * m["width"]
    weights = m["held"] * 3.0 * m["d"] * m["width"]
    row_bytes = rows * 2.0 * (2 * m["d"] + 3 * m["width"])
    return layers * per_layer_ops, layers * (weights * (2 + 2 + 4)
                                             + 3.0 * row_bytes)


def lead_ffn_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` of the dense layers' feed-forward in one
    training step on one chip, forward + backward (twice the forward): 6 x
    3 x d x width FLOPs a token a layer; the weights read once forward and
    once backward in bf16 and their gradient written in float32, the rows in
    and out of each product in bf16.  Compute-bound."""
    m = dims(config)
    layers = m["ffns"].count(DENSE)
    rows = per_chip_batch * seq
    weights = 3.0 * m["d"] * m["ffn_width"]
    ops = 3.0 * rows * 3 * 2.0 * m["d"] * m["ffn_width"]
    row_bytes = rows * 2.0 * (2 * m["d"] + 3 * m["ffn_width"])
    return layers * ops, layers * (weights * (2 + 2 + 4) + 3.0 * row_bytes)
