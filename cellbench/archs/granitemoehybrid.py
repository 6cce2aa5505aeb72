"""``model_type`` ``granitemoehybrid`` with no routed experts
(``num_local_experts`` 0): a decoder whose layers are a mixer followed by a
gated feed-forward, ``h = x + r * Mixer_l(RMSNorm(x))``, ``y = h + r *
MLP(RMSNorm(h))`` with ``r = residual_multiplier`` on BOTH sublayers'
outputs; ``layer_types`` says whether a layer's mixer is a Mamba-2
state-space mixer (``mamba``) or softmax attention (``attention``); by the
keys of the model's own ``config.json``.  The published module is
``transformers.models.granitemoehybrid`` (``GraniteMoeHybridForCausalLM``):
``tests/test_granite_hybrid.py`` holds the program to it on shared weights.

**A share.**  The configuration file may hold a chip's share of a stated
deployment in which the chips that share a layer hold its heads between
them: ``mamba_n_heads`` and ``num_attention_heads`` / ``num_key_value_heads``
are then the heads HELD here (key/value heads with the query heads that read
them) and ``vocab_size`` the slice of the vocabulary held; the published
counts stand under ``published``.  The state-space GROUPS are not cut where
the members outnumber them: ``mamba_n_groups`` stays the published count and
every member holds each group's ``B`` and ``C`` whole, with its columns of
``W_in``, its conv channels and bias.  The feed-forward is whole.  What the
absent heads would add to a mixer's output is left out, here as in the
program, and that partial result goes on.  One statistic would cross the
cut, the gated norm's mean square over a group's channels: a lone member
takes it over the heads it holds, here as in the program (``departures``).

The reference is written from the equations (float32 ``jax.numpy``, no
biases but the convolution's), importing nothing of the program and nothing
of another architecture's module:

- ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w`` (``w`` round 1) before each
  sublayer; ``x_0 = embedding_multiplier * E[ids]``; a final norm;
  ``logits = (x E^T) / logits_scaling`` (``tie_word_embeddings``: the head
  IS the embedding); mean next-token cross entropy;
- ``mamba``: ``[z, xBC, dt] = W_in u``; ``xBC = SiLU(conv(xBC) + b)``
  (depthwise, causal, left-padded), split into ``x [heads, p]``, ``B, C
  [groups, n]`` (head ``j`` reads group ``j // (heads / groups)``);
  ``dt = softplus(dt + dt_bias)`` (no clamp), ``a_t = exp(-exp(A_log) *
  dt_t)``; ``h_t = a_t h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D
  x_t``, **one position at a time** (the program computes it in chunks);
  the gated norm ``w * g * rsqrt(mean(g^2) + eps)``, ``g = y * SiLU(z)``, its
  mean over a group's channels; ``W_out``;
- ``attention``: ``q, k, v = W_q u, W_k u, W_v u``; query head ``j`` reads
  key/value head ``j // (heads / kv)``; no bias, no q/k norm and **no
  positions** (``position_embedding_type`` ``nope``); scores ``q . k *
  attention_multiplier`` (the multiplier IS the softmax scale, not
  ``1 / sqrt(head_dim)``), causal, softmax, ``W_o``;
- ``MLP``: ``W_down (SiLU(W_gate v) * W_up v)``; the published module holds
  ``W_gate`` and ``W_up`` as the two halves of one ``input_linear``, the
  gate first.

**Memory and the size of the executable** are what shape the code (weights +
Adam + one gradient are 16 bytes a parameter, 11.68 GB of the chip's 16.9 at
the real size; the step's and this reference's executables have to fit the
chip machine's 192 MiB compile cache together): the Mamba layers are of one
shape and their tensors are STACKED on a leading axis, so that ONE
``lax.scan`` runs them all and the compiler sees one layer's code, forward
and backward; an attention layer runs inside that loop, under a ``lax.cond``
at the step of the Mamba layer that follows it (behind the loop where none
does), its tensors entries of their own; rows one at a time; every layer and
every piece of a layer under ``jax.checkpoint``; attention a head and a
block of ``QUERY_BLOCK`` queries at a time over dense masked scores; the
feed-forward and the loss a block of positions at a time (``lax.map``); the
recurrence as a two-level ``lax.scan`` whose inner level (``SCAN_CHUNK``
positions) is rematerialised, so that one state a chunk is kept and not one
a position.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from cellbench import flops
from cellbench.reference import matmul, seed_key, t_last

MAMBA, ATTENTION = "mamba", "attention"
#: a Mamba layer's tensors, stacked over the Mamba layers in their order
_MAMBA_LEAVES = ("norm", "in_proj", "conv", "conv_bias", "A_log", "D",
                 "dt_bias", "gated_norm", "out_proj", "mlp_norm", "mlp_gate",
                 "mlp_up", "mlp_down")
STACKED = tuple(f"{MAMBA}.{leaf}" for leaf in _MAMBA_LEAVES)
#: positions of the recurrence's rematerialised inner scan; queries of one
#: attention block; positions of a block of the feed-forward and of the loss
SCAN_CHUNK = 64
QUERY_BLOCK = 2048
FFN_BLOCK = 2048
LOSS_BLOCK = 1024


def dims(config: dict) -> dict:
    """Sizes under short names.  ``layers`` is 1 for the runner's count of
    custom calls (``custom_calls_per_layer`` is then the step's total: a
    pattern's layers do not run the same kernels); ``depth`` is the number
    of layers.  Head counts are those HELD; ``*_all`` the published ones
    (the head width is ``hidden_size`` over the PUBLISHED query heads)."""
    run = config["as_run"]
    whole = config.get("published", {})
    depth = config["num_hidden_layers"]
    kinds = tuple(config["layer_types"])
    if len(kinds) != depth or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types is not {depth} of {MAMBA!r} and "
                         f"{ATTENTION!r}")
    if config["num_local_experts"] or config["num_experts_per_tok"]:
        raise ValueError("routed experts are not written down here: "
                         "num_local_experts is 0")
    if config["position_embedding_type"] != "nope":
        raise ValueError("attention takes no positions here "
                         "(position_embedding_type 'nope')")
    if (not config["tie_word_embeddings"] or config["attention_bias"]
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"]
            or config["hidden_act"] != "silu"):
        raise ValueError("a tied head, SiLU, a bias on the convolution and "
                         "on nothing else are what is written down here")
    d = config["hidden_size"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    heads_all = whole.get("num_attention_heads", heads)
    mh, mg = config["mamba_n_heads"], config["mamba_n_groups"]
    mh_all = whole.get("mamba_n_heads", mh)
    if config["mamba_expand"] * d != mh_all * config["mamba_d_head"]:
        raise ValueError("mamba_expand * hidden_size is the published heads' "
                         "mamba_d_head each")
    if heads % kv or mh % mg or d % heads_all:
        raise ValueError(f"{heads} query heads on {kv}, {mh} state-space "
                         f"heads on {mg} groups, {d} dims over {heads_all}")
    return dict(
        vocab=config["vocab_size"], seq=config["max_position_embeddings"],
        d=d, layers=1, depth=depth, kinds=kinds, eps=config["rms_norm_eps"],
        heads=heads, kv=kv, dh=d // heads_all, heads_all=heads_all,
        mh=mh, mg=mg, mp=config["mamba_d_head"], mn=config["mamba_d_state"],
        conv=config["mamba_d_conv"], mh_all=mh_all,
        mg_all=whole.get("mamba_n_groups", mg),
        # the chunks the PROGRAM's scan takes (the result does not depend
        # on them)
        chunk=config["mamba_chunk_size"],
        ffn=config["shared_intermediate_size"],
        emb_scale=float(config["embedding_multiplier"]),
        res_scale=float(config["residual_multiplier"]),
        logit_div=float(config["logits_scaling"]),
        attn_scale=float(config["attention_multiplier"]),
        ffn_products_kept=run.get("ffn_products_kept", True))


def _mamba_layers(m: dict) -> list:
    return [i for i, k in enumerate(m["kinds"]) if k == MAMBA]


def _layer_shapes(m: dict, kind: str) -> dict:
    d, f = m["d"], m["ffn"]
    mlp = {"mlp_norm": (d,), "mlp_gate": (d, f), "mlp_up": (d, f),
           "mlp_down": (f, d)}
    if kind == MAMBA:
        inner, bc = m["mh"] * m["mp"], m["mg"] * m["mn"]
        return {"norm": (d,), "in_proj": (d, 2 * inner + 2 * bc + m["mh"]),
                "conv": (inner + 2 * bc, m["conv"]),
                "conv_bias": (inner + 2 * bc,), "A_log": (m["mh"],),
                "D": (m["mh"],), "dt_bias": (m["mh"],),
                "gated_norm": (inner,), "out_proj": (inner, d), **mlp}
    h, kv, dh = m["heads"], m["kv"], m["dh"]
    return {"norm": (d,), "q_proj": (d, h * dh), "k_proj": (d, kv * dh),
            "v_proj": (d, kv * dh), "o_proj": (h * dh, d), **mlp}


def weight_shapes(config: dict) -> dict:
    """name -> shape: ``embed`` (the head too: no ``head`` entry),
    ``final_norm``, an attention layer's tensors as ``layer_<i>.<leaf>``,
    and the Mamba layers' as ``mamba.<leaf>``, stacked on axis 0 in the
    layers' order."""
    m = dims(config)
    shapes = {"embed": (m["vocab"], m["d"]), "final_norm": (m["d"],)}
    n = len(_mamba_layers(m))
    if n:
        shapes.update({f"{MAMBA}.{leaf}": (n,) + shape for leaf, shape
                       in _layer_shapes(m, MAMBA).items()})
    for i, kind in enumerate(m["kinds"]):
        if kind == ATTENTION:
            shapes.update({f"layer_{i}.{leaf}": shape for leaf, shape
                           in _layer_shapes(m, ATTENTION).items()})
    return shapes


def leaf_names(config: dict) -> list:
    """One name a tensor as a model holds them (``layer_3.in_proj``), in
    the order of ``reference.leaf_norms``."""
    m = dims(config)
    names = []
    for name in sorted(weight_shapes(config)):
        if name in STACKED:
            leaf = name.partition(".")[2]
            names += [f"layer_{i}.{leaf}" for i in _mamba_layers(m)]
        else:
            names.append(name)
    return names


def init_weights(config: dict, seed_words) -> dict:
    """Seeded weights (``assumed`` in the configuration file): matrices
    normal(0, ``as_run.init_std``); ``A_log = log(1 .. heads held)``, the
    published module's own init for heads ``0 .. heads - 1``; ``dt_bias``
    the inverse softplus of steps drawn log-uniform between
    ``as_run.time_step_min`` and ``time_step_max`` (the module's 0.001 and
    0.1); norm weights, the gated norm's and ``D`` normal(1, ``norm_std``),
    the convolution's taps normal(0, 1 / sqrt(width)), its bias normal(0,
    ``conv_bias_std``): off the module's defaults (1, 1, 1, uniform, 0), so
    that one left out shows.  Stacked tensors are drawn layer by layer, so
    that a program that wants single layers never holds the stack."""
    run = config["as_run"]
    m = dims(config)
    key = seed_key(seed_words)

    def one(leaf: str, shape: tuple, k):
        draw = jax.random.normal(k, shape, jnp.float32)
        if leaf == "A_log":
            return jnp.log(jnp.arange(1, m["mh"] + 1, dtype=jnp.float32))
        if leaf == "dt_bias":
            lo, hi = (math.log(run[f"time_step_{e}"]) for e in ("min", "max"))
            step = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
            return step + jnp.log(-jnp.expm1(-step))   # softplus^-1
        if leaf.endswith("norm") or leaf == "D":
            return 1.0 + run["norm_std"] * draw
        if leaf == "conv":
            return draw / math.sqrt(m["conv"])
        if leaf == "conv_bias":
            return run["conv_bias_std"] * draw
        return run["init_std"] * draw

    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(config).items())):
        k = jax.random.fold_in(key, i)
        leaf = name.rpartition(".")[2]
        if name in STACKED:
            out[name] = jnp.stack([one(leaf, shape[1:],
                                       jax.random.fold_in(k, l))
                                   for l in range(shape[0])])
        else:
            out[name] = one(leaf, shape, k)
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
    if s % block or s == block:
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


def mamba(x, w, *, m, mode, carry=True):
    """``x [s, d]`` (normed) -> ``[s, d]``.  ``carry=False`` is the planted
    fault of the tests (the state zeroed at every ``mamba_chunk_size``)."""
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


def attention(x, w, *, m, mode):
    """``x [s, d]`` (normed) -> ``[s, d]``: a head and a block of queries
    at a time, each against the keys up to the block's end, the scores
    times ``attention_multiplier``."""
    s = x.shape[0]
    h, kv, dh = m["heads"], m["kv"], m["dh"]
    q = matmul(x, w["q_proj"], mode).reshape(s, h, dh)
    k = matmul(x, w["k_proj"], mode).reshape(s, kv, dh)
    v = matmul(x, w["v_proj"], mode).reshape(s, kv, dh)

    @functools.partial(jax.checkpoint, static_argnums=3)
    def attend(q_b, k_h, v_h, start):
        scores = matmul(q_b, t_last(k_h), mode) * m["attn_scale"]
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


def mlp(x, w, *, mode):
    """``x [s, d]`` (normed) -> ``[s, d]``, a block of positions at a time."""
    @jax.checkpoint
    def one(block, gate, up, down):
        return matmul(jax.nn.silu(matmul(block, gate, mode))
                      * matmul(block, up, mode), down, mode)

    return _by_blocks(
        lambda block: one(block, w["mlp_gate"], w["mlp_up"], w["mlp_down"]),
        x, FFN_BLOCK)


def sublayer(x, w, *, kind, m, mode, carry=True):
    """One layer's mixer alone, ``x [s, d]`` (normed) -> ``[s, d]`` (the
    share tests compare it)."""
    if kind == MAMBA:
        return mamba(x, w, m=m, mode=mode, carry=carry)
    return attention(x, w, m=m, mode=mode)


def _layer(x, w, *, kind, m, mode, carry=True):
    mixer = jax.checkpoint(functools.partial(sublayer, kind=kind, m=m,
                                             mode=mode, carry=carry))
    x = x + m["res_scale"] * mixer(_rms(x, m["eps"]) * w["norm"], w)
    arm = jax.checkpoint(functools.partial(mlp, mode=mode))
    return x + m["res_scale"] * arm(_rms(x, m["eps"]) * w["mlp_norm"], w)


def of_layer(weights: dict, i: int, m: dict) -> dict:
    """Layer ``i``'s tensors: an attention layer's own entries, a Mamba
    layer's slices of the stacked ones."""
    if m["kinds"][i] == MAMBA:
        j = _mamba_layers(m).index(i)
        return {name.partition(".")[2]: weights[name][j] for name in STACKED}
    prefix = f"layer_{i}."
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def hidden(config: dict, weights: dict, row, mode: str = "f32",
           carry: bool = True):
    """One row ``[s] int`` -> the last layer's output ``[s, d]``.  ONE scan
    over the stacked Mamba layers; an attention layer runs inside it, under
    a ``lax.cond``, at the step of the Mamba layer that follows it, and
    behind the scan where none does."""
    m = dims(config)
    x = m["emb_scale"] * weights["embed"][row]
    layer = lambda kind: functools.partial(_layer, kind=kind, m=m, mode=mode,
                                           carry=carry)
    # the attention layers ahead of Mamba layer ``j`` (``n``: behind the last)
    ahead, n = {}, 0
    for i, kind in enumerate(m["kinds"]):
        if kind == ATTENTION:
            ahead.setdefault(n, []).append(i)
        else:
            n += 1

    # rematerialised as a whole: the loop keeps its carry a step and nothing
    # of a layer, nor of a ``cond``'s branch that did not run
    @jax.checkpoint
    def body(x, jw):
        j, w = jw
        for at in sorted(set(ahead) - {n}):
            for i in ahead[at]:
                x = lax.cond(
                    j == at,
                    lambda x, i=i: layer(ATTENTION)(x, of_layer(weights, i,
                                                                m)),
                    lambda x: x, x)
        return layer(MAMBA)(x, w), None

    if n:
        stacked = {name.partition(".")[2]: weights[name] for name in STACKED}
        x, _ = lax.scan(body, x, (jnp.arange(n), stacked))
    for i in ahead.get(n, ()):
        x = jax.checkpoint(layer(ATTENTION))(x, of_layer(weights, i, m))
    return x


def _logits(x, weights, m, mode):
    return matmul(_rms(x, m["eps"]) * weights["final_norm"],
                  t_last(weights["embed"]), mode) / m["logit_div"]


def forward(config: dict, weights: dict, tokens, mode: str = "f32"):
    """``tokens [rows, s]`` -> logits ``[rows, s, vocab]`` (tests; the loss
    below never holds them whole)."""
    m = dims(config)
    return jnp.stack([_logits(hidden(config, weights, r, mode), weights, m,
                              mode) for r in tokens])


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
        logp = jax.nn.log_softmax(_logits(x, weights, m, mode), axis=-1)
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(live, picked, 0.0))

    return jnp.sum(lax.map(one, (x, targets, live)))


def loss_and_grads(config: dict, weights: dict, tokens, mode: str = "f32",
                   carry: bool = True):
    """Mean next-token cross entropy over every predicted position of the
    block's rows, and its gradient (the tied embedding's is ONE gradient:
    the gather's and the head's together); the rows one after another."""
    rows, s = tokens.shape

    def loss(w):
        if rows == 1:   # nothing to loop over, or to save memory against
            return _row_loss(config, w, tokens[0], mode, carry) / (s - 1)
        per_row = lax.map(jax.checkpoint(
            lambda r: _row_loss(config, w, r, mode, carry)), tokens)
        return jnp.sum(per_row) / (rows * (s - 1))

    return jax.value_and_grad(loss)(weights)


# ---------------------------------------------------------------------------
# the program side: tpudist's HybridLM, and the benchmark's weights in its
# tree and back

_MLP_PATHS = {"mlp_norm": ("mlp_norm", "scale"),
              "mlp_gate": ("mlp", "gate_proj", "kernel"),
              "mlp_up": ("mlp", "up_proj", "kernel"),
              "mlp_down": ("mlp", "down_proj", "kernel")}
#: reference leaf -> path under a layer of the program's tree, by the
#: layer's kind
_LAYER_PATHS = {
    MAMBA: {
        "norm": ("mixer_norm", "scale"),
        "in_proj": ("ssm", "in_proj", "kernel"), "conv": ("ssm", "conv"),
        "conv_bias": ("ssm", "conv_bias"), "A_log": ("ssm", "A_log"),
        "D": ("ssm", "D"), "dt_bias": ("ssm", "dt_bias"),
        "gated_norm": ("ssm", "norm"),
        "out_proj": ("ssm", "out_proj", "kernel"), **_MLP_PATHS},
    ATTENTION: {
        "norm": ("mixer_norm", "scale"),
        **{f"{n}_proj": ("attn", f"{n}_proj", "kernel") for n in "qkvo"},
        **_MLP_PATHS}}
_TOP_PATHS = {"embed": ("tok_embed", "embedding"),
              "final_norm": ("final_norm", "scale")}


def _path(name: str, kinds: tuple) -> tuple:
    """A ``leaf_names`` name's path in the program's tree."""
    layer, _, leaf = name.rpartition(".")
    if not layer:
        return _TOP_PATHS[name]
    return (layer,) + _LAYER_PATHS[kinds[int(layer.rpartition("_")[2])]][leaf]


def build_module(config: dict, job: dict):
    from tpudist.models.hybrid import HybridLM, HybridSizes
    from tpudist.telemetry import names

    m = dims(config)
    kind = {MAMBA: names.STATE_SPACE, ATTENTION: names.FULL}
    sizes = HybridSizes(
        d_model=m["d"], n_heads=m["heads"], n_kv_heads=m["kv"],
        head_dim=m["dh"], rotary_dim=0, n_heads_total=m["heads_all"],
        attention=names.GROUPED_ATTN, softmax_scale=m["attn_scale"],
        ssm_heads=m["mh"], ssm_groups=m["mg"], ssm_head_dim=m["mp"],
        ssm_state=m["mn"], ssm_conv_width=m["conv"], ssm_chunk=m["chunk"],
        ssm_heads_total=m["mh_all"], ssm_groups_total=m["mg_all"],
        norm=names.PLAIN, feed_forward=names.DENSE_FFN, ffn_width=m["ffn"],
        ffn_products_kept=m["ffn_products_kept"],
        embedding_scale=m["emb_scale"], residual_scale=m["res_scale"],
        logits_divisor=m["logit_div"], tied_head=True, eps=m["eps"])
    return HybridLM(
        vocab=m["vocab"], layer_types=tuple(kind[k] for k in m["kinds"]),
        sizes=sizes, dtype=jnp.dtype(config["as_run"]["compute_dtype"]),
        remat=job["remat"] is not None,
        remat_policy=job["remat"] or "nothing")


def program_tree(config: dict, weights: dict) -> dict:
    m = dims(config)
    params: dict = {}

    def put(name, value):
        node = params
        *parents, last = _path(name, m["kinds"])
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value

    for name, value in weights.items():
        if name in STACKED:
            leaf = name.partition(".")[2]
            for j, i in enumerate(_mamba_layers(m)):
                put(f"layer_{i}.{leaf}", value[j])
        else:
            put(name, value)
    return {"params": params}


def named_leaves(config: dict, params: dict) -> list:
    kinds = dims(config)["kinds"]
    out = []
    for name in leaf_names(config):
        node = params["params"]
        for p in _path(name, kinds):
            node = node[p]
        out.append(node)
    return out


# ---------------------------------------------------------------------------
# the yardstick: what the architecture's algorithm needs, from shapes


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """Model FLOPs of one forward pass, a token, by part, for the heads
    held (one multiply-add is 2; norms, softmax, gates, the convolution,
    the multipliers and other vector work are left out as ``flops.py``
    leaves them out); ``head`` is the tied head's product, once."""
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
        "mlp": 3 * 2.0 * d * m["ffn"],
        "head": 2.0 * d * m["vocab"],
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    m = dims(config)
    f = forward_flops_per_token(config, seq)
    per_kind = {MAMBA: f["mamba_matmuls"] + f["ssd"] + f["mlp"],
                ATTENTION: f["attn_matmuls"] + f["attn_pairs"] + f["mlp"]}
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
    one chip, all Mamba layers, forward + backward (twice the forward): 3
    multiply-adds a state entry a position; x, z and y in bf16, B and C a
    group in bf16 and dt a head in float32 across HBM once forward and
    twice backward.  The chunked form's extra products and every
    recomputation are the program's and not counted."""
    m = dims(config)
    n = m["kinds"].count(MAMBA) * per_chip_batch * seq
    ops = 3.0 * n * forward_flops_per_token(config, seq)["ssd"]
    forward_bytes = n * (2.0 * (3 * m["mh"] * m["mp"] + 2 * m["mg"] * m["mn"])
                         + 4.0 * m["mh"])
    return ops, 3.0 * forward_bytes


def mlp_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` of every layer's feed-forward in one training
    step on one chip, forward + backward (twice the forward): 6 x 3 x d x
    width FLOPs a token a layer; the weights read once forward and once
    backward in bf16 and their gradient written in float32, the rows in and
    out of each product in bf16.  Compute-bound.  The rematerialised forward
    is the program's choice, not counted."""
    m = dims(config)
    rows = per_chip_batch * seq
    weights = 3.0 * m["d"] * m["ffn"]
    ops = 3.0 * rows * forward_flops_per_token(config, seq)["mlp"]
    row_bytes = rows * 2.0 * (2 * m["d"] + 3 * m["ffn"])
    return m["depth"] * ops, m["depth"] * (weights * (2 + 2 + 4)
                                           + 3.0 * row_bytes)
