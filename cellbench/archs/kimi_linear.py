"""``model_type`` ``kimi_linear``: a decoder whose layers are a mixer
followed by a feed-forward, ``h = x + Mixer_l(RMSNorm(x))``, ``y = h +
FFN_l(RMSNorm(h))``, where the mixer is **Kimi Delta Attention** (a delta
rule whose decay is a number a CHANNEL, behind low-rank gates) on the layers
``linear_attn_config.kda_layers`` names and **latent attention without
positions** on ``linear_attn_config.full_attn_layers`` (both lists count
from 1), and the feed-forward is dense on the first
``first_k_dense_replace`` layers and routed experts beside a shared expert
behind them; by the keys of the model's own ``config.json`` (Kimi Team
2025, "Kimi Linear: An Expressive, Efficient Attention Architecture").

**A share.**  The configuration file may hold a chip's share of a stated
expert-parallel deployment: ``num_experts`` is then the experts HELD here
(``as_run.first_expert`` on) while the router keeps its published width
(``as_run.router_experts``), its ``num_experts_per_token`` picks, its
renormalisation and its scale; ``vocab_size`` is the slice of the vocabulary
held; the published counts stand under ``published``.  Mixers, router, shared
expert and the dense feed-forward are whole.  What the absent experts would
add to the routed sum is left out, here as in the program, and that partial
result goes on.

The reference is written from the equations (float32 ``jax.numpy``, no
biases anywhere), importing nothing of the program:

- ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w`` (plain, ``w`` round 1)
  before each sublayer; embedding, final norm, untied head, mean next-token
  cross entropy;
- *KDA* (``h`` heads of ``dk = dv = linear_attn_config.head_dim``):
  ``q, k, v = silu(conv(W_q u)), silu(conv(W_k u)), silu(conv(W_v u))``,
  the convolution depthwise, causal (left-padded) and without bias; ``q``
  and ``k`` L2-normed a head, ``q`` times ``dk^-0.5``; the forget gate ``g =
  -exp(A_log[head]) * softplus(W_fb (W_fa u) + dt_bias)``, a number a
  channel, ``[s, h, dk]``; the write strength ``beta = sigmoid(W_b u)``, a
  number a head; per head, from ``S = 0 [dk, dv]``, **one position at a
  time** (the program computes it in chunks): ``S <- Diag(exp(g_t)) S``,
  ``S <- S + k_t (x) (beta_t (v_t - S^T k_t))``, ``o_t = S^T q_t``; ``y =
  W_o (w_n * rms(o_t) * sigmoid(W_gb (W_ga u)))``, the RMS over a head's
  ``dv`` and the gate a SIGMOID;
- *latent attention*: ``q = W_q u`` viewed ``[heads, nope + rope]``
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``W_kva u`` gives a latent
  of ``kv_lora_rank`` and ONE key part of ``qk_rope_head_dim`` that all
  heads share; ``W_kvb RMSNorm(latent)`` viewed ``[heads, nope + v]`` gives
  each head its own key part and its values (``v_head_dim``); head ``j``'s
  key is ``[own_j | shared]``, the shared part NOT turned (``mla_use_nope``:
  no rotary positions); causal softmax of ``q . k / sqrt(nope + rope)``;
  ``W_o`` reads the heads' values side by side;
- *dense feed-forward* and an expert: ``W_down (SiLU(W_gate v) * W_up v)``;
- *experts*: ``s = sigmoid(W_r v)`` over all the router's experts in
  float32; the picks are the ``k`` largest of ``s + b`` (``choice_bias``, a
  buffer that steers the choice alone: ``use_grouped_topk`` with ONE group
  is the plain top ``k``); their weights ``scale * s_i / (sum of the picked
  s + 1e-20)`` (the scores WITHOUT the bias); the routed part is ``sum_i
  w_i E_i(v)`` over the picks held here; plus one shared expert, unscored,
  once.

``choice_bias`` is a buffer: ``init_weights`` seeds it (``layer_<i>.
choice_bias``) and ``program_tree`` hands it on, but it is no entry of
``weight_shapes`` / ``leaf_names`` (not a parameter, not compared); its
gradient is zero, so Adam leaves it where it was, here and in the program.

**Departures** from the published description, each also in the
configuration's ``departures``: the router's bias is seeded and never
updated (the balancing rule that moves it between steps is not run); no
auxiliary loss; the program's chunked scan clamps the forget gate at
``-5`` a position a channel where this reference takes it as it is
(``tpudist/ops/gated_delta.py``; at the seeded decays, 0.999 to 0.5 a
position, nothing comes near it); what the absent experts would add is
left out (the share).

**Memory and the compile cache** are what shape the code (weights + Adam +
one gradient are 16 bytes a parameter, 9.64 GB of the chip's 16.9 at the
real size, before any activation; the step's and this reference's
executables have to fit the chip machine's 192 MiB compile cache together
with a dozen small programs of 40-odd MB, and a layer written out costs 20
MB of executable: five of them 101 MB beside the step's 60, which did not
fit): the first run of CONSECUTIVE layers of one shape, a KDA mixer before
routed experts (layers 1 and 2 of the five), has its tensors STACKED on a
leading axis, so that ONE ``lax.scan`` runs it and the compiler sees one
such layer's code, forward and backward; every other layer's tensors are
entries of their own (a third layer of that shape behind the latent layer
too: inside the loop under a ``lax.cond`` it costs no code and 3.6 GB, a
branch not taken hands back a gradient of zeros as large as its weights);
rows one at a time (``lax.map``; a lone row as it is); every layer and the
pieces of a layer that hold wide intermediates under ``jax.checkpoint`` (a
level between the two, round a whole mixer or feed-forward, made the
executable 8 MB and the temporaries 0.5 GB larger); the recurrence
as a two-level ``lax.scan`` whose inner level (``SCAN_CHUNK`` positions) is
rematerialised, so that one state a chunk is kept and not one a position;
attention a head and a block of ``QUERY_BLOCK`` queries at a time over
dense masked scores (``lax.map`` over heads and over a head's blocks); the
feed-forwards and the loss a block of positions at a time (``lax.map``),
the held experts one after another (``lax.scan``): loops, not unrolled
copies, which the compiler would take minutes over.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from cellbench import flops
from cellbench.reference import matmul, seed_key, t_last

KDA, LATENT = "kda", "latent_attention"
DENSE, SPARSE = "dense", "sparse"
#: the tensors of the layers of ONE shape, a KDA mixer before routed experts,
#: are stacked over those layers under this prefix (axis 0); every other
#: layer's are entries of their own, ``layer_<i>.<leaf>``
STACK = "kda_moe"
_NORM_LEAVES = ("mixer_norm", "ffn_norm")
_KDA_LEAVES = ("q_proj", "k_proj", "v_proj", "conv", "f_a_proj", "f_b_proj",
               "A_log", "dt_bias", "b_proj", "g_a_proj", "g_b_proj",
               "gated_norm", "o_proj")
_SPARSE_LEAVES = ("router", "experts_gate", "experts_up", "experts_down",
                  "shared_gate", "shared_up", "shared_down")
STACKED = tuple(f"{STACK}.{leaf}"
                for leaf in _NORM_LEAVES + _KDA_LEAVES + _SPARSE_LEAVES)
#: positions of the recurrence's rematerialised inner scan; queries of one
#: attention block; positions of a block of a feed-forward and of the loss
SCAN_CHUNK = 64
QUERY_BLOCK = 2048
FFN_BLOCK = 2048
LOSS_BLOCK = 1024
L2_EPS = 1e-6
#: the program's scan and its chunk (``as_run.kda_chunk``): what
#: ``kernel_work`` counts the chunked products of
KDA_SCAN = "kda_scan"


def dims(config: dict) -> dict:
    """Sizes under short names.  ``layers`` is 1 for the runner's count of
    custom calls (``custom_calls_per_layer`` is then the step's total: the
    layers do not run the same kernels); ``depth`` is the number of layers.
    The expert count is that HELD.  The gates' rank is
    ``linear_attn_config.head_dim`` (``assumed`` in the configuration
    file)."""
    run = config["as_run"]
    lin = config["linear_attn_config"]
    depth = config["num_hidden_layers"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or kda | full != set(range(1, depth + 1)):
        raise ValueError(f"kda_layers {sorted(kda)} and full_attn_layers "
                         f"{sorted(full)} name each of the {depth} layers "
                         f"once, counting from 1")
    if not config["mla_use_nope"] or config["q_lora_rank"] is not None:
        raise ValueError("the latent attention written down here has no "
                         "rotary positions and no query rank")
    if (config["moe_router_activation_func"] != "sigmoid"
            or not config["moe_renormalize"] or config["num_expert_group"] != 1
            or config["topk_group"] != 1 or config["num_shared_experts"] != 1
            or config["moe_layer_freq"] != 1 or config["hidden_act"] != "silu"
            or config["tie_word_embeddings"]):
        raise ValueError("the expert layer written down here scores by "
                         "sigmoid, renormalises its picks, has one group, "
                         "one shared expert and gated SiLU experts behind "
                         "every mixer past the dense ones; the head is untied")
    dense = config["first_k_dense_replace"]
    return dict(
        vocab=config["vocab_size"], seq=config["model_max_length"],
        d=config["hidden_size"], layers=1, depth=depth,
        kinds=tuple(KDA if i + 1 in kda else LATENT for i in range(depth)),
        ffns=tuple(DENSE if i < dense else SPARSE for i in range(depth)),
        eps=config["rms_norm_eps"],
        kh=lin["num_heads"], kd=lin["head_dim"], gate_rank=lin["head_dim"],
        conv=lin["short_conv_kernel_size"], chunk=run["kda_chunk"],
        heads=config["num_attention_heads"], rank=config["kv_lora_rank"],
        own=config["qk_nope_head_dim"], shared_key=config["qk_rope_head_dim"],
        dv=config["v_head_dim"],
        ffn_width=config["intermediate_size"],
        held=config["num_experts"], experts=run["router_experts"],
        first=run["first_expert"], top_k=config["num_experts_per_token"],
        width=config["moe_intermediate_size"],
        shared=config["moe_intermediate_size"] * config["num_shared_experts"],
        scale=float(config["routed_scaling_factor"]),
        router_trained=run["router_trained"])


def _layer_shapes(m: dict, i: int) -> dict:
    d = m["d"]
    shapes = {"mixer_norm": (d,), "ffn_norm": (d,)}
    if m["kinds"][i] == KDA:
        inner, rank = m["kh"] * m["kd"], m["gate_rank"]
        shapes.update({
            "q_proj": (d, inner), "k_proj": (d, inner), "v_proj": (d, inner),
            "conv": (3 * inner, m["conv"]),
            "f_a_proj": (d, rank), "f_b_proj": (rank, inner),
            "A_log": (m["kh"],), "dt_bias": (inner,), "b_proj": (d, m["kh"]),
            "g_a_proj": (d, rank), "g_b_proj": (rank, inner),
            "gated_norm": (m["kd"],), "o_proj": (inner, d)})
    else:
        h, own, dv = m["heads"], m["own"], m["dv"]
        shapes.update({
            "q_proj": (d, h * (own + m["shared_key"])),
            "kv_a_proj": (d, m["rank"] + m["shared_key"]),
            "kv_norm": (m["rank"],), "kv_b_proj": (m["rank"], h * (own + dv)),
            "o_proj": (h * dv, d)})
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


def stacked_layers(m: dict) -> list:
    """The layers whose tensors are stacked: the first run of two or more
    CONSECUTIVE layers of a KDA mixer before routed experts (``[]`` where
    there is none)."""
    run = []
    for i, (kind, ffn) in enumerate(zip(m["kinds"], m["ffns"])):
        if kind == KDA and ffn == SPARSE:
            run.append(i)
        elif len(run) > 1:
            break
        else:
            run = []
    return run if len(run) > 1 else []


def weight_shapes(config: dict) -> dict:
    """name -> shape; the tensors of :func:`stacked_layers` stacked on axis
    0 under ``STACK``."""
    m = dims(config)
    shapes = {"embed": (m["vocab"], m["d"]), "final_norm": (m["d"],),
              "head": (m["d"], m["vocab"])}
    stacked = stacked_layers(m)
    for i in range(m["depth"]):
        if i not in stacked:
            shapes.update({f"layer_{i}.{name}": shape
                           for name, shape in _layer_shapes(m, i).items()})
    if stacked:
        shapes.update({f"{STACK}.{name}": (len(stacked),) + shape for
                       name, shape in _layer_shapes(m, stacked[0]).items()})
    return shapes


def buffer_shapes(config: dict) -> dict:
    """What a layer holds that is no parameter: each expert layer's
    ``choice_bias`` over the router's experts (the stacked layers' stacked
    too)."""
    m = dims(config)
    stacked = stacked_layers(m)
    shapes = {f"layer_{i}.choice_bias": (m["experts"],)
              for i, ffn in enumerate(m["ffns"])
              if ffn == SPARSE and i not in stacked}
    if stacked:
        shapes[f"{STACK}.choice_bias"] = (len(stacked), m["experts"])
    return shapes


def leaf_names(config: dict) -> list:
    """One name per tensor as a model holds them (``layer_2.q_proj``), in
    the order of ``reference.leaf_norms``: a stacked entry gives its layers'
    one after another."""
    m = dims(config)
    names = []
    for name in sorted(weight_shapes(config)):
        if name in STACKED:
            leaf = name.partition(".")[2]
            names += [f"layer_{i}.{leaf}" for i in stacked_layers(m)]
        else:
            names.append(name)
    return names


def unstacked(config: dict, tree: dict) -> dict:
    """A tree like the weights' (gradients, weights, buffers among them)
    under a name a tensor a layer: :func:`leaf_names`' names, and each
    expert layer's ``layer_<i>.choice_bias``."""
    m = dims(config)
    out = {}
    for name, value in tree.items():
        prefix, _, leaf = name.partition(".")
        if prefix == STACK:
            for j, i in enumerate(stacked_layers(m)):
                out[f"layer_{i}.{leaf}"] = value[j]
        else:
            out[name] = value
    return out


def init_weights(config: dict, seed_words) -> dict:
    """Seeded weights and buffers (``assumed`` in the configuration file):
    matrices normal(0, ``as_run.init_std``), the router's normal(0,
    ``router_init_std``); norm weights (the gated norm's and the latent's
    too) normal(1, ``norm_std``), so that one left out shows; the
    convolution's taps normal(0, 1 / sqrt(width)); ``A_log`` evenly spaced
    over heads and ``dt_bias`` evenly spaced over a head's channels from
    ``-dt_bias_span`` to ``+dt_bias_span``, such that the per-position decay
    ``exp(g)`` at a zero gate projection runs from ``decay_slowest`` (head 0,
    channel 0) to ``decay_fastest`` (the last head's last channel): the
    decays differ over heads AND over a head's channels, and a wrong carried
    state reaches the logits; ``choice_bias`` normal(0,
    ``choice_bias_std``)."""
    run = config["as_run"]
    m = dims(config)
    key = seed_key(seed_words)
    shapes = {**weight_shapes(config), **buffer_shapes(config)}
    span = run["dt_bias_span"]
    softplus = lambda x: math.log1p(math.exp(x))

    def one(leaf, shape, k):
        draw = jax.random.normal(k, shape, jnp.float32)
        if leaf == "A_log":
            # -g = exp(A_log) * softplus(dt_bias) at a zero projection
            lo = math.log(-math.log(run["decay_slowest"]) / softplus(-span))
            hi = math.log(-math.log(run["decay_fastest"]) / softplus(span))
            return jnp.linspace(lo, hi, m["kh"], dtype=jnp.float32)
        if leaf == "dt_bias":
            return jnp.tile(jnp.linspace(-span, span, m["kd"],
                                         dtype=jnp.float32), m["kh"])
        if leaf.endswith("norm"):
            return 1.0 + run["norm_std"] * draw
        if leaf == "conv":
            return draw / math.sqrt(m["conv"])
        if leaf == "router":
            return run["router_init_std"] * draw
        if leaf == "choice_bias":
            return run["choice_bias_std"] * draw
        return run["init_std"] * draw

    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        prefix, _, leaf = name.rpartition(".")
        if prefix == STACK:
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


def _by_blocks(fn, x, block: int):
    """``fn`` over ``x [s, ...]`` a block of positions at a time (one after
    another; whole where the blocks do not divide ``s``)."""
    s = x.shape[0]
    if s % block:
        return fn(x)
    out = lax.map(fn, x.reshape(s // block, block, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def delta_rule(q, k, v, g, beta):
    """The recurrence as written, a decay a channel: ``q, k, g [s, heads,
    dk]``, ``v [s, heads, dv]``, ``beta [s, heads]`` -> ``o [s, heads,
    dv]``, one position at a time on ``S [heads, dk, dv]``.  Elementwise
    float32 (no matmul unit, so no precision to state); no chunked
    algebra."""
    s, heads, dk = q.shape
    chunk = SCAN_CHUNK if s % SCAN_CHUNK == 0 else 1

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, :, None]         # a number a row
        read = jnp.sum(state * k_t[:, :, None], axis=1)
        write = beta_t[:, None] * (v_t - read)
        state = state + k_t[:, :, None] * write[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    @jax.checkpoint
    def inner(state, xs):
        return lax.scan(step, state, xs)

    by_chunk = lambda x: x.reshape(s // chunk, chunk, *x.shape[1:])
    state0 = jnp.zeros((heads, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(inner, state0, tuple(map(by_chunk, (q, k, v, g, beta))))
    return o.reshape(s, heads, v.shape[-1])


def kda(x, w, *, m, mode, carry=True, by_channel=True):
    """``x [s, d]`` (normed) -> ``[s, d]``.  ``carry=False`` (the state
    zeroed at every ``SCAN_CHUNK``) and ``by_channel=False`` (a head's
    channels all forgetting at their mean rate) are planted faults of the
    tests."""
    s = x.shape[0]
    h, dk = m["kh"], m["kd"]

    @jax.checkpoint
    def project(x, w_q, w_k, w_v, w_conv):
        mixed = jnp.concatenate([matmul(x, w_q, mode), matmul(x, w_k, mode),
                                 matmul(x, w_v, mode)], axis=-1)
        width = w_conv.shape[1]
        padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(padded[j:j + s] * w_conv[:, j]
                                for j in range(width)))
        return tuple(t.reshape(s, h, dk) for t in jnp.split(mixed, 3, axis=-1))

    q, k, v = project(x, w["q_proj"], w["k_proj"], w["v_proj"], w["conv"])
    f = matmul(matmul(x, w["f_a_proj"], mode), w["f_b_proj"], mode)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (f + w["dt_bias"]).reshape(s, h, dk))
    if not by_channel:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(matmul(x, w["b_proj"], mode))
    unit = lambda t: t * lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    if carry:
        o = delta_rule(q, k, v, g, beta)
    else:
        cut = lambda t: t.reshape(s // SCAN_CHUNK, SCAN_CHUNK, *t.shape[1:])
        o = jax.vmap(delta_rule)(*map(cut, (q, k, v, g, beta)))
        o = o.reshape(s, h, dk)

    @jax.checkpoint
    def close(x, o, w_ga, w_gb, w_n, w_out):
        gate = jax.nn.sigmoid(matmul(matmul(x, w_ga, mode), w_gb, mode))
        o = w_n * _rms(o, m["eps"]) * gate.reshape(s, h, dk)
        return matmul(o.reshape(s, h * dk), w_out, mode)

    return close(x, o, w["g_a_proj"], w["g_b_proj"], w["gated_norm"],
                 w["o_proj"])


def latent_attention(x, w, *, m, mode, shared_first=False):
    """``x [s, d]`` (normed) -> ``[s, d]``: a head and a block of queries
    at a time against all the keys, dense scores under the causal mask.
    ``shared_first=True`` is a planted fault of the tests (the split of a
    head's query taken the other way round: the shared part first)."""
    s = x.shape[0]
    h, own, shared, dv = m["heads"], m["own"], m["shared_key"], m["dv"]
    q = matmul(x, w["q_proj"], mode).reshape(s, h, own + shared)
    if shared_first:
        q = jnp.concatenate([q[..., shared:], q[..., :shared]], axis=-1)
    c = matmul(x, w["kv_a_proj"], mode)
    latent, k_shared = c[:, :m["rank"]], c[:, m["rank"]:]
    kv = matmul(_rms(latent, m["eps"]) * w["kv_norm"], w["kv_b_proj"],
                mode).reshape(s, h, own + dv)
    k = jnp.concatenate([kv[..., :own], jnp.broadcast_to(
        k_shared[:, None], (s, h, shared))], axis=-1)
    v = kv[..., own:]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def attend(q_b, k_h, v_h, q_at):
        scores = matmul(q_b, t_last(k_h), mode) / math.sqrt(own + shared)
        seen = q_at + jnp.arange(block)[:, None] >= jnp.arange(s)[None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v_h, mode)

    def head(qkv):
        q_h, k_h, v_h = qkv
        return lax.map(
            lambda lo: attend(lax.dynamic_slice_in_dim(q_h, lo, block), k_h,
                              v_h, lo), jnp.arange(0, s, block)).reshape(s, dv)

    heads_first = lambda t: jnp.moveaxis(t, 1, 0)
    attn = lax.map(head, (heads_first(q), heads_first(k), heads_first(v)))
    return matmul(jnp.moveaxis(attn, 0, 1).reshape(s, h * dv), w["o_proj"],
                  mode)


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


def route(x, router, choice_bias, *, m, scale=None):
    """``(picks [s, k], weights [s, k])`` over all the router's experts:
    the ``k`` largest of score + bias, weighed by their scores alone.  The
    reference routes for itself, at the highest precision whatever ``mode``
    (a pick is no matmul operand to round)."""
    scores = jax.nn.sigmoid(
        jnp.matmul(x, router, precision=lax.Precision.HIGHEST))
    _, picks = lax.top_k(scores + lax.stop_gradient(choice_bias), m["top_k"])
    weights = jnp.take_along_axis(scores, picks, axis=-1)
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
    picks, weights = route(x, router, w["choice_bias"], m=m, scale=scale)
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


def _layer(x, w, *, kind, ffn, m, mode, faults):
    mixer = (functools.partial(kda, m=m, mode=mode, **faults.get(KDA, {}))
             if kind == KDA else
             functools.partial(latent_attention, m=m, mode=mode,
                               **faults.get(LATENT, {})))
    x = x + mixer(_rms(x, m["eps"]) * w["mixer_norm"], w)
    arm = (functools.partial(dense_ffn, mode=mode) if ffn == DENSE else
           functools.partial(experts, m=m, mode=mode))
    return x + arm(_rms(x, m["eps"]) * w["ffn_norm"], w)


def of_layer(weights: dict, i: int, m: dict) -> dict:
    """Layer ``i``'s tensors and buffer: its own entries, or its slices of
    the stacked ones."""
    stacked = stacked_layers(m)
    if i in stacked:
        j = stacked.index(i)
        return {k.partition(".")[2]: v[j] for k, v in weights.items()
                if k.startswith(f"{STACK}.")}
    prefix = f"layer_{i}."
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def hidden(config: dict, weights: dict, row, mode: str = "f32", faults=None):
    """One row ``[s] int`` -> the last layer's output ``[s, d]``: a layer
    after another, each rematerialised as a whole, the stacked run as ONE
    scan.  ``faults`` (the tests'): ``{KDA: {...}, LATENT: {...}}``, the
    planted faults of :func:`kda` and :func:`latent_attention`."""
    m = dims(config)
    x = weights["embed"][row]
    layer = lambda i: jax.checkpoint(functools.partial(
        _layer, kind=m["kinds"][i], ffn=m["ffns"][i], m=m, mode=mode,
        faults=faults or {}))
    stacked = stacked_layers(m)
    for i in range(m["depth"]):
        if i not in stacked:
            x = layer(i)(x, of_layer(weights, i, m))
        elif i == stacked[0]:
            of_stack = {k.partition(".")[2]: v for k, v in weights.items()
                        if k.startswith(f"{STACK}.")}
            x, _ = lax.scan(lambda x, w: (layer(i)(x, w), None), x, of_stack)
    return x


def forward(config: dict, weights: dict, tokens, mode: str = "f32",
            faults=None):
    """``tokens [rows, s]`` -> logits ``[rows, s, vocab]`` (tests; the loss
    below never holds them whole)."""
    m = dims(config)

    def row(r):
        x = _rms(hidden(config, weights, r, mode, faults), m["eps"])
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
    "choice_bias": ("experts", "choice_bias"),
    "experts_gate": ("experts", "gate"), "experts_up": ("experts", "up"),
    "experts_down": ("experts", "down"),
    "shared_gate": ("experts", "shared_gate"),
    "shared_up": ("experts", "shared_up"),
    "shared_down": ("experts", "shared_down")}
_DENSE_PATHS = {
    "ffn_norm": ("mlp_norm", "scale"),
    **{f"ffn_{n}": ("mlp", f"{n}_proj", "kernel")
       for n in ("gate", "up", "down")}}
#: the mixer's name in the program's tree, by the layer's kind, and its
#: tensors that are no ``Dense`` kernel
_MIXER = {KDA: "kda", LATENT: "latent_attn"}
_MIXER_LEAVES = {"conv": ("conv",), "A_log": ("A_log",),
                 "dt_bias": ("dt_bias",), "gated_norm": ("norm",),
                 "kv_norm": ("kv_norm", "scale")}


def _path(name: str, m: dict) -> tuple:
    layer, _, leaf = name.rpartition(".")
    if not layer:
        return _TOP_PATHS[name]
    i = int(layer.rpartition("_")[2])
    if leaf == "mixer_norm":
        return (layer, "mixer_norm", "scale")
    if leaf in _MIXER_LEAVES:
        return (layer, _MIXER[m["kinds"][i]]) + _MIXER_LEAVES[leaf]
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
    from tpudist.models.hybrid import HybridLM, HybridSizes
    from tpudist.telemetry import names

    m = dims(config)
    kind = {KDA: names.CHANNEL_LINEAR, LATENT: names.LATENT}
    arm = {DENSE: names.DENSE_FFN, SPARSE: names.EXPERT_SHARE}
    sizes = HybridSizes(
        d_model=m["d"], head_dim=m["own"] + m["shared_key"],
        n_heads=m["heads"], n_kv_heads=m["heads"],
        latent_rank=m["rank"], latent_key_dims=(m["own"], m["shared_key"]),
        latent_value_dim=m["dv"],
        linear_key_heads=m["kh"], linear_value_heads=m["kh"],
        linear_key_dim=m["kd"], linear_value_dim=m["kd"],
        linear_conv_width=m["conv"], linear_gate_rank=m["gate_rank"],
        norm=names.PLAIN, feed_forward=names.EXPERT_SHARE,
        ffn_width=m["ffn_width"], n_experts=m["experts"], held=m["held"],
        first_expert=m["first"], top_k=m["top_k"], expert_width=m["width"],
        shared_width=m["shared"], scoring=names.SIGMOID_BIAS,
        routed_scale=m["scale"], router_trained=m["router_trained"],
        expert_fn=names.GATED_SILU, shared_scored=False, eps=m["eps"])
    if m["chunk"] != 64:
        raise ValueError("the program's channel-gated mixer scans in chunks "
                         "of 64: as_run.kda_chunk says what it runs")
    return HybridLM(
        vocab=m["vocab"], layer_types=tuple(kind[k] for k in m["kinds"]),
        sizes=sizes, dtype=jnp.dtype(config["as_run"]["compute_dtype"]),
        remat=job["remat"] is not None,
        remat_policy=job["remat"] or "nothing",
        feed_forwards=tuple(arm[f] for f in m["ffns"]))


def program_tree(config: dict, weights: dict) -> dict:
    m = dims(config)
    params: dict = {}
    for name, value in unstacked(config, weights).items():
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


def kda_scan_flops_per_token(m: dict) -> float:
    """FLOPs of one position of ONE KDA layer's chunked recurrence, all its
    heads, forward, each product once at its live (causal) half: with ``c``
    the chunk, a position's row of ``A`` and of ``q k^T`` against the
    ``c / 2`` keys before it in the mean (``dk`` each), its row of ``T``
    into ``u`` (``dv``) and ``w`` (``dk``), of the scan ``w S``, ``q S`` and
    ``k^T v'`` (``dk x dv`` each) and ``(q k^T) v'`` (``c / 2`` x ``dv``).
    The inverse's own products, the sub-blocks' dead entries and every
    rematerialisation are the program's choice and are not counted."""
    c, dk, dv = m["chunk"], m["kd"], m["kd"]
    half = c / 2.0
    return 2.0 * m["kh"] * (3 * dk * dv + half * (3 * dk + 2 * dv))


def forward_flops_per_token(config: dict, seq: int) -> list:
    """Model FLOPs of one forward pass, a token, by part, a LAYER, for the
    experts held, and ``head`` last (one multiply-add is 2; norms, softmax,
    the convolution, gates' sigmoids and other vector work are left out as
    ``flops.py`` leaves them out)."""
    m = dims(config)
    d = m["d"]
    out = []
    for kind, ffn in zip(m["kinds"], m["ffns"]):
        if kind == KDA:
            inner, rank = m["kh"] * m["kd"], m["gate_rank"]
            part = {
                # q, k, v, o; the two gates' two steps; the write strength
                "kda_matmuls": 2.0 * d * inner * 4
                + 2 * 2.0 * (d * rank + rank * inner) + 2.0 * d * m["kh"],
                "kda_scan": kda_scan_flops_per_token(m)}
        else:
            h, qk = m["heads"], m["own"] + m["shared_key"]
            part = {
                "latent_matmuls": 2.0 * d * h * qk
                + 2.0 * d * (m["rank"] + m["shared_key"])
                + 2.0 * m["rank"] * h * (m["own"] + m["dv"])
                + 2.0 * h * m["dv"] * d,
                # scores at nope + rope wide, values at v wide
                "attn_pairs": 2.0 * flops.causal_pairs(seq) * h
                * (qk + m["dv"]) / seq}
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


def kernel_work(config: dict, per_chip_batch: int, seq: int) -> dict:
    """``name -> (operations, bytes)`` on one chip in one step.

    The three flash kernels of the latent layers at scores ``nope + rope``
    wide and values ``v`` wide, at their live pairs.  Operations: two of the
    six matmuls each (``flops.py``): the forward ``q k^T`` and ``p v``, dq
    ``do v^T`` and ``ds k``, dk/dv ``p^T do`` and ``ds^T q``, each pair one
    product at the scores' width and one at the values'.  Bytes: q, k, dq,
    dk at the scores' width, v, o, do, dv at the values', every tensor
    across HBM once, split over the kernels as ``flops.flash_kernel_work``
    splits them (each backward kernel its own outputs and half of the five
    reads they share).

    ``KDA_SCAN``: the chunked recurrence of the KDA layers, forward once
    and backward twice (:func:`kda_scan_flops_per_token`); q, k, v read
    and o written in bf16, ``g`` (a number a channel) and ``beta`` in
    float32, and as many again twice for the backward's reads and writes."""
    m = dims(config)
    n_latent, n_kda = m["kinds"].count(LATENT), m["kinds"].count(KDA)
    h, qk, dv = m["heads"], m["own"] + m["shared_key"], m["dv"]
    rows = float(per_chip_batch * seq)
    f = n_latent * 2.0 * per_chip_batch * flops.causal_pairs(seq) * h * (
        qk + dv)
    wide, narrow = (n_latent * rows * h * w * 2 for w in (qk, dv))
    shared_reads = 2 * wide + 3 * narrow      # q, k; v, o, do
    scan_bytes = n_kda * rows * m["kh"] * (
        2.0 * (2 * m["kd"] + 2 * m["kd"]) + 4.0 * m["kd"] + 4.0)
    return {flops.FLASH_FWD: (f, 2 * wide + 2 * narrow),
            flops.FLASH_BWD_DQ: (f, wide + shared_reads / 2),
            flops.FLASH_BWD_DKV: (f, wide + narrow + shared_reads / 2),
            KDA_SCAN: (3.0 * n_kda * rows * kda_scan_flops_per_token(m),
                       3.0 * scan_bytes)}


def kda_scan_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` of the KDA layers' chunked recurrence
    (:func:`kernel_work`'s ``KDA_SCAN``), for the scan's roofline reader."""
    return kernel_work(config, per_chip_batch, seq)[KDA_SCAN]


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
