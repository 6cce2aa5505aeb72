"""``model_type`` ``qwen3_next``: a decoder whose layers follow a pattern of
gated delta-rule linear attention (``full_attention_interval - 1`` layers in
every ``full_attention_interval``) and gated softmax attention, each
followed by routed experts with a gated shared expert, by the keys of the
model's own ``config.json`` (``linear_num_value_heads``, ``num_experts``,
``partial_rotary_factor``, ...).

**A share.**  The configuration file may hold a chip's share of a stated
expert-parallel deployment: ``num_experts`` is then the experts HELD here
(``as_run.first_expert`` on) while the router keeps its published width
(``as_run.router_experts``), its top ``num_experts_per_tok`` and its
renormalisation over all of them; ``vocab_size`` is the slice of the
vocabulary held.  What the absent experts would add is left out, here as in
the program, and that partial result goes on to the next layer.

The reference is written from the equations (float32 ``jax.numpy``, no
biases anywhere), importing nothing of the program:

- ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred);
  ``h = x + Mixer(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; embedding,
  final norm, untied head, mean next-token cross entropy;
- *full attention*: ``q_proj`` viewed ``[heads, 2 * dh]`` gives the query and
  a gate; q and k RMS-normed per head (``1 + w``); rotary on the first
  ``partial_rotary_factor * dh`` dims (pairs ``(i, i + half)``); causal
  softmax, each kv head serving ``heads / kv`` query heads;
  ``o_proj(attn * sigmoid(gate))``;
- *gated delta rule*: ``in_proj_qkvz`` per key head ``[q, k, v x r, z x r]``,
  ``in_proj_ba`` per key head ``[b x r, a x r]``; depthwise causal conv
  (left-padded, no bias) + SiLU over ``concat(q, k, v)``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; q, k
  repeated ``r`` times, L2-normed, q scaled ``dk^-0.5``; per head and
  position ``S <- exp(g) S``, ``S <- S + k (x) (beta (v - S^T k))``,
  ``o = S^T q``, **one position at a time** (the program computes it in
  chunks); ``out_proj(w_n * rms(o) * silu(z))`` (plain ``w_n``);
- *experts*: ``softmax(x W_r)`` over all experts, top k renormalised to sum
  1; the sum runs over the picks that are held here;
  ``shared = sigmoid(x w_s) * E_shared(x)``.

**Memory** is what shapes the code (weights + Adam + one gradient are
16 bytes a parameter before any activation): one entry a tensor
(``STACKED = ()``), rows one at a time (``lax.map``), every layer, every
piece of a layer, every attention head, every held expert and every block
of positions of the loss under ``jax.checkpoint``; the recurrence as a
two-level ``lax.scan`` whose inner level (``SCAN_CHUNK`` positions) is
rematerialised, so that one state a chunk is kept and not one a position.
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
LINEAR, FULL = "linear_attention", "full_attention"
#: positions of the recurrence's rematerialised inner scan, and of a block
#: of the loss
SCAN_CHUNK = 64
LOSS_BLOCK = 1024
L2_EPS = 1e-6


def dims(config: dict) -> dict:
    """Sizes under short names.  ``layers`` is 1 for the runner's count of
    custom calls (``custom_calls_per_layer`` is then the step's total: a
    pattern's layers do not run the same kernels); ``depth`` is the number
    of layers."""
    run = config["as_run"]
    nk, nv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    depth = config["num_hidden_layers"]
    period = config["full_attention_interval"]
    dh = config["head_dim"]
    return dict(
        vocab=config["vocab_size"], seq=config["max_position_embeddings"],
        d=config["hidden_size"], layers=1, depth=depth,
        kinds=tuple(FULL if (i + 1) % period == 0 else LINEAR
                    for i in range(depth)),
        heads=config["num_attention_heads"],
        kv=config["num_key_value_heads"], dh=dh,
        rotary=int(config["partial_rotary_factor"] * dh),
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        nk=nk, nv=nv, dk=config["linear_key_head_dim"],
        dv=config["linear_value_head_dim"], r=nv // nk,
        conv=config["linear_conv_kernel_dim"],
        held=config["num_experts"], experts=run["router_experts"],
        first=run["first_expert"], top_k=config["num_experts_per_tok"],
        width=config["moe_intermediate_size"],
        shared=config["shared_expert_intermediate_size"])


def _layer_shapes(m: dict, kind: str) -> dict:
    d, w, sw, e = m["d"], m["width"], m["shared"], m["held"]
    shapes = {
        "mixer_norm": (d,), "experts_norm": (d,),
        "router": (d, m["experts"]),
        # a layer's held experts as ONE two-axis tensor a projection
        "experts_gate": (e * d, w), "experts_up": (e * d, w),
        "experts_down": (e * w, d),
        "shared_gate": (d, sw), "shared_up": (d, sw), "shared_down": (sw, d),
        "shared_score": (d, 1),
    }
    if kind == FULL:
        h, kv, dh = m["heads"], m["kv"], m["dh"]
        shapes.update({
            "q_proj": (d, h * 2 * dh), "k_proj": (d, kv * dh),
            "v_proj": (d, kv * dh), "o_proj": (h * dh, d),
            "q_norm": (dh,), "k_norm": (dh,)})
    else:
        nk, nv, dk, dv, r = m["nk"], m["nv"], m["dk"], m["dv"], m["r"]
        shapes.update({
            "in_proj_qkvz": (d, nk * (2 * dk + 2 * r * dv)),
            "in_proj_ba": (d, nk * 2 * r),
            "conv": (2 * nk * dk + nv * dv, m["conv"]),
            "A_log": (nv,), "dt_bias": (nv,), "gated_norm": (dv,),
            "out_proj": (nv * dv, d)})
    return shapes


def weight_shapes(config: dict) -> dict:
    m = dims(config)
    shapes = {"embed": (m["vocab"], m["d"]), "final_norm": (m["d"],),
              "head": (m["d"], m["vocab"])}
    for i, kind in enumerate(m["kinds"]):
        shapes.update({f"layer_{i}.{name}": shape
                       for name, shape in _layer_shapes(m, kind).items()})
    return shapes


def leaf_names(config: dict) -> list:
    return sorted(weight_shapes(config))


def init_weights(config: dict, seed_words) -> dict:
    """Seeded weights (``assumed`` in the configuration file): matrices
    normal(0, ``as_run.init_std``), the two residual projections' outputs as
    the others; zero-centred norm weights normal(0, ``norm_std``) round 0
    and the gated norm's round 1, so that ``1 + w`` against ``w`` shows;
    ``A_log`` evenly spaced over heads so that the per-position decay
    ``exp(g)`` at ``a + dt_bias = 0`` runs from ``decay_slowest`` to
    ``decay_fastest``; ``dt_bias`` normal(0, ``dt_bias_std``)."""
    run = config["as_run"]
    m = dims(config)
    key = seed_key(seed_words)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(config).items())):
        k = jax.random.fold_in(key, i)
        leaf = name.rpartition(".")[2]
        draw = jax.random.normal(k, shape, jnp.float32)
        if leaf == "A_log":
            # -g = exp(A_log) * softplus(0): from -log(slowest) to -log(fastest)
            lo = math.log(-math.log(run["decay_slowest"]) / math.log(2.0))
            hi = math.log(-math.log(run["decay_fastest"]) / math.log(2.0))
            out[name] = jnp.linspace(lo, hi, m["nv"], dtype=jnp.float32)
        elif leaf == "dt_bias":
            out[name] = run["dt_bias_std"] * draw
        elif leaf == "gated_norm":
            out[name] = 1.0 + run["norm_std"] * draw
        elif leaf.endswith("norm"):
            out[name] = run["norm_std"] * draw
        elif leaf == "router":
            out[name] = run["router_init_std"] * draw
        elif leaf == "conv":
            out[name] = draw / math.sqrt(m["conv"])
        else:
            out[name] = run["init_std"] * draw
    return out


# ---------------------------------------------------------------------------
# the reference: one row at a time


def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(x, w, eps):
    return _rms(x, eps) * (1.0 + w)


def _rotate(x, m):
    """Rotary positions on the first ``m['rotary']`` dims of ``x [s, heads,
    dh]``, pairs ``(i, i + rotary / 2)``; the rest passes through."""
    half = m["rotary"] // 2
    freq = m["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., 2 * half:]], axis=-1)


def _full_attention(x, w, *, m, mode):
    """``x [s, d]`` (normed) -> ``[s, d]``, a query head at a time."""
    s = x.shape[0]
    h, kv, dh = m["heads"], m["kv"], m["dh"]
    qg = matmul(x, w["q_proj"], mode).reshape(s, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = matmul(x, w["k_proj"], mode).reshape(s, kv, dh)
    v = matmul(x, w["v_proj"], mode).reshape(s, kv, dh)
    q = _rotate(_norm(q, w["q_norm"], m["eps"]), m)
    k = _rotate(_norm(k, w["k_norm"], m["eps"]), m)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(q_h, k_h, v_h):
        scores = matmul(q_h, t_last(k_h), mode) / math.sqrt(dh)
        scores = jnp.where(causal, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v_h, mode)

    group = h // kv
    attn = jnp.stack([head(q[:, i], k[:, i // group], v[:, i // group])
                      for i in range(h)], axis=1)
    attn = attn * jax.nn.sigmoid(gate)
    return matmul(attn.reshape(s, h * dh), w["o_proj"], mode)


def delta_rule(q, k, v, g, beta):
    """The recurrence as written: ``q, k [s, heads, dk]``, ``v [s, heads,
    dv]``, ``g, beta [s, heads]`` -> ``o [s, heads, dv]``.  Elementwise
    float32 (no matmul unit, so no precision to state)."""
    s, heads, dk = q.shape
    chunk = SCAN_CHUNK if s % SCAN_CHUNK == 0 else 1

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
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


def _linear_attention(x, w, *, m, mode, carry=True):
    """``x [s, d]`` (normed) -> ``[s, d]``.  ``carry=False`` is the planted
    fault of the tests (the state zeroed at every ``SCAN_CHUNK``)."""
    s = x.shape[0]
    nk, nv, dk, dv, r = m["nk"], m["nv"], m["dk"], m["dv"], m["r"]

    @jax.checkpoint
    def project(x, w_qkvz, w_ba, w_conv):
        qkvz = matmul(x, w_qkvz, mode).reshape(s, nk, 2 * dk + 2 * r * dv)
        ba = matmul(x, w_ba, mode).reshape(s, nk, 2 * r)
        q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        mixed = jnp.concatenate([q.reshape(s, -1), k.reshape(s, -1),
                                 v.reshape(s, -1)], axis=-1)
        width = w_conv.shape[1]
        padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(padded[j:j + s] * w_conv[:, j]
                                for j in range(width)))
        q, k, v = jnp.split(mixed, [nk * dk, 2 * nk * dk], axis=-1)
        return (q.reshape(s, nk, dk), k.reshape(s, nk, dk),
                v.reshape(s, nv, dv), z.reshape(s, nv, dv),
                ba[..., :r].reshape(s, nv), ba[..., r:].reshape(s, nv))

    q, k, v, z, b, a = project(x, w["in_proj_qkvz"], w["in_proj_ba"],
                               w["conv"])
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])

    def unit(t):
        t = t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
        return jnp.repeat(t, r, axis=1)

    q, k = unit(q) * dk ** -0.5, unit(k)
    if carry:
        o = delta_rule(q, k, v, g, beta)
    else:
        cut = lambda t: t.reshape(s // SCAN_CHUNK, SCAN_CHUNK, *t.shape[1:])
        o = jax.vmap(delta_rule)(*map(cut, (q, k, v, g, beta)))
        o = o.reshape(s, nv, dv)

    @jax.checkpoint
    def close(o, z, w_n, w_out):
        o = w_n * _rms(o, m["eps"]) * jax.nn.silu(z)
        return matmul(o.reshape(s, nv * dv), w_out, mode)

    return close(o, z, w["gated_norm"], w["out_proj"])


def _gated_ffn(x, gate, up, down, mode):
    return matmul(jax.nn.silu(matmul(x, gate, mode)) * matmul(x, up, mode),
                  down, mode)


def route(x, router, *, m):
    """``(picks [s, k], weights [s, k])`` over all the router's experts: the
    reference routes for itself, at the highest precision whatever ``mode``
    (a pick is no matmul operand to round)."""
    logits = jnp.matmul(x, router, precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, picks = lax.top_k(probs, m["top_k"])
    return picks, weights / jnp.sum(weights, axis=-1, keepdims=True)


def _experts(x, w, *, m, mode, first=None, held=None, shared=True):
    """``x [s, d]`` (normed) -> ``[s, d]``: the picks held here, every held
    expert over every position with its weight (0 where it was not
    picked), plus the shared expert.  ``first`` / ``held`` (the tests')
    take a narrower run of the experts whose weights are here."""
    first = m["first"] if first is None else first
    held = m["held"] if held is None else held
    d, width = m["d"], m["width"]
    picks, weights = route(x, w["router"], m=m)
    gate = w["experts_gate"].reshape(-1, d, width)
    up = w["experts_up"].reshape(-1, d, width)
    down = w["experts_down"].reshape(-1, width, d)
    one = jax.checkpoint(functools.partial(_gated_ffn, mode=mode))
    y = jnp.zeros_like(x)
    for e in range(first, first + held):
        weight = jnp.sum(jnp.where(picks == e, weights, 0.0), axis=-1)
        i = e - m["first"]        # where the weights here hold expert e
        y = y + weight[:, None] * one(x, gate[i], up[i], down[i])
    if shared:
        score = jax.nn.sigmoid(matmul(x, w["shared_score"], mode))
        y = y + score * one(x, w["shared_gate"], w["shared_up"],
                            w["shared_down"])
    return y


def _layer(x, w, *, kind, m, mode, carry=True):
    mixer = (functools.partial(_full_attention, m=m, mode=mode)
             if kind == FULL else
             functools.partial(_linear_attention, m=m, mode=mode,
                               carry=carry))
    x = x + jax.checkpoint(mixer)(_norm(x, w["mixer_norm"], m["eps"]), w)
    experts = jax.checkpoint(functools.partial(_experts, m=m, mode=mode))
    return x + experts(_norm(x, w["experts_norm"], m["eps"]), w)


def _of_layer(weights: dict, i: int) -> dict:
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
        x = layer(x, _of_layer(weights, i))
    return x


def forward(config: dict, weights: dict, tokens, mode: str = "f32"):
    """``tokens [rows, s]`` -> logits ``[rows, s, vocab]`` (tests; the loss
    below never holds them whole)."""
    m = dims(config)

    def row(r):
        x = _norm(hidden(config, weights, r, mode), weights["final_norm"],
                  m["eps"])
        return matmul(x, weights["head"], mode)

    return jnp.stack([row(r) for r in tokens])


def _row_loss(config, weights, row, mode, carry):
    """Sum over the row's predicted positions of the next token's negative
    log-likelihood, a block of positions at a time."""
    m = dims(config)
    x = hidden(config, weights, row, mode, carry)[:-1]
    targets = row[1:]
    n = x.shape[0]
    block = LOSS_BLOCK if n > LOSS_BLOCK else n
    pad = -n % block
    x = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, block)
    live = (jnp.arange(n + pad) < n).reshape(-1, block)

    @jax.checkpoint
    def one(x, targets, live, w_norm, w_head):
        logits = matmul(_norm(x, w_norm, m["eps"]), w_head, mode)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(live, picked, 0.0))

    return sum(one(x[i], targets[i], live[i], weights["final_norm"],
                   weights["head"]) for i in range(x.shape[0]))


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

#: reference leaf -> path under a layer of the program's tree
_MIXER_PATHS = {
    "mixer_norm": ("mixer_norm", "scale"),
    "experts_norm": ("experts_norm", "scale"),
    "q_proj": ("attn", "q_proj", "kernel"),
    "k_proj": ("attn", "k_proj", "kernel"),
    "v_proj": ("attn", "v_proj", "kernel"),
    "o_proj": ("attn", "o_proj", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"),
    "k_norm": ("attn", "k_norm", "scale"),
    "in_proj_qkvz": ("linear_attn", "in_proj_qkvz", "kernel"),
    "in_proj_ba": ("linear_attn", "in_proj_ba", "kernel"),
    "conv": ("linear_attn", "conv"), "A_log": ("linear_attn", "A_log"),
    "dt_bias": ("linear_attn", "dt_bias"),
    "gated_norm": ("linear_attn", "norm"),
    "out_proj": ("linear_attn", "out_proj", "kernel"),
    "router": ("experts", "router"),
    "experts_gate": ("experts", "gate"), "experts_up": ("experts", "up"),
    "experts_down": ("experts", "down"),
    "shared_gate": ("experts", "shared_gate"),
    "shared_up": ("experts", "shared_up"),
    "shared_down": ("experts", "shared_down"),
    "shared_score": ("experts", "shared_score"),
}
_TOP_PATHS = {"embed": ("tok_embed", "embedding"),
              "final_norm": ("final_norm", "scale"),
              "head": ("head", "kernel")}


def _path(name: str) -> tuple:
    layer, _, leaf = name.rpartition(".")
    return ((layer,) + _MIXER_PATHS[leaf]) if layer else _TOP_PATHS[name]


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
    kind = {LINEAR: names.LINEAR, FULL: names.FULL}
    sizes = HybridSizes(
        d_model=m["d"], n_heads=m["heads"], n_kv_heads=m["kv"],
        head_dim=m["dh"], rotary_dim=m["rotary"], rope_theta=m["theta"],
        linear_key_heads=m["nk"], linear_value_heads=m["nv"],
        linear_key_dim=m["dk"], linear_value_dim=m["dv"],
        linear_conv_width=m["conv"], n_experts=m["experts"], held=m["held"],
        first_expert=m["first"], top_k=m["top_k"], expert_width=m["width"],
        shared_width=m["shared"], eps=m["eps"])
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
        *parents, last = _path(name)
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value if shape is None else value.reshape(shape)
    return {"params": params}


def named_leaves(config: dict, params: dict) -> list:
    out = []
    for name in leaf_names(config):
        node = params["params"]
        for p in _path(name):
            node = node[p]
        out.append(node.reshape(-1, node.shape[-1]) if node.ndim == 3
                   else node)
    return out


# ---------------------------------------------------------------------------
# the yardstick: what the architecture's algorithm needs, from shapes


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """Model FLOPs of one forward pass, a token, by part (one multiply-add
    is 2; norms, softmax, gates, the convolution and other vector work are
    left out as ``flops.py`` leaves them out)."""
    m = dims(config)
    d = m["d"]
    qk, vz = m["nk"] * m["dk"], m["nv"] * m["dv"]
    expert = 3 * 2.0 * d * m["width"]
    return {
        "linear_attn_matmuls": 2.0 * d * (2 * qk + 2 * vz + 2 * m["nv"])
        + 2.0 * vz * d,
        # S^T k, the rank-one update and S^T q: 3 multiply-adds a state entry
        "delta_rule": 3 * 2.0 * m["nv"] * m["dk"] * m["dv"],
        "attn_matmuls": 2.0 * d * (2 * m["heads"] + 2 * m["kv"]) * m["dh"]
        + 2.0 * m["heads"] * m["dh"] * d,
        "attn_pairs": flops.attention_forward_flops(
            batch=1, seq=seq, d_model=m["heads"] * m["dh"]) / seq,
        "router": 2.0 * d * m["experts"],
        # a token's top_k picks fall on the held experts held / experts of
        # the time when the router is even
        "held_experts": expert * m["top_k"] * m["held"] / m["experts"],
        "shared_expert": 3 * 2.0 * d * m["shared"] + 2.0 * d,
        "head": 2.0 * d * m["vocab"],
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    m = dims(config)
    f = forward_flops_per_token(config, seq)
    moe = f["router"] + f["held_experts"] + f["shared_expert"]
    linear = f["linear_attn_matmuls"] + f["delta_rule"] + moe
    full = f["attn_matmuls"] + f["attn_pairs"] + moe
    n_full = m["kinds"].count(FULL)
    return 3.0 * ((m["depth"] - n_full) * linear + n_full * full + f["head"])


def kernel_work(config: dict, per_chip_batch: int, seq: int) -> dict:
    """The three flash kernels of the full-attention layers.  Operations:
    two of the six matmuls each at ``heads * dh`` (``flops.py``).  Bytes:
    grouped key/value heads make k, v, dk, dv ``kv * dh`` wide where q, o,
    do, dq are ``heads * dh``; split over the kernels as
    ``flops.flash_kernel_work`` splits them (each backward kernel its own
    outputs and half of the five reads they share)."""
    m = dims(config)
    n_full = m["kinds"].count(FULL)
    f = n_full * flops.attention_forward_flops(
        batch=per_chip_batch, seq=seq, d_model=m["heads"] * m["dh"])
    wide = n_full * float(per_chip_batch * seq * m["heads"] * m["dh"] * 2)
    narrow = wide * m["kv"] / m["heads"]
    shared_reads = 3 * wide + 2 * narrow      # q, o, do; k, v
    return {flops.FLASH_FWD: (f, 2 * wide + 2 * narrow),
            flops.FLASH_BWD_DQ: (f, wide + shared_reads / 2),
            flops.FLASH_BWD_DKV: (f, 2 * narrow + shared_reads / 2)}


def delta_rule_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` the recurrence needs in one training step on
    one chip, all linear-attention layers, forward + backward (twice the
    forward): 3 multiply-adds a state entry a position; q, k, v read and o
    written once in bf16, g and beta in float32, and as many again twice
    for the backward's reads and writes."""
    m = dims(config)
    n = m["kinds"].count(LINEAR) * per_chip_batch * seq
    ops = 3.0 * n * forward_flops_per_token(config, seq)["delta_rule"]
    forward_bytes = n * m["nv"] * (2.0 * (2 * m["dk"] + 2 * m["dv"]) + 8.0)
    return ops, 3.0 * forward_bytes


def expert_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` of the held experts' grouped products in one
    training step on one chip, all layers, forward + backward, at the rows
    that arrive in the mean (``top_k * held / experts`` of a token's
    picks): the three projections' multiply-adds; every held expert's
    weights read once forward and once backward and their gradient written
    (bf16 in, float32 out), the rows in and out of each product in bf16."""
    m = dims(config)
    rows = per_chip_batch * seq * m["top_k"] * m["held"] / m["experts"]
    per_layer_ops = 3.0 * rows * 3 * 2.0 * m["d"] * m["width"]
    weights = m["held"] * 3.0 * m["d"] * m["width"]
    row_bytes = rows * 2.0 * (2 * m["d"] + 3 * m["width"])
    per_layer_bytes = weights * (2 + 2 + 4) + 3.0 * row_bytes
    return m["depth"] * per_layer_ops, m["depth"] * per_layer_bytes
