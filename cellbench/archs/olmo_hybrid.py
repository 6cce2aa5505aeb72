"""``model_type`` ``olmo_hybrid``: a dense decoder whose layers follow the
pattern of its ``layer_types`` (gated delta-rule linear attention, three in
four, and plain softmax attention), each followed by a gated feed-forward,
with Olmo's norm AFTER each sublayer, by the keys of the model's own
``config.json`` (``linear_key_head_dim``, ``linear_allow_neg_eigval``, ...).

**A share.**  The configuration file may hold a chip's share of a stated
head-parallel deployment: the four head counts (``num_attention_heads``,
``num_key_value_heads``, ``linear_num_key_heads``,
``linear_num_value_heads``) are then the heads HELD here, of the published
counts under ``published``; ``vocab_size`` is the slice of the vocabulary
held; the feed-forward is whole.  What the absent heads would add to a
mixer's output is left out, here as in the program, and that partial output
goes through the layer's norm and on.  The one number heads do not compute
alone, the mean square of ``q_norm`` / ``k_norm`` over all heads' dims, is
taken over the held heads' dims (``departures`` in the file).

The reference is written from the equations (float32 ``jax.numpy``, no
biases anywhere), importing nothing of the program:

- ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w`` (plain, ``w`` round 1);
  ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(FFN(h))``; embedding,
  final norm, untied head, mean next-token cross entropy;
- *full attention*: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` with the
  statistic over all (held) heads' dims together, no rotary positions, causal
  softmax at ``dh^-0.5`` a head, ``W_o``; no gate;
- *gated delta rule*: a projection each for q, k, v, the output gate, ``b``
  and ``a``; a depthwise causal conv (left-padded, no bias) + SiLU over q,
  k, v; ``beta = 2 * sigmoid(b)`` where ``linear_allow_neg_eigval`` (else
  ``sigmoid(b)``), ``g = -exp(A_log) * softplus(a + dt_bias)``; q, k
  L2-normed per head, q scaled ``dk^-0.5``; per head and position
  ``S <- exp(g) S``, ``S <- S + k (x) (beta (v - S^T k))``, ``o = S^T q``,
  **one position at a time** (the program computes it in chunks);
  ``W_o (w_n * rms_dv(o) * silu(gate))``;
- *feed-forward*: ``W_down (silu(x W_gate) * x W_up)``.

**Memory** is what shapes the code (weights + Adam + one gradient are
16 bytes a parameter, 12.26 GB of the chip's 16.9 at the real size, before
any activation): one entry a tensor (``STACKED = ()``), rows one at a time
(``lax.map``), every layer and every piece of a layer under
``jax.checkpoint``; attention a head and a block of ``QUERY_BLOCK`` queries
at a time; the feed-forward, the head and the loss a block of positions at
a time; the recurrence as a two-level ``lax.scan`` whose inner level
(``SCAN_CHUNK`` positions) is rematerialised, so that one state a chunk is
kept and not one a position (``archs/qwen3_next.py::delta_rule``, the same
recurrence, imported).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from cellbench import flops
# the recurrence a position at a time, as a two-level scan: one copy
from cellbench.archs.qwen3_next import SCAN_CHUNK, delta_rule
from cellbench.reference import matmul, seed_key, t_last

#: every tensor is an entry of its own; none is stacked over layers
STACKED = ()
LINEAR, FULL = "linear_attention", "full_attention"
#: queries of one attention block; positions of a block of the
#: feed-forward and of the loss
QUERY_BLOCK = 2048
FFN_BLOCK = 2048
LOSS_BLOCK = 1024
L2_EPS = 1e-6


def dims(config: dict) -> dict:
    """Sizes under short names.  ``layers`` is 1 for the runner's count of
    custom calls (``custom_calls_per_layer`` is then the step's total: a
    pattern's layers do not run the same kernels); ``depth`` is the number
    of layers, the first ``depth`` of the published ``layer_types``.  Head
    counts are those HELD; ``*_all`` the published ones."""
    depth = config["num_hidden_layers"]
    whole = config.get("published", {})
    heads, nv = config["num_attention_heads"], config["linear_num_value_heads"]
    theta = (config.get("rope_parameters") or {}).get("rope_theta")
    if theta is not None:
        raise ValueError("this architecture's full-attention layers carry no "
                         f"rotary positions; rope_theta is {theta}")
    return dict(
        vocab=config["vocab_size"], seq=config["max_position_embeddings"],
        d=config["hidden_size"], layers=1, depth=depth,
        kinds=tuple(config["layer_types"][:depth]),
        heads=heads, kv=config["num_key_value_heads"], dh=config["head_dim"],
        heads_all=whole.get("num_attention_heads", heads),
        eps=config["rms_norm_eps"],
        nk=config["linear_num_key_heads"], nv=nv,
        nv_all=whole.get("linear_num_value_heads", nv),
        dk=config["linear_key_head_dim"], dv=config["linear_value_head_dim"],
        r=nv // config["linear_num_key_heads"],
        conv=config["linear_conv_kernel_dim"],
        beta_scale=2.0 if config["linear_allow_neg_eigval"] else 1.0,
        ffn=config["intermediate_size"])


def _layer_shapes(m: dict, kind: str) -> dict:
    d, f = m["d"], m["ffn"]
    shapes = {"mixer_norm": (d,), "ffn_norm": (d,), "ffn_gate": (d, f),
              "ffn_up": (d, f), "ffn_down": (f, d)}
    if kind == FULL:
        h, kv, dh = m["heads"], m["kv"], m["dh"]
        shapes.update({
            "q_proj": (d, h * dh), "k_proj": (d, kv * dh),
            "v_proj": (d, kv * dh), "o_proj": (h * dh, d),
            "q_norm": (h * dh,), "k_norm": (kv * dh,)})
    else:
        nk, nv, dk, dv = m["nk"], m["nv"], m["dk"], m["dv"]
        shapes.update({
            "q_proj": (d, nk * dk), "k_proj": (d, nk * dk),
            "v_proj": (d, nv * dv), "g_proj": (d, nv * dv),
            "b_proj": (d, nv), "a_proj": (d, nv),
            # the three depthwise convolutions (q, k, v) as one tensor
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
    normal(0, ``as_run.init_std``); norm weights normal(1, ``norm_std``), so
    that a norm left out or put on the other side of its sublayer shows;
    ``A_log`` evenly spaced over the held heads so that the per-position
    decay ``exp(g)`` at ``a + dt_bias = 0`` runs from ``decay_slowest`` to
    ``decay_fastest``; ``dt_bias`` normal(0, ``dt_bias_std``); the
    convolution's taps normal(0, 1 / sqrt(width))."""
    run = config["as_run"]
    m = dims(config)
    key = seed_key(seed_words)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(config).items())):
        leaf = name.rpartition(".")[2]
        draw = jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)
        if leaf == "A_log":
            # -g = exp(A_log) * softplus(0): from -log(slowest) to -log(fastest)
            lo = math.log(-math.log(run["decay_slowest"]) / math.log(2.0))
            hi = math.log(-math.log(run["decay_fastest"]) / math.log(2.0))
            out[name] = jnp.linspace(lo, hi, m["nv"], dtype=jnp.float32)
        elif leaf == "dt_bias":
            out[name] = run["dt_bias_std"] * draw
        elif leaf.endswith("norm"):
            out[name] = 1.0 + run["norm_std"] * draw
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
    return _rms(x, eps) * w


def _blocks(n: int, block: int) -> list:
    return [(i, min(i + block, n)) for i in range(0, n, block)]


def _full_attention(x, w, *, m, mode):
    """``x [s, d]`` -> ``[s, d]``: a head and a block of queries at a
    time, each against the keys up to the block's end."""
    s = x.shape[0]
    h, kv, dh = m["heads"], m["kv"], m["dh"]
    # one statistic a token over every held head's dims together
    q = _norm(matmul(x, w["q_proj"], mode), w["q_norm"], m["eps"])
    k = _norm(matmul(x, w["k_proj"], mode), w["k_norm"], m["eps"])
    q, k = q.reshape(s, h, dh), k.reshape(s, kv, dh)
    v = matmul(x, w["v_proj"], mode).reshape(s, kv, dh)

    @functools.partial(jax.checkpoint, static_argnums=3)
    def attend(q_b, k_h, v_h, start):
        scores = matmul(q_b, t_last(k_h), mode) / math.sqrt(dh)
        seen = (start + jnp.arange(q_b.shape[0])[:, None]
                >= jnp.arange(k_h.shape[0])[None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v_h, mode)

    group = h // kv
    attn = jnp.stack([jnp.concatenate(
        [attend(q[lo:hi, i], k[:hi, i // group], v[:hi, i // group], lo)
         for lo, hi in _blocks(s, QUERY_BLOCK)]) for i in range(h)], axis=1)
    return matmul(attn.reshape(s, h * dh), w["o_proj"], mode)


def _linear_attention(x, w, *, m, mode, carry=True):
    """``x [s, d]`` -> ``[s, d]``.  ``carry=False`` is the planted fault of
    the tests (the state zeroed at every ``SCAN_CHUNK``)."""
    s = x.shape[0]
    nk, nv, dk, dv, r = m["nk"], m["nv"], m["dk"], m["dv"], m["r"]

    @jax.checkpoint
    def project(x, w_q, w_k, w_v, w_g, w_b, w_a, w_conv):
        mixed = jnp.concatenate([matmul(x, w_q, mode), matmul(x, w_k, mode),
                                 matmul(x, w_v, mode)], axis=-1)
        width = w_conv.shape[1]
        padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(padded[j:j + s] * w_conv[:, j]
                                for j in range(width)))
        q, k, v = jnp.split(mixed, [nk * dk, 2 * nk * dk], axis=-1)
        return (q.reshape(s, nk, dk), k.reshape(s, nk, dk),
                v.reshape(s, nv, dv), matmul(x, w_g, mode).reshape(s, nv, dv),
                matmul(x, w_b, mode), matmul(x, w_a, mode))

    q, k, v, gate, b, a = project(x, *(w[f"{n}_proj"] for n in "qkvgba"),
                                  w["conv"])
    beta = m["beta_scale"] * jax.nn.sigmoid(b)
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
    def close(o, gate, w_n, w_out):
        o = w_n * _rms(o, m["eps"]) * jax.nn.silu(gate)
        return matmul(o.reshape(s, nv * dv), w_out, mode)

    return close(o, gate, w["gated_norm"], w["out_proj"])


def _gated_ffn(x, gate, up, down, *, mode):
    """``x [s, d]`` -> ``[s, d]``, a block of positions at a time."""
    one = jax.checkpoint(lambda x, gate, up, down: matmul(
        jax.nn.silu(matmul(x, gate, mode)) * matmul(x, up, mode), down, mode))
    return jnp.concatenate([one(x[lo:hi], gate, up, down)
                            for lo, hi in _blocks(x.shape[0], FFN_BLOCK)])


def mixer(x, w, *, kind, m, mode, carry=True):
    """One layer's mixer alone, ``x [s, d]`` -> ``[s, d]`` before the
    layer's norm (the share test compares it)."""
    if kind == FULL:
        return _full_attention(x, w, m=m, mode=mode)
    return _linear_attention(x, w, m=m, mode=mode, carry=carry)


def _layer(x, w, *, kind, m, mode, carry=True):
    mix = jax.checkpoint(functools.partial(mixer, kind=kind, m=m, mode=mode,
                                           carry=carry))
    x = x + _norm(mix(x, w), w["mixer_norm"], m["eps"])
    y = _gated_ffn(x, w["ffn_gate"], w["ffn_up"], w["ffn_down"], mode=mode)
    return x + _norm(y, w["ffn_norm"], m["eps"])


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

    @jax.checkpoint
    def one(x, targets, w_norm, w_head):
        logits = matmul(_norm(x, w_norm, m["eps"]), w_head, mode)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None],
                                            axis=-1))

    return sum(one(x[lo:hi], targets[lo:hi], weights["final_norm"],
                   weights["head"])
               for lo, hi in _blocks(x.shape[0], LOSS_BLOCK))


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
_FFN_PATHS = {
    "mixer_norm": ("mixer_norm", "scale"), "ffn_norm": ("mlp_norm", "scale"),
    "ffn_gate": ("mlp", "gate_proj", "kernel"),
    "ffn_up": ("mlp", "up_proj", "kernel"),
    "ffn_down": ("mlp", "down_proj", "kernel")}
_LAYER_PATHS = {
    FULL: {
        **_FFN_PATHS,
        **{f"{n}_proj": ("attn", f"{n}_proj", "kernel") for n in "qkvo"},
        "q_norm": ("attn", "q_norm", "scale"),
        "k_norm": ("attn", "k_norm", "scale")},
    LINEAR: {
        **_FFN_PATHS,
        **{f"{n}_proj": ("linear_attn", f"{n}_proj", "kernel")
           for n in "qkvgba"},
        "conv": ("linear_attn", "conv"), "A_log": ("linear_attn", "A_log"),
        "dt_bias": ("linear_attn", "dt_bias"),
        "gated_norm": ("linear_attn", "norm"),
        "out_proj": ("linear_attn", "out_proj", "kernel")}}
_TOP_PATHS = {"embed": ("tok_embed", "embedding"),
              "final_norm": ("final_norm", "scale"),
              "head": ("head", "kernel")}


def _path(name: str, kinds: tuple) -> tuple:
    layer, _, leaf = name.rpartition(".")
    if not layer:
        return _TOP_PATHS[name]
    return (layer,) + _LAYER_PATHS[kinds[int(layer.rpartition("_")[2])]][leaf]


def build_module(config: dict, job: dict):
    from tpudist.models.hybrid import HybridLM, HybridSizes
    from tpudist.telemetry import names

    m = dims(config)
    kind = {LINEAR: names.LINEAR, FULL: names.FULL}
    sizes = HybridSizes(
        d_model=m["d"], n_heads=m["heads"], n_kv_heads=m["kv"],
        head_dim=m["dh"], rotary_dim=0, n_heads_total=m["heads_all"],
        attention=names.NORMED_ATTN,
        linear_key_heads=m["nk"], linear_value_heads=m["nv"],
        linear_key_dim=m["dk"], linear_value_dim=m["dv"],
        linear_conv_width=m["conv"], linear_value_heads_total=m["nv_all"],
        linear_projections=names.SEPARATE, beta_scale=m["beta_scale"],
        norm=names.PLAIN, norm_after=True, feed_forward=names.DENSE_FFN,
        ffn_width=m["ffn"], eps=m["eps"])
    return HybridLM(
        vocab=m["vocab"], layer_types=tuple(kind[k] for k in m["kinds"]),
        sizes=sizes, dtype=jnp.dtype(config["as_run"]["compute_dtype"]),
        remat=job["remat"] is not None,
        remat_policy=job["remat"] or "nothing")


def program_tree(config: dict, weights: dict) -> dict:
    kinds = dims(config)["kinds"]
    params: dict = {}
    for name, value in weights.items():
        node = params
        *parents, last = _path(name, kinds)
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
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
    held (one multiply-add is 2; norms, softmax, gates, the convolution and
    other vector work are left out as ``flops.py`` leaves them out)."""
    m = dims(config)
    d = m["d"]
    qk, vz = m["nk"] * m["dk"], m["nv"] * m["dv"]
    return {
        "linear_attn_matmuls": 2.0 * d * (2 * qk + 2 * vz + 2 * m["nv"])
        + 2.0 * vz * d,
        # S^T k, the rank-one update and S^T q: 3 multiply-adds a state entry
        "delta_rule": 3 * 2.0 * m["nv"] * m["dk"] * m["dv"],
        "attn_matmuls": 2.0 * d * (m["heads"] + 2 * m["kv"]) * m["dh"]
        + 2.0 * m["heads"] * m["dh"] * d,
        "attn_pairs": flops.attention_forward_flops(
            batch=1, seq=seq, d_model=m["heads"] * m["dh"]) / seq,
        "ffn": 3 * 2.0 * d * m["ffn"],
        "head": 2.0 * d * m["vocab"],
    }


def train_flops_per_token(config: dict, seq: int) -> float:
    m = dims(config)
    f = forward_flops_per_token(config, seq)
    linear = f["linear_attn_matmuls"] + f["delta_rule"] + f["ffn"]
    full = f["attn_matmuls"] + f["attn_pairs"] + f["ffn"]
    n_full = m["kinds"].count(FULL)
    return 3.0 * ((m["depth"] - n_full) * linear + n_full * full + f["head"])


def kernel_work(config: dict, per_chip_batch: int, seq: int) -> dict:
    """The three flash kernels of the full-attention layers, as many
    key/value heads as query heads (``flops.flash_kernel_work``)."""
    m = dims(config)
    if m["kv"] != m["heads"]:
        raise ValueError("flops.flash_kernel_work counts equal query and "
                         "key/value heads")
    return flops.flash_kernel_work(
        batch=per_chip_batch, seq=seq, d_model=m["heads"] * m["dh"],
        n_layers=m["kinds"].count(FULL))


def delta_rule_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` the recurrence needs in one training step on
    one chip, all linear-attention layers, forward + backward (twice the
    forward): 3 multiply-adds a state entry a position; q, k, v read and o
    written once in bf16, g and beta in float32, and as many again twice
    for the backward's reads and writes.  The program's padding of 96 and
    192 to the 128 lanes and every recomputation are its own, not counted."""
    m = dims(config)
    n = m["kinds"].count(LINEAR) * per_chip_batch * seq
    ops = 3.0 * n * forward_flops_per_token(config, seq)["delta_rule"]
    forward_bytes = n * m["nv"] * (2.0 * (2 * m["dk"] + 2 * m["dv"]) + 8.0)
    return ops, 3.0 * forward_bytes


def dense_ffn_work(config: dict, per_chip_batch: int, seq: int) -> tuple:
    """``(operations, bytes)`` of the feed-forward's three products in one
    training step on one chip, all layers, forward + backward (twice the
    forward): 6 x 3 x d x width FLOPs a token a layer; the weights read
    once forward and once backward in bf16 and their gradient written in
    float32, the rows in and out of each product in bf16.  The
    rematerialised forward is the program's choice, not counted."""
    m = dims(config)
    rows = per_chip_batch * seq
    weights = 3.0 * m["d"] * m["ffn"]
    ops = 3.0 * rows * forward_flops_per_token(config, seq)["ffn"]
    row_bytes = rows * 2.0 * (2 * m["d"] + 3 * m["ffn"])
    return m["depth"] * ops, m["depth"] * (weights * (2 + 2 + 4)
                                            + 3.0 * row_bytes)
