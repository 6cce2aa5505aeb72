"""Test fixture: a second architecture added as files alone (this module,
``../configs`` and ``../workloads``), the way a later PR adds a real one
under ``cellbench/archs``, ``configs`` and ``workloads``.  Not GPT-2's
shape: RMSNorm, rotary positions (no position table), a gated SiLU
feed-forward, and a ``config.json`` in its source's own keys
(``hidden_size``, ``num_hidden_layers``, ...).  ``tpudist`` has no such
block, so the program side is a small flax module of this file's own; the
reference below shares no function with it.  For CPU tests only.
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from cellbench import flops
from cellbench.reference import lm_loss, matmul, seed_key, t_last

#: per-layer tensors, stacked on axis 0
STACKED = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "gate", "up",
           "down")


def dims(config: dict) -> dict:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    return dict(vocab=config["vocab_size"],
                seq=config["max_position_embeddings"], d=d,
                layers=config["num_hidden_layers"], heads=heads,
                dh=d // heads, ff=config["intermediate_size"])


def weight_shapes(config: dict) -> dict:
    m = dims(config)
    L, d, f = m["layers"], m["d"], m["ff"]
    return {
        "embed": (m["vocab"], d), "attn_norm": (L, d), "wq": (L, d, d),
        "wk": (L, d, d), "wv": (L, d, d), "wo": (L, d, d),
        "mlp_norm": (L, d), "gate": (L, d, f), "up": (L, d, f),
        "down": (L, f, d), "norm": (d,), "lm_head": (d, m["vocab"]),
    }


def init_weights(config: dict, seed_words) -> dict:
    """normal(0, initializer_range) for every matrix, norm scales 1."""
    key = seed_key(seed_words)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(config).items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = config["initializer_range"] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    return out


def leaf_names(config: dict) -> list:
    names = []
    for name, shape in sorted(weight_shapes(config).items()):
        if name in STACKED:
            names += [f"layer_{i}.{name}" for i in range(shape[0])]
        else:
            names.append(name)
    return names


# ---------------------------------------------------------------------------
# the reference


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """Rotary positions on ``[b, heads, s, dh]``, the two halves of a head
    paired (the GPT-NeoX convention)."""
    s, half = x.shape[-2], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(x, w, *, m, eps, theta, mode):
    b, s, d = x.shape
    h = _rmsnorm(x, w["attn_norm"], eps)

    def heads(t):
        return t.reshape(b, s, m["heads"], m["dh"]).transpose(0, 2, 1, 3)

    q = _rotate(heads(matmul(h, w["wq"], mode)), theta)
    k = _rotate(heads(matmul(h, w["wk"], mode)), theta)
    v = heads(matmul(h, w["wv"], mode))
    scores = matmul(q, t_last(k), mode) / math.sqrt(m["dh"])
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = matmul(jax.nn.softmax(scores, axis=-1), v, mode)
    x = x + matmul(attn.transpose(0, 2, 1, 3).reshape(b, s, d), w["wo"], mode)
    h = _rmsnorm(x, w["mlp_norm"], eps)
    h = jax.nn.silu(matmul(h, w["gate"], mode)) * matmul(h, w["up"], mode)
    return x + matmul(h, w["down"], mode)


def forward(config: dict, weights: dict, tokens, mode: str = "f32"):
    layer = jax.checkpoint(functools.partial(
        _layer, m=dims(config), eps=config["rms_norm_eps"],
        theta=config["rope_theta"], mode=mode))
    x, _ = lax.scan(lambda x, w: (layer(x, w), None), weights["embed"][tokens],
                    {k: weights[k] for k in STACKED})
    x = _rmsnorm(x, weights["norm"], config["rms_norm_eps"])
    return matmul(x, weights["lm_head"], mode)


def loss_and_grads(config: dict, weights: dict, tokens, mode: str = "f32"):
    return jax.value_and_grad(
        lambda w: lm_loss(forward(config, w, tokens, mode), tokens))(weights)


# ---------------------------------------------------------------------------
# the program side: a flax module of the fixture's own


class Decoder(nn.Module):
    vocab: int
    d: int
    layers: int
    heads: int
    ff: int
    eps: float
    theta: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, tokens):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  param_dtype=jnp.float32)
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype, param_dtype=jnp.float32)
        b, s = tokens.shape
        dh = self.d // self.heads
        position = jnp.arange(s, dtype=jnp.float32)[:, None]
        angle = position * (self.theta ** (
            -jnp.arange(dh // 2, dtype=jnp.float32) * 2.0 / dh))[None]
        cos = jnp.cos(angle)[None, :, None, :]
        sin = jnp.sin(angle)[None, :, None, :]

        def rope(t):   # [b, s, heads, dh]
            lo, hi = jnp.split(t.astype(jnp.float32), 2, axis=-1)
            return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                                   axis=-1).astype(self.dtype)

        x = nn.Embed(self.vocab, self.d, dtype=self.dtype,
                     param_dtype=jnp.float32, name="embed_tokens")(tokens)
        for i in range(self.layers):
            h = norm(name=f"layer_{i}_input_norm")(x)
            q, k, v = (dense(self.d, name=f"layer_{i}_{n}_proj")(h).reshape(
                b, s, self.heads, dh) for n in "qkv")
            attn = jax.nn.dot_product_attention(rope(q), rope(k), v,
                                                is_causal=True)
            x = x + dense(self.d, name=f"layer_{i}_o_proj")(
                attn.reshape(b, s, self.d))
            h = norm(name=f"layer_{i}_post_norm")(x)
            h = (nn.silu(dense(self.ff, name=f"layer_{i}_gate_proj")(h))
                 * dense(self.ff, name=f"layer_{i}_up_proj")(h))
            x = x + dense(self.d, name=f"layer_{i}_down_proj")(h)
        x = norm(name="final_norm")(x)
        return dense(self.vocab, name="lm_head")(x)


#: reference name -> (the module's parameter name, with {i} for a layer; leaf)
PROGRAM_NAMES = {
    "embed": ("embed_tokens", "embedding"), "norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "kernel"),
    "attn_norm": ("layer_{i}_input_norm", "scale"),
    "mlp_norm": ("layer_{i}_post_norm", "scale"),
    "wq": ("layer_{i}_q_proj", "kernel"), "wk": ("layer_{i}_k_proj", "kernel"),
    "wv": ("layer_{i}_v_proj", "kernel"), "wo": ("layer_{i}_o_proj", "kernel"),
    "gate": ("layer_{i}_gate_proj", "kernel"),
    "up": ("layer_{i}_up_proj", "kernel"),
    "down": ("layer_{i}_down_proj", "kernel"),
}


def build_module(config: dict, job: dict):
    m = dims(config)
    return Decoder(vocab=m["vocab"], d=m["d"], layers=m["layers"],
                   heads=m["heads"], ff=m["ff"], eps=config["rms_norm_eps"],
                   theta=config["rope_theta"],
                   dtype=jnp.dtype(config["as_run"]["compute_dtype"]))


def program_tree(config: dict, weights: dict) -> dict:
    params = {}
    for name, (module, leaf) in PROGRAM_NAMES.items():
        if name in STACKED:
            for i in range(weights[name].shape[0]):
                params[module.format(i=i)] = {leaf: weights[name][i]}
        else:
            params[module] = {leaf: weights[name]}
    return {"params": params}


def named_leaves(config: dict, params: dict) -> list:
    out = []
    for name in leaf_names(config):
        layer, _, kind = name.rpartition(".")
        module, leaf = PROGRAM_NAMES[kind]
        i = layer.split("_")[-1] if layer else ""
        out.append(params["params"][module.format(i=i)][leaf])
    return out


# ---------------------------------------------------------------------------
# the yardstick


def train_flops_per_token(config: dict, seq: int) -> float:
    """Three forward passes' worth; a layer's matmuls a token: q, k, v, o
    8 d^2, causal attention, the gated feed-forward's three 6 d f; head
    2 d V."""
    m = dims(config)
    d = m["d"]
    layer = (8.0 * d * d + 6.0 * d * m["ff"]
             + flops.attention_forward_flops(batch=1, seq=seq, d_model=d) / seq)
    return 3.0 * (m["layers"] * layer + 2.0 * d * m["vocab"])


def kernel_work(config: dict, per_chip_batch: int, seq: int) -> dict:
    """Its step runs no named kernel."""
    return {}
