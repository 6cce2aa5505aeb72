"""``model_type`` ``gpt2``: the GPT-2-shaped decoder LM (pre-LN, learned
positions, ungated GELU feed-forward), by the keys of a GPT-2-style
``config.json`` (``n_embd``, ``n_layer``, ``n_head``, ``n_inner``, ...).

The reference is written from the published GPT-2 equations (Radford et al.
2019; the Cerebras-GPT ``config.json`` keys) with the departures each
configuration file lists under ``as_run`` (no biases, untied head, tanh
GELU, LayerNorm epsilon).  Layers are stacked on a leading axis and run
under ``lax.scan`` with each block rematerialised, so a 24-layer model
compiles as one block and the reference's activations stay small beside its
16 bytes a parameter.  The program side is ``tpudist``'s ``TransformerLM``,
imported only where it is built.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from cellbench import flops
from cellbench.reference import lm_loss, matmul, seed_key, t_last

#: per-layer tensors, stacked on axis 0
STACKED = ("ln1", "qkv", "proj", "ln2", "wi", "wo")


def dims(config: dict) -> dict:
    """The sizes of a GPT-2-style ``config.json``, under short names."""
    d = config["n_embd"]
    return dict(vocab=config["vocab_size"], seq=config["n_positions"], d=d,
                layers=config["n_layer"], heads=config["n_head"],
                dh=d // config["n_head"], ff=config["n_inner"])


def weight_shapes(config: dict) -> dict:
    """name -> shape.  Per-layer tensors are stacked on axis 0."""
    m = dims(config)
    L, d, f = m["layers"], m["d"], m["ff"]
    return {
        "tok_embed": (m["vocab"], d), "pos_embed": (m["seq"], d),
        "ln1": (L, d), "qkv": (L, d, 3 * d), "proj": (L, d, d),
        "ln2": (L, d), "wi": (L, d, f), "wo": (L, f, d),
        "ln_f": (d,), "head": (d, m["vocab"]),
    }


def init_weights(config: dict, seed_words) -> dict:
    """GPT-2's published init from the seed (``reference.split_seed`` words):
    normal(0, initializer_range) for embeddings and matrices, the two
    residual projections scaled by 1/sqrt(2 * n_layer), LayerNorm scales 1.
    Trace it under ``jit`` with ``out_shardings`` to make the weights on the
    device, laid out.  Stacked tensors are drawn layer by layer, so that a
    program that wants single layers never holds the stack."""
    std = config["initializer_range"]
    resid = 1.0 / math.sqrt(2 * config["n_layer"])
    key = seed_key(seed_words)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(config).items())):
        if name.startswith("ln"):
            out[name] = jnp.ones(shape, jnp.float32)
            continue
        scale = std * (resid if name in ("proj", "wo") else 1.0)
        k = jax.random.fold_in(key, i)
        if name in STACKED:
            out[name] = jnp.stack([
                scale * jax.random.normal(jax.random.fold_in(k, l),
                                          shape[1:], jnp.float32)
                for l in range(shape[0])])
        else:
            out[name] = scale * jax.random.normal(k, shape, jnp.float32)
    return out


def leaf_names(config: dict) -> list:
    """One name per tensor as a model holds them: ``tok_embed``,
    ``block_3.qkv``, ...  The order of ``reference.leaf_norms``."""
    names = []
    for name, shape in sorted(weight_shapes(config).items()):
        if name in STACKED:
            names += [f"block_{i}.{name}" for i in range(shape[0])]
        else:
            names.append(name)
    return names


# ---------------------------------------------------------------------------
# the reference: forward pass, loss and gradients of one block of rows


def _layernorm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale


def _gelu(x, kind):
    if kind == "gelu_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if kind == "gelu":
        return 0.5 * x * (1.0 + lax.erf(x / math.sqrt(2.0)))
    raise ValueError(f"unknown activation {kind!r}")


def _block(x, w, *, m, eps, act, mode):
    b, s, d = x.shape
    h = _layernorm(x, w["ln1"], eps)
    qkv = matmul(h, w["qkv"], mode)

    def heads(t):   # [b, s, d] -> [b, heads, s, dh]
        return t.reshape(b, s, m["heads"], m["dh"]).transpose(0, 2, 1, 3)

    q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
    scores = matmul(q, t_last(k), mode) / math.sqrt(m["dh"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = matmul(jax.nn.softmax(scores, axis=-1), v, mode)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + matmul(attn, w["proj"], mode)
    h = _layernorm(x, w["ln2"], eps)
    h = _gelu(matmul(h, w["wi"], mode), act)
    return x + matmul(h, w["wo"], mode)


def forward(config: dict, weights: dict, tokens: jax.Array,
            mode: str = "f32") -> jax.Array:
    """``tokens [batch, seq] int`` -> logits ``[batch, seq, vocab]`` f32."""
    m = dims(config)
    run = config["as_run"]
    eps, act = run["layer_norm_epsilon"], run["activation"]
    x = weights["tok_embed"][tokens] + weights["pos_embed"][:tokens.shape[1]]
    stacked = {k: weights[k] for k in STACKED}
    block = jax.checkpoint(functools.partial(
        _block, m=m, eps=eps, act=act, mode=mode))
    x, _ = lax.scan(lambda x, w: (block(x, w), None), x, stacked)
    x = _layernorm(x, weights["ln_f"], eps)
    return matmul(x, weights["head"], mode)


def loss_and_grads(config: dict, weights: dict, tokens: jax.Array,
                   mode: str = "f32"):
    return jax.value_and_grad(
        lambda w: lm_loss(forward(config, w, tokens, mode), tokens))(weights)


# ---------------------------------------------------------------------------
# the program side: tpudist's TransformerLM, and the benchmark's weights in
# its tree and back


def build_module(config: dict, job: dict):
    """The module ``make_lm_train_step`` takes, at the configuration's
    sizes and the cell's remat policy."""
    from tpudist.models.transformer import TransformerLM

    m = dims(config)
    return TransformerLM(
        vocab=m["vocab"], d_model=m["d"], n_layers=m["layers"],
        n_heads=m["heads"], d_ff=m["ff"], max_len=m["seq"],
        dtype=jnp.dtype(config["as_run"]["compute_dtype"]),
        remat=job["remat"] is not None,
        remat_policy=job["remat"] or "nothing")


def program_tree(config: dict, weights: dict) -> dict:
    """The reference's stacked weights as ``TransformerLM``'s parameters."""
    params = {
        "tok_embed": {"embedding": weights["tok_embed"]},
        "pos_embed": {"embedding": weights["pos_embed"]},
        "LayerNorm_0": {"scale": weights["ln_f"]},
        "head": {"kernel": weights["head"]},
    }
    for i in range(weights["qkv"].shape[0]):
        params[f"block_{i}"] = {
            "LayerNorm_0": {"scale": weights["ln1"][i]},
            "LayerNorm_1": {"scale": weights["ln2"][i]},
            "qkv": {"kernel": weights["qkv"][i]},
            "proj": {"kernel": weights["proj"][i]},
            "wi": {"kernel": weights["wi"][i]},
            "wo": {"kernel": weights["wo"][i]},
        }
    return {"params": params}


def named_leaves(config: dict, params: dict) -> list:
    """The tensors of a program tree in :func:`leaf_names` order."""
    p = params["params"]
    top = {"tok_embed": p["tok_embed"]["embedding"],
           "pos_embed": p["pos_embed"]["embedding"],
           "ln_f": p["LayerNorm_0"]["scale"], "head": p["head"]["kernel"]}
    inner = {"ln1": ("LayerNorm_0", "scale"), "ln2": ("LayerNorm_1", "scale"),
             "qkv": ("qkv", "kernel"), "proj": ("proj", "kernel"),
             "wi": ("wi", "kernel"), "wo": ("wo", "kernel")}
    out = []
    for name in leaf_names(config):
        if name in top:
            out.append(top[name])
        else:
            block, kind = name.split(".")
            a, b = inner[kind]
            out.append(p[block][a][b])
    return out


# ---------------------------------------------------------------------------
# the yardstick: what the architecture's algorithm needs, from shapes


def train_flops_per_token(config: dict, seq: int) -> float:
    m = dims(config)
    return flops.lm_train_flops_per_token(
        seq=seq, d_model=m["d"], n_layers=m["layers"], d_ff=m["ff"],
        vocab=m["vocab"])


def kernel_work(config: dict, per_chip_batch: int, seq: int) -> dict:
    """Every layer's attention runs the three flash kernels."""
    m = dims(config)
    return flops.flash_kernel_work(batch=per_chip_batch, seq=seq,
                                   d_model=m["d"], n_layers=m["layers"])
