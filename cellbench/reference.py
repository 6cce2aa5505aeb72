"""Plain reference for the decoder-LM training cells, the part that is free
of architecture: the seed, matmuls at a stated precision, the next-token
loss, Adam, the per-tensor norms and projections, and the loop that follows
a training job's first steps in blocks of rows.  What only an architecture
knows (its weights, their init, the forward pass and so the loss and
gradients of one block of rows) is the configuration's module under
``archs/`` (``archs.load(config)``), passed in as ``arch``.

It imports nothing of the program and takes nothing the program made: the
benchmark makes the weights here, from the seed, and hands the program a
copy.

Precision is one argument, ``mode``:

- ``"f32"``: every matmul at ``Precision.HIGHEST`` - the reference proper;
- ``"fp8"`` (:data:`CONTROL`): the matmul operands (forward and backward)
  are rounded to fp8 e4m3 first, per tensor, and multiplied exactly with
  f32 accumulation.  It is the control of a bf16 configuration: the nearest
  precision below the one the configuration states, the step that would
  tempt a later PR.  A configuration that states another precision brings
  its control with it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MODES = ("f32", "fp8")
CONTROL = "fp8"
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def split_seed(seed: int):
    """Any non-negative whole seed (the driver's pass 2**31) as two int32
    words, so that it is data to the jitted programs: a new seed then runs
    the programs the compile cache already holds."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must not be negative, got {seed}")
    return np.array([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF], np.int32)


def seed_key(seed_words) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed_words[0]), seed_words[1])


def leaf_norms(arch, config: dict, tree: dict) -> jax.Array:
    """L2 norm of every tensor of a stacked tree, ordered as
    ``arch.leaf_names`` (a stacked entry gives one norm per layer)."""
    parts = []
    for name in sorted(arch.weight_shapes(config)):
        x = tree[name].astype(jnp.float32)
        if name in arch.STACKED:
            parts.append(jnp.sqrt(jnp.sum(
                x * x, axis=tuple(range(1, x.ndim)))))
        else:
            parts.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(parts)


PROJECTIONS = 8


def sign_projections(x: jax.Array) -> jax.Array:
    """``[PROJECTIONS]`` inner products of one tensor (one or two axes) with
    fixed +-1 patterns, hashed from the element's indices in exact uint32
    arithmetic, so that two programs and two layouts draw the same pattern.

    Why: rounding errors are noise, and noise all but cancels in a tensor's
    NORM (a 3% error moves it by 0.05%), so the gap of two norms cannot tell
    bf16 from 8 bits.  Against a fixed random direction the noise does not
    cancel: ``<got - ref, r>`` has the size of ``|got - ref|``.  A few scalars
    a tensor then say how far two gradients are apart without either side
    ever holding the other's."""
    x = x.astype(jnp.float32)
    if x.ndim == 1:
        x = x[None]
    i = lax.broadcasted_iota(jnp.uint32, x.shape, 0)
    j = lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    h = i * jnp.uint32(0x9E3779B1) + j * jnp.uint32(0x85EBCA77)
    out = []
    for k in range(PROJECTIONS):
        g = (h + jnp.uint32((k + 1) * 0xC2B2AE3D & 0xFFFFFFFF))
        g = (g ^ (g >> 15)) * jnp.uint32(0x2C1B3C6D)
        g = (g ^ (g >> 12)) * jnp.uint32(0x297A2D39)
        g = g ^ (g >> 15)
        sign = 1.0 - 2.0 * (g >> 31).astype(jnp.float32)
        out.append(jnp.sum(x * sign))
    return jnp.stack(out)


def leaf_projections(arch, config: dict, tree: dict) -> jax.Array:
    """``[tensors, PROJECTIONS]`` of a stacked tree, in ``arch.leaf_names``
    order."""
    parts = []
    for name in sorted(arch.weight_shapes(config)):
        if name in arch.STACKED:
            parts.append(jax.vmap(sign_projections)(tree[name]))
        else:
            parts.append(sign_projections(tree[name])[None])
    return jnp.concatenate(parts)


# ---------------------------------------------------------------------------
# matmuls at a stated precision


def _round(x: jax.Array, mode: str) -> jax.Array:
    """``x`` rounded to ``mode``'s operand type, returned as float32."""
    if mode == "f32":
        return x
    if mode != "fp8":
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)   # e4m3's largest
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _dot(a, b):
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def t_last(x):
    """``x`` with its last two axes swapped."""
    return jnp.swapaxes(x, -1, -2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul(a, b, mode):
    """``a @ b`` over the last two axes with both operands rounded to
    ``mode``; the two backward products round their operands too.  ``b``
    is either a weight ``[k, n]`` or shaped like ``a`` in its batch axes."""
    return _dot(_round(a, mode), _round(b, mode))


def _matmul_fwd(a, b, mode):
    return matmul(a, b, mode), (a, b)


def _matmul_bwd(mode, res, g):
    a, b = res
    ra, rb, rg = _round(a, mode), _round(b, mode), _round(g, mode)
    da = _dot(rg, t_last(rb))
    if b.ndim == 2:
        db = _dot(t_last(ra.reshape(-1, ra.shape[-1])),
                  rg.reshape(-1, rg.shape[-1]))
    else:
        db = _dot(t_last(ra), rg)
    return da, db


matmul.defvjp(_matmul_fwd, _matmul_bwd)


# ---------------------------------------------------------------------------
# loss, optimizer and the loop over a job's first steps


def lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy over every predicted position."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def adam_update(weights, grads, mu, nu, step, lr):
    """One Adam step (Kingma & Ba 2015, with bias correction; b1 0.9,
    b2 0.999, eps 1e-8, no weight decay).  ``step`` counts from 1."""
    c1, c2 = 1.0 - ADAM_B1 ** step, 1.0 - ADAM_B2 ** step

    def one(w, g, m, v):
        m = ADAM_B1 * m + (1.0 - ADAM_B1) * g
        v = ADAM_B2 * v + (1.0 - ADAM_B2) * g * g
        return w - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS), m, v

    out = jax.tree.map(one, weights, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def train_readings(arch, config: dict, seed: int, batches: list, *, lr: float,
                   rows_per_block: int, mode: str = "f32",
                   weight_sharding=None, token_sharding=None) -> dict:
    """Follow ``len(batches)`` Adam steps from the seeded weights and return
    what ``correct`` compares: every step's loss, and the per-tensor norm
    with its :func:`sign_projections` of the first gradient and of the
    parameters' change.

    Each batch ``[rows, seq]`` is taken ``rows_per_block`` rows at a time and
    the gradients summed, so the reference fits beside nothing else on the
    device whatever the timed batch is.  ``weight_sharding`` (a dict like
    the weights, of ``jax.sharding.Sharding``) lays the reference's state
    over several devices with XLA's own partitioner.
    """
    make = jax.jit(functools.partial(arch.init_weights, config),
                   out_shardings=weight_sharding)
    grad_block = jax.jit(functools.partial(arch.loss_and_grads, config),
                         static_argnames="mode",
                         out_shardings=(None, weight_sharding))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t),
                    donate_argnums=0)
    update = jax.jit(adam_update, donate_argnums=(0, 2, 3))
    norms = jax.jit(lambda t: (leaf_norms(arch, config, t),
                               leaf_projections(arch, config, t)))
    delta = jax.jit(lambda a, s: norms(jax.tree.map(
        jnp.subtract, a, arch.init_weights(config, s))))
    # zeros depend on no input, so without a layout of their own the
    # partitioner replicates them: two whole copies of the model a chip
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=weight_sharding)

    words = split_seed(seed)
    weights = make(words)
    mu, nu = zeros(weights), zeros(weights)
    losses, first_grad, first_proj = [], None, None
    for step, batch in enumerate(batches, start=1):
        if batch.shape[0] % rows_per_block:
            raise ValueError(f"{batch.shape[0]} rows do not divide into "
                             f"blocks of {rows_per_block}")
        n_blocks = batch.shape[0] // rows_per_block
        total, grads = 0.0, None
        for i in range(n_blocks):
            rows = jnp.asarray(
                batch[i * rows_per_block:(i + 1) * rows_per_block])
            if token_sharding is not None:
                rows = jax.device_put(rows, token_sharding)
            loss, g = grad_block(weights, rows, mode=mode)
            total += float(loss)
            grads = g if grads is None else add(grads, g)
        grads = scale(grads, 1.0 / n_blocks)
        losses.append(total / n_blocks)
        if first_grad is None:
            first_grad, first_proj = jax.device_get(norms(grads))
        weights, mu, nu = update(weights, grads, mu, nu, jnp.float32(step),
                                 jnp.float32(lr))
    update_norms, update_proj = jax.device_get(delta(weights, words))
    return dict(losses=losses, grad_norms=first_grad, grad_proj=first_proj,
                update_norms=update_norms, update_proj=update_proj)
