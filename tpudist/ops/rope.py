"""Rotary position embedding: the angles and the half-split rotation.

The one place the angle math lives: the models rotate q and k through
:func:`rope_rotate` (or :func:`rope_rotate_packed` on a fused projection's
output as it lies), and the fused RoPE+QKV kernel
(:mod:`tpudist.ops.fused_linear`) builds its cos/sin tables from the same
:func:`rope_angles`, so the two cannot drift.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_inv_freq(half: int, base: float) -> jax.Array:
    """The plain inverse frequencies ``base^(-i / half)``, ``i`` in ``[0,
    half)``, f32."""
    return base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def yarn_inv_freq(half: int, base: float, *, factor: float,
                  original_positions: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jax.Array:
    """YaRN's inverse frequencies (Peng et al. 2023, arXiv:2309.00071) for
    ``half`` rotated pairs, f32: a pair that turns more than ``beta_fast``
    times in ``original_positions`` keeps its plain frequency, one that
    turns fewer than ``beta_slow`` times takes it over ``factor``, and the
    pairs between them (their numbers cut off to whole ones, outwards) go
    linearly from one to the other.  A function of constants: the
    frequencies do not change with the length.  What multiplies cos and
    sin with them (the attention factor) is the caller's ``scale``."""
    def pair_that_turns(times):
        return half * math.log(original_positions / (times * 2 * math.pi)) / (
            math.log(base))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), 2 * half - 1)
    if low == high:
        high += 0.001
    plain = rope_inv_freq(half, base)
    keep = 1.0 - jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return plain / factor * (1.0 - keep) + plain * keep


def rope_angles_at(offset, seq: int, inv_freq: jax.Array) -> jax.Array:
    """f32 rotary angles ``[(b,) seq, half]`` for positions ``offset +
    [0, seq)`` at the inverse frequencies ``inv_freq [half]``: the one
    place the angle math lives."""
    off = jnp.asarray(offset, jnp.float32)
    positions = off[..., None] + jnp.arange(seq, dtype=jnp.float32)
    return positions[..., None] * inv_freq


def rope_angles(offset, seq: int, half: int, base: float) -> jax.Array:
    """:func:`rope_angles_at` the plain frequencies of ``base``
    (``rope_rotate`` and the fused RoPE+QKV kernel's tables both call it,
    so they cannot drift)."""
    return rope_angles_at(offset, seq, rope_inv_freq(half, base))


def rope_rotate(x: jax.Array, base: float = 10000.0, offset=0,
                seq_axis: int = 2) -> jax.Array:
    """Rotary position embedding over ``[batch, heads, seq, head_dim]``, or
    with ``seq_axis=1`` over the ``[batch, seq, heads, head_dim]`` view of
    a projection's output.

    Angles are computed in f32 (precision-sensitive at long context) on the
    GLOBAL sequence axis — callers apply it before any seq sharding, so
    ring-attention shards see correct absolute positions.  Half-split
    rotation (GPT-NeoX convention).  ``offset`` (static or traced scalar,
    or a ``[batch]`` vector for the slot-batched paged-kernel decode path
    where every lane sits at its own cursor) shifts positions — the
    KV-cache decode path rotates tokens at their absolute position.
    """
    half = x.shape[-1] // 2
    angles = rope_angles(offset, x.shape[seq_axis], half, base)
    if seq_axis == 1:
        angles = angles[..., None, :]                # [(b,) s, 1, half]
    elif angles.ndim == 3:
        # per-batch offsets: broadcast over the heads axis
        angles = angles[:, None]                     # [b, 1, s, half]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    # rotate in f32 (position precision at long context), cast back after
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def rope_rotate_packed(qkv: jax.Array, n_rotated: int, dh: int) -> jax.Array:
    """:func:`rope_rotate` on a fused projection's ``[b, s, (h + 2·kv)·dh]``
    output as it lies: q's and k's heads are its leading ``n_rotated``
    column blocks, rotated on the ``[b, s, heads, dh]`` view (the sequence
    is axis 1 there); v's pass through."""
    b, s, _ = qkv.shape
    by_head = qkv.reshape(b, s, -1, dh)
    return jnp.concatenate(
        [rope_rotate(by_head[:, :, :n_rotated], seq_axis=1),
         by_head[:, :, n_rotated:]], axis=2).reshape(qkv.shape)
