"""Rotary position embedding: the angles and the half-split rotation.

The one place the angle math lives: the models rotate q and k through
:func:`rope_rotate` (or :func:`rope_rotate_packed` on a fused projection's
output as it lies), and the fused RoPE+QKV kernel
(:mod:`tpudist.ops.fused_linear`) builds its cos/sin tables from the same
:func:`rope_angles`, so the two cannot drift.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rope_angles(offset, seq: int, half: int, base: float) -> jax.Array:
    """f32 rotary angles ``[(b,) seq, half]`` for positions ``offset +
    [0, seq)`` — the one place the angle math lives (``rope_rotate`` and
    the fused RoPE+QKV kernel's tables both call it, so they cannot
    drift)."""
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    off = jnp.asarray(offset, jnp.float32)
    positions = off[..., None] + jnp.arange(seq, dtype=jnp.float32)
    return positions[..., None] * freqs


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
