"""Pallas TPU fused linear kernels for the decode hot path: RoPE+QKV
projection and the LoRA gather-matmul.

:func:`fused_rope_qkv` fuses the decode step's QKV projection, the
head split/transpose, and the rotary embedding into one kernel over a
``(slots,)`` grid.  The unfused path runs these as separate HLOs —
Dense matmul, three slices, three reshape/transposes, then
``rope_rotate``'s trig tower — each round-tripping the ``[S, T, d]``
activations through HBM.  Here the weight tile stays resident in VMEM
across the slot loop, the per-slot VECTOR offsets (PR 11's paged
cursors) become per-slot rotary tables built in-graph
(:func:`rope_tables` — the trig tower is ``[S, T, dh]``, tiny), and the
rotation applies in-registers right after the matmul, bit-matching
``rope.rope_rotate`` (same f32 angle/trig math, same half-split
layout).  The optional ``extra`` operand is the LoRA delta, applied
pre-rotation under its ``on`` mask — exactly where ``Block._ad``
applies it on the unfused path.

:func:`lora_delta` is the in-kernel LoRA gather-matmul: instead of
``gather_collection`` materializing each slot's ``[d_in, r]`` /
``[r, d_out]`` factors with an in-graph gather before a batched double
matmul, the FULL adapter pool rides in and each slot's grid step DMAs
only its own factor block, addressed through the scalar-prefetched
adapter ids — the same indirection discipline as the paged KV walk
(sentinel ids clamp; the caller keeps the ``on`` mask select, so
adapter-less slots stay bit-identical to the base model).

``interpret=True`` (any non-TPU backend) is the tier-1 CPU path.
Weight/factor tiles are loaded whole per grid step — fine for the model
sizes this repo runs; tile the contraction dimension before pointing
this at multi-GB weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops.rope import rope_angles, rope_rotate
from tpudist.telemetry import names


def rope_tables(offsets: jax.Array, T: int, dh: int, base: float):
    """Full-width rotary tables ``(cos, sin) [S, T, dh]`` f32 for the
    kernel's roll form of the half-split rotation:
    ``x * [cos, cos] + roll(x, dh/2) * [-sin, sin]`` is, term for term,
    ``rope.rope_rotate``'s ``[x1*cos - x2*sin, x1*sin + x2*cos]``
    (negating a factor negates the product exactly).  The angles are
    ``rope_rotate``'s own (``rope.rope_angles``), per-slot absolute
    offsets."""
    angles = rope_angles(offsets, T, dh // 2, base)    # [S, T, half]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _rope_qkv_kernel(on_ref, h_ref, w_ref, e_ref, cos_ref, sin_ref,
                     q_ref, k_ref, v_ref, *, n_heads: int, n_kv: int,
                     dh: int, rope: bool, has_extra: bool):
    """One slot: matmul -> (+ masked LoRA delta) -> per-head split ->
    rotate.  Heads are static lane slices of the projection (no
    in-kernel reshape/transpose)."""
    s = pl.program_id(0)
    hm = h_ref[0]                                      # [T, d]
    # f32 accumulator (the MXU's; Mosaic refuses a bf16 one), rounded to
    # the compute dtype like the unfused Dense
    qkv = jnp.dot(hm, w_ref[...],
                  preferred_element_type=jnp.float32).astype(hm.dtype)
    if has_extra:
        qkv = jnp.where(on_ref[s] != 0, qkv + e_ref[0], qkv)
    if rope:
        cos, sin = cos_ref[0], sin_ref[0]              # [T, dh] f32

    def emit(o_ref, first, n, rotate):
        for i in range(n):
            x = qkv[:, (first + i) * dh: (first + i + 1) * dh]
            if rotate:
                x = x.astype(jnp.float32)
                x = x * cos + pltpu.roll(x, dh // 2, 1) * sin
            o_ref[0, i] = x.astype(o_ref.dtype)

    emit(q_ref, 0, n_heads, rope)
    emit(k_ref, n_heads, n_kv, rope)
    emit(v_ref, n_heads + n_kv, n_kv, False)


def fused_rope_qkv(
    h: jax.Array,
    w: jax.Array,
    offsets: jax.Array,
    extra: jax.Array | None = None,
    on: jax.Array | None = None,
    *,
    n_heads: int,
    n_kv: int,
    dh: int,
    base: float = 10000.0,
    rope: bool = True,
    interpret: bool = False,
):
    """Fused QKV projection + head split + rotary embedding.

    - ``h [S, T, d]`` — post-norm activations in the compute dtype;
    - ``w [d, n_heads*dh + 2*n_kv*dh]`` — the ``qkv`` Dense kernel
      (same param, fetched via ``_Kernel``), compute dtype;
    - ``offsets [S]`` int32 — each slot's absolute position of the
      window's first token (the rope offset vector);
    - ``extra [S, T, d + 2*kv_dim]`` — optional additive delta (the
      LoRA qkv delta), applied pre-rotation where ``on [S]`` is
      nonzero — the ``Block._ad`` contract in-kernel;
    - ``rope=False`` skips rotation (non-rope models still win the
      dispatch fusion).

    Returns ``(q [S, n_heads, T, dh], k [S, n_kv, T, dh], v)`` with q/k
    already rotated — feed straight to the attention arms with their
    own rope skipped.
    """
    S, T, d = h.shape
    dtot = w.shape[1]
    if w.shape[0] != d or dtot != (n_heads + 2 * n_kv) * dh:
        raise ValueError(f"qkv kernel shape {w.shape} does not match "
                         f"d={d}, n_heads={n_heads}, n_kv={n_kv}, dh={dh}")
    has_extra = extra is not None
    if on is None:
        on = jnp.ones((S,), jnp.int32)

    def hidx(s, *_):
        return (s, 0, 0)

    in_specs = [
        pl.BlockSpec((1, T, d), hidx),
        # one resident copy: the index never moves, so a second
        # pipeline buffer would only double the largest VMEM tenant
        pl.BlockSpec((d, dtot), lambda s, *_: (0, 0),
                     pipeline_mode=pl.Buffered(1)),
    ]
    operands = [on.astype(jnp.int32), h, w]
    if has_extra:
        in_specs.append(pl.BlockSpec((1, T, dtot), hidx))
        operands.append(extra)
    if rope:
        in_specs += [pl.BlockSpec((1, T, dh), hidx)] * 2
        operands += rope_tables(offsets, T, dh, base)

    def kernel(on_ref, h_ref, w_ref, *refs):
        refs = list(refs)
        e_ref = refs.pop(0) if has_extra else None
        cos_ref, sin_ref = (refs.pop(0), refs.pop(0)) if rope else (None,
                                                                    None)
        _rope_qkv_kernel(on_ref, h_ref, w_ref, e_ref, cos_ref, sin_ref,
                         *refs, n_heads=n_heads, n_kv=n_kv, dh=dh,
                         rope=rope, has_extra=has_extra)

    def oidx(s, *_):
        return (s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n_heads, T, dh), oidx),
            pl.BlockSpec((1, n_kv, T, dh), oidx),
            pl.BlockSpec((1, n_kv, T, dh), oidx),
        ],
    )
    q, k, v = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((S, n_heads, T, dh), h.dtype),
            jax.ShapeDtypeStruct((S, n_kv, T, dh), h.dtype),
            jax.ShapeDtypeStruct((S, n_kv, T, dh), h.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the whole weight is one VMEM tenant (12 MiB in f32 at
            # d1024): past the 16 MiB default scope, well inside the
            # v5e's 128 MiB
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * S * T * d * dtot),
            transcendentals=int(S * T * dh),
            bytes_accessed=int((h.size + S * w.size + 3 * S * T * dtot)
                               * h.dtype.itemsize),
        ),
        **names.kernel(names.FUSED_ROPE_QKV),
        interpret=interpret,
    )(*operands)
    return q, k, v


def fused_rope_qkv_reference(h, w, offsets, extra=None, on=None, *,
                             n_heads, n_kv, dh, base=10000.0, rope=True):
    """Plain-jnp twin: Dense matmul + `_ad` select + head split +
    `rope_rotate`, composed exactly as `Block.__call__` does."""
    S, T, d = h.shape
    qkv = h @ w
    if extra is not None:
        m = (on if on is not None else jnp.ones((S,), bool))
        qkv = jnp.where(m[:, None, None] != 0, qkv + extra, qkv)

    def heads(t, n):
        return t.reshape(S, T, n, dh).transpose(0, 2, 1, 3)

    q = heads(qkv[..., : n_heads * dh], n_heads)
    k = heads(qkv[..., n_heads * dh: (n_heads + n_kv) * dh], n_kv)
    v = heads(qkv[..., (n_heads + n_kv) * dh:], n_kv)
    if rope:
        q = rope_rotate(q, base=base, offset=offsets)
        k = rope_rotate(k, base=base, offset=offsets)
    return q, k, v


def _lora_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    """One slot: double matmul against its own factor block."""
    x = x_ref[0]                                       # [T, din]
    a = a_ref[0, 0].astype(x.dtype)                    # [din, r]
    bm = b_ref[0, 0].astype(x.dtype)                   # [r, dout]
    # f32 accumulators, each product rounded to the compute dtype like
    # the unfused ``(x @ a) @ b``
    xa = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)
    o_ref[0] = jnp.dot(xa, bm,
                       preferred_element_type=jnp.float32).astype(o_ref.dtype)


def lora_delta(
    x: jax.Array,
    pool_a: jax.Array,
    pool_b: jax.Array,
    ids: jax.Array,
    *,
    layer: int,
    interpret: bool = False,
):
    """In-kernel LoRA gather-matmul: ``delta[s] = (x[s] @ A[ids[s]]) @
    B[ids[s]]`` without materializing the gathered factors.

    - ``x [S, T, d_in]`` — activations in the compute dtype;
    - ``pool_a [L, B, d_in, r]`` / ``pool_b [L, B, r, d_out]`` — the
      FULL adapter pool (f32 factors, cast to the compute dtype
      in-registers, matching ``Block._ad``);
    - ``ids [S]`` int32 — per-slot adapter block ids (sentinel ``B`` =
      no adapter; clamped here, masked by the caller's ``on`` select).

    Returns ``[S, T, d_out]`` in ``x.dtype``.
    """
    S, T, d_in = x.shape
    L, B, _, r = pool_a.shape
    d_out = pool_b.shape[-1]
    if not 0 <= layer < L:
        raise ValueError(f"layer {layer} out of range [0, {L})")

    def a_index(s, ids_ref):
        return (layer, jnp.minimum(ids_ref[s], B - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, T, d_in), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec((1, 1, d_in, r), a_index),
            pl.BlockSpec((1, 1, r, d_out), a_index),
        ],
        out_specs=pl.BlockSpec((1, T, d_out), lambda s, *_: (s, 0, 0)),
    )
    out = pl.pallas_call(
        _lora_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, T, d_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * S * T * r * (d_in + d_out)),
            transcendentals=0,
            bytes_accessed=int(
                (x.size + S * (d_in * r + r * d_out) + S * T * d_out)
                * x.dtype.itemsize),
        ),
        **names.kernel(names.LORA_DELTA),
        interpret=interpret,
    )(ids.astype(jnp.int32), x, pool_a, pool_b)
    return out


def lora_delta_reference(x, pool_a, pool_b, ids, *, layer):
    """Plain-jnp twin: `gather_collection`'s gather + `Block._ad`'s
    double matmul."""
    B = pool_a.shape[1]
    rows = jnp.minimum(ids, B - 1)
    a = pool_a[layer][rows].astype(x.dtype)            # [S, d_in, r]
    bm = pool_b[layer][rows].astype(x.dtype)           # [S, r, d_out]
    return (x @ a) @ bm
