"""Pallas TPU paged-attention decode kernel: in-kernel block-table walk.

The serving engine's paged KV cache (:mod:`tpudist.models.paged`) keeps
K/V in a ``[L, num_blocks, n_kv, block_size, dh]`` pool addressed through
per-slot block tables.  The gather path materializes a dense
``[slots, max_len]`` view of that pool per dispatch before attention
runs — bytes moved per token scale with POOL GEOMETRY (``max_len``), not
with the tokens a lane actually holds, on exactly the path measured at
100.6% of its HBM roofline (ROOFLINE_r05).  This kernel is the
vLLM-PagedAttention idea in Pallas: the block table rides in as a
scalar-prefetch operand, each grid step's ``BlockSpec`` index map reads
it to DMA ONLY the slot's mapped live blocks straight out of the pool,
int8 blocks dequantize in-registers against their per-(layer, block,
kv-head) scales, and a blockwise online softmax accumulates across the
walk — bytes per token drop to live-KV, at any occupancy.

Decode-window fusion: the query operand is a WINDOW of ``s >= 1`` tokens
(s == 1 is plain decode; s == K+1 is the speculative-decoding verify
pass), and the window's own fresh K/V — written this dispatch, not yet
committed to the pool — rides in as a small side buffer processed as the
walk's final virtual block under the per-query causal mask
(``col <= fill + i``).  One kernel covers every decode shape the slot
engine dispatches, so the spec-verify path and the s=1 hot path cannot
drift apart.

Grid: ``(slots, kv_heads, M + 1)`` with the block walk innermost (TPU
grids run sequentially, so the (m, l, acc) online-softmax state lives in
VMEM scratch across one (slot, head)'s walk).  Steps past a slot's live
block count re-map to its last live block — Pallas elides the DMA when
consecutive grid steps repeat a block index, so a short lane costs
fetches proportional to ITS prefix, not the table width.  Grouped-query
attention runs natively: the q rows of one kv head's group are the
kernel's row tile, and each K/V block is fetched once per GROUP, never
per q head.

``interpret=True`` (any non-TPU backend) runs the kernel through the
Pallas interpreter — tier-1 exercises the exact same walk/mask/dequant
code on CPU.  Numerical contract vs the gather path: identical
dequantization (``int8.astype(compute) * scale.astype(compute)``),
identical masking constant (−1e30), f32 score/softmax accumulation —
the only difference is online-softmax accumulation order, so logits
agree to float tolerance and greedy token streams are byte-identical in
practice (tests pin both).

No reference counterpart (the reference ships no kernels — SURVEY.md
§0); PAPER.md names Pallas kernels as the TPU-native equivalent of the
reference's native stack.  This is the serving half's first custom
kernel and the template for the next ones (fused sampling, fused
RoPE+QKV).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.telemetry import names

_MASK_VALUE = -1e30


def walk_scales(scale: jax.Array, layer: int, table: jax.Array) -> jax.Array:
    """One layer's dequant scales gathered along each slot's block table:
    ``[L, num_blocks, n_kv]`` -> ``[S, n_kv, 1, M]`` f32.

    The kernels read them as a VMEM row per (slot, kv head), so their
    footprint follows the TABLE (slots x blocks-per-slot), not the pool:
    the whole ``[L, num_blocks, n_kv]`` array as a scalar-prefetch
    operand pads its last axis to 128 words and overflows the v5e's
    1 MiB of SMEM at a pool of 1024 blocks.  The singleton axis makes
    the ``(1, M)`` block's last two dims whole-array dims (Mosaic's
    block rule).  Sentinel ids clamp, like the walk's own index map.
    """
    rows = jnp.minimum(table, scale.shape[1] - 1)
    g = scale[layer][rows].astype(jnp.float32)         # [S, M, n_kv]
    return jnp.swapaxes(g, 1, 2)[:, :, None, :]


def scale_at(row: jax.Array, j) -> jax.Array:
    """Lane ``j`` of a ``[1, M]`` scale row as a scalar (exact: one live
    term plus zeros) — a dynamic lane index Mosaic can lower."""
    lane = lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == j, row, 0.0))


def _kernel(*refs, block_size: int, s: int, quantized: bool,
            scale: float, window):
    """One (slot, kv_head, walk_step) grid step.

    Walk steps ``j < live(slot)`` consume pool block ``table[slot, j]``
    (dequantized in-registers when the pool is int8); the final step
    (``j == M``) consumes the window side buffer under the per-query
    causal mask and emits the normalized output.  Dead steps in between
    (``live <= j < M``) skip compute and, because their index map
    repeats the last live block, their DMA too.
    """
    table_ref, pos_ref, fill_ref, q_ref, pk_ref, pv_ref = refs[:6]
    sk_ref, sv_ref = refs[6:8] if quantized else (None, None)
    wk_ref, wv_ref, o_ref, m_ref, l_ref, acc_ref = refs[-6:]
    del table_ref                     # consumed by the index maps only
    b = pl.program_id(0)
    j = pl.program_id(2)
    nsteps = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    pos0 = pos_ref[b]
    fill = fill_ref[b]
    live = lax.div(pos0 + block_size - 1, block_size)

    def update(s_tile, v_tile):
        """Online-softmax rescale/accumulate (FlashAttention-2 form —
        the same recurrence as ops/flash_attention.py)."""
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(s_tile, axis=-1))
        p = jnp.exp(s_tile - m_new[:, None])
        corr = jnp.exp(m - m_new)
        m_ref[:, 0] = m_new
        l_ref[:, 0] = l * corr + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * corr[:, None] + jnp.dot(
            p.astype(v_tile.dtype), v_tile,
            preferred_element_type=jnp.float32)

    @pl.when(j < live)
    def _():
        q = q_ref[0, 0]                       # [R, dh] (R = group * s)
        k = pk_ref[0, 0, 0]                   # [bs, dh] storage dtype
        v = pv_ref[0, 0, 0]
        if quantized:
            # in-register dequant, bit-matching the gather path's
            # ``int8.astype(compute) * scale.astype(compute)``; lane j
            # of the slot's gathered scale row is block table[b, j]'s
            k = k.astype(q.dtype) * scale_at(sk_ref[0, 0], j).astype(q.dtype)
            v = v.astype(q.dtype) * scale_at(sv_ref[0, 0], j).astype(q.dtype)
        st = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        R, bs = st.shape
        kpos = j * block_size + lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        # pool positions below the dispatch cursor are the live prefix;
        # at/after it is stale/another-tenant garbage (the paged-gather
        # contract) — masked with the same hard constant
        keep = kpos < pos0
        if window is not None:
            qpos = pos0 + fill + lax.broadcasted_iota(
                jnp.int32, (R, bs), 0) % s
            keep &= kpos > qpos - window
        update(jnp.where(keep, st, _MASK_VALUE), v)

    @pl.when(j == nsteps - 1)
    def _():
        q = q_ref[0, 0]
        k = wk_ref[0, 0]                      # [W, dh] compute dtype
        v = wv_ref[0, 0]
        st = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        R, W = st.shape
        col = lax.broadcasted_iota(jnp.int32, (R, W), 1)
        row_i = lax.broadcasted_iota(jnp.int32, (R, W), 0) % s
        # the fused decode-window mask: query i of the window sees the
        # buffer's pre-existing fill plus window tokens 0..i (itself
        # included) — s=1 plain decode and the s=K+1 spec-verify window
        # are the same mask at different s
        keep = col <= fill + row_i
        if window is not None:
            qpos = pos0 + fill + row_i
            keep &= (pos0 + col) > qpos - window
        update(jnp.where(keep, st, _MASK_VALUE), v)
        # every row keeps at least its own token (col == fill + i), so
        # l > 0 always — no dead-row guard needed
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, 0][:, None]).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    scale_k: jax.Array,
    scale_v: jax.Array,
    table: jax.Array,
    pos0: jax.Array,
    fill: jax.Array,
    wk: jax.Array,
    wv: jax.Array,
    *,
    layer: int,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode attention over a block pool, one model layer.

    - ``q [S, n_heads, s, dh]`` — the decode window's queries (already
      rope-rotated at their absolute positions); ``s == 1`` is plain
      decode, ``s > 1`` the speculative verify window;
    - ``pool_k``/``pool_v [L, num_blocks, n_kv, block_size, dh]`` — the
      WHOLE pool (int8 when quantized); ``layer`` is the static layer
      index, consumed by the index map so no per-layer slice (and no
      pool copy) is ever materialized;
    - ``scale_k``/``scale_v [L, num_blocks, n_kv]`` f32 dequant scales
      (gathered along the table into a VMEM row per slot — see
      :func:`walk_scales`; not passed to the kernel at all unless the
      pool is int8);
    - ``table [S, M]`` int32 — per-slot physical block ids (sentinel
      ``num_blocks`` = unmapped; only entries below a slot's live count
      are ever dereferenced, and the walk clamps defensively);
    - ``pos0 [S]`` int32 — the dispatch-start cursor: pool positions
      ``< pos0`` are the live prefix every window query sees;
    - ``fill [S]`` int32 — window-buffer tokens already written BEFORE
      this call's ``s`` queries (the decode scan's step index; 0 for a
      verify window);
    - ``wk``/``wv [S, n_kv, W, dh]`` — the uncommitted window buffer in
      the compute dtype, current tokens included at
      ``[fill, fill + s)``; ``fill + s <= W`` is the caller's contract.

    Returns ``[S, n_heads, s, dh]`` in ``q.dtype``.  ``window`` is the
    sliding-window (local-attention) bound, matching the gather path's
    decode mask.  ``interpret`` routes through the Pallas interpreter
    (the tier-1 CPU path).
    """
    S, nh, s, dh = q.shape
    L, nb, n_kv, bs, _ = pool_k.shape
    M = table.shape[1]
    W = wk.shape[2]
    if nh % n_kv:
        raise ValueError(f"n_heads {nh} must be a multiple of n_kv {n_kv}")
    if not 0 <= layer < L:
        raise ValueError(f"layer {layer} out of range [0, {L})")
    group = nh // n_kv
    R = group * s
    quantized = pool_k.dtype == jnp.int8
    # q heads are kv-major contiguous ([nk, group]) — the same grouping
    # convention as the gather path's grouped einsum
    q4 = q.reshape(S, n_kv, R, dh)

    def phys(b, j, tbl, pos, *_):
        live1 = jnp.maximum(lax.div(pos[b] + bs - 1, bs), 1)
        jj = jnp.minimum(j, live1 - 1)
        return jnp.minimum(tbl[b, jj], nb - 1)

    def q_index(b, h, j, *_):
        return (b, h, 0, 0)

    def pool_index(b, h, j, *refs):
        return (layer, phys(b, j, *refs), h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, R, dh), q_index),
        pl.BlockSpec((1, 1, 1, bs, dh), pool_index),
        pl.BlockSpec((1, 1, 1, bs, dh), pool_index),
    ]
    operands = [table, pos0, fill, q4, pool_k, pool_v]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, M), q_index)] * 2
        operands += [walk_scales(scale_k, layer, table),
                     walk_scales(scale_v, layer, table)]
    in_specs += [pl.BlockSpec((1, 1, W, dh), q_index)] * 2
    operands += [wk, wv]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, n_kv, M + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, R, dh), q_index),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),   # m (running row max)
            pltpu.VMEM((R, 1), jnp.float32),   # l (running normalizer)
            pltpu.VMEM((R, dh), jnp.float32),  # acc (unnormalized out)
        ],
    )
    kernel = functools.partial(
        _kernel, block_size=bs, s=s, quantized=quantized,
        scale=dh ** -0.5, window=window)
    # Upper-bound cost for the XLA scheduler: a full walk touches every
    # table entry plus the window (live-KV elision only shrinks it).
    work = S * n_kv * R * (M * bs + W)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, n_kv, R, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * work * dh),
            transcendentals=int(work),
            bytes_accessed=int(
                (q4.size + 2 * S * n_kv * M * bs * dh + wk.size + wv.size
                 + q4.size) * q.dtype.itemsize),
        ),
        **names.kernel(names.PAGED_ATTENTION),
        interpret=interpret,
    )(*operands)
    return out.reshape(S, nh, s, dh)


paged_attention.supports_gqa = True


def paged_attention_reference(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    scale_k: jax.Array,
    scale_v: jax.Array,
    table: jax.Array,
    pos0: jax.Array,
    fill: jax.Array,
    wk: jax.Array,
    wv: jax.Array,
    *,
    layer: int,
    window: int | None = None,
) -> jax.Array:
    """Gather-to-dense XLA reference with the identical masking contract
    — what the kernel must match (the equivalence-oracle in tests; also
    the documentation of the math in plain jnp).

    Gathers the slot's mapped blocks into a dense ``[max_len]`` view
    (sentinels clamp into masked territory, exactly like
    ``_Paged._dense_kv``), appends the window buffer, and runs one
    dense masked softmax per query.
    """
    S, nh, s, dh = q.shape
    L, nb, n_kv, bs, _ = pool_k.shape
    M = table.shape[1]
    W = wk.shape[2]
    group = nh // n_kv
    rows = jnp.minimum(table, nb - 1)                  # [S, M]
    compute = q.dtype

    def view(pool, scale):
        g = pool[layer][rows].astype(compute)          # [S, M, nk, bs, dh]
        if pool.dtype == jnp.int8:
            sc = scale[layer][rows]                    # [S, M, nk]
            g = g * sc[..., None, None].astype(compute)
        g = jnp.moveaxis(g, 2, 1)                      # [S, nk, M, bs, dh]
        return g.reshape(S, n_kv, M * bs, dh)

    ks = jnp.concatenate([view(pool_k, scale_k), wk], axis=2)
    vs = jnp.concatenate([view(pool_v, scale_v), wv], axis=2)
    scale = dh ** -0.5
    qg = q.reshape(S, n_kv, group, s, dh)
    scores = jnp.einsum("bngqd,bnkd->bngqk", qg, ks,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(M * bs + W)
    in_pool = kpos < M * bs
    qpos = pos0[:, None] + fill[:, None] + jnp.arange(s)[None]   # [S, s]
    live = jnp.where(
        in_pool[None, None],
        kpos[None, None] < pos0[:, None, None],
        (kpos[None, None] - M * bs)
        <= fill[:, None, None] + jnp.arange(s)[None, :, None])
    if window is not None:
        abs_k = jnp.where(in_pool[None, None], kpos[None, None],
                          pos0[:, None, None] + kpos[None, None] - M * bs)
        live &= abs_k > qpos[:, :, None] - window
    scores = jnp.where(live[:, None, None], scores, _MASK_VALUE)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngqk,bnkd->bngqd", w.astype(compute), vs,
                     preferred_element_type=jnp.float32)
    return out.reshape(S, nh, s, dh).astype(q.dtype)
