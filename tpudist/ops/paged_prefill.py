"""Pallas TPU paged-PREFILL flash-attention kernel: block-table walk plus
in-kernel KV block WRITES.

:mod:`tpudist.ops.paged_attention` closed the decode path's dense
``[slots, max_len]`` gather; this kernel closes the last one — prefill.
The gather prefill path (``_force_chunk``) teacher-forces a chunk one
token at a time over a DENSE per-lane cache gathered from the pool up
front and scattered back afterwards (``_Paged.commit_lanes`` /
``commit_window``), so bytes moved scale with POOL GEOMETRY and the
chunk runs as ``prefill_pad`` sequential dispatches.  Here the whole
batch of chunks runs in ONE fused dispatch per layer:

- the reused prefix (prefix caching / chunked prefill) is walked
  straight out of the pool via the scalar-prefetched block table,
  exactly like the decode kernel — bytes read scale with live prefix;
- the chunk attends to itself under the causal mask as the walk's
  final virtual block (FlashAttention-2 online softmax throughout);
- the blocks the chunk TOUCHES (``ceil`` span of ``[pos0, pos0+clen)``)
  are then emitted as quantized pool blocks in-kernel: the original
  block is read back (partial first block of a chunked-prefill step
  keeps its committed prefix), the chunk's fresh K/V is overlaid via an
  exact one-hot gather, and the merged block is requantized with the
  same ``amax/127`` formula as ``_Paged._scatter_values`` — the caller
  scatters the returned blocks with a sentinel-dropping ``.at[].set``
  (``_Paged.commit_quantized``), never materializing a dense view.

Grid: ``(slots, kv_heads, M + 1 + Mw)`` — ``M`` prefix walk steps (dead
steps past a lane's live count elide their DMA by repeating the last
block index), one chunk self-attention step that also emits the
attention output, then ``Mw`` write steps addressed through a second
scalar-prefetched table (``wtable``) holding the touched blocks' ids
(sentinel rows — dead lanes, untouched tail — clamp and are dropped at
commit).  Because positions at/after ``pos0 + clen`` keep the ORIGINAL
block contents, a partially-filled block's quantization scale is not
polluted by another lane's garbage — slightly better int8 numerics than
the gather path's dense round-trip, same masking contract.

``interpret=True`` (any non-TPU backend) is the tier-1 CPU path.  The
dequant scales ride in, and the fresh scales ride out, as one VMEM row
per (slot, kv head) — ``[S, n_kv, 1, M]`` / ``[S, n_kv, 1, Mw]``, see
:func:`tpudist.ops.paged_attention.walk_scales` — and only for an int8
pool: a float pool passes and emits no scales at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.ops.paged_attention import scale_at, walk_scales
from tpudist.telemetry import names

_MASK_VALUE = -1e30


def _kernel(*refs, block_size: int, chunk: int, n_prefix: int,
            quantized: bool, scale: float, window):
    """One (slot, kv_head, step) grid step.

    Steps ``j < live(slot)`` walk the prefix out of the pool;
    ``j == n_prefix`` is the chunk's causal self-attention and emits the
    normalized output; ``j > n_prefix`` are the write steps — each reads
    the touched block's ORIGINAL contents (same ref pair as the walk,
    re-aimed by the shared index map), overlays the chunk's K/V, and
    emits the requantized block + scale.
    """
    pos_ref, clen_ref, q_ref, kn_ref, vn_ref, pk_ref, pv_ref = refs[2:9]
    if quantized:
        # walk scales [1, 1, 1, M], touched blocks' scales [1, 1, 1, Mw]
        sk_ref, sv_ref, swk_ref, swv_ref = refs[9:13]
        o_ref, ok_ref, ov_ref, osk_ref, osv_ref = refs[13:18]
    else:
        sk_ref = sv_ref = swk_ref = swv_ref = osk_ref = osv_ref = None
        o_ref, ok_ref, ov_ref = refs[9:12]
    m_ref, l_ref, acc_ref = refs[-3:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    bs = block_size
    P = chunk
    M = n_prefix

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    pos0 = pos_ref[b]
    cl = clen_ref[b]
    live = lax.div(pos0 + bs - 1, bs)

    def update(s_tile, v_tile):
        """FlashAttention-2 online-softmax rescale/accumulate (the same
        recurrence as ops/paged_attention.py)."""
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
        # prefix walk: identical contract to the decode kernel — pool
        # positions below pos0 are the live prefix, masked hard past it
        q = q_ref[0, 0]                       # [R, dh] (R = group * P)
        k = pk_ref[0, 0, 0]                   # [bs, dh] storage dtype
        v = pv_ref[0, 0, 0]
        if quantized:
            k = k.astype(q.dtype) * scale_at(sk_ref[0, 0], j).astype(q.dtype)
            v = v.astype(q.dtype) * scale_at(sv_ref[0, 0], j).astype(q.dtype)
        st = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        R, _ = st.shape
        kpos = j * bs + lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        keep = kpos < pos0
        if window is not None:
            qpos = pos0 + lax.broadcasted_iota(jnp.int32, (R, bs), 0) % P
            keep &= kpos > qpos - window
        update(jnp.where(keep, st, _MASK_VALUE), v)

    @pl.when(j == M)
    def _():
        # the chunk is the walk's final virtual block: query i sees
        # chunk columns 0..i (itself included), so every row keeps at
        # least its own token and l > 0 — padding rows past clen emit
        # garbage the caller never reads (causality: row i's output only
        # depends on columns <= i)
        q = q_ref[0, 0]
        k = kn_ref[0, 0]                      # [P, dh] compute dtype
        v = vn_ref[0, 0]
        st = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        R, _ = st.shape
        col = lax.broadcasted_iota(jnp.int32, (R, P), 1)
        row_i = lax.broadcasted_iota(jnp.int32, (R, P), 0) % P
        keep = col <= row_i
        if window is not None:
            keep &= (pos0 + col) > (pos0 + row_i) - window
        update(jnp.where(keep, st, _MASK_VALUE), v)
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, 0][:, None]).astype(o_ref.dtype)

    @pl.when(j > M)
    def _():
        # write step w: merge chunk K/V into touched block t0 + w and
        # requantize, bit-matching _Paged._scatter_values.  Positions
        # outside [pos0, pos0 + clen) keep the ORIGINAL block contents
        # (chunked prefill's partial first block; untouched tail).
        w = j - (M + 1)
        blk0 = (lax.div(pos0, bs) + w) * bs
        kpos = blk0 + lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        in_new = (kpos >= pos0) & (kpos < pos0 + cl)
        # one-hot gather from the chunk: each in-range row selects
        # exactly one chunk position, so the matmul is exact
        sel = ((kpos - pos0)
               == lax.broadcasted_iota(jnp.int32, (bs, P), 1)) & in_new
        selm = sel.astype(jnp.float32)

        def emit(chunk_ref, pool_ref, sc_ref, oq_ref, osc_ref):
            orig = pool_ref[0, 0, 0]          # [bs, dh] storage dtype
            cdtype = chunk_ref.dtype
            if quantized:
                orig = orig.astype(cdtype) * scale_at(
                    sc_ref[0, 0], w).astype(cdtype)
            else:
                orig = orig.astype(cdtype)
            new = jnp.dot(selm, chunk_ref[0, 0].astype(jnp.float32),
                          preferred_element_type=jnp.float32).astype(cdtype)
            merged = jnp.where(in_new, new, orig)
            if quantized:
                v32 = merged.astype(jnp.float32)
                amax = jnp.max(jnp.abs(v32))
                sc = jnp.where(amax > 0, amax / 127.0, 1.0)
                oq_ref[0, 0, 0] = jnp.clip(
                    jnp.round(v32 / sc), -127, 127).astype(oq_ref.dtype)
                # the (slot, head)'s scale row stays resident across the
                # write steps; step w fills lane w (every lane is
                # written — all Mw steps always run)
                row = osc_ref[0, 0]
                lane = lax.broadcasted_iota(jnp.int32, row.shape, 1)
                osc_ref[0, 0] = jnp.where(lane == w, sc, row)
            else:
                oq_ref[0, 0, 0] = merged.astype(oq_ref.dtype)

        emit(kn_ref, pk_ref, swk_ref, ok_ref, osk_ref)
        emit(vn_ref, pv_ref, swv_ref, ov_ref, osv_ref)


def paged_prefill_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    scale_k: jax.Array,
    scale_v: jax.Array,
    table: jax.Array,
    wtable: jax.Array,
    pos0: jax.Array,
    clen: jax.Array,
    *,
    layer: int,
    window: int | None = None,
    interpret: bool = False,
):
    """Paged prefill attention + in-kernel block writes, one model layer.

    - ``q [S, n_heads, P, dh]`` — the chunk's queries, already
      rope-rotated at absolute positions ``pos0 + i``;
    - ``k_new``/``v_new [S, n_kv, P, dh]`` — the chunk's fresh K
      (rotated) / V in the compute dtype;
    - ``pool_k``/``pool_v``/``scale_k``/``scale_v``/``table``/``pos0``
      — exactly as in :func:`tpudist.ops.paged_attention.paged_attention`;
    - ``wtable [S, Mw]`` int32 — physical ids of the blocks the chunk
      touches (logical blocks ``pos0 // bs + w``), sentinel
      ``num_blocks`` for dead lanes / untouched tail (their emitted
      blocks are garbage the commit scatter drops);
    - ``clen [S]`` int32 — the chunk's live length per lane (ragged;
      ``clen <= P``); queries/writes past it are garbage-by-contract.

    Returns ``(o, qk, qv, sk, sv)``: attention output
    ``[S, n_heads, P, dh]`` in ``q.dtype``, the touched blocks
    ``[S, Mw, n_kv, bs, dh]`` in the pool's storage dtype, and their
    dequant scales ``[S, Mw, n_kv]`` f32 (all-ones when the pool is not
    quantized).  Feed the last four to ``_Paged.commit_quantized``.
    """
    S, nh, P, dh = q.shape
    L, nb, n_kv, bs, _ = pool_k.shape
    M = table.shape[1]
    Mw = wtable.shape[1]
    if nh % n_kv:
        raise ValueError(f"n_heads {nh} must be a multiple of n_kv {n_kv}")
    if not 0 <= layer < L:
        raise ValueError(f"layer {layer} out of range [0, {L})")
    group = nh // n_kv
    R = group * P
    quantized = pool_k.dtype == jnp.int8
    q4 = q.reshape(S, n_kv, R, dh)

    def chunk_index(b, h, j, *_):
        return (b, h, 0, 0)

    def pool_index(b, h, j, tbl, wtbl, pos, cl, *_):
        # walk steps (j <= M) follow the table, clamped to the last live
        # block so dead steps elide their DMA; write steps re-aim the
        # SAME ref pair at the touched block to read its original
        # contents for the merge
        live1 = jnp.maximum(lax.div(pos[b] + bs - 1, bs), 1)
        walk = jnp.minimum(tbl[b, jnp.minimum(j, live1 - 1)], nb - 1)
        w = jnp.clip(j - (M + 1), 0, Mw - 1)
        wr = jnp.minimum(wtbl[b, w], nb - 1)
        return (layer, jnp.where(j <= M, walk, wr), h, 0, 0)

    def wblock_index(b, h, j, *_):
        return (b, jnp.clip(j - (M + 1), 0, Mw - 1), h, 0, 0)

    storage = pool_k.dtype
    in_specs = [
        pl.BlockSpec((1, 1, R, dh), chunk_index),   # q4
        pl.BlockSpec((1, 1, P, dh), chunk_index),   # k_new
        pl.BlockSpec((1, 1, P, dh), chunk_index),   # v_new
        pl.BlockSpec((1, 1, 1, bs, dh), pool_index),
        pl.BlockSpec((1, 1, 1, bs, dh), pool_index),
    ]
    operands = [table, wtable, pos0, clen, q4, k_new, v_new, pool_k, pool_v]
    out_specs = [
        pl.BlockSpec((1, 1, R, dh), chunk_index),
        pl.BlockSpec((1, 1, 1, bs, dh), wblock_index),
        pl.BlockSpec((1, 1, 1, bs, dh), wblock_index),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((S, n_kv, R, dh), q.dtype),
        jax.ShapeDtypeStruct((S, Mw, n_kv, bs, dh), storage),
        jax.ShapeDtypeStruct((S, Mw, n_kv, bs, dh), storage),
    ]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, M), chunk_index)] * 2
        in_specs += [pl.BlockSpec((1, 1, 1, Mw), chunk_index)] * 2
        operands += [walk_scales(scale_k, layer, table),
                     walk_scales(scale_v, layer, table),
                     walk_scales(scale_k, layer, wtable),
                     walk_scales(scale_v, layer, wtable)]
        out_specs += [pl.BlockSpec((1, 1, 1, Mw), chunk_index)] * 2
        out_shape += [jax.ShapeDtypeStruct((S, n_kv, 1, Mw), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, n_kv, M + 1 + Mw),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),   # m (running row max)
            pltpu.VMEM((R, 1), jnp.float32),   # l (running normalizer)
            pltpu.VMEM((R, dh), jnp.float32),  # acc (unnormalized out)
        ],
    )
    kernel = functools.partial(
        _kernel, block_size=bs, chunk=P, n_prefix=M,
        quantized=quantized, scale=dh ** -0.5, window=window)
    work = S * n_kv * R * (M * bs + P)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * work * dh),
            transcendentals=int(work),
            bytes_accessed=int(
                (q4.size + 2 * S * n_kv * (M + Mw) * bs * dh
                 + k_new.size + v_new.size + q4.size
                 + 2 * S * Mw * n_kv * bs * dh) * q.dtype.itemsize),
        ),
        **names.kernel(names.PAGED_PREFILL),
        interpret=interpret,
    )(*operands)
    o, qk, qv = outs[:3]
    if quantized:
        # [S, n_kv, 1, Mw] rows -> the commit's [S, Mw, n_kv]
        sk, sv = (jnp.swapaxes(x[:, :, 0, :], 1, 2) for x in outs[3:])
    else:
        sk = sv = jnp.ones((S, Mw, n_kv), jnp.float32)
    return o.reshape(S, nh, P, dh), qk, qv, sk, sv


paged_prefill_attention.supports_gqa = True


def paged_prefill_reference(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    scale_k: jax.Array,
    scale_v: jax.Array,
    table: jax.Array,
    wtable: jax.Array,
    pos0: jax.Array,
    clen: jax.Array,
    *,
    layer: int,
    window: int | None = None,
):
    """Gather-to-dense XLA reference with the identical mask/merge/quant
    contract — the equivalence oracle for the kernel's tests and the
    plain-jnp documentation of its math."""
    S, nh, P, dh = q.shape
    L, nb, n_kv, bs, _ = pool_k.shape
    M = table.shape[1]
    Mw = wtable.shape[1]
    group = nh // n_kv
    rows = jnp.minimum(table, nb - 1)
    compute = q.dtype

    def view(pool, scale):
        g = pool[layer][rows].astype(compute)          # [S, M, nk, bs, dh]
        if pool.dtype == jnp.int8:
            sc = scale[layer][rows]                    # [S, M, nk]
            g = g * sc[..., None, None].astype(compute)
        g = jnp.moveaxis(g, 2, 1)                      # [S, nk, M, bs, dh]
        return g.reshape(S, n_kv, M * bs, dh)

    ks = jnp.concatenate([view(pool_k, scale_k), k_new], axis=2)
    vs = jnp.concatenate([view(pool_v, scale_v), v_new], axis=2)
    scale = dh ** -0.5
    qg = q.reshape(S, n_kv, group, P, dh)
    scores = jnp.einsum("bngqd,bnkd->bngqk", qg, ks,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(M * bs + P)
    in_pool = kpos < M * bs
    row = jnp.arange(P)
    live = jnp.where(
        in_pool[None, None],
        kpos[None, None] < pos0[:, None, None],
        (kpos[None, None] - M * bs) <= row[None, :, None])
    if window is not None:
        qpos = pos0[:, None] + row[None]                       # [S, P]
        abs_k = jnp.where(in_pool[None, None], kpos[None, None],
                          pos0[:, None, None] + kpos[None, None] - M * bs)
        live &= abs_k > qpos[:, :, None] - window
    scores = jnp.where(live[:, None, None], scores, _MASK_VALUE)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bngqk,bnkd->bngqd", w.astype(compute), vs,
                   preferred_element_type=jnp.float32)
    o = o.reshape(S, nh, P, dh).astype(q.dtype)

    # --- writes: merge the chunk into the touched blocks + requantize
    wrows = jnp.minimum(wtable, nb - 1)                        # [S, Mw]
    blk0 = (pos0[:, None] // bs + jnp.arange(Mw)[None]) * bs   # [S, Mw]
    kpos_w = blk0[..., None] + jnp.arange(bs)[None, None]      # [S, Mw, bs]
    in_new = ((kpos_w >= pos0[:, None, None])
              & (kpos_w < (pos0 + clen)[:, None, None]))
    ci = jnp.clip(kpos_w - pos0[:, None, None], 0, P - 1)

    def write(chunk, pool, scale):
        orig = pool[layer][wrows].astype(compute)      # [S, Mw, nk, bs, dh]
        if pool.dtype == jnp.int8:
            sc = scale[layer][wrows]
            orig = orig * sc[..., None, None].astype(compute)
        idx = jnp.broadcast_to(ci[:, :, None, :, None],
                               (S, Mw, n_kv, bs, dh))
        src = jnp.broadcast_to(chunk[:, None], (S, Mw, n_kv, P, dh))
        new = jnp.take_along_axis(src, idx, axis=3)
        merged = jnp.where(in_new[:, :, None, :, None],
                           new.astype(compute), orig)
        if pool.dtype == jnp.int8:
            v32 = merged.astype(jnp.float32)
            amax = jnp.max(jnp.abs(v32), axis=(-2, -1))
            sc = jnp.where(amax > 0, amax / 127.0, 1.0)
            qq = jnp.clip(jnp.round(v32 / sc[..., None, None]),
                          -127, 127).astype(jnp.int8)
            return qq, sc.astype(jnp.float32)
        return (merged.astype(pool.dtype),
                jnp.ones((S, Mw, n_kv), jnp.float32))

    qk, sk = write(k_new, pool_k, scale_k)
    qv, sv = write(v_new, pool_v, scale_v)
    return o, qk, qv, sk, sv
