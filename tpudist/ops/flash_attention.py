"""Pallas TPU flash attention (blockwise online-softmax) kernel.

The single-chip hot op behind the long-context path: materializes no
``[seq, seq]`` score matrix — the grid is (batch·heads, q_block, kv step)
with KV innermost, the (m, l, acc) online-softmax state lives in VMEM
scratch across each Q row's KV sweep, and only one [block_k, d] K/V tile
is VMEM-resident at a time (sequence length is bounded by HBM, not VMEM);
both matmuls per block land on the MXU.  Combined with
:mod:`tpudist.parallel.ring_attention` (which rotates K/V between chips),
this covers intra-chip blocking while the ring covers inter-chip sharding.

Backward: ``jax.custom_vjp`` with two Pallas kernels (the standard
FlashAttention-2 split): the forward additionally emits the per-row
logsumexp, the host computes ``delta = rowsum(dO · O)``, then a dq kernel
(KV innermost, dq accumulated in VMEM across the KV sweep) and a dk/dv
kernel (Q innermost, dk/dv accumulated across the Q sweep) reconstruct
``p = exp(s − lse)`` per tile — no [seq, seq] matrix is ever materialized
forward or backward, and both causal variants elide dead-block DMAs the
same way the forward does.  Fwd and bwd match ``attention_reference``
numerically (see tests).  ``blockwise_attention`` (plain-XLA scan with the
same online-softmax math) remains as the kernel-free fallback path.

Two operand layouts, one set of kernel bodies.  The three ``pallas_call``s
are built once (``_flash_forward``, ``_flash_backward``) over a
:class:`_Layout` that says where one head's ``[tile, d]`` block lives in
each array; the kernels only ever see that block.

- *head-major* — :func:`flash_attention`, :func:`flash_attention_with_lse`:
  ``q [b, h, s, d]``, ``k, v [b, h_kv, s, d]``, seen as ``[b·h, s, d]``.
  The values may have a width of their own, ``v [b, h_kv, s, d_v]`` (latent
  attention scores at 192 and reads values of 128): ``o``, ``do`` and ``dv``
  are then ``d_v`` wide, the accumulators with them, and the softmax scale
  is the queries' ``d ** -0.5``; every tile's last dimension is the
  array's own, whole.
  K/V are arrays of their own, which is what ring attention rotates
  between chips; the pipeline schedules and any injected
  ``attention_fn`` use it too.
- *packed* — :func:`flash_attention_packed`: a fused q/k/v projection's own
  ``[b, s, (h + 2·h_kv)·d]`` output in, ``[b, s, h·d]`` out (what the
  output projection reads), ``do`` in that layout and ONE cotangent back.
  A head is a ``d``-wide column block, so with ``d`` a multiple of the
  128 lanes a ``(1, tile, d)`` block at ``(batch, tile, head)`` is a legal
  Mosaic block and nothing is transposed, sliced or copied round the
  kernels.  dq, dk, dv leave the kernels as three ``[b, s, ·]`` arrays
  and are joined by a ``concatenate`` that XLA fuses into the
  projection's backward matmuls (no device op of its own in the compiled
  step; the other way, one ``[b, s, 3·d]`` buffer passed from the dk/dv
  call to the dq call through ``input_output_aliases``, cannot hold all
  three: dk and dv are two outputs of one call and an output has one
  ``BlockSpec``, so one of them would still be copied in).

What a tile costs.  A tile above the causal diagonal is elided (no fetch,
no compute), a tile under it is interior (no mask chain).  A tile ON the
diagonal is half dead, and with 1024 x 1024 tiles over 2,048 positions two
of a head's three computed tiles are such tiles.  Under the plain causal
band with equal blocks (:func:`diag_sub`) all three bodies take it as
``block/sub`` strips of ``sub`` q rows, statically unrolled, each strip
ONE rectangle against the keys up to its own ``sub x sub`` square (dk/dv
too: strip ``i`` adds into rows ``[0, (i+1)·sub)`` of ``dk_acc`` and
``dv_acc``).  The squares above the diagonal are never computed and only
the square on it passes through the mask (one triangle, the same in every
strip): at ``block/sub`` 4, 10 of 16 squares computed and 4 masked where
the whole tile computes and masks 16; 1.50 -> 1.125 times the live pairs
at 2,048 positions, 1.125 -> 1.031 at 8,192
(``ops.attention.computed_over_live``).  Each strip is one call of the
same body that takes an interior tile whole, so there is one copy of each
kernel's math.  Strips of q rows and not of keys, and one rectangle a
strip and not its square and the rest apart: a strip of keys updates the
row statistics (or reads ``lse`` and ``delta``) for every q row below it,
2.5 times a tile's rows, and each further piece is a further round trip
of ``m``, ``l``, ``acc`` through VMEM (PERF.md section 6, PR 33).  A
sliding window as wide as a whole number of (equal) tiles has a second
such edge: the tile ``window / block`` tiles before the diagonal lives
ABOVE the same staircase, and goes by the complementary strips, each
against the keys from its own square to the tile's end
(:func:`_far_edge_strips`); at a window of one tile every live tile is one
of the two.  Every other band (a window the block does not divide, a ring
hop's shifted band, unequal blocks) computes its band-edge tiles whole and
masked.  ``sub`` is an argument like the blocks: ``ops/attention.py``
passes its table's, a caller that names none gets ``DIAG_SUB``.

What a grid step costs.  A grid pays for the tiles it skips (a quarter to
four tenths of a microsecond a dead step on a v5e, PR 41 and PR 42), so the
grids follow the band the call states (``lo <= q − k < hi``).  The live key
tiles of a query tile are ONE run, :func:`_first_live_kv` to
:func:`_last_live_kv`, and the forward and dq grids' innermost axis is as
long as the longest run and no longer (:func:`band_grid`): step ``j`` works
tile ``first + j``.  dk/dv the transposed way: a key tile's live query tiles
are a run too, and its innermost axis is the group x the longest such run.
Steps past a run's end (near the sequence's ends) are skipped with their
fetch clamped.  Without an upper edge (the plain causal band, a ring hop
with none) a run starts at tile 0 and the longest is the whole sequence:
those grids, index maps and bodies are what they were before the band had a
say.  A window of 512 over 8,192 positions takes 32 steps a head for 31
live by 512 x 512 tiles, where a grid over every tile took 256.

``tpudist.ops.attention.make_length_aware_attention`` picks the layout,
the tiles and ``sub`` from what it can observe (device kind, length,
``d % 128``, the window).

The module also holds the plain-XLA side of the same math:
:func:`attention_reference` (the dense ground truth every kernel is tested
against) and the online-softmax block update that
:func:`blockwise_attention` and ring attention's shard-local bodies share.

No reference counterpart (the reference has no attention and ships no
kernels of its own — SURVEY.md §0, §5.7); this is TPU-native capability.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.telemetry import names

# Finite stand-in for -inf: keeps exp() NaN-free when a whole row is masked
# (a fully-masked KV block contributes exp(NEG - m_finite) == 0).
_MASK_VALUE = -1e30

# Width of the squares a tile on the diagonal is worked by (`diag_sub`)
# for a caller that names none; `ops/attention.py` passes its table's.
DIAG_SUB = 256


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Plain softmax attention — the single-device ground truth.

    Shapes: ``q, k, v: [batch, heads, seq, head_dim]``.  ``window``
    (requires ``causal``) masks to the sliding band ``q − k < window``.
    """
    scale = q.shape[-1] ** -0.5
    # Mixed-precision discipline (a no-op for f32 inputs): MXU operands in
    # the input dtype, score accumulation + softmax in f32, output cast back.
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if causal:
        q_len, k_len = scores.shape[-2], scores.shape[-1]
        qi = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        kj = lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        keep = qi >= kj
        if window is not None:
            keep &= qi - kj < window
        scores = jnp.where(keep, scores, _MASK_VALUE)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _block_update(q, k, v, m, l, o, *, scale, mask=None):
    """One online-softmax accumulation step over a KV block.

    ``m`` row-max, ``l`` normalizer sum, ``o`` unnormalized output — the
    (m, l, o) running triple of blockwise/flash attention.  The carry is
    f32 whatever the input dtype (mixed-precision discipline: MXU operands
    in the input dtype, accumulation in f32).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _MASK_VALUE)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _causal_mask(q_off, k_off, bq: int, bk: int, window=None):
    q_pos = q_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    return keep


def _normalize_band(causal, window):
    """Reduce (causal, window) to the internal band ``lo <= q − k < hi``
    (either side ``None`` = unbounded).

    ``window`` forms: ``None`` (plain causal / full), an ``int`` W
    (causal sliding window: band [0, W)), or an explicit ``(lo, hi)``
    tuple (a shifted band in LOCAL coordinates — how ring attention
    expresses an off-diagonal hop, where the global offset q − k = t·S
    is static; requires ``causal=False`` since the band subsumes it).
    """
    if window is None:
        return (0, None) if causal else (None, None)
    if isinstance(window, tuple):
        if causal:
            raise ValueError("band-tuple window subsumes causal; pass "
                             "causal=False")
        lo, hi = window
        if lo is not None and hi is not None and lo >= hi:
            raise ValueError(f"empty band: lo {lo} >= hi {hi}")
        return lo, hi
    if not causal:
        raise ValueError("sliding window requires causal=True")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    return 0, window


def _band_live_pairs(seq_q: int, seq_k: int, lo, hi) -> int:
    """Exact number of (q, k) pairs inside the band — the FLOP-proportional
    work the cost estimates feed the XLA scheduler (a hi-only ring-hop band
    can be a thin corner; calling it dense would overstate work by the
    seq/window ratio)."""
    import numpy as np

    q = np.arange(seq_q)
    k_hi = np.minimum(q - (lo if lo is not None else -seq_k), seq_k - 1)
    k_lo = np.maximum(q - ((hi if hi is not None else seq_q + seq_k) - 1), 0)
    return int(np.clip(k_hi - k_lo + 1, 0, None).sum())


def _tile_live(qi, kv, block_q: int, block_k: int, lo, hi, inside=None):
    """Whether tile (qi, kv) intersects the band ``lo <= q − k < hi``
    (and, of a sweep that can run past the sequence's end, is ``inside``
    it: :func:`_sweep_tile`).  The unbounded form keeps a traced always-true
    predicate so every variant flows through the same ``pl.when``."""
    live = kv >= 0 if inside is None else inside
    if lo is not None:
        # max(q − k) over the tile = (qi+1)·bq − 1 − kv·bk
        live &= (qi + 1) * block_q - 1 - kv * block_k >= lo
    if hi is not None:
        # min(q − k) over the tile = qi·bq − ((kv+1)·bk − 1)
        live &= qi * block_q - ((kv + 1) * block_k - 1) < hi
    return live


def _tile_interior(qi, kv, block_q: int, block_k: int, lo, hi):
    """Whether EVERY (q, k) pair of tile (qi, kv) lies inside the band —
    the band mask is then a provable no-op.  Interior tiles skip the
    whole VPU mask chain (two [bq, bk] iotas + compare + select per
    tile); at d_head 64 the kernel is VPU-bound, not MXU-bound, and on
    causal long-sequence grids most live tiles are interior (s=8192,
    1024-tiles: 28 of 36), so this is where the attention time goes."""
    inside = kv >= 0
    if lo is not None:
        # min(q − k) over the tile = qi·bq − ((kv+1)·bk − 1)
        inside &= qi * block_q - ((kv + 1) * block_k - 1) >= lo
    if hi is not None:
        # max(q − k) over the tile = (qi+1)·bq − 1 − kv·bk
        inside &= (qi + 1) * block_q - 1 - kv * block_k < hi
    return inside


_ALL = slice(None)


def diag_sub(block_q: int, block_k: int, lo, hi, sub) -> int:
    """The width of the squares a tile a band's edge crosses is worked by,
    or 0 where such a tile is computed whole and masked.  Decided by what
    the call states: a causal band (``lo == 0``) over equal blocks with no
    upper edge, or one at a multiple of the block (a sliding window as wide
    as a whole number of tiles), is the case where every band-edge tile has
    its corner on an edge: a tile on the diagonal (``qi == kv``) lives under
    the same static staircase of ``sub x sub`` squares, and a tile on the
    window's far edge (``qi - kv == hi / block``) above it.  Any other
    window, a ring hop's shifted band, unequal blocks, or a ``sub`` that
    does not cut the block into at least two keep the whole-tile branch."""
    if (lo == 0 and block_q == block_k and sub and block_q % sub == 0
            and block_q > sub and (hi is None or hi % block_q == 0)):
        return sub
    return 0


def _diagonal_strips(block: int, sub: int):
    """The live part of a tile on the diagonal as ``block/sub`` strips of
    ``sub`` q rows, each ONE rectangle ``(rows, cols)``: the strip against
    the keys up to its own square.  Only that square (the strip's last
    ``sub`` columns: the same triangle in every strip) is half dead, the
    rest is interior; the squares above the diagonal appear in no strip:
    at ``block/sub`` 4, 10 of 16 squares are computed and 4 of those pass
    through the mask."""
    return [(slice(i * sub, (i + 1) * sub), slice(0, (i + 1) * sub))
            for i in range(block // sub)]


def _far_edge_strips(block: int, sub: int):
    """The live part of a tile on a window's far edge (``q - k < hi`` with
    ``hi`` a multiple of the block: the strict upper triangle, the
    complement of :func:`_diagonal_strips`' staircase): each strip of
    ``sub`` q rows against the keys FROM its own square to the tile's end.
    Only that square (the strip's first ``sub`` columns) is half dead."""
    return [(slice(i * sub, (i + 1) * sub), slice(i * sub, block))
            for i in range(block // sub)]


def _mask_edge_square(s, keep, far: bool = False):
    """Mask the one square of a strip's scores ``s`` that an edge crosses to
    ``keep``: the strip's last columns and the square's ``q >= k`` on the
    diagonal (:func:`_diagonal_strips`), its first columns and ``q < k`` on
    a window's far edge (:func:`_far_edge_strips`); the rest of the strip
    passes untouched, so the mask chain costs ``sub x sub`` a strip."""
    sub = keep.shape[0]
    square = jnp.where(keep, s[:, :sub] if far else s[:, -sub:], _MASK_VALUE)
    if s.shape[1] == sub:
        return square
    if far:
        return jnp.concatenate([square, s[:, sub:]], axis=1)
    return jnp.concatenate([s[:, :-sub], square], axis=1)


def _masked_tile_branches(live, qi, kv, block_q: int, block_k: int, lo, hi,
                          update, sub: int = 0):
    """Run ``update(rows, cols, mask)`` (one rectangle of the tile, ``mask``
    a function of its scores or ``None``) under the live predicate: an
    interior tile whole and unmasked; a band-edge tile whole and masked to
    the band, or, where :func:`diag_sub` says its corner lies on an edge
    (``sub``), strip by strip: :func:`_diagonal_strips` on the diagonal,
    :func:`_far_edge_strips` on a window's far edge.  Bandless kernels keep
    the single unmasked branch."""
    if lo is None and hi is None:
        @pl.when(live)
        def _():
            update(_ALL, _ALL, None)
        return
    interior = _tile_interior(qi, kv, block_q, block_k, lo, hi)

    @pl.when(live & interior)
    def _():
        update(_ALL, _ALL, None)

    edge = live & jnp.logical_not(interior)

    def by_strips(far: bool):
        row = lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
        col = lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
        mask = functools.partial(_mask_edge_square, far=far,
                                 keep=row < col if far else row >= col)
        strips = _far_edge_strips if far else _diagonal_strips
        for rows, cols in strips(block_q, sub):
            update(rows, cols, mask)

    if not sub:
        @pl.when(edge)
        def _():
            update(_ALL, _ALL, lambda s: _tile_band_mask(
                s, qi, kv, block_q, block_k, lo, hi))
    elif hi is None:
        pl.when(edge)(lambda: by_strips(False))
    else:
        # equal blocks: the tile on the diagonal, or the one the window's
        # far edge crosses
        pl.when(edge & (qi == kv))(lambda: by_strips(False))
        pl.when(edge & (qi != kv))(lambda: by_strips(True))


def _tile_band_mask(s, qi, kv, block_q: int, block_k: int, lo, hi):
    """Mask score tile ``s`` at tile coords (qi, kv) to the band."""
    if lo is None and hi is None:
        return s
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = kv * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    keep = None
    if lo is not None:
        keep = q_pos - k_pos >= lo
    if hi is not None:
        upper = q_pos - k_pos < hi
        keep = upper if keep is None else keep & upper
    return jnp.where(keep, s, _MASK_VALUE)


def _last_live_kv(qi, nkv, block_q: int, block_k: int, lo):
    """Index of Q row ``qi``'s last live KV tile.  Only the band's lower
    edge bounds it: k ranges up to q − lo."""
    if lo is None:
        return nkv - 1
    return jnp.clip(
        ((qi + 1) * block_q - 1 - lo) // block_k, 0, nkv - 1
    )


def _first_live_kv(qi, nkv, block_q: int, block_k: int, hi):
    """Index of Q row ``qi``'s first live KV tile.  Only the band's upper
    edge bounds it (k ranges down to q − hi + 1); without one it is the
    Python constant 0 and nothing is traced."""
    if hi is None:
        return 0
    return jnp.minimum(jnp.maximum(qi * block_q - hi + 1, 0) // block_k,
                       nkv - 1)


def _first_live_q(kv, nq, block_q: int, block_k: int, lo, hi):
    """Where KV tile ``kv``'s sweep over Q tiles starts (the transposed
    schedule of dk/dv).  Only under an upper edge is the longest run of
    live Q tiles shorter than the sequence, and the sweep then starts at the
    tile's first live one (q ranges up from k + lo); without one key tile 0
    sees every Q tile, so the sweep is over all of them from tile 0, the
    dead ones clamped, as it has always been."""
    if hi is None or lo is None:
        return 0
    return jnp.minimum(jnp.maximum(kv * block_k + lo, 0) // block_q, nq - 1)


def band_grid(nq: int, nkv: int, block_q: int, block_k: int, lo,
              hi) -> tuple[int, int, int, int]:
    """``(kv_steps, q_steps, live, edge)`` of the band ``lo <= q − k < hi``
    over ``nq x nkv`` tiles, static, from what the call states: the length
    of a Q tile's sweep over KV tiles (forward, dq) and of a KV tile's sweep
    over Q tiles (dk/dv), the number of live tiles, and how many of those an
    edge of the band crosses.  A tile row's (or column's) live tiles are ONE
    run, :func:`_first_live_kv` to :func:`_last_live_kv`, and a sweep is as
    long as the longest: the window's width in tiles plus one under an
    upper edge, every tile without one (the plain causal band keeps the
    grid it has always had)."""
    import numpy as np

    at = np.indices((nq, nkv))
    live = _tile_live(*at, block_q, block_k, lo, hi)
    edge = live & ~_tile_interior(*at, block_q, block_k, lo, hi)
    return (max(int(live.sum(1).max()), 1),
            nq if hi is None else max(int(live.sum(0).max()), 1),
            int(live.sum()), int(edge.sum()))


def _band_kv_index(block_q: int, block_k: int, lo, hi, nkv: int):
    """Index map for the KV-innermost sweeps: step ``j`` of Q row ``i``
    reads the row's first live tile + ``j`` (tile ``j`` itself where the
    band has no upper edge); steps past the row's last live tile re-map to
    it — Pallas elides the DMA when consecutive grid steps repeat a block
    index, so dead steps cost neither fetch bandwidth nor compute (the
    kernels' ``_tile_live`` predicate is already false there)."""
    def kv_index(b, i, j):
        if hi is not None:
            j = j + _first_live_kv(i, nkv, block_q, block_k, hi)
        if lo is not None:
            j = jnp.minimum(j, ((i + 1) * block_q - 1 - lo) // block_k)
        return (b, jnp.clip(j, 0, nkv - 1), 0)

    return kv_index


def _sweep_tile(first, j, n_tiles: int):
    """``(tile, inside)`` of step ``j`` of a sweep that starts at tile
    ``first``: the step itself where ``first`` is the constant 0 (the sweep
    is then no longer than the sequence: ``inside`` is ``None``), else
    ``first + j`` and whether that is still a tile of the sequence."""
    if isinstance(first, int) and first == 0:
        return j, None
    tile = first + j
    return tile, tile < n_tiles


def _emit_step(qi, steps, block_q: int, block_k: int, lo, hi):
    """The step of Q row ``qi``'s KV sweep that emits.  Without an upper
    edge the sweep starts at tile 0 and that is the row's last live tile,
    as it has always been; a band's own sweep emits at its last step (a row
    with no live tile at all has one too)."""
    if hi is None:
        return _last_live_kv(qi, steps, block_q, block_k, lo)
    return steps - 1


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, block_q: int, block_k: int, lo, hi, sub: int,
                  scale: float, nkv: int):
    """One (bh, q_block, kv step) grid step.

    The grid's KV dimension is innermost (TPU grids run sequentially), so
    the (m, l, acc) online-softmax state lives in VMEM scratch across the
    KV sweep of each Q block; only one [block_k, d] K/V tile is resident at
    a time — sequence length is bounded by HBM, not VMEM.  The sweep is the
    run of the Q block's live tiles (:func:`band_grid`): ``nkv`` tiles from
    tile 0 without an upper edge, else from :func:`_first_live_kv`.
    """
    qi = pl.program_id(1)
    j = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Tiles outside the band contribute nothing — skip.  Interior tiles
    # (fully inside the band) additionally skip the mask chain, and a tile
    # on the diagonal is taken strip by strip (`_masked_tile_branches`).
    def update(rows, cols, mask):
        # MXU operands stay in the input dtype (bf16 runs at bf16 MXU
        # throughput); accumulation is always f32 via preferred_element_type.
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = mask(s)
        m = m_ref[rows, 0]
        l = l_ref[rows, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        correction = jnp.exp(m - m_new)
        m_ref[rows, 0] = m_new
        l_ref[rows, 0] = l * correction + jnp.sum(p, axis=-1)
        acc_ref[rows] = acc_ref[rows] * correction[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    kv, inside = _sweep_tile(_first_live_kv(qi, nkv, block_q, block_k, hi),
                             j, nkv)
    _masked_tile_branches(
        _tile_live(qi, kv, block_q, block_k, lo, hi, inside),
        qi, kv, block_q, block_k, lo, hi, update, sub)

    # Last step of this Q row's sweep: normalize and emit.  A row with no
    # live tile at all (possible under a shifted band — e.g. a ring hop
    # whose window edge crosses mid-shard) emits out=0, lse=_MASK_VALUE:
    # exactly the "no contribution" partial for logsumexp merging, and a
    # 0/0 NaN otherwise.
    @pl.when(j == _emit_step(qi, steps, block_q, block_k, lo, hi))
    def _():
        l = l_ref[:, 0]
        # A row is dead when m never left its init — catches both "no live
        # tile" (l == 0) and "live tile but every entry masked" (l counts
        # exp(_MASK − _MASK) = 1 per masked entry, so l alone misses it).
        dead = m_ref[:, 0] <= _MASK_VALUE * 0.5
        safe_l = jnp.where(dead, 1.0, l)
        o_ref[0] = jnp.where(
            dead[:, None], 0.0, acc_ref[:] / safe_l[:, None]
        ).astype(o_ref.dtype)
        # Per-row logsumexp (scaled-score domain) — the backward's residual:
        # p = exp(s·scale − lse) reconstructs the softmax tile exactly.
        lse_ref[0, 0, :] = jnp.where(
            dead, _MASK_VALUE, m_ref[:, 0] + jnp.log(safe_l)
        )


def _split(row, n: int):
    """``(row // n, row % n)`` of a grid index, which is never negative: one
    truncating divide.  Index maps run on the scalar core at every grid
    step, and ``//`` and ``%`` each trace into a divide of their own plus
    sign fix-ups; ``n == 1`` costs nothing."""
    if n == 1:
        return row, 0
    major = lax.div(row, n)
    return major, row - major * n


def _kv_row_map(heads: int, kv_heads: int):
    """Map a batch-major q-head grid row to its KV head's row (GQA)."""
    group = heads // kv_heads
    if group == 1:
        return lambda b: b

    def kv_row(b):
        batch, head = _split(b, heads)
        return batch * kv_heads + lax.div(head, group)

    return kv_row


def _gqa_shape_check(q, k, v) -> int:
    """Validate [b, hq, sq, d] x [b, hkv, sk, d] inputs and return the KV
    head count (hkv must divide hq — grouped-query attention runs
    natively, no K/V repeat)."""
    batch, heads, _, d = q.shape
    kv_heads = k.shape[1]
    if (k.shape[:3] != v.shape[:3] or k.shape[0] != batch
            or k.shape[3] != d):
        raise ValueError(f"k {k.shape} / v {v.shape} incompatible with q "
                         f"{q.shape}: v alone may have a width of its own")
    if heads % kv_heads:
        raise ValueError(
            f"q heads {heads} must be a multiple of kv heads {kv_heads}"
        )
    return kv_heads


def _head_tile(n: int, first: int = 0):
    """Where one head's ``[tile, d]`` block lives: the index map ``(row,
    tile) -> block index`` of ``(1, tile, d)`` blocks for grid row ``row``
    (batch-major over ``n`` heads a batch element) in an array ``[batch,
    seq, (... the n heads from column block first ...)·d]``.  ``n=1`` is
    the head-major ``[batch·heads, seq, d]`` view: block ``(row, tile,
    0)``."""
    def at(row, tile):
        batch, head = _split(row, n)
        return (batch, tile, first + head)

    return at


class _Layout(NamedTuple):
    """The one thing the two entries differ in: the shape of the problem
    and where a head's tile lives in each array the three calls read and
    write (see :func:`_head_tile`).  ``o`` also places ``do`` and ``dq``,
    ``dkv`` places ``dk`` and ``dv``; the stats (``lse``, ``delta``) are
    ``[batch·heads, 1, seq_q]`` in both.  ``d`` is the width of a head's
    queries and keys (the scores', and ``dq``'s and ``dk``'s), ``d_v`` that
    of its values (and of ``o``, ``do`` and ``dv``): the head-major entry
    takes values of a width of their own, the packed one has ``d_v == d``."""

    batch: int
    heads: int
    kv_heads: int
    seq_q: int
    seq_k: int
    d: int
    d_v: int
    q: Callable
    k: Callable
    v: Callable
    o: Callable
    dkv: Callable
    o_shape: tuple
    dq_shape: tuple
    dk_shape: tuple
    dv_shape: tuple


def _head_major_layout(q, k, v) -> _Layout:
    """``[b, h, s, d]`` operands, each seen as ``[b·h, s, d]``; ``v`` (and
    with it ``o``) may be ``d_v`` wide where ``q`` and ``k`` are ``d``."""
    batch, heads, seq_q, d = q.shape
    kv_heads = _gqa_shape_check(q, k, v)
    seq_k, d_v = k.shape[2], v.shape[3]
    at = _head_tile(1)
    return _Layout(batch, heads, kv_heads, seq_q, seq_k, d, d_v, at, at, at,
                   at, at, (batch * heads, seq_q, d_v),
                   (batch * heads, seq_q, d), (batch * kv_heads, seq_k, d),
                   (batch * kv_heads, seq_k, d_v))


def _packed_layout(qkv, heads: int, kv_heads: int) -> _Layout:
    """One ``[b, s, (heads + 2·kv_heads)·d]`` array — a fused q/k/v
    projection's own output — holding q's heads, then k's, then v's, one
    ``d``-wide column block a head; ``o`` (and ``do``, ``dq``) are ``[b,
    s, heads·d]``, ``dk`` / ``dv`` ``[b, s, kv_heads·d]``."""
    batch, seq, cols = qkv.shape
    if heads % kv_heads or cols % (heads + 2 * kv_heads):
        raise ValueError(
            f"packed qkv {qkv.shape} does not hold {heads} q heads and "
            f"2 x {kv_heads} kv heads (kv heads must divide q heads)")
    d = cols // (heads + 2 * kv_heads)
    wide, narrow = (batch, seq, heads * d), (batch, seq, kv_heads * d)
    return _Layout(
        batch, heads, kv_heads, seq, seq, d, d,
        _head_tile(heads), _head_tile(kv_heads, heads),
        _head_tile(kv_heads, heads + kv_heads), _head_tile(heads),
        _head_tile(kv_heads), wide, wide, narrow, narrow)


def _blocks(lay: _Layout, block_q: int, block_k: int, interpret: bool):
    bq = min(block_q, lay.seq_q)
    bk = min(block_k, lay.seq_k)
    if lay.seq_q % bq or lay.seq_k % bk:
        raise ValueError(
            f"block sizes ({bq}, {bk}) must divide seq lengths "
            f"({lay.seq_q}, {lay.seq_k})"
        )
    if bq < lay.seq_q and bq % 128 and not interpret:
        # The (bh, 1, seq_q) stats layout puts the Q block on the LANE dim
        # of the lse/delta blocks, so a partial block must be a lane-tile
        # multiple on TPU.  Catch it here with a clear message instead of
        # deep in Mosaic's block-shape check.  (Interpret mode has no tile
        # constraints — tests exercise band edges with small blocks.)
        raise ValueError(
            f"block_q ({bq}) must be a multiple of 128 (or the full seq_q)"
        )
    return bq, bk


def _kv_innermost_specs(lay: _Layout, bq: int, bk: int, lo, hi):
    """``(q_tile, kv_tile, row_spec)`` for the ``(batch·heads, q tile, kv
    tile)`` grids of the forward and dq calls: ``q_tile(at, width)`` /
    ``kv_tile(at, width)`` make the ``(1, tile, width)`` spec of an array
    whose head tiles lie where ``at`` says, ``lay.d`` wide unless told
    (dead KV tiles re-mapped, see :func:`_band_kv_index`); ``row_spec`` is
    the stats' ``(1, 1, bq)``."""
    kv_row = _kv_row_map(lay.heads, lay.kv_heads)
    band_j = _band_kv_index(bq, bk, lo, hi, lay.seq_k // bk)

    def q_tile(at, width=lay.d):
        return pl.BlockSpec((1, bq, width), lambda b, i, j: at(b, i),
                            memory_space=pltpu.VMEM)

    def kv_tile(at, width=lay.d):
        return pl.BlockSpec(
            (1, bk, width),
            lambda b, i, j: at(kv_row(b), band_j(b, i, j)[1]),
            memory_space=pltpu.VMEM)

    row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i),
                            memory_space=pltpu.VMEM)
    return q_tile, kv_tile, row_spec


def _flash_forward(q, k, v, lay: _Layout, *, causal, block_q, block_k,
                   interpret, out_f32=False, window=None, sub=0):
    """The forward call over operands laid out as ``lay`` says (the packed
    entry passes one array three times): ``(o, lse)`` with ``o`` of
    ``lay.o_shape`` and ``lse`` ``[batch·heads, 1, seq_q]``."""
    lo, hi = _normalize_band(causal, window)
    seq_q, seq_k, d, d_v = lay.seq_q, lay.seq_k, lay.d, lay.d_v
    bq, bk = _blocks(lay, block_q, block_k, interpret)
    scale = d ** -0.5
    bh = lay.batch * lay.heads
    bh_kv = lay.batch * lay.kv_heads

    kernel = functools.partial(
        _flash_kernel, block_q=bq, block_k=bk, lo=lo, hi=hi,
        sub=diag_sub(bq, bk, lo, hi, sub), scale=scale, nkv=seq_k // bk,
    )
    q_tile, kv_tile, row_spec = _kv_innermost_specs(lay, bq, bk, lo, hi)

    # Whole-kernel cost for the XLA scheduler (matmul mult-add = 2 FLOPs;
    # exp per score entry; causal does half the score work).
    work = bh * _band_live_pairs(seq_q, seq_k, lo, hi)
    cost = pl.CostEstimate(
        flops=int(2 * work * (d + d_v)),
        transcendentals=int(work),
        bytes_accessed=int((bh * seq_q + bh_kv * seq_k) * (d + d_v))
        * q.dtype.itemsize,
    )
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(lay.o_shape,
                                 jnp.float32 if out_f32 else q.dtype),
            # Stats with seq on the LANE dim.  A trailing singleton
            # ((bh, seq_q, 1)) looks harmless but the T(8,128) HBM layout
            # pads the lane dim 1 → 128 — measured 128× expansion
            # (4 MB → 512 MB at bh=512/seq=2048, the r4 b64 OOM dump) on
            # every lse residual held live until the backward.  The
            # middle singleton here is a SUBLANE dim (1 → 8, 8× pad) —
            # the cheapest layout Pallas' block rule admits: a 2D
            # (bh, seq_q) array would need (1, bq) blocks, whose sublane
            # size 1 is neither divisible by 8 nor equal to bh.
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        grid=(bh, seq_q // bq,
              band_grid(seq_q // bq, seq_k // bk, bq, bk, lo, hi)[0]),
        in_specs=[q_tile(lay.q), kv_tile(lay.k), kv_tile(lay.v, d_v)],
        out_specs=[q_tile(lay.o, d_v), row_spec],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # m (running row max)
            pltpu.VMEM((bq, 1), jnp.float32),   # l (running normalizer)
            pltpu.VMEM((bq, d_v), jnp.float32),  # acc (unnormalized out)
        ],
        compiler_params=pltpu.CompilerParams(
            # bh and q rows are independent; only the KV sweep accumulates.
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=cost,
        **names.kernel(names.FLASH_FWD),
        interpret=interpret,
    )(q, k, v)


def _head_major_forward(q, k, v, *, causal, block_q, block_k, interpret,
                        out_f32, window, sub):
    lay = _head_major_layout(q, k, v)
    batch, heads, seq_q, _ = q.shape
    out, lse = _flash_forward(
        q.reshape(lay.dq_shape), k.reshape(lay.dk_shape),
        v.reshape(lay.dv_shape), lay, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, out_f32=out_f32, window=window,
        sub=sub)
    return (out.reshape(batch, heads, seq_q, lay.d_v),
            lse.reshape(batch, heads, seq_q))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    out_f32: bool = False,
    window: int | None = None,
    sub: int = DIAG_SUB,
):
    """Flash attention that also returns the per-row logsumexp
    ``[batch, heads, seq_q]`` (f32, scaled-score domain).

    The lse output is what makes partial attentions *mergeable*: two
    results over disjoint KV sets combine exactly via
    ``out = (out_a·e^{lse_a} + out_b·e^{lse_b}) / (e^{lse_a}+e^{lse_b})``
    (stabilized) — the decomposition ring attention uses to run this
    kernel per hop.  Differentiable in both outputs: the lse cotangent
    folds into the backward's delta term (``ds = p·(dp − Δ + dL)``).

    ``out_f32`` emits the attention output in f32 regardless of input
    dtype — partial-merging callers keep full precision across merges
    (the in-kernel accumulator is f32 either way, so this is free).
    """
    return _head_major_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, out_f32=out_f32, window=window, sub=sub,
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: int | None = None,
    sub: int = DIAG_SUB,
) -> jax.Array:
    """Flash attention over ``[batch, heads, seq, head_dim]`` inputs.

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU
    testing); on TPU leave it False.  ``window`` (requires ``causal``)
    restricts each token to the previous ``window`` positions (sliding-
    window attention, Mistral-style): tiles outside the band are dead on
    both sides — compute AND fetch cost scale with ``window``, not seq.
    """
    out, _ = flash_attention_with_lse(
        q, k, v, causal, block_q, block_k, interpret, False, window, sub
    )
    return out


# Consume grouped-query K/V natively (fewer KV heads than q heads);
# wrappers that route to these kernels should propagate the tag.
flash_attention.supports_gqa = True
flash_attention_with_lse.supports_gqa = True


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_k: int = 128,
    window: int | None = None,
) -> jax.Array:
    """Memory-efficient attention in plain XLA: ``lax.scan`` over KV blocks
    carrying the (m, l, o) online-softmax triple, each block's work wrapped
    in ``jax.checkpoint``.  Numerically identical to
    :func:`attention_reference`; peak memory O(seq·block) forward AND
    backward (XLA differentiates the scan and remat recomputes per-block
    scores instead of saving them).  The kernel-free fallback to
    :func:`flash_attention` for platforms without Pallas (the flash
    backward itself is Pallas — see `_flash_backward`)."""
    if window is not None:
        if not causal:
            raise ValueError("sliding window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    scale = q.shape[-1] ** -0.5
    seq_k = k.shape[2]
    bk = min(block_k, seq_k)
    if seq_k % bk:
        raise ValueError(f"block size {bk} must divide seq_k {seq_k}")
    num_kv = seq_k // bk
    q_len = q.shape[2]

    # [num_kv, b, h, bk, d] blocks, scanned over axis 0.
    kb = jnp.moveaxis(k.reshape(k.shape[0], k.shape[1], num_kv, bk, -1), 2, 0)
    vb = jnp.moveaxis(v.reshape(v.shape[0], v.shape[1], num_kv, bk, -1), 2, 0)

    # One shared implementation of the numerically-sensitive softmax-rescale
    # math: _block_update/_causal_mask above, which ring attention's forward
    # steps through too (so this fallback can never drift from the ring).
    @jax.checkpoint
    def body(carry, blk):
        m, l, o = carry
        kv_i, kt, vt = blk
        mask = _causal_mask(0, kv_i * bk, q_len, bk, window) \
            if causal else None
        return _block_update(q, kt, vt, m, l, o, scale=scale, mask=mask), None

    m0 = jnp.full(q.shape[:-1], _MASK_VALUE, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    o0 = jnp.zeros(q.shape[:-1] + v.shape[-1:], jnp.float32)
    (m, l, o), _ = lax.scan(
        body, (m0, l0, o0), (jnp.arange(num_kv), kb, vb)
    )
    return (o / l[..., None]).astype(q.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc_ref, *, block_q: int, block_k: int,
                         lo, hi, sub: int, scale: float, nkv: int):
    """dq: grid (bh, q_block, kv step), KV innermost — dq for one Q tile
    accumulates in VMEM scratch across its KV sweep, mirroring the forward's
    schedule (the run of its live tiles, and its dead-block elision)."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def update(rows, cols, mask):
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = mask(s)
        # Softmax tile from the saved row logsumexp — no m/l recurrence.
        # Dead rows carry the _MASK_VALUE lse sentinel: exp(s − lse) would
        # be exp(0)=1 on their masked entries, so zero them explicitly.
        row_lse = lse_ref[0, 0, rows]
        # Dead-row mask as f32: a bool ([:, None]) minor-dim insert on the
        # lane-layout row vector is unsupported by Mosaic (i1 relayout);
        # the f32 multiply lowers cleanly and is numerically identical.
        live = (row_lse > _MASK_VALUE * 0.5).astype(jnp.float32)
        p = jnp.exp(s - row_lse[:, None]) * live[:, None]
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, rows][:, None]) * scale
        dq_acc_ref[rows] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    kv, inside = _sweep_tile(_first_live_kv(qi, nkv, block_q, block_k, hi),
                             j, nkv)
    _masked_tile_branches(
        _tile_live(qi, kv, block_q, block_k, lo, hi, inside),
        qi, kv, block_q, block_k, lo, hi, update, sub)

    @pl.when(j == _emit_step(qi, steps, block_q, block_k, lo, hi))
    def _():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                          block_q: int, block_k: int, lo, hi, sub: int,
                          scale: float, q_steps: int, nq: int):
    """dk/dv: grid (bh_kv, kv_block, group·q step) with the (group member,
    Q tile) sweep innermost — dk/dv for one KV tile accumulate in VMEM
    scratch across the ``q_steps`` steps of every q head in its GQA group
    (group=1 is plain MHA): every Q tile of the ``nq``, or under a band
    with an upper edge the run of the KV tile's live ones
    (:func:`_first_live_q`).  Causal: Q tiles fully above the diagonal are
    dead (elided).  Emission at the last grid step needs no live tile."""
    kv = pl.program_id(1)
    gi = pl.program_id(2)
    qi, inside = _sweep_tile(
        _first_live_q(kv, nq, block_q, block_k, lo, hi), gi % q_steps, nq)

    @pl.when(gi == 0)
    def _():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def update(rows, cols, mask):
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = mask(s)
        row_lse = lse_ref[0, 0, rows]
        live = (row_lse > _MASK_VALUE * 0.5).astype(jnp.float32)  # see dq
        p = jnp.exp(s - row_lse[:, None]) * live[:, None]
        pt = p.astype(do.dtype).T
        dv_acc_ref[cols] += jnp.dot(pt, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0, rows][:, None]) * scale
        dk_acc_ref[cols] += jnp.dot(
            ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32
        )

    _masked_tile_branches(
        _tile_live(qi, kv, block_q, block_k, lo, hi, inside),
        qi, kv, block_q, block_k, lo, hi, update, sub)

    @pl.when(gi == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, do, lse, delta, lay: _Layout, *, causal,
                    block_q, block_k, interpret, window=None, sub=0):
    """The two backward calls over operands laid out as ``lay`` says:
    ``(dq, dk, dv)`` of ``lay.dq_shape``, ``lay.dk_shape`` and
    ``lay.dv_shape``; ``lse`` / ``delta`` are ``[batch·heads, 1, seq_q]``."""
    lo, hi = _normalize_band(causal, window)
    heads, kv_heads = lay.heads, lay.kv_heads
    seq_q, seq_k, d, d_v = lay.seq_q, lay.seq_k, lay.d, lay.d_v
    group = heads // kv_heads
    bq, bk = _blocks(lay, block_q, block_k, interpret)
    sub = diag_sub(bq, bk, lo, hi, sub)
    scale = d ** -0.5
    bh = lay.batch * heads
    bh_kv = lay.batch * kv_heads
    do = do.astype(q.dtype)
    nq = seq_q // bq
    nkv = seq_k // bk
    kv_steps, q_steps = band_grid(nq, nkv, bq, bk, lo, hi)[:2]

    work = bh * _band_live_pairs(seq_q, seq_k, lo, hi)
    # q (dq), o (do); k (dk), v (dv)
    q_bytes, o_bytes = (bh * seq_q * w * q.dtype.itemsize for w in (d, d_v))
    k_bytes, v_bytes = (bh_kv * seq_k * w * q.dtype.itemsize
                        for w in (d, d_v))
    in_bytes = int(q_bytes + o_bytes + k_bytes + v_bytes
                   + 2 * bh * seq_q * 4)

    q_tile, kv_tile, row_spec = _kv_innermost_specs(lay, bq, bk, lo, hi)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=bq, block_k=bk,
                          lo=lo, hi=hi, sub=sub, scale=scale, nkv=nkv),
        out_shape=jax.ShapeDtypeStruct(lay.dq_shape, q.dtype),
        grid=(bh, nq, kv_steps),
        in_specs=[q_tile(lay.q), kv_tile(lay.k), kv_tile(lay.v, d_v),
                  q_tile(lay.o, d_v), row_spec, row_spec],
        out_specs=q_tile(lay.o),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * work * (2 * d + d_v)), transcendentals=int(work),
            bytes_accessed=in_bytes + int(q_bytes),
        ),
        **names.kernel(names.FLASH_BWD_DQ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv sweep (group x Q steps) innermost per KV head: every Q tile, or
    # under an upper edge the run of the KV tile's live ones.  Causal dead Q
    # tiles (fully above the diagonal) re-map to the KV row's first live
    # tile of the same group head, and steps past a run's end to its last,
    # so their DMA is elided, mirroring the forward trick on the transposed
    # schedule.

    def q_index(b, j, gi):
        # KV grid row (batch-major over kv heads) + group member -> q row
        if group == 1:
            row, qi = b, gi
        else:
            g, qi = _split(gi, q_steps)
            batch, kv_head = _split(b, kv_heads)
            row = batch * heads + kv_head * group + g
        if hi is not None:
            # band's upper edge: the sweep starts at the first live q tile,
            # and q tiles past k + hi are dead
            qi = jnp.minimum(qi + _first_live_q(j, nq, bq, bk, lo, hi),
                             ((j + 1) * bk - 1 + hi - 1) // bq)
        elif lo is not None:
            # band's lower edge: q < k + lo tiles are dead
            qi = jnp.maximum(qi, (j * bk + lo) // bq)
        return row, jnp.clip(qi, 0, nq - 1)

    def q_tile_t(at, width=d):
        return pl.BlockSpec((1, bq, width),
                            lambda b, j, gi: at(*q_index(b, j, gi)),
                            memory_space=pltpu.VMEM)

    def row_index_t(b, j, gi):
        r, qi = q_index(b, j, gi)
        return (r, 0, qi)

    row_spec_t = pl.BlockSpec((1, 1, bq), row_index_t,
                              memory_space=pltpu.VMEM)

    def kv_tile_t(at, width=d):
        return pl.BlockSpec((1, bk, width), lambda b, j, gi: at(b, j),
                            memory_space=pltpu.VMEM)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          lo=lo, hi=hi, sub=sub, scale=scale,
                          q_steps=q_steps, nq=nq),
        out_shape=[
            jax.ShapeDtypeStruct(lay.dk_shape, k.dtype),
            jax.ShapeDtypeStruct(lay.dv_shape, v.dtype),
        ],
        grid=(bh_kv, nkv, q_steps * group),
        in_specs=[q_tile_t(lay.q), kv_tile_t(lay.k), kv_tile_t(lay.v, d_v),
                  q_tile_t(lay.o, d_v), row_spec_t, row_spec_t],
        out_specs=[kv_tile_t(lay.dkv), kv_tile_t(lay.dkv, d_v)],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * work * (d + d_v)), transcendentals=int(work),
            bytes_accessed=in_bytes + int(k_bytes + v_bytes),
        ),
        **names.kernel(names.FLASH_BWD_DKV),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _fwd(q, k, v, causal, block_q, block_k, interpret, out_f32, window, sub):
    out, lse = _head_major_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, out_f32=out_f32, window=window, sub=sub,
    )
    return (out, lse), (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, out_f32, window, sub, residuals,
         g):
    q, k, v, out, lse = residuals
    g_out, g_lse = g
    # delta_i = rowsum(dO_i · O_i): the dp→ds correction term, cheap
    # elementwise work XLA fuses on its own — no kernel needed.  The lse
    # cotangent enters through ds_ij = p_ij·(dp_ij − Δ_i + dL_i), i.e. it
    # just shifts the delta the kernels already consume.
    delta = jnp.sum(
        out.astype(jnp.float32) * g_out.astype(jnp.float32), axis=-1
    ) - g_lse.astype(jnp.float32)
    lay = _head_major_layout(q, k, v)
    stats = (lay.batch * lay.heads, 1, lay.seq_q)
    dq, dk, dv = _flash_backward(
        q.reshape(lay.dq_shape), k.reshape(lay.dk_shape),
        v.reshape(lay.dv_shape), g_out.reshape(lay.o_shape),
        lse.reshape(stats), delta.reshape(stats), lay, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret, window=window,
        sub=sub,
    )
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


flash_attention_with_lse.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def flash_attention_packed(
    qkv: jax.Array,
    n_heads: int,
    n_kv: int,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: int | None = None,
    sub: int = DIAG_SUB,
) -> jax.Array:
    """Flash attention straight over a fused projection's output: ``qkv``
    is ``[batch, seq, (n_heads + 2·n_kv)·head_dim]`` (q's heads, then k's,
    then v's), the result ``[batch, seq, n_heads·head_dim]`` — what the
    output projection reads.  The same three kernels as
    :func:`flash_attention`, with the same tiles in the same order (so
    the same bits); only their index maps differ, so no transpose, slice
    or copy stands between the projections and the kernels, forward or
    backward.  On TPU ``head_dim`` must be a multiple of 128: one head is
    then a whole number of lane tiles of the last dimension."""
    return _packed_fwd(qkv, n_heads, n_kv, causal, block_q, block_k,
                       interpret, window, sub)[0]


def _packed_fwd(qkv, n_heads, n_kv, causal, block_q, block_k, interpret,
                window, sub):
    out, lse = _flash_forward(
        qkv, qkv, qkv, _packed_layout(qkv, n_heads, n_kv), causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret, window=window,
        sub=sub)
    return out, (qkv, out, lse)


def _packed_bwd(n_heads, n_kv, causal, block_q, block_k, interpret, window,
                sub, residuals, g):
    qkv, out, lse = residuals
    lay = _packed_layout(qkv, n_heads, n_kv)
    # delta a head at a time: each is a lane-aligned column block of o and
    # do reduced over its own d lanes, which lands [b, s] with s minor, the
    # stats layout, so neither the operands nor the result are transposed
    d = lay.d
    prod = out.astype(jnp.float32) * g.astype(jnp.float32)
    delta = jnp.stack([jnp.sum(prod[..., h * d:(h + 1) * d], axis=-1)
                       for h in range(n_heads)], axis=1).reshape(lse.shape)
    dq, dk, dv = _flash_backward(
        qkv, qkv, qkv, g, lse, delta, lay, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window, sub=sub)
    return (jnp.concatenate([dq, dk, dv], axis=-1),)


flash_attention_packed.defvjp(_packed_fwd, _packed_bwd)
