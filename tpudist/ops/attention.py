"""How causal attention runs on one device: which implementation, in
which operand layout, with which tiles.

Three implementations of the same math live in
:mod:`tpudist.ops.flash_attention`: the dense XLA reference, the Pallas
flash kernels, and the blockwise XLA scan.  This module is the one place
that chooses among them, from what it can observe: the device kind, the
sequence length and the head width.  The tiles come from ONE table by
device kind (:data:`TILES`); the choice itself is :func:`route`, a pure
function a test can call without a device.  The models take
:data:`default_attention` (or a windowed instance from
:func:`make_length_aware_attention`) as their ``attention_fn``; ring
attention (:mod:`tpudist.parallel.ring_attention`) is the other
``attention_fn`` and covers the sequence sharded between chips.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from tpudist import telemetry
from tpudist.ops.flash_attention import (
    attention_reference,
    band_grid,
    blockwise_attention,
    diag_sub,
    flash_attention,
    flash_attention_packed,
)
from tpudist.telemetry import names


class Tiles(NamedTuple):
    """One device kind's row: where the flash kernels take over and the
    tiles they run with."""

    min_seq: int        # the flash kernels from this many positions
    block_q: int
    block_k: int
    block_k_long: int   # the KV tile from ``long_seq`` positions, where it
    long_seq: int       # divides the length
    sub: int            # a tile on the diagonal goes by squares this wide
    # a sliding window narrower than ``block_q`` runs square tiles of its
    # own width (where that is a multiple of the 128 lanes and divides the
    # length), its two edge tiles by squares this wide; 0: the row's tiles,
    # as every other call
    window_sub: int = 0


# The v5e row is what the round-2 autotuner wrote for this kind (1024 x 1024
# tiles, flash from 2,048 positions), timed at a toy shape (head width 128,
# 8 heads, batch 2), over the default row's ``long_seq``.  Both benchmark
# cells run with it (2,048 and 8,192 positions: 1024 x 1024).  A tile that
# size on the causal diagonal is half dead; the kernels work it by strips
# of ``sub`` q rows and leave out the ``sub``-wide squares above the
# diagonal (10 of 16 at 256: ``flash_attention.diag_sub`` says when,
# :func:`computed_over_live` what is left).  ``sub`` 256 is PR 33's, from
# the three kernels timed on the chip at both cells' shapes against 128
# and 512 (PERF.md section 6): a diagonal tile's 0.625 of the products
# gives dq all of it back, dk/dv and the forward less (the forward's
# strips each wait on their own row statistics; it alone would take 512).
# The tiles themselves have not been swept on the cells' shapes; where
# they turn out to depend on the shape the key grows here.
#
# A sliding window's band has two edges, and the kernels' grids follow it
# (PR 42): a query tile sweeps the run of its live key tiles and a key tile
# the run of its live query tiles (``flash_attention.band_grid``), so narrow
# tiles no longer pay for the tiles they skip (0.4 us a skipped step).
# Inside a window of 512 over 8,192 positions the row's 1024 x 1024 tiles
# compute 3.87 times the live pairs; tiles of the window's own width 2.00
# times whole, and by squares of ``window_sub`` (the tile on the diagonal
# and the one the far edge crosses are the two halves of one staircase,
# ``flash_attention.diag_sub``) 1.50 at 256 and 1.25 at 128
# (:func:`computed_over_live`).  ``window_sub`` 256 is PR 42's, from the one
# cell that has window layers (36 query on 4 key/value heads of 128), a
# traced run of it a step of the way: the three kernels of its three sliding
# layers 42.4 ms a step on the parent, 36.6 with the grid cut alone (the
# row's tiles), 28.7 by 512 x 512 tiles whole, 28.3 by squares of 256, 26.8
# of 128; and from the kernels alone timed at the same shape, where 256 x
# 256 tiles (16.5 ms a layer against 9.8), 1024 x 512 (14.1) and 512 x 1024
# (11.5) all lost (PERF.md section 6).  Squares of 128 are 1.5 ms a step
# faster and were not kept for what they cost a start: eight unrolled strips
# a kernel at twelve call sites put 2.9 s on the step's trace and 1.7 on its
# load from the compile cache, a tenth of the cell's warm set-up, where
# squares of 256 cost 0.4 s of trace.  Only a window of 512 has been timed:
# narrower ones take the same rule untried.
TILES = {
    "TPU v5 lite": Tiles(2048, 1024, 1024, 1024, 8192, 256, 256),
}
# Every other kind: the values first chosen on a v5e in round 2, before the
# autotuner.  The blockwise route off the TPU reads its ``block_k`` here.  A
# windowed call keeps the row's tiles: nothing was timed on another kind.
DEFAULT_TILES = Tiles(1024, 512, 512, 1024, 8192, 256)

FLASH, REFERENCE, BLOCKWISE = "flash", "reference", "blockwise"


class Route(NamedTuple):
    kernel: str              # FLASH, REFERENCE or BLOCKWISE
    block_q: int
    block_k: int
    why_not: Optional[str]   # why not packed flash (names.WHY_*), or None
    sub: int = 0             # the row's, for the flash kernels' band-edge tiles

    @property
    def layout(self) -> str:
        return names.PACKED if self.why_not is None else names.HEAD_MAJOR


def route(device_kind: str, seq: int, dh: int,
          window: Optional[int] = None) -> Route:
    """What runs for ``seq`` positions at head width ``dh`` on a device of
    this kind, causal to everything or inside a sliding ``window``: the
    row's tiles, or for a window narrower than them square tiles of the
    window's width where the row has a ``window_sub``.

    The flash kernels take a length from the row's ``min_seq`` that both
    tiles divide (the kernels' contract), on a TPU: a kind whose name
    begins ``TPU``, as every ``jax.Device.device_kind`` of one does.  A
    length they do not take runs the dense reference (``names.WHY_SEQ``);
    off the TPU a length they would take runs the blockwise scan over
    ``block_k`` (``names.WHY_PLATFORM``).  The flash kernels read the
    packed layout where one head is a whole number of 128-lane tiles;
    otherwise (``names.WHY_DH``) they run head-major.
    """
    t = TILES.get(device_kind, DEFAULT_TILES)
    bk = (t.block_k_long if seq >= t.long_seq and seq % t.block_k_long == 0
          else t.block_k)
    if not (seq >= t.min_seq and seq % t.block_q == 0 and seq % bk == 0):
        return Route(REFERENCE, t.block_q, bk, names.WHY_SEQ)
    if not device_kind.startswith("TPU"):
        return Route(BLOCKWISE, t.block_q, bk, names.WHY_PLATFORM)
    why_not = names.WHY_DH if dh % 128 else None
    if (window is not None and t.window_sub and window < t.block_q
            and window % 128 == 0 == seq % window):
        return Route(FLASH, window, window, why_not, t.window_sub)
    return Route(FLASH, t.block_q, bk, why_not, t.sub)


def computed_over_live(seq: int, block_q: int, block_k: int, sub: int = 0,
                       window: Optional[int] = None) -> float:
    """Score entries the flash kernels compute over the live pairs of
    causal attention (``seq·(seq+1)/2`` of them, or inside a sliding
    ``window`` position ``q``'s ``min(q + 1, window)``): every tile the band
    touches whole, but a tile an edge of the band crosses where the kernels
    work it by ``sub``-wide squares (``sub`` as :func:`diag_sub` gives it, 0
    for the whole tile), only the squares on its live side of the edge.
    1024 x 1024 tiles: 1.50 whole and 1.125 at ``sub`` 256 over 2,048
    positions, 1.125 and 1.031 over 8,192; a window of 512 over 8,192
    positions 3.87, by 512 x 512 tiles 2.00 whole, 1.50 at ``sub`` 256 and
    1.25 at 128."""
    _, _, tiles, edge = band_grid(seq // block_q, seq // block_k, block_q,
                                  block_k, 0, window)
    n = block_q // sub if sub else 0
    staircase = n * (n + 1) // 2 * sub * sub if sub else block_q * block_k
    computed = (tiles - edge) * block_q * block_k + edge * staircase
    live = (seq * (seq + 1) / 2 if window is None or window >= seq else
            window * (window + 1) / 2 + (seq - window) * window)
    return computed / live


def _per_shard(kernel, *operands):
    """Run a Pallas attention ``kernel`` on each device's own batch rows.

    Mosaic kernels cannot be partitioned automatically: inside a jit over
    several chips (every multi-chip DP / FSDP / ZeRO train step) a bare
    ``pallas_call`` is refused with "wrap the call in a shard_map".  The
    model does not know the mesh, so the step builders
    (``tpudist.train.lm``) trace under it as JAX's ambient mesh, and this
    wraps the kernel in a ``shard_map`` over its ``data`` axis — attention
    rows are independent per batch element, which is the leading axis of
    every operand (``q, k, v`` head-major, or the one packed ``qkv``) and
    of the result.  Heads are not split: under tensor parallelism every
    ``model`` shard computes all heads.  One device, no ambient mesh, or
    already inside a ``shard_map`` body (ring attention, the pipeline
    schedules): the kernel runs as it is.
    """
    from jax.sharding import PartitionSpec as P

    from tpudist.runtime.mesh import AXIS_DATA

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return kernel(*operands)
    data = (AXIS_DATA if AXIS_DATA in mesh.axis_names
            and operands[0].shape[0] % mesh.shape[AXIS_DATA] == 0 else None)
    spec = P(data)
    return jax.shard_map(kernel, in_specs=(spec,) * len(operands),
                         out_specs=spec, check_vma=False)(*operands)


def split_heads(qkv: jax.Array, n_heads: int, n_kv: int):
    """The fused projection's ``[b, s, (n_heads + 2·n_kv)·dh]`` output cut
    into head-major ``q [b, n_heads, s, dh]`` and ``k, v [b, n_kv, s,
    dh]`` — what every attention but the packed flash route takes."""
    b, s, cols = qkv.shape
    dh = cols // (n_heads + 2 * n_kv)

    def heads(t, n):  # [b, s, n·dh] -> [b, n, s, dh]
        return t.reshape(b, s, n, dh).transpose(0, 2, 1, 3)

    d, kv_dim = n_heads * dh, n_kv * dh
    return (heads(qkv[..., :d], n_heads),
            heads(qkv[..., d : d + kv_dim], n_kv),
            heads(qkv[..., d + kv_dim :], n_kv))


def merge_heads(attn: jax.Array) -> jax.Array:
    """``[b, h, s, dh]`` back to the ``[b, s, h·dh]`` the output projection
    reads."""
    b, h, s, dh = attn.shape
    return attn.transpose(0, 2, 1, 3).reshape(b, s, h * dh)


def make_length_aware_attention(window: Optional[int] = None):
    """Build the single-device causal attention that :func:`route`
    steers, reading the device kind from ``jax.devices()[0]`` at trace
    time: the dense XLA reference for lengths the flash kernels do not
    take, the Pallas flash kernels on a TPU, the blockwise XLA scan
    elsewhere.

    ``window``: sliding-window (local) attention — the flash kernels mask
    to the band and elide tiles outside it on both sides (compute scales
    with window, not seq); the non-kernel paths mask the dense scores.

    The result accepts grouped-query K/V (fewer heads than q): the flash
    kernels consume it natively — KV tiles are fetched once per group,
    never materialized at full head count; the non-kernel paths broadcast.

    Two operand layouts.  ``attend(q, k, v)`` is head-major, ``[b, h, s,
    dh]``.  ``attend.packed(qkv, n_heads, n_kv)`` takes the fused
    projection's own ``[b, s, (n_heads + 2·n_kv)·dh]`` output and returns
    ``[b, s, n_heads·dh]``: ``Block`` calls it when an attention_fn carries
    the tag.  One ``attn_layout`` event a traced call site says which
    layout :func:`route` chose, and why where it is not the packed one.
    """
    def attend(q, k, v):
        r = route(jax.devices()[0].device_kind, q.shape[2], q.shape[3],
                  window)
        if r.kernel == FLASH:
            return _per_shard(
                lambda q, k, v: flash_attention(
                    q, k, v, True, r.block_q, r.block_k, False, window,
                    r.sub),
                q, k, v)
        if k.shape[1] != q.shape[1]:
            # only the flash kernels consume grouped K/V natively
            group = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
        if r.kernel == REFERENCE:
            return attention_reference(q, k, v, causal=True, window=window)
        return blockwise_attention(q, k, v, causal=True, block_k=r.block_k,
                                   window=window)

    def attend_packed(qkv, n_heads: int, n_kv: int):
        """The same attention over the fused projection's own ``[b, s,
        (n_heads + 2·n_kv)·dh]`` output, giving the ``[b, s, n_heads·dh]``
        the output projection reads.  Where the flash kernels run and one
        head is a whole number of 128-lane tiles (``dh % 128 == 0``) they
        index that layout themselves and nothing is re-laid out round
        them; everywhere else: split, :func:`attend`, merge."""
        dh = qkv.shape[-1] // (n_heads + 2 * n_kv)
        seq = qkv.shape[1]
        r = route(jax.devices()[0].device_kind, seq, dh, window)
        said = dict(layout=r.layout)
        if r.why_not is not None:
            said["reason"] = r.why_not
        if r.kernel == FLASH:
            # how a tile an edge of the band crosses is worked (0: whole),
            # and what that makes the kernels compute
            said["diag_sub"] = diag_sub(r.block_q, r.block_k, 0, window, r.sub)
            said["computed_over_live"] = round(computed_over_live(
                seq, r.block_q, r.block_k, said["diag_sub"], window), 4)
            if window is not None:
                # the key axis of the forward and dq grids, and how many of
                # a head's grid steps find a live tile
                nq = seq // r.block_q
                steps, _, live, _ = band_grid(nq, seq // r.block_k,
                                              r.block_q, r.block_k, 0, window)
                said.update(window=window, tiles=[r.block_q, r.block_k],
                            grid_kv=steps,
                            live_steps_share=round(live / (nq * steps), 4))
        telemetry.event(names.ATTN_LAYOUT, **said)
        if r.why_not is not None:
            return merge_heads(attend(*split_heads(qkv, n_heads, n_kv)))
        return _per_shard(
            lambda qkv: flash_attention_packed(
                qkv, n_heads, n_kv, True, r.block_q, r.block_k, False,
                window, r.sub), qkv)

    # Block consults this tag before broadcasting K/V to full head count —
    # this path handles grouped-query inputs itself (see above).
    attend.supports_gqa = True
    # Block's training-path guard checks this tag against its
    # sliding_window field (decode-cache masking alone is not windowed
    # training — the mismatch must be loud, not silent).
    attend.window = window
    # Block hands an attention_fn that carries this tag the projection's
    # packed output instead of head-major q, k, v.
    attend.packed = attend_packed
    return attend


default_attention = make_length_aware_attention()


@functools.lru_cache(maxsize=None)
def attention_within(window: Optional[int]):
    """The one instance a window: :data:`default_attention` for ``None``,
    else :func:`make_length_aware_attention` of that window."""
    if window is None:
        return default_attention
    return make_length_aware_attention(window)
