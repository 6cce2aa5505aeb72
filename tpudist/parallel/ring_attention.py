"""Ring-attention sequence/context parallelism.

Long-context scaling: the sequence axis is sharded over the mesh's ``seq``
axis, each device holds one Q/K/V block, and K/V blocks rotate around the
ring with ``jax.lax.ppermute`` (one ICI hop per step) while each device
accumulates its Q block's attention with an online-softmax update — the
blockwise formulation of Liu et al.'s Ring Attention.  Peak memory per
device is O(seq/num_devices), so context length scales linearly with ring
size at constant per-chip memory.

The reference has no attention anywhere (its model is a 5-layer MLP on
2-dim inputs — ``toy_model_and_data.py:12-22``; SURVEY.md §5.7 records
sequence parallelism as absent), so this module is a capability extension,
designed TPU-first:

- every op inside the shard-local body is ``jnp``/``lax`` — XLA fuses the
  softmax-rescale chain and keeps the two matmuls per step on the MXU;
- the ring hop is ``lax.ppermute`` over the named axis, which XLA lowers to
  neighbor ICI transfers that overlap with the block's compute;
- the whole construct is differentiable (ppermute's transpose is the
  reverse permutation), so the same code path trains.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpudist.ops.flash_attention import (
    _MASK_VALUE,
    _block_update,
    _causal_mask,
)
from tpudist.runtime.mesh import AXIS_SEQ


def ring_attention_shard(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = AXIS_SEQ,
    causal: bool = False,
    inner_block: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Shard-local ring attention body (call inside ``shard_map``).

    Each device holds contiguous blocks ``q, k, v: [b, h, seq_shard, d]`` of
    the globally seq-sharded arrays.  K/V travel the ring; at step ``t`` this
    device processes the block that originated on rank ``(i - t) mod n``, so
    step 0 is its own (diagonal) block — which guarantees the first processed
    block is never fully masked under causal attention.

    ``inner_block``: when set, each ring step's KV shard is consumed by a
    rematerialized ``lax.scan`` of ``inner_block``-wide sub-blocks instead
    of one [shard, shard] score matrix — peak per-device attention memory
    drops from O(shard²) to O(shard·inner_block), which is what lets very
    long shards (many thousands of tokens per chip) train.
    """
    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    block = q.shape[-2]
    # Grouped-query K/V: the RING carries the small hkv-headed tensors
    # (group x fewer bytes per ICI hop) and each device broadcasts to
    # full heads only at compute time, inside consume_shard.
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"q heads {q.shape[1]} not a multiple of "
                         f"kv heads {k.shape[1]}")
    kv_group = q.shape[1] // k.shape[1]

    # pcast-to-varying: the carries join a scan whose outputs vary over the
    # seq axis (they mix in the sharded q/k/v), so the initial values must
    # carry the same varying-manual-axes type.
    m = lax.pcast(jnp.full(q.shape[:-1], _MASK_VALUE, jnp.float32),
                  (axis_name,), to="varying")
    l = lax.pcast(jnp.zeros(q.shape[:-1], jnp.float32),
                  (axis_name,), to="varying")
    o = lax.pcast(jnp.zeros(q.shape, jnp.float32), (axis_name,), to="varying")
    q_off = my_idx * block

    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")

    def consume_shard(kv_idx, k, v, m, l, o):
        """Fold one ring step's KV shard into the (m, l, o) carry."""
        if kv_group > 1:  # broadcast AFTER the hop — wire stays narrow
            k = jnp.repeat(k, kv_group, axis=1)
            v = jnp.repeat(v, kv_group, axis=1)
        if inner_block is None:
            mask = _causal_mask(q_off, kv_idx * block, block, block,
                                window) if causal else None
            return _block_update(q, k, v, m, l, o, scale=scale, mask=mask)
        nb = block // inner_block
        if block % inner_block:
            raise ValueError(
                f"inner_block {inner_block} must divide seq shard {block}"
            )
        kb = jnp.moveaxis(
            k.reshape(*k.shape[:-2], nb, inner_block, k.shape[-1]), -3, 0
        )
        vb = jnp.moveaxis(
            v.reshape(*v.shape[:-2], nb, inner_block, v.shape[-1]), -3, 0
        )

        @jax.checkpoint
        def sub(carry, blk):
            m, l, o = carry
            sub_i, kt, vt = blk
            mask = None
            if causal:
                mask = _causal_mask(
                    q_off, kv_idx * block + sub_i * inner_block,
                    block, inner_block, window,
                )
            return _block_update(q, kt, vt, m, l, o, scale=scale, mask=mask), None

        (m, l, o), _ = lax.scan(sub, (m, l, o), (jnp.arange(nb), kb, vb))
        return m, l, o

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    for step in range(axis_size):
        kv_idx = (my_idx - step) % axis_size
        m, l, o = consume_shard(kv_idx, k, v, m, l, o)
        if window is not None and window - (step + 1) * block <= -(block - 1):
            # Sliding window: every later hop is fully masked for every
            # device (un-wrapped hops sit left of the band at the static
            # offset (step+1)·block; wrapped hops are causally dead) —
            # stop the ring, same static break as the flash body.
            break
        if step + 1 < axis_size:
            # One ICI hop: K/V move to the right neighbor while the next
            # step's compute is still queued — XLA overlaps the two.
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
    return (o / l[..., None]).astype(q.dtype)


def _merge_partials(out_c, lse_c, out_h, lse_h):
    """Exact, stabilized merge of two attention partials over disjoint KV
    sets, each given as (normalized out, row logsumexp).  Fully-masked
    partials (lse == _MASK_VALUE, out == 0) merge to a no-op.  All f32."""
    m = jnp.maximum(lse_c, lse_h)
    w_c = jnp.exp(lse_c - m)
    w_h = jnp.exp(lse_h - m)
    denom = w_c + w_h
    lse_new = m + jnp.log(denom)
    out_new = (
        out_c * w_c[..., None] + out_h * w_h[..., None]
    ) / denom[..., None]
    return out_new, lse_new


def ring_attention_shard_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = AXIS_SEQ,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Shard-local ring attention whose per-hop math is the Pallas flash
    kernel (call inside ``shard_map``).

    Same ring schedule as :func:`ring_attention_shard`, different
    decomposition: instead of threading the raw (m, l, o) online-softmax
    carry through XLA block updates, each hop computes a *complete*
    attention over its KV shard with :func:`tpudist.ops.flash_attention_with_lse`
    and the partials are merged via their logsumexps (`_merge_partials`) —
    O(shard) XLA work per hop, while every O(shard²·d) FLOP runs in the
    flash kernels, forward AND backward (the kernel's custom VJP folds the
    lse cotangent into its delta term).

    With equal shards and the step-t block originating on rank
    ``(i−t) mod n``, causal masking collapses to three static-per-hop
    cases: hop 0 is the diagonal (causal kernel), later hops are either
    fully live (unmasked kernel) or fully dead (skipped via ``lax.cond``
    — half the ring's compute under causal attention, the same work the
    XLA path spends masked).
    """
    from tpudist.ops import flash_attention_with_lse

    # Trace-time fit check (shard shapes are static here): the kernel needs
    # the clamped blocks to divide the shard.  Fall back to the XLA carry
    # path otherwise — same semantics, no shape constraint.
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    shard = q.shape[-2]
    if shard % min(block_q, shard) or shard % min(block_k, shard):
        if k.shape[1] != q.shape[1]:  # xla body needs equal heads
            group = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
        return ring_attention_shard(
            q, k, v, axis_name=axis_name, causal=causal, window=window
        )

    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)

    # Hop 0 is this device's own (diagonal) KV shard: causal kernel
    # (windowed if requested).  out_f32: partials stay f32 through every
    # merge whatever the input dtype (parity with the XLA path's f32
    # (m, l, o) carry).
    out, lse = flash_attention_with_lse(
        q, k, v, causal, block_q, block_k, interpret, True, window
    )

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    for step in range(1, axis_size):
        if window is not None and window - step * shard <= -(shard - 1):
            # The band ends before this hop's shard for EVERY device (the
            # un-wrapped local offset q − k = step·shard is static), and
            # later hops are further left still: with a sliding window the
            # ring stops here — compute scales with window, not seq.
            break
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        kv_idx = (my_idx - step) % axis_size
        if causal:
            # Un-wrapped hops (kv_idx < my_idx) sit wholly in the causal
            # past: the per-hop kernel needs no causal mask, only the
            # window band shifted by the static hop offset step·shard.
            band = (None, window - step * shard) if window is not None \
                else None

            def live_hop(kt, vt):
                return flash_attention_with_lse(
                    q, kt, vt, False, block_q, block_k, interpret, True,
                    band,
                )

            def dead_hop(kt, vt):
                return (
                    jnp.zeros(q.shape, jnp.float32),
                    jnp.full(q.shape[:-1], _MASK_VALUE, jnp.float32),
                )

            out_h, lse_h = lax.cond(kv_idx < my_idx, live_hop, dead_hop, k, v)
        else:
            out_h, lse_h = flash_attention_with_lse(
                q, k, v, False, block_q, block_k, interpret, True
            )
        out, lse = _merge_partials(out, lse, out_h, lse_h)
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    *,
    axis_name: str = AXIS_SEQ,
    causal: bool = False,
    batch_axis: Optional[str] = None,
    inner_block: Optional[int] = None,
    kernel: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
    window: Optional[int] = None,
):
    """Jitted global-view ring attention over ``mesh``.

    Inputs/outputs are global ``[batch, heads, seq, head_dim]`` arrays with
    ``seq`` sharded over ``axis_name`` (and optionally ``batch`` over
    ``batch_axis``).  Sequence length must divide evenly by the ring size
    (the equal-block contract, like the reference's equal-batch assumption
    ``demo.py:113``).

    ``kernel`` selects the shard-local math: ``'xla'`` = the
    (m, l, o)-carry block updates (:func:`ring_attention_shard`),
    ``'flash'`` = the Pallas per-hop kernels
    (:func:`ring_attention_shard_flash`; shards whose shape doesn't fit
    the block contract fall back to the xla body at trace time),
    ``'auto'`` = flash on TPU — unless ``inner_block`` was explicitly
    requested (a memory-blocking contract only the xla body honors).
    """
    if kernel not in ("auto", "xla", "flash"):
        raise ValueError(f"kernel must be auto|xla|flash, got {kernel!r}")
    spec = P(batch_axis, None, axis_name, None)
    if kernel == "auto":
        on_tpu = jax.devices()[0].platform == "tpu"
        kernel = "flash" if (on_tpu or interpret) and inner_block is None \
            else "xla"
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if kernel == "flash":
        body = functools.partial(
            ring_attention_shard_flash, axis_name=axis_name, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window,
        )
    else:
        body = functools.partial(
            ring_attention_shard, axis_name=axis_name, causal=causal,
            inner_block=inner_block, window=window,
        )
    sharded = jax.shard_map(
        lambda q, k, v: body(q, k, v),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call's out_shape carries no varying-manual-axes type, so
        # the vma checker cannot type the flash path; the xla path keeps it
        # (its carries are explicitly pcast).
        check_vma=(kernel != "flash"),
    )
    ring = jax.jit(sharded)
    # Window tag consumed by Block's sliding_window training-path guard.
    ring.window = window
    # BOTH bodies consume grouped-query K/V natively (Block then skips
    # its repeat): the flash kernels fetch KV tiles once per group; the
    # xla body hops the small hkv-headed tensors and broadcasts post-hop
    # — either way the ring wire carries group x fewer KV bytes.
    ring.supports_gqa = True
    return ring


# ---------------------------------------------------------------------------
# Zigzag (causal-balanced) ring layout
# ---------------------------------------------------------------------------

def zigzag_indices(seq_len: int, n_shards: int) -> jnp.ndarray:
    """Token permutation for the zigzag causal-balanced ring layout.

    The sequence splits into ``2n`` half-chunks; ring position ``i`` holds
    half-chunks ``i`` and ``2n−1−i``.  Returns the gather indices ``π``
    such that ``x[..., π, :]`` is the zigzag-ordered sequence whose
    contiguous ``seq_len/n``-wide shards land one per device under the
    usual ``P(seq)`` sharding.  Invert with ``jnp.argsort(π)``.
    """
    if seq_len % (2 * n_shards):
        raise ValueError(
            f"seq {seq_len} must divide into 2*{n_shards} half-chunks")
    half = seq_len // (2 * n_shards)
    order = []
    for i in range(n_shards):
        order += [i, 2 * n_shards - 1 - i]
    import numpy as _np

    chunks = [_np.arange(c * half, (c + 1) * half) for c in order]
    return jnp.asarray(_np.concatenate(chunks), jnp.int32)


def ring_attention_shard_zigzag(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = AXIS_SEQ,
) -> jax.Array:
    """Causal ring attention over the ZIGZAG layout — FLOP-balanced.

    The contiguous causal ring wastes ~half the machine: at hop ``t``
    only devices ``i ≥ t`` hold live (unmasked) K/V, so every hop runs at
    single-block latency while early ranks idle (or, in the uniform
    formulation, burn fully-masked FLOPs) — aggregate efficiency
    ``(n+1)/2n → ½``.  The zigzag layout (Brandon et al., "Striped
    Attention"-family; each device owns half-chunks ``i`` AND ``2n−1−i``)
    makes every (device, hop) pair cost EXACTLY two half-chunk attention
    blocks:

    - my high chunk ``2n−1−i`` attends every arriving low chunk ``j``
      (always fully live, never masked);
    - exactly one of {my low × arriving low (live iff ``j ≤ i``), my
      high × arriving high (live iff ``j ≥ i``)} is live per hop —
      selected by a ``lax.cond`` whose branches cost the same, so the
      ring never waits on a straggler;
    - hop 0 (``j == i``) additionally carries the two triangular
      diagonal blocks (statically unrolled — ``t`` is a Python int).

    Inputs: this device's zigzag-local blocks ``[b, h, shard, d]`` with
    ``shard = seq/n`` tokens = half-chunks ``(i, 2n−1−i)`` concatenated
    (produce with :func:`zigzag_indices`).  Causal only (that is the
    regime with the imbalance); equal q/kv heads (broadcast GQA first);
    sliding windows not supported — the window's early-exit already
    rebalances the contiguous ring.
    """
    axis_size = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    shard = q.shape[-2]
    if shard % 2:
        raise ValueError(f"zigzag shard must be even, got {shard}")
    half = shard // 2
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"q heads {q.shape[1]} not a multiple of "
                         f"kv heads {k.shape[1]}")
    kv_group = q.shape[1] // k.shape[1]

    q_lo, q_hi = q[..., :half, :], q[..., half:, :]

    def fresh(qb):
        return (
            lax.pcast(jnp.full(qb.shape[:-1], _MASK_VALUE, jnp.float32),
                      (axis_name,), to="varying"),
            lax.pcast(jnp.zeros(qb.shape[:-1], jnp.float32),
                      (axis_name,), to="varying"),
            lax.pcast(jnp.zeros(qb.shape, jnp.float32),
                      (axis_name,), to="varying"),
        )

    lo_carry, hi_carry = fresh(q_lo), fresh(q_hi)

    def diag_mask():
        qi = lax.broadcasted_iota(jnp.int32, (half, half), 0)
        kj = lax.broadcasted_iota(jnp.int32, (half, half), 1)
        return qi >= kj

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    for t in range(axis_size):
        if kv_group > 1:  # hop the small tensors, broadcast at compute
            kf = jnp.repeat(k, kv_group, axis=1)
            vf = jnp.repeat(v, kv_group, axis=1)
        else:
            kf, vf = k, v
        k_lo, k_hi = kf[..., :half, :], kf[..., half:, :]
        v_lo, v_hi = vf[..., :half, :], vf[..., half:, :]
        if t == 0:
            # j == i: both diagonals (triangular) + the always-live full.
            lo_carry = _block_update(q_lo, k_lo, v_lo, *lo_carry,
                                     scale=scale, mask=diag_mask())
            hi_carry = _block_update(q_hi, k_lo, v_lo, *hi_carry,
                                     scale=scale)
            hi_carry = _block_update(q_hi, k_hi, v_hi, *hi_carry,
                                     scale=scale, mask=diag_mask())
        else:
            j = jnp.mod(my - t, axis_size)
            # my high × arriving low: always fully live, maskless.
            hi_carry = _block_update(q_hi, k_lo, v_lo, *hi_carry,
                                     scale=scale)

            # exactly one of (lo×lo | hi×hi) is live; equal-cost branches.
            def lo_branch(args):
                lo, hi, kl, vl, kh, vh = args
                return (_block_update(q_lo, kl, vl, *lo, scale=scale), hi)

            def hi_branch(args):
                lo, hi, kl, vl, kh, vh = args
                return (lo, _block_update(q_hi, kh, vh, *hi, scale=scale))

            lo_carry, hi_carry = lax.cond(
                j < my, lo_branch, hi_branch,
                (lo_carry, hi_carry, k_lo, v_lo, k_hi, v_hi))
        if t + 1 < axis_size:
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)

    m_lo, l_lo, o_lo = lo_carry
    m_hi, l_hi, o_hi = hi_carry
    out_lo = (o_lo / l_lo[..., None]).astype(q.dtype)
    out_hi = (o_hi / l_hi[..., None]).astype(q.dtype)
    return jnp.concatenate([out_lo, out_hi], axis=-2)


def make_zigzag_ring_attention(
    mesh: Mesh,
    *,
    axis_name: str = AXIS_SEQ,
    batch_axis: Optional[str] = None,
):
    """Jitted global-view zigzag ring attention (causal).

    Consumes/produces arrays in the ZIGZAG order — permute tokens with
    ``zigzag_indices(seq, mesh.shape[axis_name])`` before, and apply the
    inverse (``jnp.argsort``) after if positional order matters
    downstream.  For an LM, permute the token stream once at the data
    layer (positions travel with the tokens via RoPE/position ids) and
    the loss — a per-position mean — needs no unpermute.
    """
    spec = P(batch_axis, None, axis_name, None)
    sharded = jax.shard_map(
        functools.partial(ring_attention_shard_zigzag, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=True,
    )
    ring = jax.jit(sharded)
    ring.window = None
    ring.supports_gqa = True  # hops hkv-headed K/V, broadcasts post-hop
    return ring


def make_zigzag_lm_loss(seq_len: int, n_shards: int):
    """Next-token LM loss for a zigzag-permuted token stream.

    Under ``π = zigzag_indices(seq_len, n_shards)``, array position ``p``
    holds the token of temporal position ``π(p)``; its prediction target
    is the token at temporal ``π(p)+1``, which lives at array position
    ``argsort(π)[π(p)+1]``.  Both maps are static, so targets are one
    gather of the (permuted) token batch itself, with the final temporal
    position masked out.  Returns ``loss_fn(logits, tokens)`` —
    drop-in for ``make_lm_train_step(..., loss_fn=...)`` — numerically
    identical to :func:`tpudist.models.transformer.lm_loss` on the
    natural order (tests assert it).
    """
    import numpy as _np

    from tpudist.models.transformer import lm_loss_with_targets

    pi = _np.asarray(zigzag_indices(seq_len, n_shards))
    inv = _np.argsort(pi)
    nxt = _np.where(pi + 1 < seq_len, inv[(pi + 1) % seq_len], -1)
    nxt_idx = jnp.asarray(_np.where(nxt >= 0, nxt, 0), jnp.int32)
    mask = jnp.asarray(nxt >= 0)

    def loss_fn(logits, tokens):
        targets = jnp.where(mask[None, :], tokens[:, nxt_idx], -1)
        return lm_loss_with_targets(logits, targets)

    return loss_fn
