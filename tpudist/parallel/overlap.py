"""Decomposed collective matmul: ppermute-pipelined TP/FSDP gathers.

The TP and FSDP layouts in this package are *pure layouts*: they leave
every all-gather / reduce(-scatter) to the XLA SPMD partitioner, which
schedules the whole gather BEFORE the matmul that consumes it — at scale
that gather is exposed wire time on the critical path (the multiproc
scaling artifact shows the in-step collective dominating everything
else).  This module is the explicit alternative: the collective is
decomposed into a chain of ``lax.ppermute`` hops, each hop moving ONE
chunk while the PREVIOUS chunk's matmul runs — the "collective matmul"
of Wang et al. (overlap-communication-with-dependent-computation) and
the weight-update-sharding line of work (arXiv:2004.13336), hand-built
so overlap is structural, not a compiler mood.

Two shard-local primitives (call them inside ``shard_map``, like
:func:`tpudist.parallel.tensor_parallel.tp_mlp_shard`):

- :func:`ag_matmul` — all-gather fused into a matmul.  Three gather
  geometries cover the TP/FSDP hot paths:

  * ``gather="lhs"``:   ``allgather(x) @ w``   (x row-sharded — the
    sequence/batch-parallel TP input gather);
  * ``gather="rhs"``:   ``x @ allgather(w)``   (w column-sharded — the
    FSDP forward gather of a column-split weight);
  * ``gather="contract"``: ``x @ allgather(w)`` (w row/contraction-
    sharded — the FSDP gather of a row-split weight, accumulated
    chunk-by-chunk as partial products).

  ``lhs``/``rhs`` assemble disjoint output chunks — **bit-exact** vs the
  monolithic gather-then-matmul (each output element is the same dot
  product over the full contraction).  ``contract`` sums one partial
  product per hop, which *reassociates* the contraction: documented
  bound f32 ``rtol <= 1e-5`` vs the monolithic matmul at the tested
  shapes (tests pin it far tighter in practice).

- :func:`matmul_rs` — matmul producing partial products consumed by a
  pipelined reduce-scatter ring: ``psum_scatter(x @ w, axis)`` with each
  ring step's chunk-matmul overlapping the accumulator's transfer.  The
  ring's accumulation order differs from a monolithic ``psum`` —
  same documented f32 bound as ``contract``.

Both take ``mode``:

- ``"ring"``  — unidirectional ring: ``n-1`` hops of one chunk each;
- ``"bidir"`` — bidirectional ring: chunks travel both directions
  simultaneously, ``ceil((n-1)/2)`` hop *depth* at the same total wire
  bytes — the right choice on duplex links (TPU ICI) once latency, not
  bandwidth, binds.

The chains are UNROLLED Python loops over a static ring size — one
compiled program regardless of ring length (the slow-lane test pins
compile counts flat), and XLA can schedule hop ``s+1``'s
collective-permute concurrently with hop ``s``'s matmul because there
is no loop barrier between them.  Every hop is emitted under the
:data:`OVERLAP_SCOPE` named scope, so the emitted collective-permutes
carry a ``tpudist_overlap`` tag in their HLO ``op_name`` metadata —
that tag is how :mod:`tpudist.utils.hlo_audit` classifies the traffic
as *overlapped* (pipeline bytes) rather than *exposed* (monolithic
pre-matmul gathers), and how ``benchmarks/comm_audit.py`` proves from
optimized HLO that the monolithic all-gather is gone.

Selection is by the registered knob ``TPUDIST_OVERLAP``
(``off``/``ring``/``bidir``, default ``off`` — every existing call site
keeps its byte-identical default path); see :func:`overlap_mode`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: jax.named_scope wrapped around every pipelined hop; shows up in HLO
#: ``op_name`` metadata (forward, jvp AND transpose ops inherit it) and
#: is what the audit keys on to credit bytes as overlapped.
OVERLAP_SCOPE = "tpudist_overlap"

#: Valid TPUDIST_OVERLAP values.
OVERLAP_MODES = ("off", "ring", "bidir")


def overlap_mode(override: str | None = None) -> str:
    """Resolve the collective-matmul overlap mode.

    ``override`` (a call-site argument) wins when given; otherwise the
    ``TPUDIST_OVERLAP`` env knob decides.  Unset, empty, ``0``/``off``/
    ``false``/``no`` and any unrecognized value all mean ``"off"`` — a
    typo'd knob must never take a job down (envutil contract), and the
    safe behavior is the byte-identical default path.
    """
    import os

    v = override if override is not None else os.environ.get(
        "TPUDIST_OVERLAP", "")
    v = v.strip().lower()
    if v in ("ring", "bidir"):
        return v
    if override is not None and v not in ("", "0", "off", "false", "no"):
        # Explicit call-site arguments are code, not config: fail loud.
        raise ValueError(
            f"overlap must be one of {OVERLAP_MODES}, got {override!r}")
    return "off"


def _ring_perm(n: int, shift: int):
    """source_target pairs moving every shard ``shift`` ranks around the
    ring (shift=+1: rank r's shard lands on rank r+1)."""
    return [(i, (i + shift) % n) for i in range(n)]


def _axis_env(axis_name: str):
    """(ring size, my index) inside ``shard_map`` — ``psum(1)`` folds to
    a static Python int, so the unrolled chains have static length."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    return int(n), idx


def _check_mode(mode: str) -> str:
    if mode not in ("ring", "bidir"):
        raise ValueError(f"mode must be 'ring' or 'bidir', got {mode!r}")
    return mode


def ag_matmul(x: jax.Array, w: jax.Array, *, axis_name: str,
              mode: str = "ring", gather: str = "lhs") -> jax.Array:
    """All-gather fused into a matmul, pipelined over a ppermute chain.

    Shard-local (call inside ``shard_map``).  ``x: [m, k]``,
    ``w: [k, f]`` are the LOCAL operands; what is sharded (and therefore
    what rides the ring, one chunk per hop, each hop overlapping the
    previous chunk's matmul) depends on ``gather``.

    **Decode-shaped inputs**: for ``gather="rhs"``/``"contract"``, ``x``
    may carry leading batch dims (``[..., m, k]`` — the serving decode
    step's ``[slots, 1, d]`` activations); they are flattened into the
    row axis for the ring and restored on the output.  ``"lhs"`` stays
    2-D only (its row axis IS the sharded global axis — flattening
    batch dims into it would change which rows each rank owns).

    - ``"lhs"``      x is the local ROW shard of a ``[m*n, k]`` global;
                     returns ``allgather(x) @ w: [m*n, f]`` (bit-exact).
    - ``"rhs"``      w is the local COLUMN shard of a ``[k, f*n]``
                     global; returns ``x @ allgather(w): [m, f*n]``
                     (bit-exact).
    - ``"contract"`` w is the local ROW (contraction) shard of a
                     ``[k*n, f]`` global and x holds the FULL ``[m, k*n]``
                     contraction; returns ``x @ allgather(w): [m, f]``
                     accumulated one partial product per hop
                     (reassociated — documented f32 bound 1e-5).

    ``mode="bidir"`` halves the hop depth by sending chunks both ways
    (same total wire bytes).  n=1 degenerates to the plain matmul.
    """
    _check_mode(mode)
    if gather not in ("lhs", "rhs", "contract"):
        raise ValueError(
            f"gather must be 'lhs', 'rhs' or 'contract', got {gather!r}")
    lead, m = x.shape[:-2], x.shape[-2]
    if lead:
        if gather == "lhs":
            raise ValueError(
                "gather='lhs' requires 2-D x (the row axis is the sharded "
                f"global axis); got shape {x.shape} — flatten explicitly "
                "or use gather='rhs'/'contract'")
        x = x.reshape((-1, x.shape[-1]))
    n, idx = _axis_env(axis_name)
    if n == 1:
        out = x @ w
    elif gather == "lhs":
        return _ag_matmul_lhs(x, w, axis_name, n, idx, mode)
    elif gather == "rhs":
        out = _ag_matmul_rhs(x, w, axis_name, n, idx, mode)
    else:
        out = _ag_matmul_contract(x, w, axis_name, n, idx, mode)
    if lead:
        out = out.reshape(lead + (m, out.shape[-1]))
    return out


def _ag_matmul_lhs(x, w, axis_name, n, idx, mode):
    m = x.shape[0]
    out = jnp.zeros((m * n, w.shape[1]), x.dtype)

    def write(buf, src_idx, chunk):
        return lax.dynamic_update_slice(buf, chunk, (src_idx * m, 0))

    with jax.named_scope(OVERLAP_SCOPE):
        if mode == "ring":
            cur = x
            for s in range(n):
                # after s hops (+1 direction) I hold rank (idx - s)'s rows
                out = write(out, (idx - s) % n, cur @ w)
                if s + 1 < n:
                    cur = lax.ppermute(cur, axis_name, _ring_perm(n, +1))
            return out
        # bidir: fwd buffer travels +1 (delivers idx-s), bwd travels -1
        # (delivers idx+s); full steps floor((n-1)/2), plus one final
        # forward half-step when n is even.
        fwd = bwd = x
        out = write(out, idx % n, x @ w)
        for s in range(1, (n - 1) // 2 + 1):
            fwd = lax.ppermute(fwd, axis_name, _ring_perm(n, +1))
            bwd = lax.ppermute(bwd, axis_name, _ring_perm(n, -1))
            out = write(out, (idx - s) % n, fwd @ w)
            out = write(out, (idx + s) % n, bwd @ w)
        if n % 2 == 0:
            fwd = lax.ppermute(fwd, axis_name, _ring_perm(n, +1))
            out = write(out, (idx - n // 2) % n, fwd @ w)
        return out


def _ag_matmul_rhs(x, w, axis_name, n, idx, mode):
    f = w.shape[1]
    out = jnp.zeros((x.shape[0], f * n), x.dtype)

    def write(buf, src_idx, chunk):
        return lax.dynamic_update_slice(buf, chunk, (0, src_idx * f))

    with jax.named_scope(OVERLAP_SCOPE):
        if mode == "ring":
            cur = w
            for s in range(n):
                out = write(out, (idx - s) % n, x @ cur)
                if s + 1 < n:
                    cur = lax.ppermute(cur, axis_name, _ring_perm(n, +1))
            return out
        fwd = bwd = w
        out = write(out, idx % n, x @ w)
        for s in range(1, (n - 1) // 2 + 1):
            fwd = lax.ppermute(fwd, axis_name, _ring_perm(n, +1))
            bwd = lax.ppermute(bwd, axis_name, _ring_perm(n, -1))
            out = write(out, (idx - s) % n, x @ fwd)
            out = write(out, (idx + s) % n, x @ bwd)
        if n % 2 == 0:
            fwd = lax.ppermute(fwd, axis_name, _ring_perm(n, +1))
            out = write(out, (idx - n // 2) % n, x @ fwd)
        return out


def _ag_matmul_contract(x, w, axis_name, n, idx, mode):
    k = w.shape[0]  # local contraction-shard depth
    if x.shape[1] != k * n:
        raise ValueError(
            f"gather='contract' needs x.shape[1] == {k * n} "
            f"(n={n} shards of k={k}), got {x.shape[1]}")

    def xchunk(src_idx):
        return lax.dynamic_slice(x, (0, src_idx * k), (x.shape[0], k))

    with jax.named_scope(OVERLAP_SCOPE):
        if mode == "ring":
            cur = w
            acc = xchunk(idx % n) @ cur
            for s in range(1, n):
                cur = lax.ppermute(cur, axis_name, _ring_perm(n, +1))
                acc = acc + xchunk((idx - s) % n) @ cur
            return acc
        # bidir: column halves of w travel opposite directions; each
        # accumulator sees every contraction shard once.
        fh = w.shape[1] // 2
        if fh == 0:
            raise ValueError("bidir contract-gather needs w.shape[1] >= 2")
        fwd, bwd = w[:, :fh], w[:, fh:]
        acc_f = xchunk(idx % n) @ fwd
        acc_b = xchunk(idx % n) @ bwd
        for s in range(1, n):
            fwd = lax.ppermute(fwd, axis_name, _ring_perm(n, +1))
            bwd = lax.ppermute(bwd, axis_name, _ring_perm(n, -1))
            acc_f = acc_f + xchunk((idx - s) % n) @ fwd
            acc_b = acc_b + xchunk((idx + s) % n) @ bwd
        return jnp.concatenate([acc_f, acc_b], axis=1)


def matmul_rs(x: jax.Array, w: jax.Array, *, axis_name: str,
              mode: str = "ring", pad_rows: bool = False) -> jax.Array:
    """Matmul feeding a pipelined reduce-scatter ring:
    ``psum_scatter(x @ w, axis_name, scatter over rows)``.

    Shard-local (call inside ``shard_map``).  ``x: [m, k]`` (k is this
    device's shard of the contraction, e.g. a row-parallel weight's
    input), ``w: [k, f]``; every device holds a partial ``[m, f]``
    product implicitly — instead of materializing it and reduce-
    scattering afterwards, each ring step computes ONE ``[m/n, f]`` row
    chunk of the partial and adds it to the accumulator arriving from
    the neighbor, so the chunk matmul overlaps the accumulator's
    transfer.  Returns this device's fully-reduced ``[m/n, f]`` chunk.

    Accumulation order differs from a monolithic ``psum`` (ring order,
    rotated per device) — documented f32 bound ``rtol <= 1e-5`` at the
    tested shapes.  ``mode="bidir"`` splits the f columns into halves
    riding opposite directions (same hop count, both link directions
    busy).  ``m`` must divide by the ring size — unless
    ``pad_rows=True`` (the decode-shaped variant: serving batches are
    ``num_slots`` rows, rarely a ring multiple), which zero-pads the
    rows up to the next multiple; every device then returns its
    ``ceil(m/n)``-row chunk of the PADDED result, and the caller slices
    the assembled ``[pad_m, f]`` back to ``m`` rows after the
    ``shard_map`` reassembles it.
    """
    _check_mode(mode)
    n, idx = _axis_env(axis_name)
    if n == 1:
        return x @ w
    m = x.shape[0]
    if m % n:
        if not pad_rows:
            raise ValueError(
                f"matmul_rs needs rows {m} divisible by ring {n} "
                "(pass pad_rows=True for the padded decode-shaped variant)")
        pad = (n - m % n) % n
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
        m = x.shape[0]
    mloc = m // n

    def xrows(chunk_idx):
        return lax.dynamic_slice(x, (chunk_idx * mloc, 0), (mloc, x.shape[1]))

    with jax.named_scope(OVERLAP_SCOPE):
        if mode == "ring":
            # chunk c starts at rank c+1, travels +1, lands summed on c
            acc = xrows((idx - 1) % n) @ w
            for s in range(1, n):
                acc = lax.ppermute(acc, axis_name, _ring_perm(n, +1))
                acc = acc + xrows((idx - 1 - s) % n) @ w
            return acc
        fh = w.shape[1] // 2
        if fh == 0:
            raise ValueError("bidir matmul_rs needs w.shape[1] >= 2")
        wf, wb = w[:, :fh], w[:, fh:]
        # forward half: chunk c starts at c+1, travels +1; backward
        # half: chunk c starts at c-1, travels -1.
        acc_f = xrows((idx - 1) % n) @ wf
        acc_b = xrows((idx + 1) % n) @ wb
        for s in range(1, n):
            acc_f = lax.ppermute(acc_f, axis_name, _ring_perm(n, +1))
            acc_b = lax.ppermute(acc_b, axis_name, _ring_perm(n, -1))
            acc_f = acc_f + xrows((idx - 1 - s) % n) @ wf
            acc_b = acc_b + xrows((idx + 1 + s) % n) @ wb
        return jnp.concatenate([acc_f, acc_b], axis=1)


# Re-exported so call sites (tensor_parallel, fsdp) need one import and
# the registry test sees the knob consumed where it is parsed.
__all__ = [
    "OVERLAP_MODES",
    "OVERLAP_SCOPE",
    "ag_matmul",
    "matmul_rs",
    "overlap_mode",
]
