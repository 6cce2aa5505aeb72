"""Fully-sharded data parallelism (ZeRO-3-style) as a sharding layout.

The reference's DDP keeps a full replica of parameters, gradients, and
optimizer state on every rank (torch DDP, ``demo.py:70-72``); at scale the
optimizer state dominates memory.  The TPU-native formulation needs no
wrapper class and no hand-written gather/scatter: FSDP is *just a layout*
— every large parameter (and its Adam moments, which mirror the param
tree) is sharded over the ``data`` mesh axis, and the XLA SPMD partitioner
inserts the all-gather before each use and the reduce-scatter after each
backward that ZeRO implements by hand.  Per-chip state memory drops by the
data-axis size; step math is bit-identical to replicated DP (tests assert
it).

Usage::

    sharding = fsdp_sharding(mesh, state)         # state: ModelState pytree
    state = jax.device_put(state, sharding)
    step = make_lm_train_step(apply, tx, mesh, state_sharding=sharding)

Composes with tensor parallelism by passing ``skip`` specs for leaves that
:func:`tpudist.models.transformer.transformer_tp_sharding` already shards
— see :func:`merge_shardings`.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.runtime.mesh import AXIS_DATA


def _leaf_spec(leaf, n: int, axis_name: str, min_size: int) -> P:
    """Shard the largest dimension divisible by ``n``; replicate leaves that
    are small (gather overhead beats the memory win) or indivisible."""
    shape = getattr(leaf, "shape", ())
    if getattr(leaf, "ndim", 0) == 0 or np.prod(shape) < min_size:
        return P()
    candidates = [d for d in range(len(shape)) if shape[d] % n == 0]
    if not candidates:
        return P()
    dim = max(candidates, key=lambda d: shape[d])
    spec = [None] * len(shape)
    spec[dim] = axis_name
    return P(*spec)


def fsdp_sharding(
    mesh: Mesh,
    tree,
    *,
    axis_name: str = AXIS_DATA,
    min_size: int = 1024,
):
    """ZeRO-3-style layout for a state pytree (params or a whole
    ``ModelState`` — Adam moments mirror the param structure, so mapping
    leaves covers them identically).

    Every float leaf with ≥ ``min_size`` elements is sharded along its
    largest ``axis_name``-divisible dimension; the rest replicate.  Returns
    a pytree of ``NamedSharding`` matching ``tree``.
    """
    n = mesh.shape[axis_name]

    def shard_for(leaf):
        return NamedSharding(mesh, _leaf_spec(leaf, n, axis_name, min_size))

    return jax.tree.map(shard_for, tree)


def zero1_sharding(
    mesh: Mesh,
    state,
    *,
    axis_name: str = AXIS_DATA,
    min_size: int = 1024,
):
    """ZeRO-1-style weight-update sharding: parameters stay REPLICATED
    (forward/backward identical to plain DP — no per-layer all-gathers),
    only the optimizer state shards over the data axis.

    The XLA-native form of "Automatic Cross-Replica Sharding of Weight
    Update in Data-Parallel Training" (arXiv:2004.13336, the technique
    ZeRO-1 popularized): with Adam moments laid out sharded and gradients
    replicated after the all-reduce, the SPMD partitioner computes each
    moment/update on its owning shard only and all-gathers the updated
    parameters once per step — optimizer memory drops by the data-axis
    size (Adam: 2/3 of a replicated f32 state) for one extra
    param-sized all-gather, with zero change to the step function.

    Middle rung of the DP memory ladder: plain DP (everything
    replicated) → ``zero1_sharding`` (opt sharded) → :func:`fsdp_sharding`
    (params + moments sharded, ZeRO-3).  Not composable with
    ``grad_reduce_dtype`` (that path requires a pure-DP replicated
    state, and validates so).

    ``state``: a ``ModelState``; returns a matching sharding pytree.
    """
    from tpudist.train.step import ModelState

    repl = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), state.params)
    opt = fsdp_sharding(mesh, state.opt_state, axis_name=axis_name,
                        min_size=min_size)
    return ModelState(params=repl, opt_state=opt)


def overlap_fsdp_mlp(
    mesh: Mesh,
    *,
    axis_name: str = AXIS_DATA,
    overlap: str | None = None,
    activation=None,
):
    """Overlapped FSDP layer compute for the transformer MLP — the
    explicit twin of the layout-only path.

    Under :func:`fsdp_sharding` the FFN kernels land ``wi: [d, ff/n]``
    (column shard — ``ff`` is the largest dim) and ``wo: [ff/n, d]``
    (row shard), and the XLA partitioner inserts a monolithic all-gather
    of each before the matmul that consumes it — exposed wire time.
    This builder returns an ``mlp_fn(params, x) -> y`` for
    :class:`tpudist.models.transformer.Block`'s injection seam (the
    ``attention_fn`` pattern: the closure carries its own ``shard_map``)
    that consumes the SHARDED kernels directly and pipelines the gather
    into the matmuls chunk-by-chunk over ``lax.ppermute``
    (:mod:`tpudist.parallel.overlap`): the ``wi`` column gather
    assembles output columns (bit-exact), the ``wo`` contraction gather
    accumulates partial products (documented reassociation bound).  No
    all-gather of either kernel appears in the lowered HLO — the audit
    (``benchmarks/comm_audit.py`` ``fsdp_overlap_*`` regimes) asserts
    it structurally.

    ``params``: ``{"wi": [d, ff], "wo": [ff, d]}`` global kernels;
    ``x: [batch, seq, d]`` with batch sharded over ``axis_name``.
    Returns ``None`` when the resolved mode is off, so call sites can
    pass the result straight to ``create_transformer(mlp_fn=...)`` and
    keep the byte-identical dense path by default.

    ``activation`` defaults to the Block's ``gelu``.
    """
    from tpudist.parallel.overlap import ag_matmul, overlap_mode

    mode = overlap_mode(overlap)
    if mode == "off":
        return None
    from jax.sharding import PartitionSpec as P

    act = activation if activation is not None else jax.nn.gelu

    def body(params, x):
        b_loc, s, d = x.shape
        t = x.reshape(b_loc * s, d)
        h = ag_matmul(t, params["wi"], axis_name=axis_name, mode=mode,
                      gather="rhs")
        h = act(h)
        y = ag_matmul(h, params["wo"], axis_name=axis_name, mode=mode,
                      gather="contract")
        return y.reshape(b_loc, s, d).astype(x.dtype)

    param_specs = {"wi": P(None, axis_name), "wo": P(axis_name, None)}
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P(axis_name, None, None)),
        out_specs=P(axis_name, None, None), check_vma=False)

    def mlp_fn(params, x):
        return sharded(params, x)

    # Introspection tags (mirrors attention_fn's .window/.supports_gqa
    # convention): which pipeline this closure runs, for guards/tests.
    mlp_fn.overlap = mode
    mlp_fn.axis_name = axis_name
    return mlp_fn


def merge_shardings(primary, fallback):
    """Leaf-wise composition: use ``primary``'s spec unless it is fully
    replicated, else ``fallback``'s — e.g. TP specs where they exist, FSDP
    for everything TP leaves replicated."""

    def pick(p, f):
        # "replicated" includes rank-explicit spellings: P(None, None) etc.
        replicated = all(axis is None for axis in tuple(p.spec))
        return f if replicated else p

    return jax.tree.map(pick, primary, fallback)


def state_bytes_per_device(tree, sharding) -> int:
    """Analytic per-device bytes of ``tree`` under ``sharding`` — the
    memory-accounting companion (replicated leaves count full size, sharded
    leaves their shard)."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(
            sharding, is_leaf=lambda x: isinstance(x, NamedSharding))):
        size = int(np.prod(getattr(leaf, "shape", ()) or (1,)))
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        div = 1
        for axis in jax.tree.leaves(tuple(sh.spec)):
            if axis is not None:
                div *= sh.mesh.shape[axis]
        total += size * itemsize // div
    return total
