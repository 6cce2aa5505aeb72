"""Tensor parallelism: Megatron-style column/row-split linear layers.

Absent from the reference (SURVEY.md §2.4 marks TP "not required for
parity"); provided as the natural TPU extension on the mesh's ``model``
axis.  Two equivalent formulations are exposed:

1. **Sharding-spec formulation** (preferred): annotate the weight pytree
   with :func:`column_spec` / :func:`row_spec` partition specs and run the
   unmodified dense computation under ``jit`` — XLA inserts the all-reduce
   where the row-parallel contraction needs it.  This is the idiomatic
   pjit path: no manual collectives, compiler-scheduled comms.

2. **Explicit shard_map formulation** (:func:`tp_mlp_shard`,
   :func:`make_tp_mlp`): the textbook column→row pair with a single
   ``psum`` at the end, for when hand-placed collectives are wanted
   (e.g. fusing with other shard_map stages).

The pair composes as: ``y = (act(x @ W1) @ W2)`` with ``W1`` column-split
and ``W2`` row-split — one all-reduce per MLP block, activations stay
sharded on the feature axis in between.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.runtime.mesh import AXIS_MODEL


def column_spec(axis_name: str = AXIS_MODEL) -> P:
    """Weight ``[in, out]`` split on ``out`` — each device computes a slice
    of the activations; no communication in the forward."""
    return P(None, axis_name)


def row_spec(axis_name: str = AXIS_MODEL) -> P:
    """Weight ``[in, out]`` split on ``in`` — partial sums per device,
    all-reduced after the contraction."""
    return P(axis_name, None)


def mlp_param_sharding(mesh: Mesh, params: dict, *, axis_name: str = AXIS_MODEL):
    """Sharding pytree for a {'w1','b1','w2','b2'} MLP block: w1 column-split,
    w2 row-split, biases replicated/split to match."""
    specs = {
        "w1": column_spec(axis_name),
        "b1": P(axis_name),
        "w2": row_spec(axis_name),
        "b2": P(),
    }
    return {k: NamedSharding(mesh, specs[k]) for k in params}


def tp_mlp_shard(
    params: dict,
    x: jax.Array,
    *,
    axis_name: str = AXIS_MODEL,
    activation: Callable[[jax.Array], jax.Array] = jax.nn.gelu,
) -> jax.Array:
    """Shard-local column→row MLP body (call inside ``shard_map``).

    ``params['w1']: [d, f/n]`` (column shard), ``params['w2']: [f/n, d]``
    (row shard); ``x: [batch, d]`` replicated over the model axis.  One
    ``psum`` carries the row-parallel partial sums — the only collective.
    """
    h = activation(x @ params["w1"] + params["b1"])
    partial_out = h @ params["w2"]
    out = lax.psum(partial_out, axis_name)
    return out + params["b2"]


def tp_mlp_overlap_shard(
    params: dict,
    x: jax.Array,
    *,
    axis_name: str = AXIS_MODEL,
    activation: Callable[[jax.Array], jax.Array] = jax.nn.gelu,
    mode: str = "ring",
) -> jax.Array:
    """Shard-local collective-matmul MLP body (call inside ``shard_map``)
    — the overlapped twin of :func:`tp_mlp_shard`.

    Same weight shards (``w1`` column, ``w2`` row), but ``x: [batch/n, d]``
    arrives BATCH-SHARDED over the model axis and no monolithic
    collective ever runs: the input gather is pipelined into the first
    matmul (:func:`tpudist.parallel.overlap.ag_matmul`, chunk transfers
    overlapping chunk matmuls) and the row-parallel reduction is a
    pipelined reduce-scatter fused into the second matmul
    (:func:`tpudist.parallel.overlap.matmul_rs`) — so the output comes
    back batch-sharded too, and the big exposed ``psum`` of the default
    body becomes overlapped ppermute wire.  Global values match the
    default body within the reassociation bound documented in
    :mod:`tpudist.parallel.overlap` (the gather half is bit-exact; the
    reduce-scatter reassociates the n-way partial sum).
    """
    from tpudist.parallel.overlap import ag_matmul, matmul_rs

    h = ag_matmul(x, params["w1"], axis_name=axis_name, mode=mode,
                  gather="lhs")
    h = activation(h + params["b1"])
    out = matmul_rs(h, params["w2"], axis_name=axis_name, mode=mode)
    return out + params["b2"]


def make_tp_mlp(
    mesh: Mesh,
    *,
    axis_name: str = AXIS_MODEL,
    batch_axis: str | None = None,
    activation: Callable[[jax.Array], jax.Array] = jax.nn.gelu,
    overlap: str | None = None,
):
    """Jitted global-view TP MLP: weights arrive globally shaped, sharded per
    :func:`mlp_param_sharding`; ``x`` is replicated over the model axis.

    ``overlap`` selects the collective-matmul pipeline
    (``tpudist.parallel.overlap``): ``None`` defers to the
    ``TPUDIST_OVERLAP`` env knob (default off), ``"off"`` forces the
    psum body, ``"ring"``/``"bidir"`` run :func:`tp_mlp_overlap_shard` —
    batch sharded over the model axis internally, all wire traffic in
    ppermute chunks pipelined against the matmuls, no monolithic
    collective.  Global output VALUES match the default body (gather
    half bit-exact, reduce half within the documented reassociation
    bound); the output lands batch-sharded over ``axis_name`` instead of
    replicated.  The overlapped body needs ``batch_axis=None`` (the
    model axis carries the batch pipeline) and a batch divisible by the
    axis size.
    """
    from tpudist.parallel.overlap import overlap_mode

    mode = overlap_mode(overlap)
    param_specs = {
        "w1": column_spec(axis_name),
        "b1": P(axis_name),
        "w2": row_spec(axis_name),
        "b2": P(),
    }
    if mode != "off":
        if batch_axis is not None:
            raise ValueError(
                "overlapped TP MLP pipelines the batch over the model "
                "axis; batch_axis must be None")
        body = functools.partial(tp_mlp_overlap_shard, axis_name=axis_name,
                                 activation=activation, mode=mode)
        sharded = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(param_specs, P(axis_name, None)),
            out_specs=P(axis_name, None), check_vma=False)
        return jax.jit(sharded)
    body = functools.partial(tp_mlp_shard, axis_name=axis_name,
                             activation=activation)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P(batch_axis, None)),
        out_specs=P(batch_axis, None), check_vma=False)
    return jax.jit(sharded)


def init_mlp_params(rng: jax.Array, d_model: int, d_hidden: int) -> dict:
    """Dense (unsharded) init for the TP MLP block — shard with
    ``jax.device_put(params, mlp_param_sharding(mesh, params))``."""
    k1, k2 = jax.random.split(rng)
    return {
        "w1": jax.random.normal(k1, (d_model, d_hidden)) / jnp.sqrt(d_model),
        "b1": jnp.zeros((d_hidden,)),
        "w2": jax.random.normal(k2, (d_hidden, d_model)) / jnp.sqrt(d_hidden),
        "b2": jnp.zeros((d_model,)),
    }
