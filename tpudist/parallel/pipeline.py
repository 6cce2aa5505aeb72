"""Pipeline parallelism: microbatched GPipe-style schedule over the
``stage`` mesh axis.

The reference's only model parallelism is a manual 2-stage vertical split
with the activation hand-carried between two GPUs inside ``forward``
(``demo_one_model_multi_gpu.py:36-42``) — no microbatching, no schedule.
The TPU-native generalization here runs N stages on N devices with
``lax.ppermute`` moving activations stage-to-stage over ICI and a rotating
microbatch schedule, all inside one jitted ``shard_map``:

- each device holds ONE stage's params (sharded on the ``stage`` axis);
- the loop runs ``num_microbatches + num_stages - 1`` ticks (pipeline
  fill + drain); at every tick each device applies its stage to the
  activation it holds, then the activations rotate one hop;
- compiler-friendly: the tick loop is a ``lax.scan`` over stacked
  microbatches, static shapes throughout, no data-dependent control flow;
- differentiable end-to-end (ppermute transposes to the reverse ring), so
  the same code trains — unlike hand-written send/recv schedules.

For the reference's exact 2-stage shape (parity), see
``tpudist.models.split_mlp`` which expresses it as layer sharding instead;
this module is the scalable schedule.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpudist.runtime.mesh import AXIS_STAGE

# StageFn: (stage_params, activation [micro_batch, d]) -> activation
StageFn = Callable[[dict, jax.Array], jax.Array]


# Substring match: this JAX lowers pmean/psum to `psum_invariant`, and
# names have shifted across versions (psum/psum2/psum_invariant), so
# matching exact names would silently stop detecting anything on upgrade.
_COLLECTIVE_PRIM_SUBSTRINGS = (
    "psum", "pmean", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "pgather",
)


def _collectives_in_jaxpr(jaxpr, found: set) -> None:
    """Recursively collect collective primitive names in ``jaxpr``
    (descending into call/scan/cond sub-jaxprs via eqn params)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(s in name for s in _COLLECTIVE_PRIM_SUBSTRINGS):
            found.add(name)
        for v in eqn.params.values():
            for cand in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(cand, "jaxpr", None)
                if inner is not None:
                    _collectives_in_jaxpr(inner, found)
                elif hasattr(cand, "eqns"):
                    _collectives_in_jaxpr(cand, found)


def head_grad_branches(loss_fn):
    """``(head, head_zeros)`` cond branches for the vocab head: value and
    grad of ``loss_fn(out_params, activation, aux)`` vs shape-matched
    zeros.  Shared by both hand-scheduled pipelines so only the device
    holding the last global stage's fresh activation pays head FLOPs.

    HARD REQUIREMENT on ``loss_fn``: it must be collective-free (no
    psum/pmean/ppermute).  It runs inside a ``lax.cond`` whose predicate
    VARIES per device — a collective in the true branch would be executed
    by a subset of the mesh and deadlock at runtime (``check_vma=False``
    on the wrapping shard_maps means nothing catches it at trace time).
    Reduce over the data axis AFTER the pipeline call, as
    ``pipeline_1f1b_shard``'s ``data_axis`` handling does.

    The contract is ENFORCED at trace time: the first trace of ``head``
    scans ``loss_fn``'s jaxpr for collective primitives and raises
    ``ValueError`` naming them — without this, a user loss containing a
    ``pmean`` would hang the whole mesh at runtime with no diagnostic."""
    _checked = []  # once per head_grad_branches() instance

    def _assert_collective_free(args):
        def vg(a):
            return jax.value_and_grad(loss_fn, argnums=(0, 1))(*a)

        try:
            jaxpr = jax.make_jaxpr(vg)(args).jaxpr
        except Exception:
            return  # never let the guard break a traceable loss_fn
        found: set = set()
        _collectives_in_jaxpr(jaxpr, found)
        if found:
            raise ValueError(
                "head_grad_branches: loss_fn contains collective "
                f"primitive(s) {sorted(found)}. The vocab head runs inside "
                "a lax.cond whose predicate varies per device, so a "
                "collective here is executed by only a subset of the mesh "
                "and deadlocks at runtime. Make loss_fn collective-free "
                "and reduce over the data axis AFTER the pipeline call "
                "(see pipeline_1f1b_shard's data_axis handling)."
            )

    def head(args):
        if not _checked:
            _assert_collective_free(args)
            _checked.append(True)
        out_p, a_out, aux_m = args
        return jax.value_and_grad(loss_fn, argnums=(0, 1))(
            out_p, a_out, aux_m)

    def head_zeros(args):
        # trace-time only — eval_shape does no FLOPs
        shapes = jax.eval_shape(head, args)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    return head, head_zeros


def pipeline_shard(
    stage_params,
    x_microbatches: jax.Array,
    *,
    stage_fn: StageFn,
    axis_name: str = AXIS_STAGE,
    remat: bool = False,
) -> jax.Array:
    """Shard-local GPipe body (call inside ``shard_map``).

    ``stage_params``: this device's stage weights, arriving as a
    size-1-leading-axis block of the ``[n_stages, ...]`` stack (shard_map
    keeps the sharded dim).  ``x_microbatches``:
    ``[num_micro, micro_size, d]`` — the full input lives on stage 0; other
    stages ignore their copy (shard_map replicates it when the caller
    passes ``P(None, ...)``; pass it sharded over stages to save memory and
    only stage 0's block is read).

    Returns ``[num_micro, micro_size, d]`` — valid on the LAST stage,
    zeros elsewhere.  Callers gather with a stage-axis out_spec and slice
    the last stage's block (see :func:`make_pipeline`): XLA then moves one
    stage's data instead of all-reducing the whole ``n_stages`` stack,
    which is what a ``psum`` broadcast would do.
    """
    stage_params = jax.tree.map(lambda a: a[0], stage_params)
    if remat:
        # Recompute each tick's stage forward during the backward instead
        # of stashing its internals: per-device activation memory drops to
        # the tick *boundaries* the scan already carries — the memory
        # property a hand-scheduled 1F1B buys, obtained compiler-side.
        stage_fn = jax.checkpoint(stage_fn)
    n_stages = lax.axis_size(axis_name)
    my_stage = lax.axis_index(axis_name)
    num_micro = x_microbatches.shape[0]
    micro_shape = x_microbatches.shape[1:]
    total_ticks = num_micro + n_stages - 1

    # Shift perm: stage i -> i+1 (last stage's output falls off the end).
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        state, outputs = carry  # state: activation this device holds
        # Stage 0 feeds a fresh microbatch while any remain; other stages
        # use what arrived from the left neighbor.
        feed_idx = jnp.minimum(t, num_micro - 1)
        fresh = lax.dynamic_index_in_dim(
            x_microbatches, feed_idx, axis=0, keepdims=False
        )
        inp = jnp.where(my_stage == 0, fresh, state)
        out = stage_fn(stage_params, inp)

        # Last stage banks its result for microbatch (t - n_stages + 1).
        bank_idx = t - (n_stages - 1)
        is_valid = jnp.logical_and(my_stage == n_stages - 1, bank_idx >= 0)
        outputs = lax.cond(
            is_valid,
            lambda o: lax.dynamic_update_index_in_dim(
                o, out, jnp.maximum(bank_idx, 0), axis=0
            ),
            lambda o: o,
            outputs,
        )
        state = lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    init_state = jnp.zeros(micro_shape, x_microbatches.dtype)
    init_out = jnp.zeros((num_micro,) + micro_shape, x_microbatches.dtype)
    (_, outputs), _ = lax.scan(
        tick, (init_state, init_out), jnp.arange(total_ticks)
    )
    return outputs


def pipeline_1f1b_shard(
    stage_params,
    out_params,
    x_microbatches: jax.Array,
    aux_microbatches: jax.Array,
    *,
    stage_fn: StageFn,
    loss_fn,
    axis_name: str = AXIS_STAGE,
    data_axis=None,
):
    """Shard-local 1F1B schedule: forward AND backward in ONE scan, with
    per-stage activation recompute and an O(num_stages) residual buffer.

    GPipe (:func:`pipeline_shard` + autodiff) runs all ``M`` forwards, then
    lets autodiff replay all ``M`` backwards — every microbatch's residuals
    are live at the phase boundary, so peak memory grows with ``M``.  This
    schedule hand-interleaves them instead, which autodiff cannot be asked
    to do: backward of microbatch ``m`` starts as soon as the loss for
    ``m`` exists, so at most ``2·(S−1)+1`` stage-input activations are ever
    held per device — **constant in M**.  That unlocks the 1F1B trade:
    raise ``M`` to amortize the pipeline bubble without activation memory
    growing with it (the schedule of Narayanan et al.'s PipeDream-Flush /
    Megatron's non-interleaved 1F1B, formulated SPMD-uniformly).

    Timeline (0-indexed tick ``t``, stage ``s``, ``S`` stages, ``M``
    microbatches; each tick every device runs one fwd unit and one
    recompute+bwd unit, ``jnp.where``-gated like the GPipe loop):

    - forward of micro ``m`` on stage ``s`` at tick ``t = s + m``;
    - the LAST stage computes the microbatch loss and its cotangent the
      same tick its forward lands (``loss_fn`` grad) and immediately
      backwards it — 1F1B's defining move;
    - backward of micro ``m`` on stage ``s`` at tick ``2(S−1) − s + m``
      (cotangents hop right→left on the reverse ring each tick);
    - total ticks ``M + 2(S−1)``; stage-input residuals live in a ring
      buffer of depth ``2S − 1``, indexed ``m mod (2S−1)`` (lifetime of a
      residual is ``2(S−1−s)`` ticks < depth, so live slots never collide).

    ``stage_params``: this device's ``[1, ...]`` block of the stage stack.
    ``out_params``: replicated params consumed by ``loss_fn`` (e.g. the LM
    final-norm + head); their gradient is accumulated on the last stage
    and ``psum``-replicated.  ``loss_fn(out_params, act, aux) -> scalar``
    maps the last stage's activation + per-micro aux (e.g. target tokens)
    to the microbatch loss.  Backward recomputes each stage forward from
    its saved INPUT (stage-granular rematerialization), so no
    ``jax.checkpoint`` is needed — 1F1B implies it.

    Head cost (r3 advisor finding, resolved): the head — the full
    vocab-projection loss, forward and backward via ``value_and_grad`` —
    runs under ``lax.cond`` on ``my_stage == last AND fwd_valid``.  Under
    ``shard_map`` each device evaluates the predicate with its OWN axis
    index at runtime, so this is a true per-device branch (NOT the
    both-branches-execute degeneration ``cond`` suffers under ``vmap``):
    non-last stages — and the last stage's warmup/drain ticks — run the
    zero-cost false branch, so the step pays exactly ``M`` head
    evaluations total.  Divergent control flow is safe only because
    ``loss_fn`` MUST be collective-free — see
    :func:`head_grad_branches` for the contract.

    Returns ``(loss_sum, stage_grads, out_grads, dx_microbatches)`` —
    all UNNORMALIZED sums over this shard's microbatches (caller divides
    by ``M`` and mean-reduces over ``data_axis``): ``loss_sum`` and
    ``out_grads`` psum-replicated over the stage axis, ``stage_grads``
    carrying the ``[1, ...]`` leading axis for a ``P(stage)`` out_spec,
    ``dx_microbatches`` the cotangent w.r.t. ``x_microbatches`` (stage 0's
    contribution, psum-replicated).
    """
    p = jax.tree.map(lambda a: a[0], stage_params)
    n_stages = lax.axis_size(axis_name)
    my_stage = lax.axis_index(axis_name)
    last = n_stages - 1
    num_micro = x_microbatches.shape[0]
    micro_shape = x_microbatches.shape[1:]
    depth = 2 * n_stages - 1
    total_ticks = num_micro + 2 * (n_stages - 1)

    perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]
    perm_bwd = [(i + 1, i) for i in range(n_stages - 1)]

    head, head_zeros = head_grad_branches(loss_fn)

    def fwd_bwd(carry, t):
        (act_state, cot_state, ring, dx_bank,
         loss_acc, sg_acc, og_acc) = carry

        # ---- forward unit: micro m_f = t - s ----
        m_f = t - my_stage
        fwd_valid = jnp.logical_and(m_f >= 0, m_f < num_micro)
        m_f_c = jnp.clip(m_f, 0, num_micro - 1)
        fresh = lax.dynamic_index_in_dim(x_microbatches, m_f_c, 0,
                                         keepdims=False)
        a_in = jnp.where(my_stage == 0, fresh, act_state)
        a_out = stage_fn(p, a_in)

        # save the stage INPUT (backward recomputes from it); a dead slot
        # keeps its old value so live residuals are never clobbered
        slot = jnp.mod(m_f_c, depth)
        old = lax.dynamic_index_in_dim(ring, slot, 0, keepdims=False)
        ring = lax.dynamic_update_index_in_dim(
            ring, jnp.where(fwd_valid, a_in, old), slot, 0)

        # last stage: loss + its cotangent for THIS micro, this tick —
        # a true runtime branch; non-last stages skip the head entirely
        # (see the docstring's head-cost note).  Predicate includes
        # fwd_valid: the last stage's warmup/drain ticks carry garbage
        # activations whose head results are fully masked anyway — safe
        # to skip because on the last stage the backward of micro m runs
        # the SAME tick as its forward (2(S-1)-(S-1)+m = (S-1)+m), so
        # d_act is never consumed on a tick the head skipped.
        aux_m = lax.dynamic_index_in_dim(aux_microbatches, m_f_c, 0,
                                         keepdims=False)
        on_last = my_stage == last
        take_loss = jnp.logical_and(on_last, fwd_valid)
        (l_m, lgrads) = lax.cond(
            take_loss, head, head_zeros, (out_params, a_out, aux_m))
        d_og, d_act = lgrads
        loss_acc = loss_acc + jnp.where(take_loss, l_m, 0.0)
        og_acc = jax.tree.map(
            lambda acc, g: acc + jnp.where(take_loss, g, 0.0), og_acc, d_og)

        # ---- backward unit: micro m_b = t - 2(S-1) + s ----
        m_b = t - 2 * (n_stages - 1) + my_stage
        bwd_valid = jnp.logical_and(m_b >= 0, m_b < num_micro)
        m_b_c = jnp.clip(m_b, 0, num_micro - 1)
        a_saved = lax.dynamic_index_in_dim(ring, jnp.mod(m_b_c, depth), 0,
                                           keepdims=False)
        cot_in = jnp.where(on_last, d_act, cot_state)
        _, stage_vjp = jax.vjp(stage_fn, p, a_saved)
        dp, da = stage_vjp(cot_in)
        sg_acc = jax.tree.map(
            lambda acc, g: acc + jnp.where(bwd_valid, g, 0.0), sg_acc, dp)
        take_dx = jnp.logical_and(my_stage == 0, bwd_valid)
        old_dx = lax.dynamic_index_in_dim(dx_bank, m_b_c, 0, keepdims=False)
        dx_bank = lax.dynamic_update_index_in_dim(
            dx_bank, jnp.where(take_dx, da, old_dx), m_b_c, 0)

        act_state = lax.ppermute(a_out, axis_name, perm_fwd)
        cot_state = lax.ppermute(da, axis_name, perm_bwd)
        return (act_state, cot_state, ring, dx_bank,
                loss_acc, sg_acc, og_acc), None

    dtype = x_microbatches.dtype
    zeros_g = functools.partial(jax.tree.map, jnp.zeros_like)
    init = (
        jnp.zeros(micro_shape, dtype),                  # act_state
        jnp.zeros(micro_shape, dtype),                  # cot_state
        jnp.zeros((depth,) + micro_shape, dtype),       # residual ring
        jnp.zeros((num_micro,) + micro_shape, dtype),   # dx bank
        jnp.zeros((), jnp.float32),                     # loss sum
        zeros_g(p),                                     # stage grads
        zeros_g(out_params),                            # out grads
    )
    (_, _, _, dx_bank, loss_acc, sg_acc, og_acc), _ = lax.scan(
        fwd_bwd, init, jnp.arange(total_ticks))

    loss_sum = lax.psum(loss_acc, axis_name)
    og_sum = jax.tree.map(lambda g: lax.psum(g, axis_name), og_acc)
    dx_sum = lax.psum(dx_bank, axis_name)
    if data_axis is not None:
        # Batch is also sharded: grads/loss average over the data axis
        # (equal shard sizes — the reference's equal-batch contract).
        loss_sum = lax.pmean(loss_sum, data_axis)
        og_sum = jax.tree.map(lambda g: lax.pmean(g, data_axis), og_sum)
        sg_acc = jax.tree.map(lambda g: lax.pmean(g, data_axis), sg_acc)
    stage_grads = jax.tree.map(lambda g: g[None], sg_acc)
    return loss_sum, stage_grads, og_sum, dx_sum


def make_pipeline(
    mesh: Mesh,
    stage_fn: StageFn,
    *,
    axis_name: str = AXIS_STAGE,
    num_microbatches: int = 4,
    remat: bool = False,
):
    """Jitted global-view pipeline.

    ``stage_params`` arrive with a leading stage axis (``[n_stages, ...]``,
    sharded over ``axis_name``); input ``x: [batch, d]`` is split into
    ``num_microbatches`` equal microbatches (batch must divide evenly —
    the reference's equal-batch contract, ``demo.py:113``).
    """

    def global_fn(stage_params, x):
        num_micro = num_microbatches
        micro = x.shape[0] // num_micro
        xm = x.reshape((num_micro, micro) + x.shape[1:])

        def body(sp, xmb):
            return pipeline_shard(
                sp, xmb, stage_fn=stage_fn, axis_name=axis_name, remat=remat
            )[None]

        # Leading stage axis on the output; slicing the last block makes
        # XLA move one stage's data (a broadcast from the final stage)
        # instead of all-reducing zeros from every other stage.
        out = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=P(axis_name), check_vma=False)(stage_params, xm)
        out = out[-1]
        return out.reshape((num_micro * micro,) + out.shape[2:])

    return jax.jit(global_fn)
