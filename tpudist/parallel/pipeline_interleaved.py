"""Interleaved (virtual-stage) 1F1B pipeline schedule.

:mod:`tpudist.parallel.pipeline` gives two schedules: GPipe (autodiff
backward, O(M) residuals) and non-interleaved 1F1B (O(S) residuals).
Both pay the same pipeline-fill bubble: ~2·(D−1) full-stage units per
step on D devices.  This module adds the Megatron-style interleaved
schedule (Narayanan et al. 2021): each device holds ``V`` depth-strided
model chunks (device ``d`` owns global stages ``{c·D + d}``), so a
microbatch makes ``V`` laps around the device ring through chunks 1/V
the size — the fill/drain bubble shrinks ~÷V at the cost of ~V× more
(but V× smaller) activation hops.

TPU-first formulation — everything is ONE jitted ``lax.scan`` inside one
``shard_map``, no data-dependent control flow:

- the schedule is computed AT TRACE TIME by a Python discrete-event
  simulator (:func:`interleaved_schedule`) implementing warmup-capped
  1F1B: per tick each device runs (at most) one forward unit and one
  backward unit (the pair-tick convention of ``pipeline_1f1b_shard``),
  chosen by static readiness, with per-chunk in-flight bounded by the
  residual lifetime and per-device in-flight by Megatron's interleaved
  warmup depth ``(V−1)·D + 2(D−d)`` — residual memory stays O(V·D),
  constant in the microbatch count, like non-interleaved 1F1B (at V=1
  the simulator reproduces that schedule's canonical timeline exactly);
- the resulting per-tick (unit, operand) choices are baked into
  ``[T, D]`` integer tables the scan body indexes with
  ``lax.axis_index`` — SPMD-uniform, fully static to XLA;
- activation residuals and in-flight cotangents live in fixed-depth
  banks whose slots are assigned by OFFLINE interval allocation over the
  static schedule (lifetime [first-write, last-read]; reads precede
  writes within a tick, so a slot frees the tick its last read lands);
- activations hop right and cotangents hop left every tick with a full
  ``lax.ppermute`` ring (wrap included: leaving device D−1 re-enters
  device 0 one chunk deeper); receive-side masking keeps it uniform;
- backward recomputes each chunk's forward from the saved chunk INPUT
  (stage-granular remat), exactly like the non-interleaved schedule.

The head-cost note from ``pipeline_1f1b_shard`` applies unchanged:
``loss_fn`` (the vocab head) runs under a true per-device ``lax.cond``
branch, so only the device holding the last global stage's fresh
activation pays head FLOPs at any tick.

Reference lineage: the reference repo has no pipeline schedules at all
(its only model parallelism is the manual 2-stage split,
``demo_one_model_multi_gpu.py:17-42``); this is capability surplus
motivated by its multi-node scaling story.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpudist.parallel.pipeline import head_grad_branches
from tpudist.runtime.mesh import AXIS_STAGE

_INF = 10**9


def _fwd_order(D: int, V: int, M: int):
    """Per-device forward unit order: groups of D microbatches, each
    group walked through the V local chunks (Megatron's grouping)."""
    return [(m, c)
            for g0 in range(0, M, D)
            for c in range(V)
            for m in range(g0, g0 + D)]


def _bwd_order(D: int, V: int, M: int):
    return [(m, c)
            for g0 in range(0, M, D)
            for c in range(V - 1, -1, -1)
            for m in range(g0, g0 + D)]


def _alloc_slots(intervals):
    """Offline interval register allocation.

    ``intervals``: ``[(write_tick, last_read_tick, key), ...]``.  Returns
    ``(assignment dict key->slot, depth)``.  A slot is reusable from its
    last read tick onward because the scan body performs ALL bank reads
    before any bank write within a tick."""
    assign = {}
    free: list = []  # heap of (available_from_tick, slot)
    next_slot = 0
    for w, r, key in sorted(intervals, key=lambda iv: (iv[0], iv[1])):
        if free and free[0][0] <= w:
            _, slot = heapq.heappop(free)
        else:
            slot, next_slot = next_slot, next_slot + 1
        assign[key] = slot
        heapq.heappush(free, (r, slot))
    return assign, max(next_slot, 1)


@dataclass(frozen=True)
class InterleavedSchedule:
    """Static schedule tables, all ``[total_ticks, n_dev]`` int32."""

    n_dev: int
    n_chunks: int
    n_micro: int
    total_ticks: int
    act_depth: int
    cot_depth: int
    tables: dict = field(repr=False)

    @property
    def bubble_ticks(self) -> int:
        """Ticks beyond the per-device useful work (M·V units)."""
        return self.total_ticks - self.n_micro * self.n_chunks


def interleaved_schedule(n_dev: int, n_chunks: int,
                         n_micro: int) -> InterleavedSchedule:
    """Simulate warmup-capped interleaved 1F1B and bake the tables.

    Raises if the microbatch count does not divide into device-sized
    groups (``M % D != 0``, the Megatron grouping constraint) or if the
    simulation fails to converge (a schedule bug, not a user error).
    """
    D, V, M = n_dev, n_chunks, n_micro
    if M % D:
        raise ValueError(f"num_microbatches {M} must be a multiple of the "
                         f"pipeline width {D} for the interleaved schedule")
    S = D * V
    fq = _fwd_order(D, V, M)
    bq = _bwd_order(D, V, M)
    n_units = M * V
    # Forward admission is bounded two ways (each tick runs one fwd AND
    # one bwd unit, the pair-tick convention of pipeline_1f1b_shard):
    # per chunk, in-flight <= residual lifetime 2(S-1-g)+1 — the same
    # bound the non-interleaved ring depth encodes, so V=1 reproduces its
    # no-stall timeline exactly; per device, total in-flight <=
    # (V-1)·D + 2(D-d) — the Megatron interleaved warmup depth, keeping
    # residual memory O(V·D), constant in M.  A too-small device cap
    # deadlocks the sim; retry with slack and fail loudly if it persists.
    for slack in range(0, 4):
        dev_cap = [(V - 1) * D + 2 * (D - d) + slack for d in range(D)]
        sim = _simulate(D, V, S, M, fq, bq, n_units, dev_cap)
        if sim is not None:
            break
    else:
        raise RuntimeError("interleaved schedule simulation did not "
                           f"converge for D={D} V={V} M={M}")
    fwd_done, bwd_done, fwd_events, bwd_events, T = sim

    # ---- offline slot allocation ----
    act_iv = {d: [] for d in range(D)}   # consumer-keyed activation slots
    cot_iv = {d: [] for d in range(D)}   # consumer-keyed cotangent slots
    for (t, d, m, c) in fwd_events:
        g = c * D + d
        if g < S - 1:
            rd, cc = (g + 1) % D, (g + 1) // D
            act_iv[rd].append((t, bwd_done[(rd, m, cc)], (m, cc)))
        else:
            # loss cotangent, produced on-device at the fwd tick
            cot_iv[d].append((t, bwd_done[(d, m, c)], (m, c)))
    for (t, d, m, c) in bwd_events:
        g = c * D + d
        if g > 0:
            pd, pc = (g - 1) % D, (g - 1) // D
            cot_iv[pd].append((t, bwd_done[(pd, m, pc)], (m, pc)))
    act_assign, cot_assign = {}, {}
    act_depth = cot_depth = 1
    for d in range(D):
        a, da = _alloc_slots(act_iv[d])
        k, dk = _alloc_slots(cot_iv[d])
        act_assign[d], cot_assign[d] = a, k
        act_depth, cot_depth = max(act_depth, da), max(cot_depth, dk)

    # ---- tables ----
    def tab():
        return np.zeros((T, D), np.int32)

    t_ = {name: tab() for name in (
        "fwd_valid", "fwd_m", "fwd_c", "fwd_from_x", "fwd_slot",
        "take_loss", "loss_cot_valid", "loss_cot_slot",
        "act_recv_valid", "act_recv_slot",
        "bwd_valid", "bwd_m", "bwd_c", "bwd_from_x", "bwd_act_slot",
        "bwd_cot_slot", "take_dx",
        "cot_recv_valid", "cot_recv_slot",
    )}
    for (t, d, m, c) in fwd_events:
        g = c * D + d
        t_["fwd_valid"][t, d] = 1
        t_["fwd_m"][t, d] = m
        t_["fwd_c"][t, d] = c
        if g == 0:
            t_["fwd_from_x"][t, d] = 1
        else:
            t_["fwd_slot"][t, d] = act_assign[d][(m, c)]
        if g == S - 1:
            t_["take_loss"][t, d] = 1
            t_["loss_cot_valid"][t, d] = 1
            t_["loss_cot_slot"][t, d] = cot_assign[d][(m, c)]
        else:
            rd, cc = (g + 1) % D, (g + 1) // D
            t_["act_recv_valid"][t, rd] = 1
            t_["act_recv_slot"][t, rd] = act_assign[rd][(m, cc)]
    for (t, d, m, c) in bwd_events:
        g = c * D + d
        t_["bwd_valid"][t, d] = 1
        t_["bwd_m"][t, d] = m
        t_["bwd_c"][t, d] = c
        t_["bwd_cot_slot"][t, d] = cot_assign[d][(m, c)]
        if g == 0:
            t_["bwd_from_x"][t, d] = 1
            t_["take_dx"][t, d] = 1
        else:
            t_["bwd_act_slot"][t, d] = act_assign[d][(m, c)]
        if g > 0:
            pd, pc = (g - 1) % D, (g - 1) // D
            t_["cot_recv_valid"][t, pd] = 1
            t_["cot_recv_slot"][t, pd] = cot_assign[pd][(m, pc)]
    return InterleavedSchedule(
        n_dev=D, n_chunks=V, n_micro=M, total_ticks=T,
        act_depth=act_depth, cot_depth=cot_depth, tables=t_)


def _simulate(D, V, S, M, fq, bq, n_units, dev_cap):
    """One capped-greedy pass; returns None on deadlock."""
    fwd_done, bwd_done = {}, {}
    fi, bi = [0] * D, [0] * D
    chunk_fly = {(d, c): 0 for d in range(D) for c in range(V)}
    fwd_events, bwd_events = [], []
    bound = 8 * S + 4 * n_units + 64
    t = 0
    while any(fi[d] < n_units or bi[d] < n_units for d in range(D)):
        if t > bound:
            return None
        progressed = False
        plan_f = []
        for d in range(D):
            if fi[d] >= n_units or (fi[d] - bi[d]) >= dev_cap[d]:
                continue
            m, c = fq[fi[d]]
            g = c * D + d
            # +2, not +1: the fwd plan runs before the same tick's bwd
            # plan, so the counter still includes a unit whose backward
            # retires THIS tick (the F half of an F+B pair-tick must not
            # be blocked by it).  True residual memory is measured by the
            # offline allocator from actual lifetimes, not this cap.
            if chunk_fly[(d, c)] >= 2 * (S - 1 - g) + 2:
                continue
            if g == 0:
                ready = True
            else:
                pd, pc = (g - 1) % D, (g - 1) // D
                ready = fwd_done.get((pd, m, pc), _INF) <= t - 1
            if ready:
                plan_f.append((d, m, c))
        for d, m, c in plan_f:
            fwd_done[(d, m, c)] = t
            fi[d] += 1
            chunk_fly[(d, c)] += 1
            fwd_events.append((t, d, m, c))
            progressed = True
        plan_b = []
        for d in range(D):
            if bi[d] >= n_units:
                continue
            m, c = bq[bi[d]]
            g = c * D + d
            if g == S - 1:
                ready = fwd_done.get((d, m, c), _INF) <= t - 1
            else:
                sd, sc = (g + 1) % D, (g + 1) // D
                ready = (bwd_done.get((sd, m, sc), _INF) <= t - 1
                         and fwd_done.get((d, m, c), _INF) <= t)
            if ready:
                plan_b.append((d, m, c))
        for d, m, c in plan_b:
            bwd_done[(d, m, c)] = t
            bi[d] += 1
            chunk_fly[(d, c)] -= 1
            bwd_events.append((t, d, m, c))
            progressed = True
        if not progressed:
            # The done-maps only grow when a unit commits, so a tick with
            # zero commits can never unblock a later tick: deadlock.
            return None
        t += 1
    return fwd_done, bwd_done, fwd_events, bwd_events, t


def interleave_block_params(stacked, n_dev: int):
    """Permute a ``[S_total, ...]`` stage stack into the device-major
    interleaved layout: position ``j = d·V + c`` holds global stage
    ``c·D + d``, so sharding the leading axis ``P(stage)`` over D devices
    hands device ``d`` exactly its depth-strided chunks in local order."""
    s_total = jax.tree.leaves(stacked)[0].shape[0]
    if s_total % n_dev:
        raise ValueError(f"stage stack of {s_total} does not split over "
                         f"{n_dev} devices")
    v = s_total // n_dev
    perm = np.asarray([(j % v) * n_dev + j // v for j in range(s_total)])
    return jax.tree.map(lambda a: jnp.take(a, perm, axis=0), stacked)


def deinterleave_block_params(stacked, n_dev: int):
    """Inverse of :func:`interleave_block_params` (checkpoint interop)."""
    s_total = jax.tree.leaves(stacked)[0].shape[0]
    v = s_total // n_dev
    perm = np.asarray([(j % v) * n_dev + j // v for j in range(s_total)])
    inv = np.argsort(perm)
    return jax.tree.map(lambda a: jnp.take(a, inv, axis=0), stacked)


def pipeline_interleaved_shard(
    stage_params,
    out_params,
    x_microbatches: jax.Array,
    aux_microbatches: jax.Array,
    *,
    stage_fn,
    loss_fn,
    schedule: InterleavedSchedule,
    axis_name: str = AXIS_STAGE,
    data_axis=None,
):
    """Shard-local interleaved 1F1B body (call inside ``shard_map``).

    Same contract as :func:`tpudist.parallel.pipeline.pipeline_1f1b_shard`
    except ``stage_params`` arrives as this device's ``[V, ...]`` chunk
    stack (the :func:`interleave_block_params` layout sharded over
    ``axis_name``) and the schedule object carries the static tables.
    Returns ``(loss_sum, chunk_grads [V, ...], out_grads, dx_microbatches)``
    — unnormalized sums over this shard's microbatches, loss/out/dx
    psum-replicated over the stage axis.
    """
    D = schedule.n_dev
    V = schedule.n_chunks
    if lax.axis_size(axis_name) != D:
        raise ValueError(f"schedule built for {D} devices, axis "
                         f"{axis_name!r} has {lax.axis_size(axis_name)}")
    my = lax.axis_index(axis_name)
    num_micro = schedule.n_micro
    if x_microbatches.shape[0] != num_micro:
        raise ValueError(f"schedule built for {num_micro} microbatches, "
                         f"got {x_microbatches.shape[0]}")
    local_chunks = jax.tree.leaves(stage_params)[0].shape[0]
    if local_chunks != V:
        # Must be loud: dynamic_index_in_dim CLAMPS an out-of-range chunk
        # index, so a contiguous-layout state would otherwise train
        # silently on chunk 0's params with garbage gradients.
        raise ValueError(
            f"stage_params carry {local_chunks} chunks per device but the "
            f"schedule was built for n_chunks={V} — stack with "
            f"stack_block_params_interleaved(params, n_dev, n_chunks)")
    micro_shape = x_microbatches.shape[1:]
    dtype = x_microbatches.dtype

    ring_r = [(i, (i + 1) % D) for i in range(D)]
    ring_l = [((i + 1) % D, i) for i in range(D)]

    tabs = {k: jnp.asarray(v) for k, v in schedule.tables.items()}

    def chunk_p(c):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
            stage_params)

    # The vocab head runs under a true per-device runtime branch — only
    # the tick/device holding the last global stage's fresh activation
    # pays head FLOPs.  See head_grad_branches for the rationale and the
    # collective-free requirement on loss_fn.
    head, head_zeros = head_grad_branches(loss_fn)

    def tick(carry, rows):
        (act_bank, cot_bank, dx_bank, loss_acc, cg_acc, og_acc) = carry
        r = {k: jnp.take(v, my) for k, v in rows.items()}

        # ---- forward unit (reads banks, no writes yet) ----
        fm, fc = r["fwd_m"], r["fwd_c"]
        x_m = lax.dynamic_index_in_dim(x_microbatches, fm, 0, keepdims=False)
        a_bank = lax.dynamic_index_in_dim(act_bank, r["fwd_slot"], 0,
                                          keepdims=False)
        a_in = jnp.where(r["fwd_from_x"].astype(bool), x_m, a_bank)
        a_out = stage_fn(chunk_p(fc), a_in)

        aux_m = lax.dynamic_index_in_dim(aux_microbatches, fm, 0,
                                         keepdims=False)
        need_head = (r["take_loss"] | r["loss_cot_valid"]).astype(bool)
        (l_m, (d_og, d_act)) = lax.cond(
            need_head, head, head_zeros, (out_params, a_out, aux_m))
        take_loss = (r["take_loss"] & r["fwd_valid"]).astype(bool)
        loss_acc = loss_acc + jnp.where(take_loss, l_m, 0.0)
        og_acc = jax.tree.map(
            lambda acc, g: acc + jnp.where(take_loss, g, 0.0), og_acc, d_og)

        # ---- backward unit (reads banks BEFORE any write) ----
        bm, bc = r["bwd_m"], r["bwd_c"]
        bwd_valid = r["bwd_valid"].astype(bool)
        res_x = lax.dynamic_index_in_dim(x_microbatches, bm, 0,
                                         keepdims=False)
        res_bank = lax.dynamic_index_in_dim(act_bank, r["bwd_act_slot"], 0,
                                            keepdims=False)
        a_res = jnp.where(r["bwd_from_x"].astype(bool), res_x, res_bank)
        cot_in = lax.dynamic_index_in_dim(cot_bank, r["bwd_cot_slot"], 0,
                                          keepdims=False)
        _, chunk_vjp = jax.vjp(stage_fn, chunk_p(bc), a_res)
        dp, da = chunk_vjp(cot_in)
        cg_acc = jax.tree.map(
            lambda acc, g: lax.dynamic_update_index_in_dim(
                acc,
                lax.dynamic_index_in_dim(acc, bc, 0, keepdims=False)
                + jnp.where(bwd_valid, g, 0.0),
                bc, 0),
            cg_acc, dp)
        take_dx = (r["take_dx"].astype(bool) & bwd_valid)
        old_dx = lax.dynamic_index_in_dim(dx_bank, bm, 0, keepdims=False)
        dx_bank = lax.dynamic_update_index_in_dim(
            dx_bank, jnp.where(take_dx, da, old_dx), bm, 0)

        # ---- communication + bank writes (after ALL reads) ----
        a_msg = lax.ppermute(a_out, axis_name, ring_r)
        old_a = lax.dynamic_index_in_dim(act_bank, r["act_recv_slot"], 0,
                                         keepdims=False)
        act_bank = lax.dynamic_update_index_in_dim(
            act_bank,
            jnp.where(r["act_recv_valid"].astype(bool), a_msg, old_a),
            r["act_recv_slot"], 0)

        c_msg = lax.ppermute(da, axis_name, ring_l)
        # two cot writes can never share a tick+slot: the loss cot is
        # written by the last global stage at a fwd tick, recv cots by
        # the left hop of a bwd tick — distinct consumer units, and the
        # allocator keyed both on the consumer, so gate them in sequence.
        old_c = lax.dynamic_index_in_dim(cot_bank, r["cot_recv_slot"], 0,
                                         keepdims=False)
        cot_bank = lax.dynamic_update_index_in_dim(
            cot_bank,
            jnp.where(r["cot_recv_valid"].astype(bool), c_msg, old_c),
            r["cot_recv_slot"], 0)
        old_lc = lax.dynamic_index_in_dim(cot_bank, r["loss_cot_slot"], 0,
                                          keepdims=False)
        cot_bank = lax.dynamic_update_index_in_dim(
            cot_bank,
            jnp.where(r["loss_cot_valid"].astype(bool), d_act, old_lc),
            r["loss_cot_slot"], 0)

        return (act_bank, cot_bank, dx_bank, loss_acc, cg_acc, og_acc), None

    zeros_like_tree = lambda t: jax.tree.map(jnp.zeros_like, t)
    init = (
        jnp.zeros((schedule.act_depth,) + micro_shape, dtype),
        jnp.zeros((schedule.cot_depth,) + micro_shape, dtype),
        jnp.zeros((num_micro,) + micro_shape, dtype),
        jnp.zeros((), jnp.float32),
        jax.tree.map(lambda a: jnp.zeros_like(a), stage_params),
        zeros_like_tree(out_params),
    )
    (_, _, dx_bank, loss_acc, cg_acc, og_acc), _ = lax.scan(
        tick, init, tabs)

    loss_sum = lax.psum(loss_acc, axis_name)
    og_sum = jax.tree.map(lambda g: lax.psum(g, axis_name), og_acc)
    dx_sum = lax.psum(dx_bank, axis_name)
    if data_axis is not None:
        loss_sum = lax.pmean(loss_sum, data_axis)
        og_sum = jax.tree.map(lambda g: lax.pmean(g, data_axis), og_sum)
        cg_acc = jax.tree.map(lambda g: lax.pmean(g, data_axis), cg_acc)
    return loss_sum, cg_acc, og_sum, dx_sum


def format_timeline(schedule: InterleavedSchedule) -> str:
    """ASCII timeline of the schedule (one row per device, one column per
    tick, ``F<m>``/``B<m>``/``·``) — the at-a-glance view of warmup,
    steady 1F1B pairs, and drain.  ``python -m
    tpudist.parallel.pipeline_interleaved D V M`` prints it."""
    t = schedule.tables
    rows = []
    for d in range(schedule.n_dev):
        cells = []
        for tick in range(schedule.total_ticks):
            f = (f"F{t['fwd_m'][tick, d]}.{t['fwd_c'][tick, d]}"
                 if t["fwd_valid"][tick, d] else "")
            b = (f"B{t['bwd_m'][tick, d]}.{t['bwd_c'][tick, d]}"
                 if t["bwd_valid"][tick, d] else "")
            cells.append(f"{f}{'+' if f and b else ''}{b}" or "·")
        rows.append(f"dev{d}: " + " ".join(c.ljust(9) for c in cells))
    head = (f"D={schedule.n_dev} V={schedule.n_chunks} M={schedule.n_micro}"
            f"  ticks={schedule.total_ticks}"
            f" (bubble {schedule.bubble_ticks})"
            f"  act_bank={schedule.act_depth} cot_bank={schedule.cot_depth}")
    return "\n".join([head] + rows)


if __name__ == "__main__":  # pragma: no cover - debug CLI
    import sys as _sys

    d_, v_, m_ = (int(x) for x in _sys.argv[1:4])
    print(format_timeline(interleaved_schedule(d_, v_, m_)))
