"""Routed experts: ONE router (:func:`route`) and two ways a device
computes its part.

- :func:`moe_shard` / :func:`make_moe`, the **capacity arm**: top-k routed
  MoE (k=1 Switch, k>1 Mixtral/GShard) with ``all_to_all`` token exchange
  over the ``model`` (expert) mesh axis, one expert a device, overflow
  dropped (design notes below);
- :func:`expert_share`, the **dropless share**: a device that is told which
  ``held`` of the router's ``n_experts`` experts it holds (``first_expert``
  on) routes every token over all of them and computes the part of the
  result its own experts give, whatever the load: through ONE buffer of
  all its assignments, or, where it holds a 32nd of the experts or less,
  through windows over the rows that arrived (:func:`share_windows`).
  What the absent experts
  would add is left out: the partial sum is what goes on when a chip runs
  alone as one member of an expert-parallel group.  Nothing stands in for
  the absent chips; on one chip the layer runs without its exchange, and
  under an expert-parallel mesh the same body (:func:`_held_experts`) is what
  sits between the two ``all_to_all``s :func:`moe_shard` has.

The capacity arm:

Absent from the reference (SURVEY.md §2.4: EP "not required for parity");
provided as the TPU-native extension.  Design, TPU-first:

- **capacity-based dispatch**: every device sends exactly
  ``capacity`` token slots to every expert — static shapes, no
  data-dependent gathers, so XLA can tile the expert matmuls on the MXU;
  overflow assignments are dropped (standard Switch-Transformer
  semantics) and their outputs fall back to zero, surfaced via the
  returned stats.
- **one `lax.all_to_all` each way**: dispatch and return ride a single
  fused ICI collective rather than per-expert sends.
- differentiable: routing probabilities multiply the combined output
  (straight-through on the top-k route), so router + experts train; the
  Switch/GShard balance auxiliary rides ``MoEStats``.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from tpudist import telemetry
from tpudist.runtime.mesh import AXIS_MODEL
from tpudist.telemetry import names

# ExpertFn: (expert_params, tokens [slots, d]) -> [slots, d]
ExpertFn = Callable[[dict, jax.Array], jax.Array]


class Routing(NamedTuple):
    """What :func:`route` decides for ``tokens`` rows."""

    expert_idx: jax.Array  # [tokens, k] int32: the picks, among all experts
    weights: jax.Array     # [tokens, k] f32: their combine weights
    probs: jax.Array       # [tokens, n_experts] f32: the scores (the softmax,
    #                        or the sigmoids)
    local: jax.Array       # [tokens, k] int32: pick - first_expert where the
    #                        expert is held here, ``held`` where it is absent


def _top_scores(scores, bias, k):
    """``(picks [t, k] int32, the picks' scores [t, k])`` of ``scores [t,
    n]``: a row's ``k`` largest ``scores + bias [n]`` (``scores`` where
    ``bias`` is None) in falling order, ties to the lower index as
    ``lax.top_k`` breaks them, and ``scores`` (never ``scores + bias``) at
    the picks.  Where they are differentiated both carry the name
    ``names.ROUTER_PICKS`` for a rematerialised caller to keep.

    Without a bias they are ``lax.top_k``'s own values and indices.  With
    one, the scores ride the sort that picks them: ONE stable sort of
    ``(-(scores + bias), column, scores)`` by its first operand, cut to
    ``k`` columns, gives the picks ``top_k`` gives and moves their scores
    with them, where ``take_along_axis`` behind a ``top_k`` is a gather of
    its own (10 ns a number on the chip).  The gradient is given here:
    JAX's own for a sorted payload gathers the whole row's tangent, and
    ``top_k``'s and ``take_along_axis``'s is a scatter, which the chip's
    compiler runs as a sort of all ``t * k`` positions and a scatter
    behind it (1.7 ms a layer at 8,192 x 22, outside every scope).
    ``d_scores`` is the same numbers as a one-hot sum, the picks' cotangent
    where a column is the pick and 0 elsewhere, which fuses into one pass
    over ``[t, n]``; the choice takes no gradient.  The names are given in
    the forward rule, to its results and to its residual (a policy does
    not see into the call itself), so that a caller who keeps them runs no
    sort again for its backward pass."""
    @jax.custom_vjp
    def top(scores, bias):
        if bias is None:
            picked, idx = lax.top_k(scores, k)
            return idx, picked
        column = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        _, idx, picked = lax.sort(
            (-(scores + bias), column, scores), dimension=1, is_stable=True,
            num_keys=1)
        return idx[:, :k], picked[:, :k]

    def fwd(scores, bias):
        idx, picked = checkpoint_name(top(scores, bias), names.ROUTER_PICKS)
        return (idx, picked), idx

    def bwd(idx, cotangents):
        is_pick = idx[:, :, None] == lax.broadcasted_iota(
            jnp.int32, (1, 1, scores.shape[1]), 2)
        d_scores = jnp.sum(
            jnp.where(is_pick, cotangents[1][:, :, None], 0.0), axis=1)
        return d_scores, None if bias is None else jnp.zeros_like(bias)

    top.defvjp(fwd, bwd)
    return top(scores, bias)


def _softmax_scores(logits, k, choice_bias, scale):
    """Softmax over all experts; the raw top probability at ``k=1``
    (Switch), renormalised to sum 1 over the ``k`` at ``k>1``
    (Mixtral/GShard, HF's ``norm_topk_prob``)."""
    if choice_bias is not None or scale != 1.0:
        raise ValueError(f"{names.SOFTMAX} scoring takes no choice bias and "
                         f"no scale")
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx, weights = _top_scores(probs, None, k)
    if k > 1:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return expert_idx, weights, probs


def _sigmoid_scores(logits, k, choice_bias, scale):
    """A sigmoid an expert (DeepSeek-V3's router, one group); the picks are
    the ``k`` largest scores, or where there is a ``choice_bias`` the ``k``
    largest of ``score + choice_bias``, a buffer that steers the choice
    alone and takes no gradient; the weights are the picks' UNbiased scores
    (:func:`_top_scores`: no gather reads them) over their sum (+ 1e-20),
    times ``scale``."""
    scores = jax.nn.sigmoid(logits)
    bias = None if choice_bias is None else lax.stop_gradient(
        choice_bias.astype(jnp.float32))
    expert_idx, weights = _top_scores(scores, bias, k)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return expert_idx, weights * scale, scores


#: scoring -> ``(logits f32, k, choice_bias, scale) -> (picks, weights,
#: scores)``: the ways :func:`route` scores: ``softmax`` (over all experts,
#: the picks' probabilities renormalised), ``sigmoid`` (a sigmoid an expert,
#: the picks' scores renormalised times a scale) and ``sigmoid_bias`` (the
#: same function: whoever builds the layer gives it the bias the picks go by)
SCORINGS = {names.SOFTMAX: _softmax_scores,
            names.SIGMOID: _sigmoid_scores,
            names.SIGMOID_BIAS: _sigmoid_scores}


def route(logits: jax.Array, *, n_experts: int, k: int,
          held: int | None = None, first_expert: int = 0,
          scoring: str = names.SOFTMAX, choice_bias: jax.Array | None = None,
          scale: float = 1.0) -> Routing:
    """The one router of both arms: scores over all ``n_experts`` in f32
    whatever the compute dtype (ties and gate scales are
    precision-sensitive), the top ``k``, and their weights, by ``scoring``
    (:data:`SCORINGS`: softmax with the picks' probabilities renormalised,
    or sigmoid scores, picked as they are or by ``score + choice_bias
    [n_experts]``, and weighted by the picks' own renormalised scores times
    ``scale``).  The
    width, the picks and the renormalisation do not depend on which experts
    are held here; ``held`` / ``first_expert`` only say which of the picks
    this device computes (``local``).  What is decided, the picks and their
    scores before the renormalisation, carries the name
    ``names.ROUTER_PICKS`` (:func:`_top_scores`): a rematerialised caller
    that keeps it beside the logits runs the router once a step, and what
    runs again behind the two (the scores, the renormalisation, ``local``)
    is elementwise."""
    if logits.shape[-1] != n_experts:
        raise ValueError(f"the router gives {logits.shape[-1]} scores for "
                         f"{n_experts} experts")
    if scoring not in SCORINGS:
        raise ValueError(f"scoring is {scoring!r}; the router scores by one "
                         f"of {sorted(SCORINGS)}")
    held = n_experts if held is None else held
    if not (0 <= first_expert and 1 <= held
            and first_expert + held <= n_experts):
        raise ValueError(f"experts {first_expert}..{first_expert + held} are "
                         f"not a run of the {n_experts} routed experts")
    expert_idx, weights, probs = SCORINGS[scoring](
        logits.astype(jnp.float32), k, choice_bias, scale)
    local = expert_idx - first_expert
    local = jnp.where((local >= 0) & (local < held), local, held)
    return Routing(expert_idx, weights, probs, local)


class MoEStats(NamedTuple):
    """Per-shard routing observability (host-side metrics material) plus
    the differentiable load-balancing auxiliary loss."""

    # NOTE: at k>1 the fractions below are over the k·tokens ASSIGNMENTS,
    # not over tokens.
    dropped_fraction: jax.Array  # scalar: assignments that overflowed capacity
    expert_load: jax.Array  # [n_experts]: fraction of assignments per expert
    balance_loss: jax.Array  # scalar: Switch/GShard aux loss (1.0 = uniform)


def _topk_dispatch(router_logits, n_experts, capacity, k=1):
    """Build the [tokens, experts, capacity] dispatch/combine tensors for
    top-``k`` routing by :func:`route` (``k=1``: the raw top probability
    gates the output; ``k>1``: the k gates renormalize to sum 1).
    Capacity queues fill in choice-major priority (every token's first
    choice is placed before any second choice), the standard GShard order.

    The returned ``balance_loss`` is the Switch §2.2 / GShard auxiliary:
    ``n_experts · Σ_e f_e · P_e`` with ``f_e`` the fraction of assignments
    routed to expert *e* and ``P_e`` its mean router probability — 1.0 at
    perfect balance, differentiable through ``P_e``.
    """
    t = router_logits.shape[0]
    expert_idx, gate_vals, probs, _ = route(router_logits,
                                            n_experts=n_experts, k=k)

    # Choice-major flattening: [k·tokens] with all first choices leading.
    flat_idx = expert_idx.T.reshape(-1)
    one_hot = jax.nn.one_hot(flat_idx, n_experts, dtype=jnp.int32)
    pos_in_expert = jnp.cumsum(one_hot, axis=0) * one_hot - one_hot
    pos = jnp.sum(pos_in_expert, axis=-1)  # [k·tokens]
    kept = pos < capacity

    disp_flat = (
        one_hot[:, :, None].astype(jnp.float32)
        * jax.nn.one_hot(pos, capacity, dtype=jnp.float32)[:, None, :]
        * kept[:, None, None]
    )  # [k·tokens, experts, capacity]
    disp_kt = disp_flat.reshape(k, t, n_experts, capacity)
    dispatch = jnp.sum(disp_kt, axis=0)  # distinct experts per token: 0/1
    combine = jnp.einsum("ktec,tk->tec", disp_kt, gate_vals)

    load = jnp.mean(one_hot.astype(jnp.float32), axis=0)  # f_e over choices
    balance = n_experts * jnp.sum(load * jnp.mean(probs, axis=0))
    stats = MoEStats(
        dropped_fraction=1.0 - jnp.mean(kept.astype(jnp.float32)),
        expert_load=load,
        balance_loss=balance,
    )
    return dispatch, combine, stats


def moe_shard(
    params: dict,
    x: jax.Array,
    *,
    expert_fn: ExpertFn,
    capacity_factor: float = 1.25,
    axis_name: str = AXIS_MODEL,
    k: int = 1,
):
    """Shard-local MoE body (call inside ``shard_map``).

    ``params = {'router': [d, n_experts], 'experts': pytree with leading
    local-expert axis}``; ``x: [local_tokens, d]``.  One expert per device
    (n_experts == axis size); ``k`` routes each token to its top-k experts
    (capacity scales with k so the fair share per expert is unchanged).
    """
    n_experts = lax.axis_size(axis_name)
    tokens = x.shape[0]
    capacity = int(capacity_factor * k * tokens / n_experts + 0.5)

    dispatch, combine, stats = _topk_dispatch(
        x @ params["router"], n_experts, capacity, k=k
    )
    # [tokens, experts, cap] × [tokens, d] -> [experts, cap, d].  The f32
    # dispatch/combine masks are cast to the compute dtype so the einsums
    # (and the expert matmuls they feed) stay on the bf16 MXU path.
    expert_inputs = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    # Exchange: each device keeps rows for ITS expert from every peer.
    # -> [peers, cap, d] on each device (split experts, concat peers).
    expert_inputs = lax.all_to_all(
        expert_inputs, axis_name, split_axis=0, concat_axis=0
    )
    local_expert = jax.tree.map(lambda a: a[0], params["experts"])
    expert_out = expert_fn(
        local_expert, expert_inputs.reshape(-1, x.shape[-1])
    ).reshape(expert_inputs.shape)
    # Return trip: rows go back to their source device.
    expert_out = lax.all_to_all(expert_out, axis_name, split_axis=0, concat_axis=0)
    out = jnp.einsum("tec,ecd->td", combine.astype(expert_out.dtype),
                     expert_out)
    # Stats become job-global means so every shard returns the same value
    # (replicated out-spec) — the host logs them off the compiled path, the
    # reference's metric-reduction discipline (SURVEY.md §5.5).
    stats = MoEStats(*(lax.pmean(s, axis_name) for s in stats))
    return out, stats


def make_moe(
    mesh: Mesh,
    expert_fn: ExpertFn,
    *,
    axis_name: str = AXIS_MODEL,
    batch_axis: str | None = None,
    capacity_factor: float = 1.25,
    k: int = 1,
):
    """Jitted global-view MoE layer over ``mesh``.

    ``params['experts']`` arrives stacked ``[n_experts, ...]`` sharded over
    ``axis_name``; ``x: [tokens, d]`` sharded over ``batch_axis`` (or
    replicated).  ``k`` selects top-k routing.  Returns ``(y, MoEStats)``
    with job-global stats (``balance_loss`` stays differentiable).
    """
    def body(params, x):
        out, stats = moe_shard(
            params, x,
            expert_fn=expert_fn,
            capacity_factor=capacity_factor,
            axis_name=axis_name,
            k=k,
        )
        if batch_axis is not None:
            stats = MoEStats(*(lax.pmean(s, batch_axis) for s in stats))
        return out, stats

    param_specs = {"router": P(), "experts": P(axis_name)}
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P(batch_axis, None)),
        out_specs=(P(batch_axis, None), MoEStats(P(), P(), P())), check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# the dropless share


def gated_ffn(params: dict, rows: jax.Array, dot=jnp.matmul) -> jax.Array:
    """A gated SiLU expert, ``down(silu(gate(x)) * up(x))``.  ``dot(a, w)``
    is ``a @ w`` for one expert's weights, or the grouped product when
    ``params`` carries a leading axis over the experts held."""
    return dot(jax.nn.silu(dot(rows, params["gate"]))
               * dot(rows, params["up"]), params["down"])


def relu2_ffn(params: dict, rows: jax.Array, dot=jnp.matmul) -> jax.Array:
    """An ungated squared-ReLU expert, ``down(relu(up(x))^2)``; ``dot`` as
    :func:`gated_ffn` takes it."""
    return dot(jnp.square(jax.nn.relu(dot(rows, params["up"]))),
               params["down"])


#: what an expert computes, by name, and the projections it reads
EXPERT_FNS = {names.GATED_SILU: gated_ffn, names.RELU2: relu2_ffn}
EXPERT_LEAVES = {names.GATED_SILU: ("gate", "up", "down"),
                 names.RELU2: ("up", "down")}


#: tokens whose picks :func:`expert_share` takes through its buffers at a
#: time: the buffers hold ``SHARE_BLOCK_TOKENS * k`` rows
SHARE_BLOCK_TOKENS = 8192
#: a window of a share that takes its arrivals through windows
#: (:func:`share_windows`), in even shares of a block's assignments
WINDOW_EVEN_SHARES = 8
#: what a row scattered costs on the chip, in rows gathered (PR 30: 75 ns
#: against 20): windows are taken where this many of them are no more than
#: the one buffer of the bound.  The threshold was measured on trips that
#: scattered a whole window; a trip scatters the strips its arrivals touch
#: (:func:`share_strip`), and what it still pays a window is its gathers,
#: its selects and the experts' float32 gradient sums
SCATTER_PER_GATHER = 4


def share_windows(block_tokens: int, k: int, held: int,
                  n_experts: int) -> tuple[int, int]:
    """``(window_rows, windows_at_most)`` of a block of ``block_tokens``
    tokens' ``k`` picks at a member that holds ``held`` of ``n_experts``
    experts: the rows of a window, :data:`WINDOW_EVEN_SHARES` times the
    block's even share rounded up to a multiple of 512, and how many of
    them the block's ``block_tokens * k`` assignments fill if every pick is
    held here.  A window is what a trip gathers and runs the grouped
    products over; what it scatters goes by strips of one even share
    (:func:`share_strip`), as many as its arrivals touch.  Where
    :data:`SCATTER_PER_GATHER` windows would pass the bound (``held /
    n_experts`` over 1/32) the layer keeps ONE buffer of the bound:
    ``(block_tokens * k, 1)``."""
    bound = block_tokens * k
    rows = -(-WINDOW_EVEN_SHARES * bound * held // (512 * n_experts)) * 512
    if SCATTER_PER_GATHER * rows > bound:
        return bound, 1
    return rows, -(-bound // rows)


def share_strip(window_rows: int) -> int:
    """The rows of a strip of a window of ``window_rows``: one of its
    :data:`WINDOW_EVEN_SHARES` even shares (rounded down where they do not
    divide it: the window's last strip then starts early, as a bound's last
    window does).  A trip scatter-adds its window a strip at a time, the
    strips its arrivals touch and no other.  One even share and not two:
    on the chip a scatter-add costs a pass over its whole operand besides
    its rows, which favours fewer and longer strips, but a strip of two
    even shares of 3,072-wide float32 rows takes the compiler's fast
    memory from the tokens the window's gathers read, and they cost more
    than the calls saved (PERF.md section 6, PR 45: both cells measured
    faster by 0.6% at one)."""
    return window_rows // WINDOW_EVEN_SHARES


def _window_run(i, n_live, bound: int, window_rows: int):
    """``(lo, start, first, end)`` of window ``i`` of ``bound`` sorted
    assignments whose first ``n_live`` arrived: ``lo``, the first row that
    is this window's own; ``start``, its first row (the last window of a
    bound that is no multiple of a window starts early and overlaps the one
    before it: the rows before ``lo`` are that trip's); and the strips
    ``[first, end)`` of :func:`share_strip` rows that its live rows
    ``[lo, n_live)`` touch, none where it has none.  Elementwise over
    arrays of ``i`` and ``n_live``: the loop's trips and the layer's count
    of strips read the same arithmetic."""
    strip = share_strip(window_rows)
    lo = i * window_rows
    start = jnp.minimum(lo, bound - window_rows)
    first = (lo - start) // strip
    live_end = jnp.clip(n_live - start, 0, window_rows)
    return lo, start, first, jnp.where(n_live > lo, -(-live_end // strip),
                                       first)


def _plan(local, held: int, positions: bool = True):
    """For a block's picks ``local [t, k]`` (the held expert's number, or
    ``held`` where the expert is absent): ``order [t * k]``, the
    assignment in each buffer row, sorted by held expert with the absent
    ones behind all held ones, and ``token``, its token; ``pos [t, k]``,
    each assignment's row (a second sort: None unless ``positions``);
    ``counts [held]``, the rows of each expert."""
    k = local.shape[1]
    key = local.reshape(-1)
    order = jnp.argsort(key).astype(jnp.int32)
    pos = (jnp.argsort(order).astype(jnp.int32).reshape(local.shape)
           if positions else None)
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None], axis=0,
                     dtype=jnp.int32)
    return order // k, order, pos, counts


def _rows(a, index):
    """``a[index]`` for an index known to lie inside ``a``."""
    return a.at[index].get(mode="promise_in_bounds")


def _combine(rows, pos, keep, weights=None):
    """``[t, d]`` f32: for every token the sum of its ``k`` rows
    ``rows[pos[t, j]]`` (times ``weights[t, j]``, the product in float32),
    those of ``keep [t, k]`` alone.

    Pick-major: one gather through ``pos`` transposed gives ``k`` slabs of
    ``[t, d]``, and the slabs are added up one after another in float32.
    The TPU tiles an array's two minor dimensions ``(8 or 16, 128)``: a
    ``[t, k, d]`` gather at ``k = 10`` is laid out with ten padded to
    sixteen and summed across padded sublanes, where ``[k, t, d]`` tiles
    without padding and its sum is an elementwise add.  The adds are
    spelled out because a ``sum`` over the leading axis makes XLA write the
    whole gather out again in float32 first; so they fuse into one pass
    that reads the slabs as gathered.  Under the scope
    ``names.MOE_COMBINE``."""
    with jax.named_scope(names.MOE_COMBINE):
        picked = _rows(rows, pos.T)
        total = 0.0
        for j in range(pos.shape[1]):
            slab = picked[j].astype(jnp.float32)
            if weights is not None:
                slab = slab * weights[:, j, None]
            total = total + jnp.where(keep[:, j, None], slab, 0.0)
        return total


def _grouped(expert_fn, counts):
    return lambda experts, rows: expert_fn(
        experts, rows, functools.partial(lax.ragged_dot, group_sizes=counts))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _held_experts(experts, x, weights, local, held, expert_fn):
    """``y [t, d]`` f32: for every token of a block the weighted results of
    its picks among the ``held`` experts here, through ONE buffer of the
    bound: the path of a member that holds more than a 32nd of the
    experts (:func:`share_windows`; :func:`_held_experts_windowed` is the
    other).

    All ``t * k`` assignments go through a buffer of as many rows, sorted
    by held expert: the tokens' rows are gathered into it, one grouped
    product a projection computes the rows that arrived (the held
    experts' runs, which lead), and every token gathers its ``k`` rows
    back and adds up those of held experts, pick-major (:func:`_combine`:
    ``k`` slabs of ``[t, d]``, never a ``[t, k, d]`` tensor, whose ten in
    second-minor place the ``(8, 128)`` tile would pay as sixteen).  So
    on this path the gathers cost the same whatever the router does, the
    products go with the rows that arrived, nothing is dropped, and the
    layer has no buffer to outgrow.  (Gathers, not scatter-adds: on the
    TPU a row scattered costs four of a row gathered, so a path that
    scatters what arrived wins only while it moves under a quarter of
    the bound's rows.)

    The grouped products define only the rows of their groups: behind the
    last arrival a row of their result, and of their cotangents, is
    whatever the buffer held.  Those are absent experts' rows, and the
    ``where`` over ``local < held`` leaves them out, forward and
    backward."""
    token, _, pos, counts = _plan(local, held)
    with jax.named_scope(names.EXPERTS):
        out = _grouped(expert_fn, counts)(experts, _rows(x, token))
    return _combine(out, pos, local < held, weights)


def _held_experts_fwd(experts, x, weights, local, held, expert_fn):
    y = _held_experts(experts, x, weights, local, held, expert_fn)
    return y, (experts, x, weights, local)


def _held_experts_bwd(held, expert_fn, residuals, dy):
    """The buffer again (the products are recomputed, nothing of a block
    is kept but its inputs); each row takes its token's cotangent, each
    token gathers its rows' back and adds them up pick-major, as the
    forward combine does and for the same tile."""
    experts, x, weights, local = residuals
    token, order, pos, counts = _plan(local, held)
    is_held = local < held
    with jax.named_scope(names.EXPERTS):
        out, pull = jax.vjp(_grouped(expert_fn, counts), experts,
                            _rows(x, token))
    # in the compute dtype, as the products take it
    dy_rows = _rows(dy.astype(x.dtype), token).astype(jnp.float32)
    d_weight_rows = jnp.sum(dy_rows * out.astype(jnp.float32), axis=-1)
    d_out = dy_rows * _rows(weights.reshape(-1), order)[:, None]
    with jax.named_scope(names.EXPERTS):
        d_experts, d_rows = pull(d_out.astype(out.dtype))
    d_x = _combine(d_rows, pos, is_held)
    d_weights = jnp.where(is_held, _rows(d_weight_rows, pos), 0.0)
    return d_experts, d_x.astype(x.dtype), d_weights.astype(weights.dtype), None


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class _Window(NamedTuple):
    """What a trip of a windowed block's loop takes (:func:`_windows`)."""

    at: jax.Array       # [window_rows] int32: the rows' assignments
    token: jax.Array    # [window_rows] int32: their tokens
    grouped: Callable   # the experts, as grouped products over the part of
    #                     each expert's run that lies in the window
    live: jax.Array     # [window_rows, 1] bool: the rows this trip computes
    first: jax.Array    # the strips ``[first, end)`` that hold them
    end: jax.Array
    strip: Callable     # ``(j, *arrays [window_rows, ...]) -> (live
    #                     [strip_rows] bool, *the arrays' rows of strip j)``


def _windows(local, held: int, expert_fn, window_rows: int):
    """A windowed block's loop over :func:`_plan`'s order, whose first
    ``n_live`` rows are the ones that arrived (the held experts' runs
    lead): ``more(carry)``, whether trip ``carry[0]`` has rows to take, and
    ``window(i)``, the ``i``-th :class:`_Window` of ``window_rows`` rows.

    A window's live rows are one run, ``[lo, n_live)`` clipped to the
    window, and what a trip scatters it scatters by the strips of
    :func:`share_strip` rows that the run touches (:func:`_window_run`):
    ``strip(j, ...)`` slices strip ``j``'s rows out of the window's arrays
    and says which of them are live, those of the run that no strip before
    it took (a window's last strip starts early where the strips do not
    divide the window)."""
    k = local.shape[1]
    _, order, _, counts = _plan(local, held, positions=False)
    ends = jnp.cumsum(counts)
    n_live = ends[-1]
    strip_rows = share_strip(window_rows)

    def window(i):
        lo, start, first, end = _window_run(i, n_live, order.shape[0],
                                            window_rows)
        at = lax.dynamic_slice(order, (start,), (window_rows,))
        cuts = jnp.clip(ends, start, start + window_rows)
        sizes = jnp.diff(cuts, prepend=start)
        row = start + jnp.arange(window_rows, dtype=jnp.int32)
        live = ((row >= lo) & (row < n_live))[:, None]

        def strip(j, *arrays):
            own = j * strip_rows
            at_row = jnp.minimum(own, window_rows - strip_rows)
            row = start + at_row + jnp.arange(strip_rows, dtype=jnp.int32)
            live = (row >= jnp.maximum(lo, start + own)) & (row < n_live)
            return live, *(lax.dynamic_slice_in_dim(a, at_row, strip_rows)
                           for a in arrays)

        return _Window(at, at // k, _grouped(expert_fn, sizes), live, first,
                       end, strip)

    return lambda carry: carry[0] * window_rows < n_live, window


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _held_experts_windowed(experts, x, weights, local, held, expert_fn,
                           window_rows):
    """``y [t, d]`` f32 as :func:`_held_experts` gives it, by windows over
    the sorted assignments: the rows that arrived are the prefix
    ``[0, n_live)`` of the plan's order, and a ``lax.while_loop`` takes
    them ``window_rows`` at a time, as many trips as the arrivals fill
    (none where nothing arrived).  A trip gathers its rows' tokens into a
    ``[window_rows, d]`` buffer and runs the grouped products over the part
    of each expert's run that lies in the window; an inner loop then takes
    the result by strips of one even share (:func:`share_strip`), as many
    as the window's arrivals touch: a strip weighs its rows in float32 and
    scatter-adds them into ``y``, so the scatter goes with what arrived
    where the buffer and the products' shapes go with the window.  A
    token's picks add up in the order of the sort.  Nothing is dropped: if
    every pick is held here the loops run over all ``t * k`` rows.  No
    tensor of ``t * k`` rows is built but the sort's own.

    The masking rule is :func:`_held_experts`': a row of the window at or
    behind ``n_live`` is undefined in the products' result and in their
    cotangents, and the ``where`` over a strip's live rows leaves it out,
    forward and backward."""
    more, window = _windows(local, held, expert_fn, window_rows)
    flat_weights = weights.reshape(-1)

    def trip(carry):
        i, y = carry
        w = window(i)
        with jax.named_scope(names.MOE_COMBINE):
            rows = _rows(x, w.token)
        with jax.named_scope(names.EXPERTS):
            out = w.grouped(experts, rows)

        def scatter(j, y):
            live, rows, token, at = w.strip(j, out, w.token, w.at)
            rows = rows.astype(jnp.float32) * _rows(flat_weights, at)[:, None]
            return y.at[token].add(jnp.where(live[:, None], rows, 0.0),
                                   mode="promise_in_bounds")

        with jax.named_scope(names.MOE_COMBINE):
            y = lax.fori_loop(w.first, w.end, scatter, y)
        return i + 1, y

    return lax.while_loop(
        more, trip, (jnp.int32(0), jnp.zeros(x.shape, jnp.float32)))[1]


def _held_experts_windowed_fwd(experts, x, weights, local, held, expert_fn,
                               window_rows):
    y = _held_experts_windowed(experts, x, weights, local, held, expert_fn,
                               window_rows)
    return y, (experts, x, weights, local)


def _held_experts_windowed_bwd(held, expert_fn, window_rows, residuals, dy):
    """The same loops (the products are recomputed, nothing of a block is
    kept but its inputs): a trip gathers its rows' tokens and their
    cotangents and pulls the window's cotangent back through the products;
    the strips its arrivals touch scatter-add the rows' input gradients
    into ``d_x`` in float32 and each row's weight gradient to its
    assignment.  The experts' gradients add up over the trips in float32
    and are rounded once."""
    experts, x, weights, local = residuals
    more, window = _windows(local, held, expert_fn, window_rows)
    flat_weights = weights.reshape(-1)
    dy = dy.astype(x.dtype)             # as the products take it

    def trip(carry):
        i, d_experts, d_x, d_weights = carry
        w = window(i)
        with jax.named_scope(names.MOE_COMBINE):
            rows = _rows(x, w.token)
            dy_rows = _rows(dy, w.token).astype(jnp.float32)
        with jax.named_scope(names.EXPERTS):
            out, pull = jax.vjp(w.grouped, experts, rows)
        d_weight_rows = jnp.sum(dy_rows * out.astype(jnp.float32), axis=-1)
        # whole: the products take a window, and its rows before ``lo`` are
        # in their groups
        d_out = jnp.where(w.live,
                          dy_rows * _rows(flat_weights, w.at)[:, None], 0.0)
        with jax.named_scope(names.EXPERTS):
            d_trip, d_rows = pull(d_out.astype(out.dtype))
        d_experts = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 d_experts, d_trip)

        def scatter(j, carry):
            d_x, d_weights = carry
            live, rows, token, at, d_weight = w.strip(
                j, d_rows, w.token, w.at, d_weight_rows)
            d_x = d_x.at[token].add(
                jnp.where(live[:, None], rows.astype(jnp.float32), 0.0),
                mode="promise_in_bounds")
            # the rows of a window are assignments of their own
            d_weights = d_weights.at[at].add(
                jnp.where(live, d_weight, 0.0),
                mode="promise_in_bounds", unique_indices=True)
            return d_x, d_weights

        with jax.named_scope(names.MOE_COMBINE):
            d_x, d_weights = lax.fori_loop(w.first, w.end, scatter,
                                           (d_x, d_weights))
        return i + 1, d_experts, d_x, d_weights

    _, d_experts, d_x, d_weights = lax.while_loop(
        more, trip,
        (jnp.int32(0),
         jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), experts),
         jnp.zeros(x.shape, jnp.float32),
         jnp.zeros(flat_weights.shape, jnp.float32)))
    return (jax.tree.map(lambda g, w: g.astype(w.dtype), d_experts, experts),
            d_x.astype(x.dtype),
            d_weights.reshape(weights.shape).astype(weights.dtype), None)


_held_experts_windowed.defvjp(_held_experts_windowed_fwd,
                              _held_experts_windowed_bwd)


def expert_share(params: dict, x: jax.Array, *, n_experts: int, held: int,
                 first_expert: int, k: int,
                 expert_fn: ExpertFn = gated_ffn,
                 router_input: jax.Array | None = None,
                 block_tokens: int = SHARE_BLOCK_TOKENS,
                 scoring: str = names.SOFTMAX, scale: float = 1.0):
    """This device's part of one routed-expert layer, dropless: ``(y
    [tokens, d] in x's dtype, assignments per held expert [held], windows
    taken a block [blocks], strips scattered a block [blocks])``.

    ``params = {"router": [d_router, n_experts] f32, "experts": a pytree
    with a leading axis over the ``held`` experts held (``first_expert``
    on), and optionally "choice_bias": [n_experts] (``scoring``
    ``names.SIGMOID_BIAS``) and "shared": one expert's weights with
    "score": [d, 1]}``; ``x [tokens, d]`` in the compute dtype.  The
    router's logits are a float32 product at the highest precision of
    ``router_input`` (``x`` before it was rounded to the compute dtype,
    where the caller has it; or the tokens at their own width
    ``d_router`` where ``x`` holds their projection into the experts'
    latent space) and carry the name ``names.ROUTER_LOGITS`` for a
    rematerialised caller to keep; its scores, top ``k``, renormalisation
    and ``scale``
    are :func:`route`'s by ``scoring``, over all ``n_experts``, and the
    picks and their scores carry ``names.ROUTER_PICKS``.  The
    experts compute in ``x``'s dtype.  ``shared``
    is ``sigmoid(x . score) * E_shared(x)``, what every member of the
    group computes alike.  The tokens are taken in equal blocks of at most
    ``block_tokens``, one after another; the result does not depend on it.

    Which of two paths a block takes is decided at trace time from the
    layer's shapes alone (:func:`share_windows`).  A member that holds
    more than a 32nd of the experts keeps ONE buffer of the bound,
    ``block_tokens * k`` rows (:func:`_held_experts`): its gathers cost
    the same whatever the router does and it has no buffer to outgrow.
    One that holds a 32nd or less takes the rows that arrived through
    windows of eight even shares (:func:`_held_experts_windowed`): one
    trip of a loop at an even load, as many as the arrivals fill
    otherwise, dropless alike.  A trip's gathers and products go with the
    window; what it scatter-adds into the result goes with the arrivals,
    by strips of one even share (:func:`share_strip`: one or two strips a
    trip at an even load, eight where the window is full).  Its result
    carries the name ``names.EXPERT_OUT`` for a rematerialised caller to
    keep.  The threshold is the measured cost of a scattered row in
    gathered rows on the chip (:data:`SCATTER_PER_GATHER`, 4 : 1), from
    when a trip scattered its whole window where the one buffer is
    gathered out of.  (A share whose router drifts towards its own
    experts, as a lone member's trained router does, may fill many
    windows: what such a member's router sees is its benchmark cell's
    question, and its answer may move that layer under the threshold
    later.)

    Returns besides ``y`` and the assignments, each ``[blocks]`` int32:
    the windows each block's arrivals took and the strips it scattered (1
    and 1 where the layer keeps one buffer, whose one strip is the bound).
    ``strips * strip_rows`` over the arrivals is how many rows the layer
    moved into its result for each that arrived.

    Runs under the scope ``names.MOE``, the grouped products under
    ``names.EXPERTS``, the combine (forward, and the tokens' input
    gradients backward; a windowed block's gathers and scatter-adds)
    under ``names.MOE_COMBINE``, the shared expert under
    ``names.SHARED_EXPERT``.
    Shard-local: inside a multi-device program call it under ``shard_map``.
    """
    tokens, d = x.shape
    blocks = max(1, -(-tokens // block_tokens))
    while tokens % blocks:
        blocks += 1
    bound = tokens // blocks * k
    window_rows, windows_at_most = share_windows(tokens // blocks, k, held,
                                                 n_experts)
    windowed = windows_at_most > 1
    telemetry.event(names.MOE_LAYOUT, experts=n_experts, held=held,
                    first=first_expert, top_k=k, dropless=True,
                    buffer_rows=bound, blocks=blocks,
                    window_rows=window_rows, windows_at_most=windows_at_most,
                    strip_rows=share_strip(window_rows) if windowed
                    else window_rows,
                    combine=names.SCATTER_ADD if windowed
                    else names.PICK_MAJOR, scoring=scoring, scale=scale,
                    width=d)
    with jax.named_scope(names.MOE):
        scored = x if router_input is None else router_input
        logits = checkpoint_name(
            jnp.matmul(scored.astype(jnp.float32),
                       params["router"].astype(jnp.float32),
                       precision=lax.Precision.HIGHEST),
            names.ROUTER_LOGITS)
        routing = route(logits, n_experts=n_experts, k=k, held=held,
                        first_expert=first_expert, scoring=scoring,
                        choice_bias=params.get("choice_bias"), scale=scale)
        counts = jnp.sum(routing.local.reshape(-1)[:, None]
                         == jnp.arange(held)[None], axis=0, dtype=jnp.int32)
        experts = jax.tree.map(lambda w: w.astype(x.dtype),
                               params["experts"])
        if windowed:
            block_fn = lambda block: _held_experts_windowed(
                experts, *block, held, expert_fn, window_rows)
        else:
            block_fn = lambda block: _held_experts(experts, *block, held,
                                                   expert_fn)
        y = lax.map(
            block_fn,
            jax.tree.map(lambda a: a.reshape(blocks, -1, *a.shape[1:]),
                         (x, routing.weights, routing.local))
        ).reshape(tokens, d)
        if "shared" in params:
            with jax.named_scope(names.SHARED_EXPERT):
                shared = jax.tree.map(lambda w: w.astype(x.dtype),
                                      params["shared"])
                score = jax.nn.sigmoid(jnp.matmul(
                    x, shared.pop("score"),
                    preferred_element_type=jnp.float32))
                y = y + score * expert_fn(shared, x).astype(jnp.float32)
        y = y.astype(x.dtype)
        if not windowed:
            one = jnp.ones((blocks,), jnp.int32)
            return y, counts, one, one
        arrived = jnp.sum((routing.local < held).reshape(blocks, -1), axis=1,
                          dtype=jnp.int32)
        _, _, first, end = _window_run(
            jnp.arange(windows_at_most), arrived[:, None], bound, window_rows)
        return (checkpoint_name(y, names.EXPERT_OUT), counts,
                -(-arrived // window_rows), jnp.sum(end - first, axis=1))
