"""Expert parallelism: top-k routed MoE (k=1 Switch, k>1 Mixtral/GShard)
with ``all_to_all`` token exchange over the ``model`` (expert) mesh axis.

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
from jax.sharding import Mesh, PartitionSpec as P

from tpudist.runtime.mesh import AXIS_MODEL

# ExpertFn: (expert_params, tokens [slots, d]) -> [slots, d]
ExpertFn = Callable[[dict, jax.Array], jax.Array]


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
    top-``k`` routing.  Routing probabilities are computed in f32 whatever
    the compute dtype (argmax ties and gate scales are precision-sensitive).

    ``k=1``: Switch semantics — the raw top probability gates the output.
    ``k>1``: Mixtral/GShard semantics — the k gates renormalize to sum 1.
    Capacity queues fill in choice-major priority (every token's first
    choice is placed before any second choice), the standard GShard order.

    The returned ``balance_loss`` is the Switch §2.2 / GShard auxiliary:
    ``n_experts · Σ_e f_e · P_e`` with ``f_e`` the fraction of assignments
    routed to expert *e* and ``P_e`` its mean router probability — 1.0 at
    perfect balance, differentiable through ``P_e``.
    """
    t = router_logits.shape[0]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, k)  # [tokens, k]
    if k > 1:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

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
