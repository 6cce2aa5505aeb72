"""Per-layer readers for a decoder whose layers follow a pattern of mixers
(``tpudist/models/hybrid.py``): the linear-attention mixer and its delta-rule
scan, and the routed expert layer, from the scopes the program writes
(``tpudist/telemetry/names.py``; read with ``readers/scopes.py``'s helpers
over the same whole steps) and, for the grouped products, from the
instruction's own name.

The compiler lowers ``jax.lax.ragged_dot`` to Mosaic grouped matmuls of its
own.  They are ``tpu_custom_call``s without a ``kernel_metadata`` name and
their ``op_name`` is ``ragged-dot-*``, so the program's ``experts`` scope is
lost on them: they are picked by the HLO instruction's own name
(``Event.short``), never by the event's text, where a consumer that lists
one among its operands would be counted too.

Every reader returns ``None`` and never raises where what it reads is
absent: a program from before these scopes, a cell whose architecture has
no such layer, a run without a trace.
"""

from __future__ import annotations

from cellbench import archs, flops, trace_reduce
from cellbench.readers.scopes import _chips, _ms_per_step, _under

try:
    from tpudist.telemetry import names
except ImportError:   # a program without the vocabulary: nothing to read
    names = None

#: how the compiler names the instructions of a grouped (ragged) product
GROUPED_PRODUCT = "ragged-dot"


def _scope(name: str):
    """The program's spelling of one of its scopes, or None before it had
    one."""
    return getattr(names, name, None) if names is not None else None


def _is_grouped(op) -> bool:
    return op.event.short.startswith(GROUPED_PRODUCT)


def _under_scope(r, attr: str, keep=lambda op: True):
    scope = _scope(attr)
    if scope is None:
        return None
    under = _under(scope)
    return _ms_per_step(r, lambda op: under.search(op.scope) is not None
                        and keep(op))


def linear_attn_ms_per_step(r):
    """Everything of the linear-attention mixers, forward and backward:
    norm, projections, convolution, gates, the scan, the output
    projection."""
    return _under_scope(r, "LINEAR_ATTN")


def delta_rule_ms_per_step(r):
    """The chunked delta-rule scan alone (``tpudist/ops/gated_delta.py``)."""
    return _under_scope(r, "DELTA_RULE")


def _work(r, fn: str):
    """``(operations, bytes)`` by the configuration's architecture, or None
    for one that counts no such work."""
    if not r.config.get("model_type"):
        return None
    count = getattr(archs.load(r.config), fn, None)
    if count is None:
        return None
    return count(r.config, r.counters["per_chip_batch"],
                 r.counters["seq_len"])


def _roofline(r, ms, fn: str, label: str):
    work = _work(r, fn)
    if not ms or work is None:
        return None
    least, bound = flops.roofline_seconds(*work, r.peak)
    print(f"[reader] {label} bound={bound} least_ms={least * 1e3:.4f} "
          f"ms={ms:.4f}", flush=True)
    return 100.0 * least * 1e3 / ms


def delta_rule_roofline(r):
    """The recurrence's least time (its multiply-adds; q, k, v, o, g, beta
    across HBM once forward and twice backward:
    ``archs/<model_type>.py::delta_rule_work``) over the time under the
    scope."""
    return _roofline(r, delta_rule_ms_per_step(r), "delta_rule_work",
                     "delta_rule_roofline")


def experts_ms_per_step(r):
    """The held experts' grouped products, forward and backward, by the
    instruction's own name."""
    if _scope("EXPERTS") is None:
        return None
    return _ms_per_step(r, _is_grouped)


def experts_roofline(r):
    """Their least time at the rows that arrive in the mean
    (``archs/<model_type>.py::expert_work``) over their time."""
    return _roofline(r, experts_ms_per_step(r), "expert_work",
                     "experts_roofline")


def moe_ms_per_step(r):
    """The expert layer from the router to the combine: what carries the
    scope ``moe``, and the grouped products, which lost it."""
    scope = _scope("MOE")
    if scope is None:
        return None
    under = _under(scope)
    return _ms_per_step(r, lambda op: _is_grouped(op)
                        or under.search(op.scope) is not None)


def moe_dispatch_ms_per_step(r):
    """Under ``moe`` and neither a matmul, a grouped product nor the shared
    expert: softmax and top-k, the sorts, the gathers into and out of the
    buffers and the masked sums."""
    shared = _scope("SHARED_EXPERT")
    if shared is None:
        return None
    in_shared = _under(shared)
    return _under_scope(r, "MOE", lambda op: (
        not _is_grouped(op) and in_shared.search(op.scope) is None
        and trace_reduce.group_of(op.event) != "matmul fusions"))
