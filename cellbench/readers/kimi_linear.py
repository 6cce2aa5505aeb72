"""Per-layer readers for what ``kimi_linear`` brought to the pattern decoder
(``tpudist/models/hybrid.py``): the delta-rule mixer whose decay is a number
a channel (scope ``kda``), its low-rank gates (``kda_gate``) and its chunked
scan (``delta_rule``, nested in it), latent attention (``latent_attn``) and
what it adds round the flash kernels (``latent_kv``), from the scopes the
program writes (``tpudist/telemetry/names.py``) over the same whole steps as
``readers/hybrid.py``, whose helpers these are; and the routed expert layers
behind the mixers under this cell's own metric names (three aliases of
accepted scopes: a files-only PR may not append a cell to an accepted
metric's list).  A reader a metric, built on the helpers and never on another
metric's reader: the tests spy on a metric by its reader's name.

Every reader returns ``None`` and never raises where what it reads is
absent: a program from before these mixers had a scope, a trace of another
decoder, a run without a trace.
"""

from __future__ import annotations

from cellbench import trace_reduce
from cellbench.readers.hybrid import (_is_grouped, _roofline, _scope, _under,
                                      _under_scope)
from cellbench.readers.scopes import _ms_per_step


def _has_kda() -> bool:
    return _scope("KDA") is not None


def kda_mixer_ms_per_step(r):
    """Everything of the KDA mixers, forward and backward: the layer's norm,
    the projections, the convolution, the gates, the scan, the output
    projection, the residual add."""
    return _under_scope(r, "KDA")


def kda_gate_ms_per_step(r):
    """What KDA adds to a delta-rule mixer: the forget gate's and the output
    gate's low-rank projections, softplus and ``exp(A_log)``, the sigmoid and
    its product into the normed output."""
    return _under_scope(r, "KDA_GATE")


def _scan_ms(r):
    return _under_scope(r, "DELTA_RULE") if _has_kda() else None


def kda_scan_ms_per_step(r):
    """The chunked scan at a decay a channel alone
    (``tpudist/ops/gated_delta.py``), nested in ``kda``."""
    return _scan_ms(r)


def kda_scan_roofline(r):
    """The chunked recurrence's least time (``archs/<model_type>.py::
    kda_scan_work``: its products once at their live halves, forward once and
    backward twice; q, k, v, o, g and beta across HBM) over the time under
    the scan's scope, which holds the rematerialised forward too."""
    return _roofline(r, _scan_ms(r), "kda_scan_work", "kda_scan_roofline")


def latent_attn_ms_per_step(r):
    """Everything of the latent-attention mixers, forward and backward:
    the layer's norm, the projections, the latent's norm, the keys'
    assembly, the re-layout to head-major, the flash kernels, the output
    projection, the residual add."""
    return _under_scope(r, "LATENT_ATTN")


def latent_kv_ms_per_step(r):
    """What latent attention adds round the kernels: the projection into the
    latent and the shared key, the latent's norm, the projection out of it
    and the assembly of the heads' keys."""
    return _under_scope(r, "LATENT_KV")


def kimi_moe_ms_per_step(r):
    """The expert layers whole: what carries the scope ``moe`` (router,
    dispatch, combine, shared expert), and the grouped products, which lost
    it."""
    if not _has_kda():
        return None
    under = _under(_scope("MOE"))
    return _ms_per_step(r, lambda op: _is_grouped(op)
                        or under.search(op.scope) is not None)


def kimi_moe_dispatch_ms_per_step(r):
    """Under ``moe`` and neither a matmul, a grouped product nor the shared
    expert: the sigmoid and top-k, the sort, the windows' gathers and
    scatter-adds and the masked sums."""
    if not _has_kda():
        return None
    in_shared = _under(_scope("SHARED_EXPERT"))
    return _under_scope(r, "MOE", lambda op: (
        not _is_grouped(op) and in_shared.search(op.scope) is None
        and trace_reduce.group_of(op.event) != "matmul fusions"))


def kimi_experts_roofline(r):
    """The grouped products' least time at the rows that arrive in the mean
    (``archs/<model_type>.py::expert_work``) over their time, by the
    instruction's own name."""
    ms = _ms_per_step(r, _is_grouped) if _has_kda() else None
    return _roofline(r, ms, "expert_work", "kimi_experts_roofline")
