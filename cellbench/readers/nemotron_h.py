"""Per-layer readers for what ``nemotron_h`` brought to the pattern decoder
(``tpudist/models/hybrid.py``): the state-space mixer and its chunked scan
(``tpudist/ops/ssd.py``), and the expert layer whose routed experts work in
a latent space beside a whole shared expert, from the scopes the program
writes (``tpudist/telemetry/names.py``) and, for the grouped products, from
the instruction's own name, over the same whole steps as
``readers/hybrid.py``, whose helpers these are.  A reader a metric: the
tests spy on a metric by its reader's name.

Every reader returns ``None`` and never raises where what it reads is
absent: a program from before these scopes, a trace of another decoder, a
run without a trace.
"""

from __future__ import annotations

from cellbench import trace_reduce
from cellbench.readers.hybrid import (_is_grouped, _roofline, _scope, _under,
                                      _under_scope)
from cellbench.readers.scopes import _ms_per_step


def ssm_mixer_ms_per_step(r):
    """Everything of the state-space mixers, forward and backward: the
    layer's norm, the input projection, convolution, the scan, the gated
    norm, the output projection, the residual add."""
    return _under_scope(r, "SSM")


def _scan_ms(r):
    return _under_scope(r, "SSD_SCAN")


def ssd_scan_ms_per_step(r):
    """The chunked state-space scan alone (``tpudist/ops/ssd.py``)."""
    return _scan_ms(r)


def ssd_scan_roofline(r):
    """The recurrence's least time (``archs/<model_type>.py::ssd_work``)
    over the time under the scan's scope."""
    return _roofline(r, _scan_ms(r), "ssd_work", "ssd_scan_roofline")


def latent_moe_ms_per_step(r):
    """The expert layer whole: what carries the scope ``moe`` (router,
    latent projections, dispatch, combine, shared expert), and the grouped
    products, which lost it."""
    if _scope("LATENT_PROJ") is None:
        return None
    under = _under(_scope("MOE"))
    return _ms_per_step(r, lambda op: _is_grouped(op)
                        or under.search(op.scope) is not None)


def latent_moe_dispatch_ms_per_step(r):
    """Under ``moe`` and neither a matmul, a grouped product nor the shared
    expert: the sigmoid and top-k, the sorts, the gathers into and out of
    the buffers and the masked sums over each token's picks."""
    if _scope("LATENT_PROJ") is None:
        return None
    in_shared = _under(_scope("SHARED_EXPERT"))
    return _under_scope(r, "MOE", lambda op: (
        not _is_grouped(op) and in_shared.search(op.scope) is None
        and trace_reduce.group_of(op.event) != "matmul fusions"))


def _experts_ms(r):
    if _scope("LATENT_PROJ") is None:
        return None
    return _ms_per_step(r, _is_grouped)


def latent_experts_ms_per_step(r):
    """The held experts' grouped products over latent rows, forward and
    backward, by the instruction's own name."""
    return _experts_ms(r)


def latent_experts_roofline(r):
    """Their least time at the rows that arrive in the mean
    (``archs/<model_type>.py::expert_work``) over their time."""
    return _roofline(r, _experts_ms(r), "expert_work",
                     "latent_experts_roofline")


def _shared_ms(r):
    if _scope("LATENT_PROJ") is None:
        return None
    return _under_scope(r, "SHARED_EXPERT")


def shared_expert_ms_per_step(r):
    """The shared expert at the model's width, forward (twice: its layer is
    rematerialised) and backward."""
    return _shared_ms(r)


def shared_expert_roofline(r):
    """Its least time (``archs/<model_type>.py::shared_expert_work``:
    compute-bound) over the time under its scope."""
    return _roofline(r, _shared_ms(r), "shared_expert_work",
                     "shared_expert_roofline")
