"""Per-layer readers for what ``laguna`` brought to the pattern decoder
(``tpudist/models/hybrid.py``): softmax attention of two kinds in one
decoder, inside a sliding window (scope ``window_attn``) and causal to
everything (``attn``), the flash kernels under the window layers' scope by
the names they carry, a leading layer's dense feed-forward (``mlp``) and the
routed expert layers behind it (``moe``), from the scopes the program writes
(``tpudist/telemetry/names.py``) and, for the grouped products, from the
instruction's own name, over the same whole steps as ``readers/hybrid.py``,
whose helpers these are.  A reader a metric, built on the helpers and never
on another metric's reader: the tests spy on a metric by its reader's name.

Every reader returns ``None`` and never raises where what it reads is
absent: a program from before a window layer had a scope of its own, a trace
of another decoder, a run without a trace.
"""

from __future__ import annotations

from cellbench import flops, trace_reduce
from cellbench.readers.hybrid import (_is_grouped, _roofline, _scope, _under,
                                      _under_scope, _work)
from cellbench.readers.olmo_hybrid import _dense_ffn_ms
from cellbench.readers.scopes import _ms_per_step


def _has_window_layers() -> bool:
    return _scope("WINDOW_ATTN") is not None


def window_attn_ms_per_step(r):
    """Everything of the sliding-window attention layers' mixers, forward
    and backward: the layer's norm, the projections, rotary positions, the
    flash kernels, the gate a head, the output projection, the residual
    add."""
    return _under_scope(r, "WINDOW_ATTN")


def _window_kernel_ms(r):
    if not _has_window_layers():
        return None
    return _under_scope(r, "WINDOW_ATTN",
                        lambda op: op.kernel in flops.FLASH_KERNELS)


def window_attn_kernel_ms_per_step(r):
    """The flash kernels under the window layers' scope, by the names they
    carry (``flops.FLASH_KERNELS``)."""
    return _window_kernel_ms(r)


def window_attn_kernel_roofline(r):
    """Their least time at the band's LIVE pairs
    (``archs/<model_type>.py::window_kernel_work``, the three kernels
    together) over their time."""
    ms, work = _window_kernel_ms(r), _work(r, "window_kernel_work")
    if not ms or work is None:
        return None
    least, bound = flops.roofline_seconds(
        sum(ops for ops, _ in work.values()),
        sum(bytes_ for _, bytes_ in work.values()), r.peak)
    print(f"[reader] window_attn_kernel_roofline bound={bound} "
          f"least_ms={least * 1e3:.4f} ms={ms:.4f}", flush=True)
    return 100.0 * least * 1e3 / ms


def full_attn_ms_per_step(r):
    """Everything of the full-attention layers' mixers (scope ``attn``),
    forward and backward, in a decoder that also has window layers."""
    if not _has_window_layers():
        return None
    return _under_scope(r, "ATTN")


def _lead_ffn_ms(r):
    """What ``readers/olmo_hybrid.py`` reads for a dense arm: scope ``mlp``
    inside a pattern layer."""
    return _dense_ffn_ms(r) if _has_window_layers() else None


def lead_ffn_ms_per_step(r):
    """Every operation under scope ``mlp`` inside a pattern layer: the
    leading dense layer's three products and ``silu(gate) * up``, forward
    and backward."""
    return _lead_ffn_ms(r)


def lead_ffn_roofline(r):
    """Its least time (``archs/<model_type>.py::lead_ffn_work``:
    compute-bound) over the time under its scope."""
    return _roofline(r, _lead_ffn_ms(r), "lead_ffn_work", "lead_ffn_roofline")


def routed_moe_ms_per_step(r):
    """The expert layers whole: what carries the scope ``moe`` (router,
    dispatch, combine, shared expert), and the grouped products, which
    lost it."""
    if not _has_window_layers():
        return None
    under = _under(_scope("MOE"))
    return _ms_per_step(r, lambda op: _is_grouped(op)
                        or under.search(op.scope) is not None)


def routed_moe_dispatch_ms_per_step(r):
    """Under ``moe`` and neither a matmul, a grouped product nor the shared
    expert: the sigmoid and top-k, the sort, the windows' gathers and
    scatter-adds and the masked sums."""
    if not _has_window_layers():
        return None
    in_shared = _under(_scope("SHARED_EXPERT"))
    return _under_scope(r, "MOE", lambda op: (
        not _is_grouped(op) and in_shared.search(op.scope) is None
        and trace_reduce.group_of(op.event) != "matmul fusions"))


def _experts_ms(r):
    return _ms_per_step(r, _is_grouped) if _has_window_layers() else None


def routed_experts_ms_per_step(r):
    """The held experts' grouped products, forward and backward, by the
    instruction's own name."""
    return _experts_ms(r)


def routed_experts_roofline(r):
    """Their least time at the rows that arrive in the mean
    (``archs/<model_type>.py::expert_work``) over their time."""
    return _roofline(r, _experts_ms(r), "expert_work",
                     "routed_experts_roofline")
