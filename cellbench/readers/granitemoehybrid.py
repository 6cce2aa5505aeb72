"""Per-layer readers for what ``granitemoehybrid`` brought to the pattern
decoder (``tpudist/models/hybrid.py``), in the measured order of their
shares of the cell's step (PERF.md section 5): a gated feed-forward behind
every mixer (``mlp``, half the step: the dense arm moves this cell's rate
first, as it does the olmo cell's), Mamba-2 mixers (a good quarter), read
whole (scope ``ssm``) and by part (the chunked scan ``ssd_scan``, the
convolution ``ssm_conv``, the gate's product and the gated norm
``ssm_norm``), an embedding that is the head too (``embed``, ``head``,
``loss``), and one position-free attention layer on the dispatch's
head-major route (``attn``); from the scopes the program writes
(``tpudist/telemetry/names.py``), over the same whole steps as
``readers/hybrid.py``, whose helpers these are.  A reader a metric, built on
the helpers and never on another metric's reader: the tests spy on a metric
by its reader's name.

Every reader returns ``None`` and never raises where what it reads is
absent: a program from before the mixer's parts had scopes of their own, a
trace of another decoder, a run without a trace.
"""

from __future__ import annotations

from cellbench import trace_reduce
from cellbench.readers.hybrid import (_roofline, _scope, _under,
                                      _under_scope)
from cellbench.readers.olmo_hybrid import _dense_ffn_ms
from cellbench.readers.scopes import _ms_per_step


def mamba_mixer_ms_per_step(r):
    """Everything of the Mamba-2 mixers, forward and backward: the layer's
    norm, the input projection, the convolution, the scan, the gated norm,
    the output projection, the multiplier and the residual add."""
    return _under_scope(r, "SSM")


def mamba_scan_ms_per_step(r):
    """The chunked state-space scan alone (``tpudist/ops/ssd.py``)."""
    return _under_scope(r, "SSD_SCAN")


def mamba_scan_roofline(r):
    """The recurrence's least time (``archs/<model_type>.py::ssd_work``)
    over the time under the scan's scope."""
    return _roofline(r, _under_scope(r, "SSD_SCAN"), "ssd_work",
                     "mamba_scan_roofline")


def mamba_conv_ms_per_step(r):
    """The depthwise causal convolution over x, B and C, its bias and SiLU,
    forward and backward."""
    return _under_scope(r, "SSM_CONV")


def mamba_norm_ms_per_step(r):
    """The gate's product ``y * silu(z)`` and the gated norm over a group's
    channels, forward and backward."""
    return _under_scope(r, "SSM_NORM")


def shared_mlp_ms_per_step(r):
    """Every operation under scope ``mlp`` inside a pattern layer: each
    layer's three products and ``silu(gate) * up`` forward, ``gate`` and
    ``up`` AGAIN in the rematerialised forward (the products' outputs are
    not kept; ``down``'s is read by nothing in the backward pass) and the
    backward: what ``readers/olmo_hybrid.py`` reads for a dense arm."""
    return _dense_ffn_ms(r)


def shared_mlp_roofline(r):
    """Its least time (``archs/<model_type>.py::mlp_work``: compute-bound,
    the forward counted once) over the time under its scope."""
    return _roofline(r, _dense_ffn_ms(r), "mlp_work", "shared_mlp_roofline")


def nope_attn_ms_per_step(r):
    """Everything of the attention layer's mixer (scope ``attn``), forward,
    rematerialised forward and backward: the layer's norm, the projections,
    the queries' scale, the head-major route's re-layouts, the flash
    kernels, the output projection, the multiplier and the residual add."""
    return _under_scope(r, "ATTN")


def nope_attn_relayout_ms_per_step(r):
    """Under ``attn`` and neither a flash kernel nor a matmul fusion: what
    the head-major route pays round the kernels (split and merge of the
    heads, the repeated layouts of the backward pass), with the layer's
    norm, the queries' scale and the residual add."""
    return _under_scope(r, "ATTN", lambda op: (
        op.kernel is None
        and trace_reduce.group_of(op.event) != "matmul fusions"))


def tied_head_ms_per_step(r):
    """Scopes ``embed``, ``head`` and ``loss`` whole, forward and backward:
    the gather and its multiplier, the final norm, the product over the
    held rows of the embedding, the logits' divisor, the cross entropy, and
    the one tensor's two gradient paths (the head's weight gradient and the
    gather's scatter-add)."""
    under = _under(_scope("EMBED"), _scope("HEAD"), _scope("LOSS"))
    return _ms_per_step(r, lambda op: under.search(op.scope) is not None)
