"""Per-layer readers for what ``olmo_hybrid`` brought to the pattern decoder
(``tpudist/models/hybrid.py``): the delta-rule mixers' work outside the scan
and the dense gated feed-forward, from the scopes the program writes
(``tpudist/telemetry/names.py``), over the same whole steps as
``readers/hybrid.py``, whose helpers these are, and the same scopes as the
share cell's readers read, under this cell's own metric names.

Every reader returns ``None`` and never raises where what it reads is
absent: a program from before the pattern decoder named its layers, a trace
of another decoder (a GPT-2 block runs under ``mlp`` too, but in no pattern
layer), a run without a trace.
"""

from __future__ import annotations

import re

from cellbench import trace_reduce
from cellbench.readers.hybrid import (_roofline, _scope, _under,
                                      _under_scope)
from cellbench.readers.scopes import _ms_per_step


def gdn_mixer_ms_per_step(r):
    """Everything of the delta-rule mixers, forward and backward (what
    ``readers/hybrid.py::linear_attn_ms_per_step`` reads, under this
    cell's own metric name: a reader of its own, because the tests spy on a
    metric by its reader's name)."""
    return _under_scope(r, "LINEAR_ATTN")


def _scan_ms(r):
    return _under_scope(r, "DELTA_RULE")


def gdn_scan_ms_per_step(r):
    """The chunked delta-rule scan alone (what
    ``readers/hybrid.py::delta_rule_ms_per_step`` reads)."""
    return _scan_ms(r)


def gdn_scan_roofline(r):
    """The recurrence's least time (``archs/<model_type>.py::
    delta_rule_work``) over the time under the scan's scope (what
    ``readers/hybrid.py::delta_rule_roofline`` reads)."""
    return _roofline(r, _scan_ms(r), "delta_rule_work", "gdn_scan_roofline")


def gdn_glue_ms_per_step(r):
    """Under ``linear_attn``, outside the scan (``delta_rule``) and no
    matmul fusion: the convolution, SiLU, the L2 norms, the gates, the
    gated norm, the layer's own norm and residual add, forward and
    backward."""
    scan = _scope("DELTA_RULE")
    if scan is None:
        return None
    in_scan = _under(scan)
    return _under_scope(r, "LINEAR_ATTN", lambda op: (
        in_scan.search(op.scope) is None
        and trace_reduce.group_of(op.event) != "matmul fusions"))


def _dense_ffn_ms(r):
    layer = _scope("PATTERN_LAYER")
    if layer is None:
        return None
    in_layer = re.compile(rf"(^|[/(]){re.escape(layer)}_\d+([/)]|$)")
    return _under_scope(r, "MLP",
                        lambda op: in_layer.search(op.scope) is not None)


def dense_ffn_ms_per_step(r):
    """Every operation under scope ``mlp`` inside a pattern layer (a
    component ``<PATTERN_LAYER>_<i>`` of its name), forward and backward:
    the three products and SiLU x up."""
    return _dense_ffn_ms(r)


def dense_ffn_roofline(r):
    """The feed-forward's least time (``archs/<model_type>.py::
    dense_ffn_work``: its products' multiply-adds forward once and backward
    twice; compute-bound) over the time under its scope, which holds the
    rematerialised forward too."""
    return _roofline(r, _dense_ffn_ms(r), "dense_ffn_work",
                     "dense_ffn_roofline")
