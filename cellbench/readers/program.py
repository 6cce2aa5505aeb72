"""Per-layer readers over what the PROGRAM records about itself: the spans
and events of the active ``tpudist.telemetry`` session (its in-memory ring)
and the compile cache's own event counter.  The names are the program's
(``tpudist/telemetry/names.py``), imported, not spelled again here.

Every reader returns ``None`` and never raises when what it reads is
absent: telemetry disarmed (``TPUDIST_TELEMETRY=0``, as in the CPU
rehearsal), or a program from before the span or counter existed.
"""

from __future__ import annotations

import statistics

from cellbench import trace_reduce

try:
    from tpudist import telemetry
    from tpudist.telemetry import names
except ImportError:   # a program without the vocabulary: nothing to read
    telemetry = names = None


def _records() -> list:
    """The active session's ring, oldest first; empty without a session."""
    session = telemetry.active() if telemetry is not None else None
    return list(session.ring) if session is not None else []


def _spans(records: list, *span_names: str) -> list:
    return [r for r in records
            if r.get("kind") == "span" and r.get("name") in span_names]


def loader_ms_per_step(r):
    """Median ``lm_batch`` span (``tpudist/data/lm.py``, one per batch the
    loader produced): the program's share of the runner's ``data_wait``."""
    if names is None:
        return None
    durs = [s["dur"] for s in _spans(_records(), names.LM_BATCH)]
    return 1e3 * statistics.median(durs) if durs else None


def runtime_init_s(r):
    """The ``init`` span of ``tpudist.runtime.initialize``."""
    if names is None:
        return None
    spans = _spans(_records(), names.INIT)
    return spans[0]["dur"] if spans else None


def step_trace_lower_s(r):
    """Seconds of set-up in which JAX traced or lowered a program: the union
    of the ``xla_trace`` and ``xla_lower`` spans (they nest, so not their
    sum) that began before the first ``lm_batch`` of the window, which is
    the ``steps``-th from the last."""
    if names is None:
        return None
    records = _records()
    batches = _spans(records, names.LM_BATCH)
    spans = _spans(records, names.XLA_TRACE, names.XLA_LOWER)
    if not batches or not spans:
        return None
    steps = int(r.counters.get("steps") or 0)
    first = batches[-steps] if 0 < steps <= len(batches) else batches[0]
    return trace_reduce.total((s["t"], s["t"] + s["dur"]) for s in spans
                              if s["t"] < first["t"])


def compile_cache_misses(r):
    """``compile_cache_miss`` events of this process: programs compiled and
    written to the persistent cache; 0 on a machine whose cache is warm."""
    if names is None:
        return None
    try:
        from tpudist.runtime import compilation_cache
        return compilation_cache.event_counts()[names.COMPILE_CACHE_MISS]
    except (ImportError, AttributeError, KeyError):
        return None
