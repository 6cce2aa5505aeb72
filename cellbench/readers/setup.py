"""Per-layer readers of ``setup_s`` that name no cell: what bringing the LM
step up cost this process, from the program's own process-wide record
(``tpudist.runtime.compilation_cache.compile_seconds``, filled by the
listener that writes the ``xla_*`` spans, whether or not a telemetry session
is active).  The step's name is the program's (``names.STEP_PROGRAM``),
imported, not spelled again here.

Every reader returns ``None`` and never raises where there is nothing to
read: a program from before the record existed, or a process that traced,
lowered or compiled no step.  A run is one process and compiles one step;
a process that compiles several reads their sum.
"""

from __future__ import annotations


def _step():
    """The record's row of the LM step, or ``None``."""
    try:
        from tpudist.runtime import compilation_cache
        from tpudist.telemetry import names
        return compilation_cache.compile_seconds().get(names.STEP_PROGRAM)
    except (ImportError, AttributeError):
        return None


def _seconds(key: str):
    row = _step()
    return (row[key] or None) if row else None


def _of_compiles(key: str):
    row = _step()
    return row[key] if row and row["compiles"] else None


def step_trace_s(r):
    return _seconds("trace_s")


def step_lower_s(r):
    return _seconds("lower_s")


def step_compile_or_load_s(r):
    return _of_compiles("compile_or_load_s")


def step_cache_hits(r):
    return _of_compiles("cache_hits")


def step_cold_compile_s(r):
    return _of_compiles("cold_compile_s")
