"""Persistent XLA compilation cache wiring.

Every process — bench, demo, sweep agent, ``chip_smoke.py`` — otherwise
re-pays every compile from scratch.  JAX ships a persistent on-disk
cache keyed by HLO hash and by the cache directory's own path, so the
directory must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads its own variable and this
  module sets NO directory in code — whoever runs the program (a chip
  machine that keeps a cache between calls, a pod's scratch filesystem)
  places the cache from outside;
- unset: the cache goes to ONE fixed path inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored) — never a path built from a
  temporary name, a pid, the home directory or the time;
- ``TPUDIST_COMPILATION_CACHE=off`` disables it.

``enable_compilation_cache()`` is called from ``initialize()`` (the
runtime bootstrap every entry point goes through) and from the bench
harnesses.  Failures (an unwritable checkout, a config option JAX does
not know) raise: a run that silently compiles uncached hides exactly
what this module exists for.

The min-compile-time floor is lowered to 0.5 s so the Pallas kernels
(a second or two each) are cached too.

What compiling costs, told by the program itself: the same call registers
(once a process) two ``jax.monitoring`` listeners that turn JAX's own
reports into telemetry — each trace / lowering / backend compile (or
cache load) becomes an ``xla_trace`` / ``xla_lower`` /
``xla_backend_compile`` span recorded from its duration, tagged with the
program it was for (``fun=``), and each persistent-cache hit or written
miss a ``compile_cache_hit`` / ``compile_cache_miss`` event, counted in
:func:`event_counts` (names in :mod:`tpudist.telemetry.names`).  JAX
reports a load and a compile under the one ``backend_compile_duration``;
the cache's own events arrive on the compiling thread just before it, so
the span also says ``cache=`` hit / miss / uncached, ``load_s=`` and
``cold_s=`` (what the compile costs without the cache).  A program under
the 0.5 s floor is compiled and not written: JAX reports neither a hit nor
a miss for it.  :func:`compile_seconds` keeps the same by program for the
life of the process, whether or not a telemetry session is active.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from pathlib import Path
from typing import Optional

from tpudist import telemetry
from tpudist.telemetry import names

_OFF_VALUES = ("0", "off", "false", "disabled", "no")

#: the fixed in-checkout default (``tpudist/runtime/`` -> repo root)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


# process-wide like the listeners themselves, which JAX keeps for the
# life of the process; compiles run on any thread, hence the lock
_counts: "collections.Counter[str]" = collections.Counter()
_seconds: dict = {}   # program -> the row of ``compile_seconds``
_lock = threading.Lock()
_listening = False
# the cache's events of the compile in progress on THIS thread: they arrive
# before the ``backend_compile_duration`` that encloses them, which takes them
_pending = threading.local()


def event_counts() -> dict:
    """``compile_cache_hit`` / ``compile_cache_miss`` events of this process
    so far (whether or not a telemetry session recorded them)."""
    with _lock:
        return {name: _counts[name]
                for name in names.XLA_CACHE_EVENTS.values()}


#: JAX reports every nested trace, thousands of a few microseconds each in
#: one start-up; a span under this floor is not recorded (an outer one holds it)
_MIN_SPAN_S = 1e-3
#: span -> the seconds of ``compile_seconds`` it adds to
_SECONDS_OF = {names.XLA_TRACE: "trace_s", names.XLA_LOWER: "lower_s",
               names.XLA_BACKEND_COMPILE: "compile_or_load_s"}
_ROW = (*_SECONDS_OF.values(), "cold_compile_s", "compiles", "cache_hits")
#: ``compile_seconds`` names at most this many programs, those that cost
#: most; the rest, and every duration under the span floor, are summed here
_MAX_PROGRAMS = 256
OTHER_PROGRAMS = "(other)"


def compile_seconds() -> dict:
    """What this process spent bringing each program up so far, by the
    program's name (``names.STEP_PROGRAM`` is the LM step's), whether or
    not a telemetry session recorded it: ``trace_s`` (JAX's trace of the
    function, nested traces inside it; each of those is also under its own
    name), ``lower_s`` (jaxpr to MLIR), ``compile_or_load_s`` (the backend
    compile, or the load from the persistent cache), ``cold_compile_s``
    (what the compiles cost without the cache: a compile's own duration, a
    loaded entry's saved time + its retrieval, which JAX keeps to the whole
    second below), and how many ``compiles`` (or loads) those were and how
    many of them ``cache_hits``.  A program traced, lowered or compiled
    more than once reads the sum."""
    with _lock:
        return {program: dict(row) for program, row in _seconds.items()}


def _program(fun_name: str) -> str:
    """JAX names a trace ``step`` and its lowering and compile ``jit(step)``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _account(span: str, duration: float, tags: dict) -> None:
    """Add one duration to its program's row of ``compile_seconds``."""
    program = tags["fun"]
    with _lock:
        if duration < _MIN_SPAN_S:
            program = OTHER_PROGRAMS
        elif program not in _seconds and len(_seconds) >= _MAX_PROGRAMS:
            cheapest = min((p for p in _seconds if p != OTHER_PROGRAMS),
                           key=lambda p: sum(_seconds[p][k]
                                             for k in _SECONDS_OF.values()))
            gone = _seconds.pop(cheapest)
            other = _seconds.setdefault(OTHER_PROGRAMS, dict.fromkeys(_ROW, 0))
            for key in _ROW:
                other[key] += gone[key]
        row = _seconds.setdefault(program, dict.fromkeys(_ROW, 0))
        row[_SECONDS_OF[span]] += duration
        if span == names.XLA_BACKEND_COMPILE:
            row["compiles"] += 1
            row["cache_hits"] += tags["cache"] == names.CACHE_HIT
            row["cold_compile_s"] += tags["cold_s"]


def _compile_tags(duration: float) -> dict:
    """What the backend compile that just ended on this thread was, from
    the cache's events it enclosed (taken: the next compile starts clean)."""
    told = dict(vars(_pending))
    vars(_pending).clear()
    cache = told.get("cache", names.UNCACHED)
    if cache != names.CACHE_HIT:
        return {"cache": cache, "cold_s": duration}
    load = told.get(names.XLA_CACHE_RETRIEVAL, 0.0)
    return {"cache": cache, "load_s": load,
            "cold_s": told.get(names.XLA_CACHE_TIME_SAVED, 0.0) + load}


def _on_duration(event: str, duration: float, fun_name: str = "", **_) -> None:
    if event in names.XLA_CACHE_DURATIONS:
        vars(_pending)[event] = duration
        return
    name = names.XLA_DURATION_SPANS.get(event)
    if name is None:
        return
    tags = {"fun": _program(fun_name)}
    if name == names.XLA_BACKEND_COMPILE:
        tags.update(_compile_tags(duration))
    _account(name, duration, tags)
    tele = telemetry.active()
    if duration >= _MIN_SPAN_S and tele is not None:
        # reported when it ends: the span started ``duration`` ago.  Traces
        # nest (a jitted function called under a trace), so these spans
        # overlap: take their union, not their sum, or one ``fun``'s alone.
        # Always detail (``parent``): the wall-clock belongs to whatever
        # span the compile happened under (``compile``, a serving
        # ``prefill``), never twice
        tele.record_span(name, time.monotonic() - duration, duration, tags,
                         parent=names.XLA_PARENT)


def _on_event(event: str, **_) -> None:
    name = names.XLA_CACHE_EVENTS.get(event)
    if name is not None:
        with _lock:
            _counts[name] += 1
        _pending.cache = names.XLA_CACHE_TAGS[name]
        telemetry.event(name)


def _listen() -> None:
    global _listening
    import jax

    with _lock:
        if _listening:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def enable_compilation_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on; returns the directory
    in use, or None when disabled by ``TPUDIST_COMPILATION_CACHE=off``.
    Safe to call repeatedly and before/after backend init; reuse starts
    with the next compile either way."""
    _listen()   # compile spans are worth having with the cache off too
    if os.environ.get("TPUDIST_COMPILATION_CACHE", "").lower() in _OFF_VALUES:
        return None
    import jax

    target = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not target:
        DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
        target = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return target
