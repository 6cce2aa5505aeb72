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
``xla_backend_compile`` span recorded from its duration, and each
persistent-cache hit or written miss a ``compile_cache_hit`` /
``compile_cache_miss`` event, counted in :func:`event_counts` (names in
:mod:`tpudist.telemetry.names`).  A program under the 0.5 s floor is
compiled and not written: JAX reports neither a hit nor a miss for it.
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
_lock = threading.Lock()
_listening = False


def event_counts() -> dict:
    """``compile_cache_hit`` / ``compile_cache_miss`` events of this process
    so far (whether or not a telemetry session recorded them)."""
    with _lock:
        return {name: _counts[name]
                for name in names.XLA_CACHE_EVENTS.values()}


#: JAX reports every nested trace, thousands of a few microseconds each in
#: one start-up; a span under this floor is not recorded (an outer one holds it)
_MIN_SPAN_S = 1e-3


def _on_duration(event: str, duration: float, **_) -> None:
    if duration < _MIN_SPAN_S:
        return
    name = names.XLA_DURATION_SPANS.get(event)
    tele = telemetry.active()
    if name is not None and tele is not None:
        # reported when it ends: the span started ``duration`` ago.  Traces
        # nest (a jitted function called under a trace), so these spans
        # overlap: take their union, not their sum.  Always detail
        # (``parent``): the wall-clock belongs to whatever span the compile
        # happened under (``compile``, a serving ``prefill``), never twice
        tele.record_span(name, time.monotonic() - duration, duration,
                         parent=names.XLA_PARENT)


def _on_event(event: str, **_) -> None:
    name = names.XLA_CACHE_EVENTS.get(event)
    if name is not None:
        with _lock:
            _counts[name] += 1
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
