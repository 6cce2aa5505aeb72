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
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

_OFF_VALUES = ("0", "off", "false", "disabled", "no")

#: the fixed in-checkout default (``tpudist/runtime/`` -> repo root)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on; returns the directory
    in use, or None when disabled by ``TPUDIST_COMPILATION_CACHE=off``.
    Safe to call repeatedly and before/after backend init; reuse starts
    with the next compile either way."""
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
