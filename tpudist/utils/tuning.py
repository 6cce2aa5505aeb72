"""Hardware-tuned constants: per-platform defaults + env overrides.

Round-2 measurements baked several magic numbers into the hot paths — the
flash-attention routing crossover and block sizes
(``tpudist/models/transformer.py``) and the train loop's scan window
(``tpudist/train/loop.py``) — all measured on ONE v5e.
This module is the escape hatch the advisor asked for: every such constant
resolves here, through

1. an environment override ``TPUDIST_<NAME>`` (operators re-tune a new
   platform generation without touching code; the benchmark harnesses in
   ``benchmarks/`` are the re-derivation tools — ``flash_sweep.py`` for
   the crossover/blocks, ``bench.py`` for the scan window), then
2. a measured tuned-constants file for this ``device_kind`` —
   ``tpudist/tuned/<device_kind>.json``, written by
   :mod:`tpudist.utils.autotune` on real hardware (or any path via
   ``TPUDIST_TUNED_FILE``), then
3. a per-``device_kind`` table of measured values, then
4. the v5e-measured default (the only hardware this repo has ever seen).

Values are read lazily at call time, so tests can monkeypatch env vars and
a process that sets overrides before building models sees them.
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Dict

# Measured on TPU v5e (round 2): dense XLA wins below seq 1024; 512-wide
# tiles; 1024-wide KV tiles amortize grid overhead from seq 8192;
# 256-step scan windows amortize per-step dispatch.
_V5E_DEFAULTS: Dict[str, int] = {
    "FLASH_MIN_SEQ": 1024,      # routing crossover: flash at/above this
    "FLASH_BLOCK_Q": 512,
    "FLASH_BLOCK_K": 512,
    "FLASH_BLOCK_K_LONG": 1024,  # KV tile once seq >= FLASH_LONG_SEQ
    "FLASH_LONG_SEQ": 8192,
    "SYNC_EVERY": 256,          # train-loop scan window / metrics cadence
}

# Per-generation tables: add entries as hardware gets measured (the
# benchmark harnesses print the winning values).  Anything missing falls
# back to the v5e numbers — a safe, conservative default since v5e is the
# smallest current chip.
_BY_DEVICE_KIND: Dict[str, Dict[str, int]] = {
    # "TPU v6e": {"FLASH_BLOCK_K_LONG": 2048, ...}  # example shape
}


def _device_kind() -> str:
    """Device kind WITHOUT initializing the backend: resolving a tuned
    constant (e.g. constructing a TrainLoopConfig at argparse time) must
    never lock in platform/topology before the caller has set JAX_PLATFORMS
    / XLA_FLAGS / jax.distributed.initialize.  Before backend init the
    per-kind tables simply don't apply and the v5e defaults hold.  Once
    a backend is up the kind is read from it, and a failure to read it
    raises: a chip that silently takes another chip's constants hides
    the device."""
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return ""
    return jax.devices()[0].device_kind


def tuned_file_path(device_kind: str | None = None) -> Path:
    """Where measured tuned constants live for ``device_kind`` (defaults
    to the current device).  ``TPUDIST_TUNED_FILE`` overrides the path
    wholesale (one file, any location — e.g. a sweep-scratch dir)."""
    env = os.environ.get("TPUDIST_TUNED_FILE")
    if env:
        return Path(env)
    kind = _device_kind() if device_kind is None else device_kind
    safe = kind.replace(" ", "_").replace("/", "_") or "unknown"
    return Path(__file__).resolve().parent.parent / "tuned" / f"{safe}.json"


_tuned_file_cache: Dict[str, tuple] = {}  # path -> (mtime_ns, parsed dict)


def _from_tuned_file(key: str):
    """Measured-constants file lookup — missing/invalid file is simply
    'no measurement recorded', never an error.  Parsed content is cached
    per (path, mtime): ``tuned()`` runs several times per layer at trace
    time, and re-reading the JSON each call would pay 40+ read/parse
    cycles per 8-layer compile (rewrites — e.g. the autotuner finishing
    mid-session — invalidate via mtime)."""
    path = tuned_file_path()
    try:
        mtime = path.stat().st_mtime_ns
    except OSError:
        return None
    cached = _tuned_file_cache.get(str(path))
    if cached is None or cached[0] != mtime:
        try:
            data = json.loads(path.read_text())
            if not isinstance(data, dict):
                data = {}
        except Exception:
            data = {}
        _tuned_file_cache[str(path)] = (mtime, data)
    else:
        data = cached[1]
    return data.get(key)


def tuned(name: str) -> int:
    """Resolve the tuned constant ``name`` (see ``_V5E_DEFAULTS`` keys):
    ``TPUDIST_<NAME>`` env var > autotuned file > device-kind table >
    v5e default."""
    key = name.upper()
    if key not in _V5E_DEFAULTS:
        raise KeyError(f"unknown tuned constant {name!r}; "
                       f"known: {sorted(_V5E_DEFAULTS)}")
    env = os.environ.get(f"TPUDIST_{key}")
    if env is not None:
        return int(env)
    measured = _from_tuned_file(key)
    if measured is not None:
        return int(measured)
    return _BY_DEVICE_KIND.get(_device_kind(), {}).get(
        key, _V5E_DEFAULTS[key])
