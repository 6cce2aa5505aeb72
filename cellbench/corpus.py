"""The one generator of training traffic: a seeded synthetic token corpus
written as the flat ``uint16`` stream the program's loader memory-maps.

A cell file's ``corpus`` object holds the parameters; a new mix is a new
data file, not new code.  ``kind``:

- ``increment_chains``: every window of ``seq_len`` tokens starts at a
  seeded random token and counts up by ``stride`` modulo the vocabulary
  (``chip_smoke.py``'s chains widened to a real vocabulary).  All windows
  differ, every token after a window's first is predictable, so the loss
  can fall within a short run.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np


def make_corpus(params: dict, *, vocab: int, seq_len: int,
                seed: int) -> np.ndarray:
    """``[windows * seq_len] uint16`` tokens for one cell and seed."""
    if vocab > np.iinfo(np.uint16).max + 1:
        raise ValueError(f"vocab {vocab} does not fit the uint16 stream")
    kind = params["kind"]
    if kind != "increment_chains":
        raise ValueError(f"unknown corpus kind {kind!r}")
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, vocab, (params["windows"], 1), dtype=np.int64)
    steps = np.arange(seq_len, dtype=np.int64) * params.get("stride", 1)
    return ((starts + steps[None]) % vocab).astype(np.uint16).reshape(-1)


def write_corpus(path: Path, params: dict, *, vocab: int, seq_len: int,
                 seed: int) -> Path:
    make_corpus(params, vocab=vocab, seq_len=seq_len, seed=seed).tofile(path)
    return path
