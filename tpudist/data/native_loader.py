"""ctypes bindings + prefetching loader over the native gather engine.

The TPU-native answer to ``DataLoader(num_workers=N)`` (``demo.py:150``;
the reference's host parallelism is torch's C++ worker pool — external
native code per SURVEY.md §2.4).  Split of responsibilities:

- **Python owns determinism**: batch order comes from the exact same
  seeded :class:`~tpudist.data.sharding.ShardPlan` permutation as the
  synchronous loader — the native path changes WHEN bytes move, never
  WHICH rows are chosen (tests assert batch-for-batch equality).
- **C++ owns the bytes**: ``gather.cpp``'s thread pool copies dataset rows
  into a ring of preallocated batch buffers up to ``prefetch_depth``
  batches ahead, overlapping host assembly with device steps.

The library is compiled lazily with g++ into a per-user cache dir (no
pip/build-system involvement — the environment bakes the toolchain) and
everything degrades to the synchronous numpy path when a compiler or the
.so is unavailable, so the native path is a pure accelerator, never a
dependency.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import subprocess
import tempfile
from pathlib import Path
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np

from tpudist.data.loader import ShardedLoader
from tpudist.data.sharding import epoch_indices

_SRC = Path(__file__).parent / "native" / "gather.cpp"
_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False
_LIB_ERROR = ""  # why the build/load failed — shown once by make_loader


def _cache_dir() -> Path:
    """Where the built library lives: ``TPUDIST_CACHE`` if set, else a
    ``build/`` directory beside the source (git-ignored) — the program
    writes nothing around its checkout."""
    p = Path(os.environ.get("TPUDIST_CACHE") or _SRC.parent / "build")
    p.mkdir(parents=True, exist_ok=True)
    return p


def _build_library() -> Optional[Path]:
    """Compile gather.cpp (cached by source hash); None if no toolchain."""
    src = _SRC.read_bytes()
    tag = hashlib.sha256(src).hexdigest()[:16]
    out = _cache_dir() / f"libtpugather-{tag}.so"
    if out.exists():
        return out
    # Build into a sibling temp dir so the final rename is same-filesystem
    # (a /tmp staging dir would make os.replace raise EXDEV on the common
    # tmpfs-/tmp + on-disk-~/.cache split).
    with tempfile.TemporaryDirectory(dir=out.parent) as td:
        tmp_out = Path(td) / out.name
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread",
               str(_SRC), "-o", str(tmp_out)]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp_out, out)  # atomic: concurrent builders are safe
        except (OSError, subprocess.SubprocessError) as e:
            global _LIB_ERROR
            _LIB_ERROR = f"{' '.join(cmd[:2])} ...: {e}"
            return None
    return out


def load_library() -> Optional[ctypes.CDLL]:
    """The process-wide gather library, built on first use; None on failure."""
    global _LIB, _LIB_TRIED
    if _LIB_TRIED:
        return _LIB
    _LIB_TRIED = True
    path = _build_library()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(str(path))
    except OSError as e:
        global _LIB_ERROR
        _LIB_ERROR = f"loading {path}: {e}"
        return None
    lib.tg_create.restype = ctypes.c_void_p
    lib.tg_create.argtypes = [ctypes.c_int]
    lib.tg_submit.restype = ctypes.c_int64
    lib.tg_submit.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                              ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
    lib.tg_wait.restype = ctypes.c_int
    lib.tg_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.tg_poll.restype = ctypes.c_int
    lib.tg_poll.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.tg_destroy.restype = None
    lib.tg_destroy.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return _LIB


def native_available() -> bool:
    return load_library() is not None


class GatherPool:
    """Thin RAII wrapper over the C thread pool."""

    def __init__(self, num_workers: int):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native gather library unavailable (no g++?)")
        self._lib = lib
        self._handle = ctypes.c_void_p(lib.tg_create(num_workers))

    def submit(self, src: np.ndarray, idx: np.ndarray, dst: np.ndarray) -> int:
        """Enqueue ``dst[i] = src[idx[i]]``.  All arrays must be C-contiguous
        and stay alive (and ``dst`` unread) until :meth:`wait` returns."""
        if self._handle is None:
            raise RuntimeError("GatherPool is closed")
        assert src.flags.c_contiguous and dst.flags.c_contiguous
        assert idx.dtype == np.int64 and idx.flags.c_contiguous
        row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
        return self._lib.tg_submit(
            self._handle,
            src.ctypes.data_as(ctypes.c_void_p), row_bytes,
            idx.ctypes.data_as(ctypes.c_void_p), len(idx),
            dst.ctypes.data_as(ctypes.c_void_p),
        )

    def wait(self, job: int) -> None:
        # After close() every worker has joined, so nothing is running and
        # waiting on a freed pool would be a use-after-free — no-op instead.
        if self._handle is None:
            return
        self._lib.tg_wait(self._handle, job)

    def close(self) -> None:
        if self._handle:
            self._lib.tg_destroy(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover - interpreter-shutdown best effort
        try:
            self.close()
        except Exception:
            pass


class PrefetchingLoader(ShardedLoader):
    """ShardedLoader with native background batch assembly.

    Yields the same ``(x, y)`` batches in the same order as the synchronous
    loader.  Batch assembly happens in a ring of ``prefetch_depth + 1``
    reused buffers sized so the batch being materialized is never
    concurrently written; the yielded arrays are **copies** of the ring
    slot, upholding ShardedLoader's contract of independent batches.  (A
    zero-copy yield would alias a slot the C++ pool later overwrites —
    JAX's CPU client can do zero-copy ``device_put`` on aligned numpy
    arrays, which would silently corrupt training data on CPU runs.)
    """

    def __init__(self, dataset, batch_size, plan, *, num_workers: int = 2,
                 prefetch_depth: int = 4):
        super().__init__(dataset, batch_size, plan)
        self.num_workers = max(1, num_workers)
        self.prefetch_depth = max(1, prefetch_depth)
        self._pool = GatherPool(self.num_workers)
        self._fields: Sequence[np.ndarray] = [
            np.ascontiguousarray(dataset.x), np.ascontiguousarray(dataset.y)
        ]
        # depth+1 slots: batch i+depth (submitted while yielding batch i)
        # lands in the slot of batch i-1, never batch i's.
        self._slots = [
            tuple(np.empty((batch_size,) + f.shape[1:], f.dtype)
                  for f in self._fields)
            for _ in range(self.prefetch_depth + 1)
        ]

    def iter_from(self, skip_batches: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        idx_all = epoch_indices(self.plan, self._epoch).astype(np.int64)
        starts = list(range(skip_batches * self.batch_size, len(idx_all),
                            self.batch_size))
        if self.plan.drop_last:
            starts = [s for s in starts if s + self.batch_size <= len(idx_all)]

        # (jobs, idx_slice, slot, n_valid) per in-flight batch, FIFO order.
        inflight: list = []

        def submit(batch_i: int) -> None:
            start = starts[batch_i]
            sel = idx_all[start:start + self.batch_size]
            slot = self._slots[batch_i % (self.prefetch_depth + 1)]
            jobs = [
                self._pool.submit(f, sel, dst[: len(sel)])
                for f, dst in zip(self._fields, slot)
            ]
            inflight.append((jobs, sel, slot, len(sel)))

        try:
            for i in range(min(self.prefetch_depth, len(starts))):
                submit(i)
            for i in range(len(starts)):
                jobs, _sel, slot, n = inflight.pop(0)
                for j in jobs:
                    self._pool.wait(j)
                out = tuple(dst[:n].copy() for dst in slot)
                nxt = i + self.prefetch_depth
                if nxt < len(starts):
                    submit(nxt)
                yield out
        finally:
            # Abandoned mid-epoch (break / exception / GeneratorExit): the
            # C++ workers hold raw pointers into idx_all and the slots —
            # drain every in-flight job before this frame (and those
            # buffers) can be freed.
            for jobs, _sel, _slot, _n in inflight:
                for j in jobs:
                    self._pool.wait(j)

    def close(self) -> None:
        self._pool.close()


def make_loader(dataset, batch_size, plan, *, num_workers: int = 0,
                prefetch_depth: int = 4) -> ShardedLoader:
    """Loader factory honoring the reference's ``--num_workers`` semantics:
    0 → synchronous; >0 → native prefetching pool when buildable, else
    the synchronous loader (the flag is a performance hint, never a
    correctness requirement) — said once on the rank-0 log, with the
    reason."""
    if num_workers > 0 and native_available():
        return PrefetchingLoader(dataset, batch_size, plan,
                                 num_workers=num_workers,
                                 prefetch_depth=prefetch_depth)
    if num_workers > 0:
        _warn_no_native(num_workers)
    return ShardedLoader(dataset, batch_size, plan)


@functools.lru_cache(maxsize=None)
def _warn_no_native(num_workers: int) -> None:
    from tpudist.runtime.rank_logging import rank_print

    rank_print(f"[tpudist.data] --num_workers {num_workers}: the native "
               f"prefetch loader is unavailable ({_LIB_ERROR}); batches are "
               "assembled synchronously")
