"""The comparison that decides ``correct`` for a training cell.

The timed step, driven from the seed through its first steps, gives its
readings (losses, the first gradient's norms and projections, the norms of
the parameters' change); the plain reference (``cellbench/reference.py``)
follows the same steps on the same batches and gives the same.  Compared are

- ``loss_gap``: the widest relative gap of a step's loss;
- ``grad_norm_gap``: the first gradient as the optimizer got it, by the
  worst tensor: the gap between the program's norm and the reference's
  (not the norm of their difference), over the reference's norm of that
  tensor or of the median tensor, whichever is larger;
- ``update_norm_gap``: the same for the parameters' change after the steps;
- ``grad_dir_gap``: how far the first gradient itself is off, by the worst
  tensor.  Rounding noise all but cancels in a norm, so the norms above
  cannot tell bf16 from 8 bits; the root mean square of the gaps of a few
  fixed +-1 projections (``reference.sign_projections``) has the size of the
  norm of the difference, and needs neither side to hold the other's
  gradient.  Over the same scale as ``grad_norm_gap``.  It is the number
  the lower-precision control has to fail;
- ``update_dir_gap``: the same projections of the parameters' change, over
  the scale of ``update_norm_gap``.  An update of the right size in a wrong
  direction (a flipped sign reads 2) keeps its norm and fails here.

Each has a limit of its own in the cell file (``check.limits``), set from
readings on the chip that ``PERF.md`` records.
"""

from __future__ import annotations

import math

import numpy as np

GAPS = ("loss_gap", "grad_norm_gap", "update_norm_gap", "grad_dir_gap",
        "update_dir_gap")


def worst_leaf_gap(got, ref) -> tuple:
    """``(gap, index)`` of the tensor whose norm is farthest off."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    scale = np.maximum(ref, np.median(ref))
    gaps = np.abs(got - ref) / scale
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def direction_gaps(got_proj, ref_proj, ref_norms) -> np.ndarray:
    """Per tensor: rms over the projections of ``got - ref``, over the
    reference's norm of that tensor or of the median tensor."""
    diff = np.asarray(got_proj, np.float64) - np.asarray(ref_proj, np.float64)
    ref = np.asarray(ref_norms, np.float64)
    gaps = np.sqrt(np.mean(diff ** 2, axis=1)) / np.maximum(ref,
                                                            np.median(ref))
    return np.where(np.isfinite(gaps), gaps, np.inf)


def train_gaps(program: dict, reference: dict) -> dict:
    """The numbers compared, from two ``dict(losses, grad_norms, grad_proj,
    update_norms, update_proj)``."""
    steps = min(len(program["losses"]), len(reference["losses"]))
    lp = np.asarray(program["losses"][:steps], np.float64)
    lr = np.asarray(reference["losses"][:steps], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    grad, _ = worst_leaf_gap(program["grad_norms"], reference["grad_norms"])
    upd, _ = worst_leaf_gap(program["update_norms"],
                            reference["update_norms"])
    direction = direction_gaps(program["grad_proj"], reference["grad_proj"],
                               reference["grad_norms"])
    moved = direction_gaps(program["update_proj"], reference["update_proj"],
                           reference["update_norms"])
    return {"loss_gap": loss_gap if math.isfinite(loss_gap) else math.inf,
            "grad_norm_gap": grad, "update_norm_gap": upd,
            "grad_dir_gap": float(direction.max()),
            "update_dir_gap": float(moved.max())}


def judge(gaps: dict, limits: dict) -> tuple:
    """``(all within, [line per number compared])``."""
    lines, ok = [], True
    for name in GAPS:
        within = gaps[name] <= limits[name]
        ok = ok and within
        lines.append(f"[check] {name}={gaps[name]:.6g} limit={limits[name]:g}"
                     f" {'ok' if within else 'OVER'}")
    return ok, lines
