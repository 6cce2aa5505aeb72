"""Per-layer readers over the device trace (``trace_reduce.reduce_trace``):
each takes the whole steps of the capture on every chip.  A reader that
finds nothing to read (no trace, a step without a Mosaic custom call)
returns ``None``.  Attention's readers go by the kernels' names, the
yardstick's own copy of them (``flops.FLASH_KERNELS``); a step whose custom
calls do not carry the names that are counted for it is an error, not a
smaller number (:func:`_ran`)."""

from __future__ import annotations

from cellbench import archs, flops
from cellbench.flops import (FLASH_BWD_DKV, FLASH_BWD_DQ, FLASH_FWD,
                             FLASH_KERNELS)


def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def device_idle_pct(r):
    """1 - union of device-op intervals / captured whole steps, worst chip."""
    if not r.reds:
        return None
    return max(100.0 * (1.0 - d.busy_ns / d.window_ns)
               for d in r.reds.values())


def host_gap_ms_max(r):
    """The longest idle gap on any chip inside the captured steps."""
    gaps = [d.gaps[0] for d in r.reds.values() if d.gaps]
    if not gaps:
        return None
    return max(e - s for s, e in gaps) / 1e6


def _work(r) -> dict:
    """``kernel name -> (operations, bytes)`` on one chip in one step, by the
    configuration's architecture; ``{}`` for a reading without one."""
    if not r.config.get("model_type"):
        return {}
    return archs.load(r.config).kernel_work(
        r.config, r.counters["per_chip_batch"], r.counters["seq_len"])


def _ran(r, kernels, work: dict) -> list:
    """Those of ``kernels`` that ran on some chip of the traced steps.

    The names are the yardstick's, so a program that renames, fuses or
    un-names a kernel must not thereby shrink what is read as attention.
    Where a chip's step holds Mosaic custom calls it is an error if none of
    them carries a name the yardstick knows (attention's, or one the
    architecture counts), or if one of ``kernels`` that the architecture
    counts for every step did not run.  Nothing ran and nothing is wrong
    where the step holds no custom call at all."""
    known = set(FLASH_KERNELS) | set(work)
    ran = set()
    for chip, d in r.reds.items():
        if d.custom_call_ns <= 0:
            continue
        seen = set(d.kernel_ns)
        if not seen & known:
            raise LookupError(
                f"chip {chip}: the traced steps hold Mosaic custom calls and "
                f"none carries a kernel name the yardstick knows "
                f"({sorted(known)}); names seen: {sorted(seen) or 'none'}")
        missing = [k for k in kernels if k in work and k not in seen]
        if missing:
            raise LookupError(
                f"chip {chip}: the architecture counts {missing} in every "
                f"step, and no custom call of the traced steps carries that "
                f"name; names seen: {sorted(seen)}")
        ran |= seen
    return [k for k in kernels if k in ran]


def _kernel_ms(r, kernels):
    """Device time per step of the Mosaic custom calls that carry one of the
    names ``kernels``, mean over the chips that ran one."""
    per_chip = []
    for d in r.reds.values():
        ns = sum(d.kernel_ns.get(k, 0.0) for k in kernels)
        if ns > 0:
            per_chip.append(ns / d.steps / 1e6)
    return _mean(per_chip)


def _kernel_roofline(r, kernels, label: str):
    """The least time one chip could take for the operations and bytes the
    algorithms of ``kernels`` need for its rows of a step (the
    architecture's own count, ``archs/<model_type>.py::kernel_work``), over
    the time those kernels took; the print of a run says which bound
    applies.  Work and time are taken over the same kernels, those that ran
    and have a count: a share over part of the work would read too low, one
    over part of the time too high."""
    if not r.reds:
        return None
    work = _work(r)
    counted = [k for k in _ran(r, kernels, work) if k in work]
    if not counted:
        return None
    ms = _kernel_ms(r, counted)
    least, bound = flops.roofline_seconds(
        sum(work[k][0] for k in counted), sum(work[k][1] for k in counted),
        r.peak)
    print(f"[reader] {label} kernels={','.join(counted)} bound={bound} "
          f"least_ms={least * 1e3:.4f} kernel_ms={ms:.4f}", flush=True)
    return 100.0 * least * 1e3 / ms


def attn_kernel_ms_per_step(r):
    """Device time per step of attention's kernels, by their names
    (``flops.FLASH_KERNELS``); a custom call of another kernel is not
    attention's."""
    if not r.reds:
        return None
    return _kernel_ms(r, _ran(r, FLASH_KERNELS, _work(r)))


def attn_kernel_roofline(r):
    """Attention's kernels together.  At head_dim 128 and 2048 positions
    the compute bound applies."""
    return _kernel_roofline(r, FLASH_KERNELS, "attn_kernel_roofline")


def flash_fwd_roofline(r):
    return _kernel_roofline(r, (FLASH_FWD,), "flash_fwd_roofline")


def flash_bwd_dq_roofline(r):
    return _kernel_roofline(r, (FLASH_BWD_DQ,), "flash_bwd_dq_roofline")


def flash_bwd_dkv_roofline(r):
    return _kernel_roofline(r, (FLASH_BWD_DKV,), "flash_bwd_dkv_roofline")


def collective_ms_per_step(r):
    per_chip = [d.collective_ns / d.steps / 1e6 for d in r.reds.values()
                if d.collective_ns > 0]
    return _mean(per_chip)


def collective_exposed_pct(r):
    """Share of the step in which a collective runs on a chip and no
    compute operation does, mean over chips."""
    per_chip = [100.0 * d.collective_exposed_ns / d.window_ns
                for d in r.reds.values() if d.collective_ns > 0]
    return _mean(per_chip)
