"""Per-layer readers over the device trace (``trace_reduce.reduce_trace``):
each takes the whole steps of the capture on every chip."""

from __future__ import annotations

from cellbench import flops


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


def attn_kernel_ms_per_step(r):
    """Device time of the Mosaic custom calls per step, mean over chips
    (today every custom call of the train step is a flash kernel)."""
    per_chip = [d.custom_call_ns / d.steps / 1e6 for d in r.reds.values()
                if d.custom_call_ns > 0]
    return _mean(per_chip)


def attn_kernel_roofline(r):
    """The least time one chip could take for the FLOPs and bytes the
    attention algorithm needs for its rows of a step, over the time its
    kernels took.  At head_dim 128 and 2048 positions the compute bound
    applies (the print of a run says which)."""
    ms = attn_kernel_ms_per_step(r)
    if not ms:
        return None
    c = r.config
    shape = dict(batch=r.counters["per_chip_batch"],
                 seq=r.counters["seq_len"], d_model=c["n_embd"],
                 n_layers=c["n_layer"])
    least, bound = flops.roofline_seconds(
        flops.flash_train_flops(**shape), flops.flash_train_bytes(**shape),
        r.peak)
    print(f"[reader] attn_kernel_roofline bound={bound} "
          f"least_ms={least * 1e3:.4f} kernel_ms={ms:.4f}", flush=True)
    return 100.0 * least * 1e3 / ms


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
