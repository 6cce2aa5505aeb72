"""Per-layer readers that need no trace: host-clock spans of the runner's
loop, program counters, and the rate the window measured.  A reader that
finds nothing to read returns ``None``."""

from __future__ import annotations

from cellbench import archs


def _ms_per_step(r, span: str):
    seconds = r.spans.get(span)
    if not seconds:
        return None
    return 1e3 * sum(seconds) / len(seconds)


def data_wait_ms_per_step(r):
    return _ms_per_step(r, "data_wait")


def dispatch_ms_per_step(r):
    return _ms_per_step(r, "dispatch")


def compiles_in_window(r):
    return r.counters.get("compiles_in_window")


def peak_hbm_gib(r):
    peak = r.counters.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None


def mfu_pct(r):
    """Model FLOPs per token (the architecture's own count, by the
    conventions of ``flops.py``: causal attention, recomputation not counted)
    x tokens/s/chip of the steps outside the capture, over the chip's peak."""
    rate = r.counters.get("tokens_per_s_per_chip")
    if not rate:
        return None
    per_token = archs.load(r.config).train_flops_per_token(
        r.config, r.counters["seq_len"])
    return 100.0 * per_token * rate / r.peak["bf16_flops_per_s"]
