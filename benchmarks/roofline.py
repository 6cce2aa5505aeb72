#!/usr/bin/env python3
"""Analytic roofline for the MFU-row configs — the no-hardware half of
"drive MFU ≥40% or prove the ceiling" (VERDICT r3 #2).

For each rung of the d1024 lever matrix (``RUNGS``) this computes, from
the model geometry alone:

- model FLOPs per step (``tpudist.utils.flops`` accounting);
- HBM bytes per step: parameter traffic (bf16 weights read in fwd AND
  bwd; f32 master params, grads, and both Adam moments read+written at
  the update) + activation traffic (every residual tensor written once
  in fwd and read once in bwd — or recomputed under remat, which moves
  the traffic to the recompute's reads);
- the resulting compute time at peak vs HBM time at peak bandwidth, and
  the MFU CEILING ``t_compute / max(t_compute, t_hbm)`` — what the chip
  allows if every matmul ran at peak and all traffic streamed at full
  bandwidth.

The point of the number: if the ceiling is ~1.0 (compute-bound) and the
measured MFU is far below it, the residual is schedulable work — kernel
quality, fusion, dispatch — NOT a bandwidth wall; the profile trace is
the tool that names it.  If the ceiling itself is low, the config is
bandwidth-bound and batch/remat are the levers.  Writes
``ROOFLINE_r{NN}.json`` (round auto-detected; r05 added the decode
rung) and prints one row per rung.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tpudist.utils.flops import (HBM_BYTES_PER_S as _HBM_BY_KIND,  # noqa: E402
                                 PEAK_BF16_FLOPS)

# This analysis is OF one chip, the v5e (public spec: 197 bf16 TFLOP/s,
# 819 GB/s HBM BW, 16 GiB HBM); its peaks come from the one table keyed
# by device kind (tpudist/utils/flops.py), not from literals here.
DEVICE_KIND = "TPU v5 lite"
HBM_BYTES_PER_S = _HBM_BY_KIND[DEVICE_KIND]
HBM_CAPACITY = 16 * 2 ** 30

GEOM = dict(seq_len=2048, d_model=1024, n_layers=8, d_ff=4096, vocab=256)

RUNGS = [  # (tag, batch, remat)
    ("b8", 8, False),
    ("b16", 16, False),
    ("b32", 32, False),
    ("b32_remat", 32, True),
    ("b64_remat", 64, True),
]


def param_count(*, d_model, n_layers, d_ff, vocab, seq_len, **_):
    """One accounting for the whole repo: the canonical formula lives in
    ``tpudist.utils.flops.transformer_param_count`` (this config's
    ``seq_len`` is the position-table ``max_len``)."""
    from tpudist.utils.flops import transformer_param_count

    return transformer_param_count(d_model=d_model, n_layers=n_layers,
                                   d_ff=d_ff, vocab=vocab, max_len=seq_len)


def activation_bytes(*, batch, seq_len, d_model, d_ff, n_layers, remat,
                     dtype_bytes=2, **_):
    """Residual tensors saved for backward, per step (write in fwd + read
    in bwd => x2 traffic).  Per block: the attention inputs/outputs and
    MLP intermediates that autodiff keeps ~ (6*d + 2*ff) values/token
    (q,k,v,attn-out,2 norms ~ 6d; two MLP intermediates ~ 2ff).  Under
    block remat only the block INPUT is saved (d values/token); the
    recompute re-reads weights instead (counted in weight traffic)."""
    tokens = batch * seq_len
    per_token = (d_model if remat
                 else 6 * d_model + 2 * d_ff)
    return 2 * tokens * per_token * n_layers * dtype_bytes


def weight_traffic_bytes(n_params, *, remat):
    """Per step: bf16 weights read by fwd + bwd (x3 with the remat
    re-forward), f32 grads written+read, f32 master read+written, two
    f32 Adam moments read+written."""
    fwd_bwd_reads = (3 if remat else 2) * 2 * n_params      # bf16
    optimizer = (2 + 2 + 4) * 4 * n_params                  # f32 r/w
    return fwd_bwd_reads + optimizer


def decode_row() -> dict:
    """Roofline for bench.py's ``lm_decode`` config (batch 8, prompt 16,
    +240 tokens, d512/L4/ff2048/V256, fp32) — decode streams weights +
    KV cache per token, so the ceiling is pure HBM bandwidth (the
    training rungs' compute-vs-bandwidth comparison collapses: decode
    compute time is negligible)."""
    from tpudist.utils.flops import decode_roofline

    cfg = dict(batch=8, prompt_len=16, max_new=240, d_model=512,
               n_layers=4, d_ff=2048, vocab=256)
    roof = decode_roofline(**cfg, param_bytes=4, cache_bytes=4,
                           hbm_bytes_per_s=HBM_BYTES_PER_S)
    return {"rung": "decode", "config": cfg, **roof,
            "bound": "bandwidth",
            "note": ("ceiling = batch / ((weight_bytes + avg KV bytes) / "
                     "HBM BW); measured lm_decode rows carry "
                     "pct_of_roofline against this")}


def paged_decode_row() -> dict:
    """Decode roofline on the PAGED serving cache: gather vs the Pallas
    paged-attention kernel (tpudist/ops/paged_attention.py), re-measured
    per the kernel PR.  Per emitted token at live-KV fraction ``f`` of
    ``max_len`` (per decoding lane; weights amortize over the batch):

    - **gather**: the dense-view path streams ``max_len × bytes/pos``
      regardless of cursors — bytes/token are FLAT in ``f`` (pool
      geometry is the denominator);
    - **kernel**: the in-kernel block-table walk streams
      ``ceil(f·max_len / block) × block × bytes/pos`` — bytes/token
      TRACK live KV.

    The serve_bench ``attn_kernel_twin`` rung applies the same per-path
    accounting to a real traffic mix (quantifying the gap at a measured
    occupancy); the independent verification of the kernel's DMA
    elision is an on-chip profile (DECODE_PROFILE's paged phases on
    TPU), not either model.  The HBM-time column converts bytes to a
    per-token floor at peak bandwidth — the ceiling the on-chip run
    decodes against."""
    cfg = dict(batch=8, d_model=512, n_layers=4, vocab=256,
               max_len=2048, kv_block=16, dtype_bytes=4)
    n_params = param_count(d_model=cfg["d_model"], n_layers=cfg["n_layers"],
                           d_ff=4 * cfg["d_model"], vocab=cfg["vocab"],
                           seq_len=cfg["max_len"])
    w_per_tok = n_params * cfg["dtype_bytes"] / cfg["batch"]
    kv_per_pos = 2 * cfg["n_layers"] * cfg["d_model"] * cfg["dtype_bytes"]
    bs = cfg["kv_block"]
    rows = []
    for f in (0.125, 0.25, 0.5, 1.0):
        live = int(f * cfg["max_len"])
        live_blocks = -(-live // bs) * bs
        gather_b = w_per_tok + cfg["max_len"] * kv_per_pos
        kernel_b = w_per_tok + live_blocks * kv_per_pos
        rows.append({
            "live_kv_fraction": f,
            "bytes_per_token_gather": int(gather_b),
            "bytes_per_token_kernel": int(kernel_b),
            "gather_over_kernel": round(gather_b / kernel_b, 3),
            "t_hbm_us_per_token_gather": round(
                gather_b / HBM_BYTES_PER_S * 1e6, 2),
            "t_hbm_us_per_token_kernel": round(
                kernel_b / HBM_BYTES_PER_S * 1e6, 2),
        })
    return {"rung": "paged_decode", "config": cfg, "bound": "bandwidth",
            "rows": rows,
            # the acceptance property, stated by the model itself:
            # kernel bytes/token are monotone in live KV, gather's flat
            "kernel_tracks_live_kv": all(
                rows[i]["bytes_per_token_kernel"]
                < rows[i + 1]["bytes_per_token_kernel"]
                for i in range(len(rows) - 1)),
            "gather_flat_in_occupancy": len(
                {r["bytes_per_token_gather"] for r in rows}) == 1,
            "note": ("bytes/token per decode path (analytic); serve_bench's "
                     "attn_kernel_twin applies the same accounting to real "
                     "traffic — on-chip DECODE_PROFILE is the independent "
                     "check of the DMA elision")}


def paged_prefill_row() -> dict:
    """Prefill roofline on the paged cache: the gather prefill vs the
    paged-prefill kernel (tpudist/ops/paged_prefill.py), per the kernel
    family PR.  Per PROMPT token when a chunk of ``P`` tokens lands on
    a lane whose cursor sits at live-KV fraction ``f`` of ``max_len``
    (the chunked-prefill steady state — each chunk after the first
    attends a committed prefix):

    - **gather**: the dense-view path streams the lane's full
      ``(1 + pad) × max_len`` geometry per dispatch and scatters the
      static pad span — KV bytes/prompt-token are FLAT in ``f``;
    - **kernel**: the in-kernel walk reads ``ceil(f·max_len / block)``
      blocks of prefix and WRITES only the ``ceil``-span of blocks the
      chunk covers — read bytes/prompt-token TRACK live KV and write
      bytes are chunk-proportional.

    ``SlotEngine._prefill_kv_bytes`` applies the same per-path model to
    real traffic (serve_bench's ``kernel_family_twin`` rung quotes it);
    the independent check of the in-kernel write DMA is an on-chip
    profile, not either model."""
    cfg = dict(d_model=512, n_layers=4, max_len=2048, kv_block=16,
               prefill_pad=64, dtype_bytes=4)
    kv_per_pos = 2 * cfg["n_layers"] * cfg["d_model"] * cfg["dtype_bytes"]
    bs, P = cfg["kv_block"], cfg["prefill_pad"]
    rows = []
    for f in (0.125, 0.25, 0.5, 0.875):
        live = int(f * cfg["max_len"])
        prefix_blocks = -(-live // bs) * bs
        chunk_blocks = (-(-(live + P) // bs) - live // bs) * bs
        gather_r = (1 + P) * cfg["max_len"] * kv_per_pos / P
        gather_w = P * kv_per_pos / P  # static pad span ≈ the chunk
        kernel_r = prefix_blocks * kv_per_pos / P
        kernel_w = chunk_blocks * kv_per_pos / P
        rows.append({
            "live_kv_fraction": f,
            "read_bytes_per_prompt_token_gather": int(gather_r),
            "read_bytes_per_prompt_token_kernel": int(kernel_r),
            "write_bytes_per_prompt_token_kernel": int(kernel_w),
            "write_bytes_per_prompt_token_gather": int(gather_w),
            "gather_over_kernel_read": round(gather_r / kernel_r, 3),
            "t_hbm_us_per_prompt_token_gather": round(
                (gather_r + gather_w) / HBM_BYTES_PER_S * 1e6, 2),
            "t_hbm_us_per_prompt_token_kernel": round(
                (kernel_r + kernel_w) / HBM_BYTES_PER_S * 1e6, 2),
        })
    return {"rung": "paged_prefill", "config": cfg, "bound": "bandwidth",
            "rows": rows,
            # the acceptance property: kernel prefill reads are monotone
            # in live KV (they track the walked prefix), gather's flat
            "kernel_tracks_live_kv": all(
                rows[i]["read_bytes_per_prompt_token_kernel"]
                < rows[i + 1]["read_bytes_per_prompt_token_kernel"]
                for i in range(len(rows) - 1)),
            "gather_flat_in_occupancy": len(
                {r["read_bytes_per_prompt_token_gather"]
                 for r in rows}) == 1,
            "kernel_below_gather_everywhere": all(
                r["read_bytes_per_prompt_token_kernel"]
                < r["read_bytes_per_prompt_token_gather"] for r in rows),
            "note": ("KV bytes per prompt token per prefill path "
                     "(analytic); serve_bench's kernel_family_twin "
                     "applies the engine's accounting to real traffic")}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="artifact path (default ROOFLINE_r{NN}.json at "
                         "the repo root, round auto-detected)")
    args = ap.parse_args(argv)
    from tpudist.utils.flops import transformer_train_flops

    peak = PEAK_BF16_FLOPS[DEVICE_KIND]
    n_params = param_count(**GEOM)
    rows = []
    for tag, batch, remat in RUNGS:
        flops = transformer_train_flops(batch=batch, **GEOM)
        if remat:  # one extra forward of the block stack
            flops = flops * 4 / 3
        act_b = activation_bytes(batch=batch, remat=remat, **GEOM)
        w_b = weight_traffic_bytes(n_params, remat=remat)
        t_c = flops / peak
        t_h = (act_b + w_b) / HBM_BYTES_PER_S
        # Peak live memory sanity: f32 master+grads+moments + bf16 copy
        # + saved activations (absolute lower bound).
        mem = n_params * (4 * 4 + 2) + act_b / 2
        rows.append({
            "rung": tag, "batch": batch, "remat": remat,
            "model_flops_per_step": flops,
            "hbm_bytes_per_step": int(act_b + w_b),
            "t_compute_ms_at_peak": round(t_c * 1e3, 2),
            "t_hbm_ms_at_peak_bw": round(t_h * 1e3, 2),
            "mfu_ceiling": round(t_c / max(t_c, t_h), 4),
            "bound": "compute" if t_c >= t_h else "bandwidth",
            "est_min_live_bytes": int(mem),
            "fits_hbm": mem < HBM_CAPACITY * 0.9,
        })
        print(json.dumps(rows[-1]), flush=True)
    rows.append(decode_row())
    print(json.dumps(rows[-1]), flush=True)
    rows.append(paged_decode_row())
    print(json.dumps(rows[-1]), flush=True)
    rows.append(paged_prefill_row())
    print(json.dumps(rows[-1]), flush=True)
    from benchmarks._round import current_round  # REPO is on sys.path

    out = {"geometry": GEOM, "n_params": n_params,
           "peak_bf16_flops": peak, "hbm_bytes_per_s": HBM_BYTES_PER_S,
           "accounting": "see module docstring", "rows": rows}
    out_path = (Path(args.out) if args.out
                else REPO / f"ROOFLINE_r{current_round():02d}.json")
    out_path.write_text(json.dumps(out, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
