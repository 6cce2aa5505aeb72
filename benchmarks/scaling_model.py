#!/usr/bin/env python3
"""Analytic multi-chip scaling model — the numbers half of the no-pod
scaling story (VERDICT r3 weak #2; the structure half is
``benchmarks/comm_audit.py``).

The compile-time collective audit proves WHAT moves per step (one
combined gradient all-reduce of exactly param+loss bytes under DP, ring
permutes of one KV shard per hop under sp, …).  This model combines
those audited byte volumes with measured single-chip step times
(``BENCH_EXTENDED.json``) and stated link-bandwidth assumptions
(:mod:`tpudist.utils.flops`) to produce falsifiable predictions:

- DP efficiency vs chip count, with and without compute/communication
  overlap (XLA overlaps the grad all-reduce with the backward; the
  no-overlap row is the hard floor);
- the spec-independent inverse: the per-chip wire bandwidth REQUIRED
  for the >=80% DP-scaling north star (``BASELINE.json``) at each n —
  robust to uncertainty in the assumed link numbers;
- ring-attention sp: per-hop communication vs per-hop compute ratio
  (the ring overlaps hops with block compute; ratio < 1 means the ICI
  hop fully hides).

Model (ring all-reduce over one mesh axis): per-chip wire bytes
``2(n-1)/n x payload``, transferred concurrently on the ring's links, so
``t_comm = wire / link_bw``; with overlap the exposed time is
``max(0, t_comm - t_bwd)`` with ``t_bwd ~ 2/3 t_step`` (the backward is
2/3 of the 3x-forward train step and is where XLA schedules the grad
reduce-scatter/all-reduce).

Writes ``SCALING_MODEL_r{NN}.json`` (round auto-detected).  Every input is recorded in the
artifact so the prediction is checkable the day a pod exists.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _force_cpu() -> None:
    """Pure-analytic script: ``eval_shape`` needs no accelerator, so it
    stays on the CPU and never claims a chip."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def _param_bytes_lm(*, d_model, n_layers, n_heads, d_ff, vocab, seq_len):
    """Parameter bytes of the bench TransformerLM via eval_shape (no
    materialization — fine for the d1024 config on CPU)."""
    import jax

    from tpudist.models import create_transformer
    from tpudist.utils.hlo_audit import tree_bytes

    def init():
        _, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=seq_len, vocab=vocab,
            d_model=d_model, n_layers=n_layers, n_heads=n_heads, d_ff=d_ff,
            max_len=seq_len)
        return params

    shapes = jax.eval_shape(init)
    return tree_bytes(shapes)


# The toy DP per-step collective payload: 2 models x 371 f32 param-grads
# + 2 f32 loss scalars.  tests/test_comm_audit.py asserts the compiled
# HLO's all-reduce total equals exactly this constant.
TOY_GRAD_BYTES = 2 * 371 * 4 + 2 * 4


def dp_rows(name, *, grad_bytes, step_s, link_bw, target=0.8,
            ns=(2, 4, 8, 16, 64, 256)):
    """Efficiency vs n for a DP regime whose audited per-step payload is
    ``grad_bytes`` (f32 grads + loss scalars; the audit pins this)."""
    t_bwd = step_s * 2.0 / 3.0
    rows = []
    for n in ns:
        wire = 2 * (n - 1) / n * grad_bytes
        t_comm = wire / link_bw
        exposed = max(0.0, t_comm - t_bwd)
        rows.append({
            "n_chips": n,
            "wire_bytes_per_chip": int(wire),
            "t_comm_ms": round(t_comm * 1e3, 4),
            "efficiency_no_overlap": round(step_s / (step_s + t_comm), 4),
            "efficiency_overlap": round(step_s / (step_s + exposed), 4),
            # Spec-independent: bandwidth needed for `target` efficiency
            # with NO overlap (the conservative requirement).
            "bw_needed_for_target_GBps": round(
                wire / (step_s * (1 - target) / target) / 1e9, 3),
        })
    return {"regime": name, "grad_bytes": int(grad_bytes),
            "step_ms_single_chip": round(step_s * 1e3, 3),
            "assumed_link_bw_GBps": round(link_bw / 1e9, 1),
            "target_efficiency": target, "rows": rows}


def ring_sp_row(*, name, batch, heads, seq, head_dim, ring, link_bw,
                peak_flops, mfu_measured, dtype_bytes=2, kv_heads=None):
    """Ring attention over `ring` chips: per-hop KV bytes vs per-hop
    compute.  The audit pins the payload (one KV shard per hop per
    tensor); the per-hop compute is the flash block attention over one
    shard, estimated from measured MFU.  Only the attention geometry
    (batch·heads·shard·head_dim) and achieved FLOPs drive this — the
    rest of the model never rides the ring."""
    shard = seq // ring
    # GQA: the ring hops the small kv-headed tensors (ring bodies are
    # GQA-native — broadcast happens post-hop), so the wire scales with
    # kv_heads while compute still scales with query heads.
    kv_hop_bytes = (2 * batch * (kv_heads or heads) * shard * head_dim
                    * dtype_bytes)
    # Per-hop attention FLOPs (fwd): one [shard x shard] block of the
    # score+value matmuls for every query shard position.
    hop_flops = 4.0 * batch * heads * shard * shard * head_dim
    achieved = peak_flops * mfu_measured
    t_hop_compute = hop_flops / achieved
    t_hop_comm = kv_hop_bytes / link_bw
    return {
        "regime": name, "ring": ring, "seq": seq, "seq_shard": shard,
        "kv_hop_bytes": int(kv_hop_bytes),
        "t_hop_comm_us": round(t_hop_comm * 1e6, 2),
        "t_hop_compute_us": round(t_hop_compute * 1e6, 2),
        "comm_over_compute": round(t_hop_comm / t_hop_compute, 4),
        "hides_under_compute": t_hop_comm < t_hop_compute,
        "assumptions": {
            "achieved_flops": achieved, "mfu_measured": mfu_measured,
            "link_bw_GBps": round(link_bw / 1e9, 1),
            "dtype_bytes": dtype_bytes},
    }


def ring_causal_balance_row(ring: int) -> dict:
    """Schedule FLOP efficiency of the causal ring, contiguous vs zigzag.

    Hops are ppermute-lockstepped, so a hop lasts one block-compute
    whenever ANY device is live.  Contiguous layout: hop ``t`` keeps
    ``n−t`` devices live → useful/executed = (n+1)/2n → ½ as n grows.
    Zigzag (``zigzag_indices`` layout, tests assert the per-hop balance):
    every device executes 2 half-blocks per hop (+1 at the diagonal hop,
    whose two triangular blocks run as fulls) → 2n/(2n+1) → 1.  Pure
    schedule math — no bandwidth assumptions; the comm side is identical
    to the contiguous ring (same payload, same hop count)."""
    n = ring
    contiguous = (n + 1) / (2 * n)
    zigzag = (2 * n) / (2 * n + 1)
    return {"ring": n,
            "contiguous_schedule_efficiency": round(contiguous, 4),
            "zigzag_schedule_efficiency": round(zigzag, 4),
            "zigzag_speedup": round(zigzag / contiguous, 3)}


def main() -> int:
    _force_cpu()
    from tpudist.utils.flops import (
        DCN_HOST_BYTES_PER_S,
        ICI_LINK_BYTES_PER_S,
        PEAK_BF16_FLOPS,
    )

    # Measured single-chip inputs: the last on-chip record
    # (BENCH_EXTENDED.json, round 2).
    # The spec lookups key off the RECORDED device kind so re-freezing on
    # a different generation can never pair its step times with another
    # chip's link/peak numbers.
    ext = json.loads((REPO / "BENCH_EXTENDED.json").read_text())
    kind = ext.get("device_kind", "TPU v5 lite")
    if kind not in ICI_LINK_BYTES_PER_S or kind not in PEAK_BF16_FLOPS:
        raise SystemExit(
            f"no link/peak specs for recorded device kind {kind!r} — add "
            f"them to tpudist/utils/flops.py before modeling")
    link_bw = ICI_LINK_BYTES_PER_S[kind]
    peak = PEAK_BF16_FLOPS[kind]

    def step_s(key):
        row = ext.get(key) or {}
        ms = row.get("step_ms")
        return ms / 1e3 if ms else None

    out = {
        "inputs": {
            "device_kind": kind,
            "assumed_ici_link_GBps": link_bw / 1e9,
            "assumed_dcn_host_GBps": DCN_HOST_BYTES_PER_S / 1e9,
            "peak_bf16_tflops": peak / 1e12,
            "measured_from": "BENCH_EXTENDED.json",
            "audited_by": (max((p.name for p in
                                REPO.glob("COMM_AUDIT_r*.json")),
                               default="COMM_AUDIT (none found)")),
        },
        "dp": [],
        "sp_ring": [],
    }

    # --- DP regimes ------------------------------------------------------
    # Toy (the reference workload, demo.py): 2 models x 371 params, f32
    # grads + 2 loss scalars — exactly the audit's all-reduce payload.
    toy = ext.get("toy", {})
    if toy.get("value"):
        # batch 256/chip at the measured rate -> per-step seconds.
        t = 256.0 / toy["value"]
        out["dp"].append(dp_rows("toy_dp_batch256",
                                 grad_bytes=TOY_GRAD_BYTES,
                                 step_s=t, link_bw=link_bw))

    for key, cfg in (
        ("lm_dense_bf16", dict(d_model=512, n_layers=4, n_heads=8,
                               d_ff=2048, vocab=256, seq_len=2048)),
        ("lm_mfu_d1024", dict(d_model=1024, n_layers=8, n_heads=8,
                              d_ff=4096, vocab=256, seq_len=2048)),
    ):
        t = step_s(key)
        if t is None:
            continue
        pb = _param_bytes_lm(**cfg)
        out["dp"].append(dp_rows(
            f"{key}_dp", grad_bytes=pb + 4, step_s=t, link_bw=link_bw))
        # Same regime with the data axis over DCN (hybrid mesh, one ring
        # hop per host): per-HOST bandwidth, conservative 1 chip/host...
        # real pods amortize over 4-8 chips/host; recorded as the floor.
        out["dp"].append(dp_rows(
            f"{key}_dp_dcn_floor", grad_bytes=pb + 4, step_s=t,
            link_bw=DCN_HOST_BYTES_PER_S))
        # grad_reduce_dtype=bf16 (tpudist/train/lm.py compressed path,
        # audited in COMM_AUDIT dp_bf16_reduce): grads ride at 2 bytes.
        out["dp"].append(dp_rows(
            f"{key}_dp_dcn_bf16_reduce", grad_bytes=pb // 2 + 4,
            step_s=t, link_bw=DCN_HOST_BYTES_PER_S))

    # --- sp ring ---------------------------------------------------------
    lc = ext.get("lm_long_context_bf16", {})
    lc_mfu = (lc.get("mfu_pct_vs_bf16_peak") or 18.0) / 100.0
    # ring=16 included deliberately: per-hop compute shrinks as shard²
    # while comm shrinks as shard, so the ratio grows ∝ ring — the model
    # must show where hops STOP hiding, not just the friendly regime.
    for ring in (2, 4, 8, 16):
        out["sp_ring"].append(ring_sp_row(
            name="lm_long_context_bf16_sp", batch=4, heads=4, seq=8192,
            head_dim=64, ring=ring,
            link_bw=link_bw, peak_flops=peak, mfu_measured=lc_mfu))
        # GQA at group 2: half the hop bytes, same compute — the
        # crossover where hops stop hiding moves out ~2 x in ring size.
        out["sp_ring"].append(ring_sp_row(
            name="lm_long_context_bf16_sp_gqa2", batch=4, heads=4,
            kv_heads=2, seq=8192, head_dim=64, ring=ring,
            link_bw=link_bw, peak_flops=peak, mfu_measured=lc_mfu))

    # --- causal-balance (layout) ----------------------------------------
    out["sp_ring_causal_balance"] = [
        ring_causal_balance_row(r) for r in (2, 4, 8, 16)]

    from benchmarks._round import current_round  # REPO is on sys.path

    path = REPO / f"SCALING_MODEL_r{current_round():02d}.json"
    path.write_text(json.dumps(out, indent=2) + "\n")
    # Human-readable headline.
    for d in out["dp"]:
        r8 = next((r for r in d["rows"] if r["n_chips"] == 8), None)
        if r8:
            print(f"{d['regime']:28s} n=8: eff(no-ovl)="
                  f"{r8['efficiency_no_overlap']:.3f} eff(ovl)="
                  f"{r8['efficiency_overlap']:.3f} "
                  f"bw needed for 80%: {r8['bw_needed_for_target_GBps']} GB/s")
    for s in out["sp_ring"]:
        print(f"{s['regime']:28s} ring={s['ring']}: comm/compute="
              f"{s['comm_over_compute']:.3f} "
              f"({'hides' if s['hides_under_compute'] else 'EXPOSED'})")
    print(json.dumps({"out": str(path), "dp_regimes": len(out["dp"]),
                      "sp_rows": len(out["sp_ring"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
