#!/usr/bin/env python3
"""Benchmark harness — prints ONE JSON line for the driver.

Headline workload: the reference's implicit benchmark definition (the
reference publishes no numbers, so this harness establishes them): the
`demo.py` hot loop — two ToyMLPs, Adam(1e-3),
batch 256 per chip, data-parallel over all local devices — measured as
samples/sec/chip.

Since the reference's published baseline is empty, ``vs_baseline`` is
reported against this repo's own recorded north-star figure when present
(``BENCH_BASELINE.json``), else 1.0 (we ARE the baseline).

Timings are closed by ``block_until_ready``; a failed section is
recorded and the run goes on, but the exit code is then non-zero.
ROADMAP S1/D7 replace this script with the cell matrix.

The toy MLP measures dispatch/loop overhead, not TPU muscle, so the
harness also times the Transformer LM family — with analytic-FLOPs MFU
accounting (:mod:`tpudist.utils.flops`) — and snapshots everything to
``BENCH_EXTENDED.json`` next to this file.  stdout stays one JSON line.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import optax

# Persistent XLA compilation cache: a re-run skips every compile that
# succeeded before (tpudist/runtime/compilation_cache.py says where).
from tpudist.runtime.compilation_cache import enable_compilation_cache

enable_compilation_cache()


def _sync(x) -> None:
    """Close a timing: JAX returns before the device finishes."""
    jax.block_until_ready(x)


def bench_toy() -> dict:
    from jax.sharding import NamedSharding, PartitionSpec

    from tpudist.data import make_toy_data
    from tpudist.models import create_toy_model
    from tpudist.runtime.mesh import data_parallel_mesh
    from tpudist.train import init_model_states, make_scanned_train_step

    n_chips = jax.local_device_count()
    mesh = data_parallel_mesh()

    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    mx, px = create_toy_model(kx)
    my, py = create_toy_model(ky)
    models = {"model_X": (mx.apply, px), "model_Y": (my.apply, py)}
    tx = optax.adam(1e-3)
    states = init_model_states(models, tx)
    # The framework hot path: device-cached dataset + scanned window
    # (what run_training uses for the reference workload).
    chunk_step = make_scanned_train_step(
        {k: f for k, (f, _) in models.items()}, tx, mesh
    )

    batch = 256 * n_chips  # reference: batch 256 per rank (demo.py:145)
    window = 256           # TrainLoopConfig.sync_every default — the
    #                        production loop's scan window

    data = make_toy_data(seed=0)  # the 512-sample reference dataset
    n_samples = len(data)
    rng = np.random.default_rng(0)
    repl = NamedSharding(mesh, PartitionSpec())
    x_all, y_all = jax.device_put(data.x, repl), jax.device_put(data.y, repl)
    idx = jax.device_put(
        rng.integers(0, n_samples, size=(window, batch)).astype(np.int32), repl
    )

    for _ in range(3):  # warmup / compile
        states, losses = chunk_step(states, x_all, y_all, idx)
    _sync(losses["model_X"])

    # Three independent >=0.5s segments, best taken: a one-chip machine
    # shares its host's cores, and max-of-segments rejects a contention
    # spike (min-of-repeats, inverted because this is a rate).
    best = 0.0
    for _ in range(3):
        total_chunks = 0
        t0 = time.perf_counter()
        while True:
            for _ in range(8):
                states, losses = chunk_step(states, x_all, y_all, idx)
            _sync(losses["model_X"])
            total_chunks += 8
            dt = time.perf_counter() - t0
            if dt >= 0.5:
                break
        best = max(best, batch * window * total_chunks / dt)

    per_chip = best / n_chips
    return {
        "metric": "toy_mlp_samples_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "samples/sec/chip",
    }


def bench_lm(*, name: str, batch: int, seq_len: int, d_model: int,
             n_layers: int, n_heads: int, d_ff: int, vocab: int = 256,
             steps: int = 5, precision: str = "fp32",
             remat: bool = False, remat_policy: str = "nothing",
             repeats: int = 1,
             profile_dir: str | None = None) -> dict:
    """Time the TransformerLM train step and report tokens/sec/chip + MFU.

    ``repeats`` > 1 re-times the ``steps``-long loop that many times on
    the ONE compiled executable and reports the MEDIAN run as the row's
    headline (plus ``step_ms_runs`` with every sample) — the band
    methodology of ``benchmarks/bands.py``: one compile, N timings, so
    the band is execution noise, not compile variance.

    ``profile_dir``: capture a ``jax.profiler`` trace of the timed steps
    (the per-op breakdown behind the MFU number; the raw trace stays on
    disk for TensorBoard)."""
    import contextlib

    import jax.numpy as jnp

    from tpudist.models import create_transformer
    from tpudist.runtime.mesh import data_parallel_mesh
    from tpudist.train import init_lm_state, make_lm_train_step, token_sharding
    from tpudist.utils import chip_peak_flops, mfu, transformer_train_flops

    n_chips = jax.local_device_count()
    mesh = data_parallel_mesh()
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=seq_len, vocab=vocab, d_model=d_model,
        n_layers=n_layers, n_heads=n_heads, d_ff=d_ff, max_len=seq_len,
        dtype=jnp.bfloat16 if precision == "bf16" else jnp.float32,
        remat=remat, remat_policy=remat_policy,
    )
    tx = optax.adam(3e-4)
    state = init_lm_state(params, tx)
    step_jit = make_lm_train_step(module.apply, tx, mesh)
    tokens = jax.device_put(
        np.random.default_rng(0).integers(0, vocab, size=(batch, seq_len))
        .astype(np.int32),
        token_sharding(mesh),
    )

    # ONE compile, AOT: the timed loop and the HBM report share this
    # executable (memory_analysis needs the compiled object; re-lowering
    # through the jit cache would pay a second full compile).
    step = step_jit.lower(state, tokens).compile()
    for _ in range(2):  # warmup
        state, loss = step(state, tokens)
    _sync(loss)
    if profile_dir:
        from tpudist.utils.profiling import trace as _trace

        profiling = _trace(profile_dir)
    else:
        profiling = contextlib.nullcontext()
    with profiling:
        step_runs = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            for _ in range(steps):
                state, loss = step(state, tokens)
            _sync(loss)
            step_runs.append((time.perf_counter() - t0) / steps)
        import statistics as _stats

        step_s = _stats.median(step_runs)

    flops = transformer_train_flops(
        batch=batch, seq_len=seq_len, d_model=d_model, n_layers=n_layers,
        d_ff=d_ff, vocab=vocab,
    )
    peak = chip_peak_flops()
    util = mfu(flops, step_s, n_chips, peak)
    mem = _hbm_in_use()
    return {
        "metric": f"lm_{name}_tokens_per_sec_per_chip",
        "value": round(batch * seq_len / step_s / n_chips, 1),
        "unit": "tokens/sec/chip",
        "step_ms": round(step_s * 1e3, 2),
        "config": {"batch": batch, "seq_len": seq_len, "d_model": d_model,
                   "n_layers": n_layers, "n_heads": n_heads, "d_ff": d_ff,
                   "vocab": vocab, "precision": precision,
                   "remat": remat,
                   "remat_policy": remat_policy if remat else None},
        "model_flops_per_step": flops,
        **({"step_ms_runs": [round(s * 1e3, 2) for s in step_runs]}
           if len(step_runs) > 1 else {}),
        # Always against the bf16 MXU peak (the chip's one headline number)
        # so fp32 and bf16 rows share a denominator: an fp32 row's value is
        # "fraction of the chip's best case", not utilization of some fp32
        # roofline.
        "mfu_pct_vs_bf16_peak": round(util * 100, 2) if util is not None else None,
        "peak_bf16_flops_per_chip": peak,
        # HBM in use after the timed steps (params + opt state + live
        # buffers) — the memory side of the MFU story, and the evidence
        # for how much headroom --remat/--accum_steps would buy.
        "hbm_bytes_in_use": mem,
    }


def bench_lm_scanned(*, name: str = "dense_bf16_scanned",
                     batch: int = 8, seq_len: int = 2048,
                     d_model: int = 512, n_layers: int = 4,
                     n_heads: int = 8, d_ff: int = 2048, vocab: int = 256,
                     scan_k: int = 8, repeats: int = 3,
                     skip_plain: bool = False) -> dict:
    """A/B the scanned LM step (K optimizer steps per dispatch) against
    the per-step path at the dense-row geometry — measures what the
    per-step dispatch/sync tax costs the LM family (the toy row's
    amortization trick, quantified at transformer scale).

    ``skip_plain`` drops the per-step arm (used by the MFU rung, where
    the per-step ladder is a separate section and re-timing it would
    double the rung's chip time)."""
    import jax.numpy as jnp

    from tpudist.models import create_transformer
    from tpudist.runtime.mesh import data_parallel_mesh
    from tpudist.train import (chunk_token_sharding, init_lm_state,
                               make_lm_train_step,
                               make_scanned_lm_train_step, token_sharding)

    mesh = data_parallel_mesh()
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=seq_len, vocab=vocab,
        d_model=d_model, n_layers=n_layers, n_heads=n_heads, d_ff=d_ff,
        max_len=seq_len, dtype=jnp.bfloat16)
    tx = optax.adam(3e-4)
    toks = np.random.default_rng(0).integers(
        0, vocab, size=(scan_k, batch, seq_len)).astype(np.int32)

    # plain: K separate dispatches.  BOTH arms donate state — the ladder
    # rows (bench_lm) donate, and donation is worth ~2% at d1024 (r5
    # measured 215.6 vs 220.0 ms scanned); a no-donate scanned arm made
    # the A/B read as a scanned slowdown that was really buffer churn.
    # init_lm_state holds `params` BY REFERENCE, and donated steps delete
    # their input buffers — each arm gets its own copy or the second arm
    # would run on deleted arrays (TPU: "Array has been deleted").
    def fresh_state():
        return init_lm_state(jax.tree.map(lambda a: a.copy(), params), tx)

    best_plain = float("inf")
    if not skip_plain:
        st = fresh_state()
        plain = make_lm_train_step(module.apply, tx, mesh)
        t_p = jax.device_put(toks[0], token_sharding(mesh))
        st, loss = plain(st, t_p)
        _sync(loss)  # compile
        for _ in range(repeats):
            t0 = time.perf_counter()
            for k in range(scan_k):
                st, loss = plain(st, t_p)
            _sync(loss)
            best_plain = min(best_plain,
                             (time.perf_counter() - t0) / scan_k)

    # scanned: one dispatch for K steps
    st2 = fresh_state()
    chunk = make_scanned_lm_train_step(module.apply, tx, mesh)
    t_c = jax.device_put(toks, chunk_token_sharding(mesh))
    st2, losses = chunk(st2, t_c)
    _sync(losses)  # compile
    best_scan = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        st2, losses = chunk(st2, t_c)
        _sync(losses)
        best_scan = min(best_scan, (time.perf_counter() - t0) / scan_k)

    from tpudist.utils import chip_peak_flops, mfu, transformer_train_flops

    flops = transformer_train_flops(
        batch=batch, seq_len=seq_len, d_model=d_model, n_layers=n_layers,
        d_ff=d_ff, vocab=vocab)
    peak = chip_peak_flops()
    util = mfu(flops, best_scan, jax.local_device_count(), peak)
    row = {
        "metric": f"lm_{name}_step_ms",
        "unit": "ms/step",
        "config": {"batch": batch, "seq_len": seq_len, "d_model": d_model,
                   "n_layers": n_layers, "d_ff": d_ff, "scan_k": scan_k},
        "step_ms_scanned": round(best_scan * 1e3, 2),
        "tokens_per_sec_per_chip_scanned": round(
            batch * seq_len / best_scan / jax.local_device_count(), 1),
        "model_flops_per_step": flops,
        "mfu_pct_vs_bf16_peak": (round(util * 100, 2)
                                 if util is not None else None),
    }
    if not skip_plain:
        row.update(
            step_ms_plain=round(best_plain * 1e3, 2),
            dispatch_tax_ms=round((best_plain - best_scan) * 1e3, 2),
            speedup=round(best_plain / best_scan, 3),
        )
    return row


def bench_decode(*, batch: int = 8, prompt_len: int = 16, max_new: int = 240,
                 d_model: int = 512, n_layers: int = 4, n_heads: int = 8,
                 d_ff: int = 2048, vocab: int = 256,
                 precision: str = "fp32") -> dict:
    """Autoregressive decode throughput (KV-cache path, greedy): one
    compiled scan over single-token cached forwards — measures the
    framework's inference loop, which training MFU says nothing about.

    ``precision='bf16'`` is the inference-serving configuration: weights
    STORED bf16 (cast once — decode has no optimizer, so no f32 masters
    to keep) and a bf16 KV cache (the module's compute dtype sizes it).
    Decode is HBM-bound, so halving stored bytes roughly doubles the
    analytic ceiling; the roofline in the row uses the matching byte
    widths."""
    import jax.numpy as jnp

    from tpudist.models import create_transformer, make_generator

    max_len = prompt_len + max_new
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=max_len, vocab=vocab, d_model=d_model,
        n_layers=n_layers, n_heads=n_heads, d_ff=d_ff, max_len=max_len,
        dtype=dtype,
    )
    if precision == "bf16":
        # stored-bf16 weights: the HBM stream per token is 2 bytes/param
        # (float leaves only; nothing else lives in the params tree)
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, size=(batch, prompt_len)),
        jnp.int32,
    )
    # ONE reusable jitted program: the warmup call compiles it, the timed
    # calls hit the jit cache (a fresh generate() per call would re-trace).
    gen = make_generator(module, params, max_new)

    _sync(gen(prompt))  # compile
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        _sync(gen(prompt))
        dt = time.perf_counter() - t0
        best = max(best, batch * max_new / dt)

    # Chip-side rate via a profiler trace of ONE decode: the whole decode
    # is a single dispatch + fetch whose fixed host cost is of the same
    # order as the decode itself, so wall differencing is noise-
    # dominated.  Summing the trace's device self-time is direct: it is
    # what the HBM roofline actually bounds.  The wall-clock `value`
    # stays the number a caller of generate() sees.
    device_rate = None
    device_rate_error = None
    try:
        import tempfile

        from tpudist.utils.profiling import trace as _trace

        repo = str(Path(__file__).parent)
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from benchmarks.profile_summary import summarize

        with tempfile.TemporaryDirectory() as td:
            with _trace(td):
                _sync(gen(prompt))
            s = summarize(td)
        if "total_us" in s:
            device_rate = batch * max_new / (s["total_us"] / 1e6)
        else:
            device_rate_error = s.get("error", "no device events in trace")
    except Exception as e:
        # expected on backends without trace support; recorded either way
        # so a summarize() regression cannot silently erase the chip-side
        # metric from every artifact
        device_rate_error = repr(e)
    # Decode is HBM-bandwidth-bound; the analytic ceiling (stream every
    # weight once per token + each sequence's KV cache) is the judgment
    # next to the measured number (VERDICT r4 weak #7).
    from tpudist.utils.flops import decode_roofline

    nbytes = 2 if precision == "bf16" else 4
    roof = decode_roofline(
        batch=batch, prompt_len=prompt_len, max_new=max_new,
        d_model=d_model, n_layers=n_layers, d_ff=d_ff, vocab=vocab,
        param_bytes=nbytes, cache_bytes=nbytes,
    )
    return {
        "metric": ("lm_decode_tokens_per_sec" if precision == "fp32"
                   else "lm_decode_bf16_tokens_per_sec"),
        "value": round(best, 1),
        "unit": "tokens/sec (batch aggregate)",
        "config": {"batch": batch, "prompt_len": prompt_len,
                   "max_new": max_new, "d_model": d_model,
                   "n_layers": n_layers, "n_heads": n_heads, "d_ff": d_ff,
                   "vocab": vocab, "precision": precision},
        "roofline": roof,
        # wall rate vs ceiling: what a caller of generate() sees
        "pct_of_roofline": (
            round(100.0 * best / roof["ceiling_tokens_per_sec"], 1)
            if roof else None),
        # device self-time rate (traced; dispatch/fetch excluded) vs
        # ceiling: the chip-side number the roofline actually bounds
        "tokens_per_sec_device": (round(device_rate, 1)
                                  if device_rate else None),
        **({"tokens_per_sec_device_error": device_rate_error}
           if device_rate is None and device_rate_error else {}),
        "pct_of_roofline_device": (
            round(100.0 * device_rate / roof["ceiling_tokens_per_sec"], 1)
            if roof and device_rate else None),
    }


def _hbm_in_use() -> int | None:
    """Device memory in use (bytes) per ``Device.memory_stats``; None
    where the backend keeps no memory statistics (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return int(stats["bytes_in_use"]) if stats else None


def numerics_gate(interpret: bool = False, quick: bool = False) -> dict:
    """Kernel-correctness gate — runs ON THE REAL CHIP before any timing.

    The test suite forces CPU (``tests/conftest.py``), so every Pallas test
    exercises interpret mode only; a silent Mosaic miscompilation on a new
    libtpu would otherwise ship a plausible-looking number.  Assert the
    flash kernels (fwd + bwd; dense / sliding-window / GQA / both) against
    the XLA reference — at small shapes for mask/GQA semantics AND at the
    tile sizes of ``tpudist.ops.attention``'s default row (512-wide blocks
    at seq 1024, 1024-wide KV blocks at seq 8192), since a miscompile can
    be specific to one tile layout.  A
    mismatch raises — main() turns that into a value-0 record and a NONZERO
    exit, so a bad kernel can never produce a recorded measurement.

    ``quick=True`` runs only the small-block semantic cases (used by the
    CPU interpret-mode test, where an 8192-seq interpreted kernel is
    prohibitively slow).

    Returns per-case max relative error (snapshotted to BENCH_EXTENDED so
    every artifact carries the evidence the gate ran).
    """
    import jax.numpy as jnp

    from tpudist.ops import attention_reference, flash_attention

    h = 4
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)

    def rel_err(got, want) -> float:
        got, want = np.asarray(got), np.asarray(want)
        return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))

    # Loose enough for MXU-vs-MXU f32 accumulation-order differences,
    # tight enough that a miscompiled tile (garbage, zeros, wrong mask)
    # cannot slip through.
    tol = 1e-2
    #         tag           heads hkv  seq  blocks    window
    cases = [("dense",        h,  h,   512, (128, 128), None),
             ("window",       h,  h,   512, (128, 128), 192),
             ("gqa",          h,  2,   512, (128, 128), None),
             ("gqa_window",   h,  2,   512, (128, 128), 192)]
    if not quick:
        # The tiles the timed paths actually run (transformer.py routing:
        # 512/512 from seq 1024, 512/1024 from seq 8192).
        cases += [("tile512_gqa_window", h, 2, 1024, (512, 512), 768),
                  ("tile1024_dense",     1, 1, 8192, (512, 1024), None)]
    report = {}
    for tag, nh, hkv, s, (bq, bk), window in cases:
        # Progress to stderr: a failure must show WHICH case died.
        print(f"# numerics_gate: {tag} ...", file=sys.stderr, flush=True)
        q = jax.random.normal(kq, (1, nh, s, 64), jnp.float32)
        k = jax.random.normal(kk, (1, hkv, s, 64), jnp.float32)
        v = jax.random.normal(kv, (1, hkv, s, 64), jnp.float32)

        def loss_flash(q, k, v, bq=bq, bk=bk, window=window):
            return (flash_attention(q, k, v, True, bq, bk, interpret,
                                    window) ** 2).sum()

        def loss_ref(q, k, v, nh=nh, hkv=hkv, window=window):
            kf, vf = (k, v) if hkv == nh else (
                jnp.repeat(k, nh // hkv, axis=1),
                jnp.repeat(v, nh // hkv, axis=1))
            return (attention_reference(q, kf, vf, causal=True,
                                        window=window) ** 2).sum()

        # One value+grad evaluation covers the forward kernel and all
        # three backward kernels (dq, dk/dv) in this configuration.
        fg, got = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        rg, want = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        errs = {"loss": rel_err(fg, rg),
                "dq": rel_err(got[0], want[0]),
                "dk": rel_err(got[1], want[1]),
                "dv": rel_err(got[2], want[2])}
        worst = max(errs.values())
        report[tag] = {"max_rel_err": round(worst, 6), **{
            kk_: round(v_, 6) for kk_, v_ in errs.items()}}
        if not np.isfinite(worst) or worst > tol:
            raise AssertionError(
                f"flash kernel numerics gate FAILED [{tag}]: {errs} "
                f"(tolerance {tol}) — refusing to record a benchmark")
    return report


def same_window_pair(results: dict, measured_now, key: str, fp32_key: str,
                     bf16_key: str, field: str = "step_ms",
                     invert: bool = False) -> None:
    """Pair two rows measured back-to-back in THIS invocation, so
    BENCH_EXTENDED never invites a cross-session fp32-vs-bf16 wall
    comparison (r5 verdict Weak #3: the decode artifact showed bf16 1.7x
    'slower' purely from drift between sessions).
    When only one side was measured now, the pair is explicitly
    voided rather than silently stale.  Module-level (not a main()
    closure) so the voiding/pairing rules are unit-testable."""
    if fp32_key in measured_now and bf16_key in measured_now:
        a, b = results[fp32_key], results[bf16_key]
        va, vb = a.get(field), b.get(field)
        if isinstance(va, (int, float)) and isinstance(vb, (int, float)) \
                and va and vb:
            speed = (vb / va) if invert else (va / vb)
            results[key] = {
                "metric": key, "unit": a.get("unit"),
                f"{field}_fp32": va, f"{field}_bf16": vb,
                "bf16_speedup": round(speed, 3),
                "note": "fp32/bf16 measured back-to-back in one "
                        "session — the only wall pair safe to compare",
            }
            return
    results[key] = {
        "error": "not a same-window pair: both precisions were not "
                 "measured in this invocation"}


def main() -> None:
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sections", default="all",
                    help="comma list of toy,dense,mfu,mfu_scanned,"
                         "decode,long,dh128 "
                         "(default: all).  Targeted reruns merge "
                         "into the existing BENCH_EXTENDED.json instead of "
                         "clobbering other sections' evidence.")
    cli = ap.parse_args()
    want = {s.strip() for s in cli.sections.split(",") if s.strip()}
    known = {"all", "toy", "dense", "mfu", "mfu_scanned",
             "decode", "long", "dh128"}
    if not want or want - known:
        # A typo'd section must not produce a success-looking empty run.
        print(json.dumps({"error": f"unknown sections {sorted(want - known)}; "
                          f"known: {sorted(known)}"}))
        sys.exit(64)  # EX_USAGE

    def sec(name: str) -> bool:
        return "all" in want or name in want

    on_tpu = jax.devices()[0].platform == "tpu"
    results = {"device_kind": jax.devices()[0].device_kind,
               "n_chips": jax.local_device_count()}
    ran_now: list = []  # sections THIS invocation executed (not merged)
    measured_now: list = []  # sections THIS invocation actually measured
    ext_path = Path(__file__).parent / "BENCH_EXTENDED.json"
    if want != {"all"} and ext_path.exists():
        # Partial run: keep the sections this invocation doesn't touch.
        results = {**json.loads(ext_path.read_text()), **results}

    # The gate certifies the flash kernels; any section that can route
    # through them needs it (dense/MFU at seq 2048 included).  It runs
    # BEFORE any timing and a mismatch raises: a bad kernel never
    # records a number.
    if on_tpu and any(sec(s) for s in ("dense", "mfu", "mfu_scanned",
                                       "long", "dh128")):
        results["numerics_gate"] = numerics_gate()

    toy = None
    if sec("toy"):
        toy = results["toy"] = bench_toy()

    def run_section(key: str, fn) -> None:
        """A failed section is recorded and the run goes on — the other
        rows are still evidence — but the exit code says so."""
        ran_now.append(key)
        try:
            results[key] = fn()
            measured_now.append(key)
        except Exception as e:
            results[key] = {"error": repr(e)}
            print(f"# {key} failed: {e!r}", file=sys.stderr)
        # persist after EVERY section: a later crash keeps earlier rows
        ext_path.write_text(json.dumps(results, indent=2) + "\n")

    def pair(key, fp32_key, bf16_key, **kw):
        same_window_pair(results, measured_now, key, fp32_key, bf16_key,
                         **kw)

    # MXU-dense LM config: matmul-dominated, the MFU yardstick — timed at
    # both precisions (bf16 = the MXU's native throughput, the number that
    # matters; fp32 tracks numerics-reference cost round over round).
    for precision in ("fp32", "bf16"):
        if not sec("dense"):
            break
        run_section(
            f"lm_dense_{precision}",
            lambda p=precision: bench_lm(
                name=f"dense_{p}", batch=8, seq_len=2048, d_model=512,
                n_layers=4, n_heads=8, d_ff=2048, precision=p))
    if sec("dense"):
        pair("lm_dense_same_window_pair",
             "lm_dense_fp32", "lm_dense_bf16")

    # d_head-128 twin rungs (r5 verdict next #1): same model FLOPs as
    # the dense d512 and long-context rows, but 128-deep heads — the
    # falsification experiment for the round-5 "d_head-64 structural
    # ceiling" claim.
    if sec("dh128"):
        run_section(
            "lm_dense_bf16_dh128",
            lambda: bench_lm(
                name="dense_bf16_dh128", batch=8, seq_len=2048,
                d_model=512, n_layers=4, n_heads=4, d_ff=2048,
                precision="bf16"))
        run_section(
            "lm_long_context_bf16_dh128",
            lambda: bench_lm(
                name="long_context_bf16_dh128", batch=4, seq_len=8192,
                d_model=256, n_layers=4, n_heads=2, d_ff=1024,
                precision="bf16"))

    if on_tpu and sec("dense"):
        # Dispatch-tax A/B: the scanned LM step (K steps/dispatch) vs the
        # per-step path at the dense geometry.
        run_section("lm_dense_bf16_scanned", bench_lm_scanned)

    # MXU-saturating MFU row (VERDICT r2: demonstrate >=35% or profile
    # why not): d1024/L8/ff4096/seq2048 bf16 — wide enough matmuls that
    # small-model dispatch/layernorm overheads stop dominating.
    # TPUDIST_BENCH_PROFILE=dir adds a jax.profiler trace of the timed
    # steps.
    if on_tpu and sec("mfu"):
        run_section(
            "lm_mfu_d1024",
            lambda: bench_lm(
                name="mfu_d1024_bf16", batch=8, seq_len=2048,
                d_model=1024, n_layers=8, n_heads=8, d_ff=4096,
                precision="bf16", steps=3,
                profile_dir=os.environ.get("TPUDIST_BENCH_PROFILE"),
            ))

        # MFU lever #1 — arithmetic intensity via batch (VERDICT r3 #2):
        # the d1024 matmuls at b8 leave the MXU waiting on dispatch and
        # HBM; doubling batch amortizes both.  b32 runs under
        # remat(dots): the roofline (ROOFLINE_r04.json) shows plain b32
        # exceeds the 16 GiB HBM while the dots-policy rung fits at ~1/5
        # the live bytes — and the config is compute-bound either way,
        # so the recompute sliver is the whole cost.
        for b, rm in ((16, False), (32, True)):
            run_section(
                f"lm_mfu_d1024_b{b}" + ("_remat" if rm else ""),
                lambda b=b, rm=rm: bench_lm(
                    name=f"mfu_d1024_bf16_b{b}" + ("_remat" if rm else ""),
                    batch=b, seq_len=2048,
                    d_model=1024, n_layers=8, n_heads=8, d_ff=4096,
                    precision="bf16", steps=3,
                    remat=rm, remat_policy="dots" if rm else "nothing"))

    # MFU lever #2 — dispatch amortization: the same model under the
    # scanned step (K optimizer steps per dispatch), i.e. the DEVICE rate
    # the MFU ladder's per-step wall-clock rows understate.
    if on_tpu and sec("mfu_scanned"):
        run_section(
            "lm_mfu_d1024_b16_scanned",
            lambda: bench_lm_scanned(
                name="mfu_d1024_bf16_b16_scanned", batch=16, seq_len=2048,
                d_model=1024, n_layers=8, n_heads=8, d_ff=4096,
                scan_k=4, repeats=2, skip_plain=True))

    if sec("decode"):
        run_section("lm_decode", bench_decode)
        # serving configuration: stored-bf16 weights + bf16 KV cache —
        # decode is HBM-bound, so this is the one-line 2x ceiling lever
        run_section("lm_decode_bf16",
                    lambda: bench_decode(precision="bf16"))
        # decode throughput: HIGHER is better, so the speedup inverts
        pair("lm_decode_same_window_pair",
             "lm_decode", "lm_decode_bf16",
             field="value", invert=True)

    # Long-context LM config: flash-attention regime, attention-
    # dominated — tracks the kernel round over round.
    for precision in ("fp32", "bf16"):
        if not sec("long"):
            break
        run_section(
            f"lm_long_context_{precision}",
            lambda p=precision: bench_lm(
                name=f"long_context_{p}", batch=4, seq_len=8192,
                d_model=256, n_layers=4, n_heads=4, d_ff=1024,
                precision=p))
    if sec("long"):
        pair("lm_long_context_same_window_pair",
             "lm_long_context_fp32", "lm_long_context_bf16")

    ext_path.write_text(json.dumps(results, indent=2) + "\n")

    if toy is not None:
        baseline_path = Path(__file__).parent / "BENCH_BASELINE.json"
        vs = 1.0
        if baseline_path.exists():
            recorded = json.loads(baseline_path.read_text()).get("value")
            if recorded:
                vs = toy["value"] / recorded
        print(json.dumps({**toy, "vs_baseline": round(vs, 3)}), flush=True)
    else:  # targeted partial run — still exactly one JSON line
        print(json.dumps({"metric": "bench_sections_ok",
                          "value": len(measured_now),
                          "unit": "sections", "ran": sorted(ran_now),
                          "ok": sorted(measured_now)}), flush=True)
    failed = sorted(set(ran_now) - set(measured_now))
    if failed:
        print(f"# failed sections: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
