#!/usr/bin/env python3
"""Flash-attention kernel tuning sweep: dense XLA vs Pallas blocks,
with an A/B column against JAX's stock TPU flash attention.

Times causal attention forward (and optionally fwd+bwd) at the demo shapes
(head_dim 64) across (block_q, block_k) and prints one JSON line per
configuration.  Run on the real chip; value-fetch synced (see bench.py).

The ``stock_flash`` rows time ``jax.experimental.pallas.ops.tpu``'s
shipped flash-attention kernel at the same geometry — the external
yardstick the in-house kernels are matched against (r5 verdict next #2:
beating your own history is not a perf claim).  Import- and
platform-guarded: on CPU CI or a jax build without the op the row
records WHY it was skipped instead of crashing the sweep.  Caveats
recorded in the row: the stock kernel has no sliding-window support
(window geometries skip it) and no GQA-native path (K/V are repeated to
full heads, so it pays MHA-equivalent bandwidth — that difference IS
the comparison).

Usage:
  python benchmarks/flash_sweep.py --seq 2048 --blocks 256x256,512x512
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

from tpudist.runtime.compilation_cache import enable_compilation_cache

enable_compilation_cache()
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, steps=10):
    """Per-application seconds for ``fn``, measured as ONE dispatched XLA
    program that chains ``steps`` serially-dependent applications via
    lax.scan — per-call dispatch costs more than the kernel itself, so
    timing separate calls measures the host, not the op."""
    from jax import lax

    q0, rest = args[0], args[1:]

    @functools.partial(jax.jit, static_argnums=(0,))
    def chained(length, q, *rest):
        def body(carry, _):
            out = fn(carry, *rest)
            # feed the output back as q: same [b, h, s, d] shape, forces
            # serial execution of every application
            return out.reshape(carry.shape).astype(carry.dtype), ()

        final, _ = lax.scan(body, q, (), length=length)
        return final.sum()  # fetch one scalar, not the whole output

    def once(length):
        out = chained(length, q0, *rest)
        float(jax.device_get(out))

    once(1)       # compile short program
    once(steps)   # compile long program

    # Two-point measurement: (t_long - t_short) cancels the fixed
    # dispatch/fetch overhead; min-of-repeats rejects contention spikes
    # (a one-chip machine shares its host's cores).
    short = long_ = float("inf")
    for _ in range(4):
        t0 = time.perf_counter()
        once(1)
        short = min(short, time.perf_counter() - t0)
        t0 = time.perf_counter()
        once(steps)
        long_ = min(long_, time.perf_counter() - t0)
    return (long_ - short) / (steps - 1)


def _stock_flash_fn(causal: bool):
    """Import the stock TPU flash-attention kernel, or explain why not.

    Returns ``(fn, None)`` with ``fn(q, k, v) -> out`` consuming
    full-head (MHA) inputs, or ``(None, reason)`` when the row must be
    skipped (non-TPU platform, missing module on this jax build)."""
    import jax as _jax

    if _jax.devices()[0].platform != "tpu":
        return None, "stock kernel runs on TPU only (CPU CI skips)"
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as stock)
    except ImportError as e:
        return None, f"stock kernel unavailable on this jax: {e!r}"

    def fn(q, k, v):
        return stock(q, k, v, causal=causal)

    return fn, None


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seq", default=2048, type=int)
    p.add_argument("--batch", default=4, type=int)
    p.add_argument("--heads", default=4, type=int)
    p.add_argument("--head-dim", default=64, type=int)
    p.add_argument("--kv-heads", default=None, type=int,
                   help="grouped-query KV head count (default = --heads)")
    p.add_argument("--window", default=None, type=int,
                   help="sliding-window band (band-tile DMA elision: cost "
                        "should scale with window, not seq)")
    p.add_argument("--blocks", default="128x128,256x256,256x512,512x512,512x1024,1024x1024")
    p.add_argument("--steps", default=10, type=int)
    p.add_argument("--grad", action="store_true", help="time fwd+bwd too")
    p.add_argument("--skip-dense", action="store_true")
    p.add_argument("--skip-stock", action="store_true",
                   help="drop the jax stock TPU flash-attention A/B rows")
    args = p.parse_args(argv)

    from tpudist.ops import flash_attention
    from tpudist.parallel.ring_attention import attention_reference

    rng = np.random.default_rng(0)
    kv_heads = args.heads if args.kv_heads is None else args.kv_heads
    if kv_heads < 1 or args.heads % kv_heads:
        raise SystemExit(
            f"--kv-heads {kv_heads} must be >= 1 and divide --heads {args.heads}")
    if args.window is not None and args.window < 1:
        raise SystemExit(f"--window must be >= 1, got {args.window}")
    shape = (args.batch, args.heads, args.seq, args.head_dim)
    kv_shape = (args.batch, kv_heads, args.seq, args.head_dim)
    q = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    k = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)

    results = []

    def report(name, secs):
        row = {"kernel": name, "seq": args.seq,
               "heads": args.heads, "kv_heads": kv_heads,
               "window": args.window, "ms": round(secs * 1e3, 3)}
        results.append(row)
        print(json.dumps(row))

    if not args.skip_dense:
        # GQA baseline: dense on repeated K/V — the MHA-equivalent compute
        # the grouped kernel's bandwidth win is measured against.
        group = args.heads // kv_heads
        kd = jnp.repeat(k, group, axis=1) if group > 1 else k
        vd = jnp.repeat(v, group, axis=1) if group > 1 else v
        dense = jax.jit(lambda a, b, c: attention_reference(
            a, b, c, causal=True, window=args.window))
        report("dense_xla_fwd", _time(dense, q, kd, vd, steps=args.steps))
        if args.grad:
            dense_g = jax.jit(jax.grad(
                lambda a, b, c: attention_reference(
                    a, b, c, causal=True, window=args.window).sum()
            ))
            report("dense_xla_fwdbwd", _time(dense_g, q, kd, vd, steps=args.steps))

    if not args.skip_stock:
        # A/B yardstick: jax's shipped TPU flash attention at the same
        # geometry (MHA-equivalent inputs — K/V repeated for GQA, like
        # the dense baseline above; it has no grouped-KV fast path).
        if args.window is not None:
            row = {"kernel": "stock_flash", "seq": args.seq,
                   "heads": args.heads, "kv_heads": kv_heads,
                   "window": args.window,
                   "skipped": "stock kernel has no sliding-window support"}
            results.append(row)
            print(json.dumps(row))
        else:
            stock, reason = _stock_flash_fn(causal=True)
            if stock is None:
                row = {"kernel": "stock_flash", "seq": args.seq,
                       "heads": args.heads, "kv_heads": kv_heads,
                       "window": args.window, "skipped": reason}
                results.append(row)
                print(json.dumps(row))
            else:
                group = args.heads // kv_heads
                ks = jnp.repeat(k, group, axis=1) if group > 1 else k
                vs = jnp.repeat(v, group, axis=1) if group > 1 else v
                st = jax.jit(stock)
                report("stock_flash_fwd", _time(st, q, ks, vs,
                                                steps=args.steps))
                if args.grad:
                    st_g = jax.jit(jax.grad(
                        lambda a, b, c: stock(a, b, c).sum()))
                    report("stock_flash_fwdbwd",
                           _time(st_g, q, ks, vs, steps=args.steps))

    for spec in args.blocks.split(","):
        bq, bk = (int(x) for x in spec.split("x"))
        if args.seq % bq or args.seq % bk:
            continue
        fl = jax.jit(lambda a, b, c, bq=bq, bk=bk:
                     flash_attention(a, b, c, True, bq, bk, False,
                                     args.window))
        report(f"flash_{bq}x{bk}_fwd", _time(fl, q, k, v, steps=args.steps))
        if args.grad:
            fl_g = jax.jit(jax.grad(
                lambda a, b, c, bq=bq, bk=bk:
                flash_attention(a, b, c, True, bq, bk, False,
                                args.window).sum()
            ))
            report(f"flash_{bq}x{bk}_fwdbwd", _time(fl_g, q, k, v, steps=args.steps))
    return results


if __name__ == "__main__":
    main()
