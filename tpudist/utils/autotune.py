"""On-hardware autotuner for the flash-attention tuned constants.

``benchmarks/flash_sweep.py`` prints A/B timings for a human to read;
this module closes the loop — it times the candidate (block_q, block_k)
tiles and the dense-vs-flash crossover ON THE CURRENT DEVICE and writes
the winners to ``tpudist/tuned/<device_kind>.json``, where
:func:`tpudist.utils.tuning.tuned` resolves them ahead of the baked v5e
defaults (env vars still win over everything).  One command ports the
kernel routing to a new TPU generation:

    python -m tpudist.utils.autotune            # measure + write
    python -m tpudist.utils.autotune --dry-run  # measure + print only

Measurement method matches the sweep harness: each configuration is ONE
dispatched XLA program chaining serially-dependent applications via
``lax.scan``, so the per-dispatch host latency is amortized out of the
per-application number.

Tuned keys written (see ``tuning._V5E_DEFAULTS``):
- ``FLASH_BLOCK_Q`` / ``FLASH_BLOCK_K`` — fastest tile at the short
  production shape (seq 2048, fwd+bwd);
- ``FLASH_BLOCK_K_LONG`` — fastest KV tile at the long shape (seq 8192);
- ``FLASH_MIN_SEQ`` — smallest measured seq where flash beats the dense
  XLA reference (fwd+bwd), i.e. the routing crossover.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpudist.utils.tuning import tuned_file_path

# Production attention shape: the d1024 MFU geometry's head width (d1024 /
# 8 heads = 128; the d512 demo geometry's 64-wide heads share tiles fine),
# bf16 operands (the MXU's native precision — tile selection at f32 rates
# does not transfer), and enough batch×heads that the grid fills the chip
# the way a real step does (b1/h4 measured a different winner than b2/h8).
HEAD_DIM = 128
HEADS = 8
BATCH = 2
DTYPE = jnp.bfloat16


def time_one_program(fn: Callable, *args, steps: int = 128,
                     steps_short: int = 16, repeats: int = 5) -> float:
    """Per-application seconds for ``fn(*args)``: two-point measurement
    over scans of ``steps`` and ``steps_short`` serially-dependent calls,
    per-app = (t_long − t_short) / (steps − steps_short) — the same
    methodology ``benchmarks/flash_sweep.py`` uses, for the same reasons:

    - The serial dependence must run THROUGH the inputs: re-feeding the
      same operands makes ``fn(*xs)`` loop-invariant — XLA hoists the
      application out of the scan and the "timing" measures a scalar
      loop (microsecond readings for millisecond kernels; the winners
      the first tuned file picked were noise).  Feeding ``eps·out`` back
      into the first operand pins one application per iteration.
    - Timings close on the program's one scalar result
      (``block_until_ready``).
    - Two points subtract the constant per-dispatch cost, which at
      single-kernel scale dwarfs the op.
    - The long/short gap must be LARGE: at 10-vs-2 steps the extra work
      (~8 sub-ms applications) sat inside the run-to-run jitter and
      three consecutive runs picked three different "winners";
      128-vs-16 puts ~50-100x the jitter between the two points
      (lax.scan is rolled, so compile time does not grow with length)."""

    def make(length):
        def chained(*xs):
            def body(carry, _):
                acc, x0, *rest = carry
                out = fn(x0, *rest)
                x0 = x0 + (out
                           * jnp.asarray(1e-8, out.dtype)).astype(x0.dtype)
                return (acc + out.ravel()[0].astype(jnp.float32),
                        x0, *rest), None

            (acc, *_), _ = lax.scan(body, (jnp.float32(0), *xs), None,
                                    length=length)
            return acc

        return jax.jit(chained)

    def best_total(length) -> float:
        compiled = make(length)
        jax.block_until_ready(compiled(*args))  # compile + warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    t_short = best_total(steps_short)
    t_long = best_total(steps)
    if t_long <= t_short:
        # Jitter swallowed the extra applications: the difference
        # carries no signal.  Raising (callers skip the candidate) beats
        # returning a near-zero sentinel that would unbeatably "win" the
        # tile selection — the noise-picked-winner failure this timer
        # exists to prevent.
        raise RuntimeError(
            f"two-point timing nonpositive ({t_long:.4f}s <= "
            f"{t_short:.4f}s) — dispatch jitter dominated; remeasure")
    return (t_long - t_short) / (steps - steps_short)


def _qkv(seq: int, heads: int = HEADS, batch: int = BATCH,
         head_dim: int = HEAD_DIM, dtype=None):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    shape = (batch, heads, seq, head_dim)
    dtype = DTYPE if dtype is None else dtype
    return (jax.random.normal(kq, shape, dtype),
            jax.random.normal(kk, shape, dtype),
            jax.random.normal(kv, shape, dtype))


def _flash_grad_fn(bq: int, bk: int):
    from tpudist.ops import flash_attention

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, bq, bk, False, None) ** 2).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def _dense_grad_fn():
    from tpudist.parallel import attention_reference

    def loss(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def _first_output(fn):
    """Adapt a tuple-returning grad fn to the scalar-chaining timer."""

    @functools.wraps(fn)
    def one(*args):
        return fn(*args)[0]

    return one


def autotune_flash(
    *,
    short_seq: int = 2048,
    long_seq: int = 8192,
    tiles: Sequence[tuple[int, int]] = ((256, 256), (512, 256), (512, 512),
                                       (512, 1024), (1024, 512),
                                       (1024, 1024)),
    long_k_tiles: Sequence[int] = (512, 1024, 2048),
    crossover_seqs: Sequence[int] = (512, 1024, 2048),
    timer: Callable = time_one_program,
    compile_check: Callable | None = None,
    log: Callable = functools.partial(print, file=sys.stderr, flush=True),
) -> dict:
    """Measure and return the tuned-constant dict (no file IO here).

    ``timer`` and ``compile_check`` are injectable so the selection logic
    is testable without hardware (tests feed synthetic timings/verdicts).

    A candidate that fails to compile (VMEM stack OOM at big tiles) or to
    measure (two-point delta swallowed by dispatch jitter) is SKIPPED,
    not fatal — and because the tuned constants apply to every model
    geometry, each winning tile must also COMPILE at the worst-VMEM
    shape the benches actually run (f32 operands, 64-wide heads:
    measured r4, (1024, 2048) timed fine at bf16/d128 and then OOM'd the
    scoped VMEM in the long bench's f32/d64 rows).  The feasibility probe
    is a single compile+run, not a timing — it only answers yes/no."""
    report: dict = {"measurements": {}}

    if compile_check is None:
        def compile_check(fn, *args) -> bool:
            float(np.asarray(jax.jit(fn)(*args).ravel()[0]))
            return True

    def try_time(tag: str, fn, args) -> float | None:
        try:
            t = timer(fn, *args)
        except Exception as e:  # compile OOM / jitter-dominated — skip
            report["measurements"][tag] = {"error": repr(e)[:300]}
            log(f"# autotune {tag}: SKIPPED ({repr(e)[:120]})")
            return None
        report["measurements"][tag] = t
        log(f"# autotune {tag}: {t * 1e3:.3f} ms")
        return t

    def feasible(tag: str, bq: int, bk: int, seq: int) -> bool:
        try:
            ok = compile_check(_first_output(_flash_grad_fn(bq, bk)),
                               *_qkv(seq, head_dim=64, dtype=jnp.float32))
        except Exception as e:
            report["measurements"][tag] = {"error": repr(e)[:300]}
            log(f"# autotune {tag}: INFEASIBLE ({repr(e)[:120]})")
            return False
        report["measurements"][tag] = bool(ok)
        log(f"# autotune {tag}: {'ok' if ok else 'INFEASIBLE'}")
        return bool(ok)

    # --- short-shape tile: FLASH_BLOCK_Q / FLASH_BLOCK_K ---
    timed: list[tuple[float, tuple[int, int]]] = []
    for bq, bk in tiles:
        if short_seq % bq or short_seq % bk:
            continue
        t = try_time(f"short{short_seq}_{bq}x{bk}",
                     _first_output(_flash_grad_fn(bq, bk)), _qkv(short_seq))
        if t is not None:
            timed.append((t, (bq, bk)))
    best_tile = None
    for t, (bq, bk) in sorted(timed):
        if feasible(f"short{short_seq}_{bq}x{bk}_f32d64", bq, bk, short_seq):
            best_tile = (bq, bk)
            break
    if best_tile is None:
        raise ValueError(
            f"no usable short tile for seq {short_seq}: every candidate "
            "either does not divide the sequence, failed to measure, or "
            "failed the worst-case (f32, 64-wide heads) VMEM feasibility "
            f"probe — see the measurements report: {report['measurements']}")
    report["FLASH_BLOCK_Q"], report["FLASH_BLOCK_K"] = best_tile

    # --- long-shape KV tile: FLASH_BLOCK_K_LONG ---
    bq = report["FLASH_BLOCK_Q"]
    best_t, best_bk = float("inf"), None
    for bk in long_k_tiles:
        if long_seq % bk or long_seq % bq:
            continue
        t = try_time(f"long{long_seq}_{bq}x{bk}",
                     _first_output(_flash_grad_fn(bq, bk)), _qkv(long_seq))
        if t is None or t >= best_t:
            continue
        # A tile that only compiles at the probe shape must not be
        # written as THE constant.
        if not feasible(f"long{long_seq}_{bq}x{bk}_f32d64", bq, bk,
                        long_seq):
            continue
        best_t, best_bk = t, bk
    if best_bk is not None:
        report["FLASH_BLOCK_K_LONG"] = best_bk

    # --- routing crossover: FLASH_MIN_SEQ ---
    # Smallest seq where flash (at the winning tile, clipped to fit)
    # beats dense.  If flash never wins (or no crossover point could be
    # measured), the crossover parks above the largest probed seq so
    # routing stays dense — a failed measurement must not abort the run
    # and discard the completed tile phases.
    bq0, bk0 = best_tile
    crossover = None
    for s in sorted(crossover_seqs):
        fb_q, fb_k = min(bq0, s), min(bk0, s)
        if s % fb_q or s % fb_k:
            continue
        tf = try_time(f"crossover{s}_flash",
                      _first_output(_flash_grad_fn(fb_q, fb_k)), _qkv(s))
        td = try_time(f"crossover{s}_dense",
                      _first_output(_dense_grad_fn()), _qkv(s))
        if tf is None or td is None:
            continue
        log(f"# autotune crossover seq{s}: flash {tf * 1e3:.3f} ms "
            f"vs dense {td * 1e3:.3f} ms")
        if tf < td and crossover is None:
            crossover = s
    report["FLASH_MIN_SEQ"] = (crossover if crossover is not None
                               else max(crossover_seqs) * 2)
    return report


def write_tuned(report: dict, path=None) -> str:
    """Persist the tuned keys (measurements stay out of the file — the
    resolver wants an int table, the evidence goes to the caller/log)."""
    path = tuned_file_path() if path is None else path
    path.parent.mkdir(parents=True, exist_ok=True)
    keys = {k: v for k, v in report.items() if k.isupper()}
    meta = {"device_kind": jax.devices()[0].device_kind,
            "method": "tpudist.utils.autotune"}
    path.write_text(json.dumps({**keys, "_meta": meta}, indent=2) + "\n")
    return str(path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="measure and print, do not write the tuned file")
    ap.add_argument("--short-seq", type=int, default=2048)
    ap.add_argument("--long-seq", type=int, default=8192)
    args = ap.parse_args(argv)

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "autotune needs a real TPU "
                          f"(got {jax.devices()[0].platform})"}))
        return 2
    report = autotune_flash(short_seq=args.short_seq, long_seq=args.long_seq)
    out = {k: v for k, v in report.items() if k != "measurements"}
    if not args.dry_run:
        out["written_to"] = write_tuned(report)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
