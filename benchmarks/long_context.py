#!/usr/bin/env python3
"""Long-context throughput harness: tokens/sec for the ring-attention LM
as sequence length and the ``seq`` mesh axis grow.

Demonstrates the point of sequence parallelism: per-chip attention memory
is O(seq/ring), so doubling the ring doubles the reachable context at
constant memory.  On virtual CPU devices the numbers validate mechanics
only (labeled in the output); on a pod they are hardware truth.

Usage:
  python benchmarks/long_context.py --seq-lens 512,1024 --seq-shards 1,4
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

from tpudist.runtime.compilation_cache import enable_compilation_cache

enable_compilation_cache()
import numpy as np
import optax
from jax.sharding import Mesh


def measure(seq_len: int, seq_shards: int, *, batch: int, steps: int,
            d_model: int, n_layers: int, window=None) -> dict:
    from tpudist.models import create_transformer
    from tpudist.parallel import make_ring_attention
    from tpudist.runtime.mesh import AXIS_DATA, AXIS_SEQ
    from tpudist.train import init_lm_state, make_lm_train_step, token_sharding

    devices = jax.devices()
    if seq_shards > len(devices) or len(devices) % seq_shards:
        raise ValueError(f"{seq_shards} seq shards on {len(devices)} devices")
    # Data axis: the largest divisor of the batch that fits the remaining
    # devices (a seq_shards=1 rung must not demand batch % all_devices == 0).
    data_size = len(devices) // seq_shards
    while batch % data_size:
        data_size -= 1
    mesh = Mesh(
        np.asarray(devices[: data_size * seq_shards]).reshape(
            data_size, seq_shards
        ),
        axis_names=(AXIS_DATA, AXIS_SEQ),
    )
    attention = (
        make_ring_attention(mesh, causal=True, batch_axis=AXIS_DATA,
                            window=window)
        if seq_shards > 1 else None
    )
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=seq_len, attention_fn=attention,
        vocab=256, d_model=d_model, n_layers=n_layers, max_len=seq_len,
        sliding_window=window if seq_shards == 1 else None,
    )
    tx = optax.adam(3e-4)
    state = init_lm_state(params, tx)
    step = make_lm_train_step(module.apply, tx, mesh)

    tokens = jax.device_put(
        np.random.default_rng(0).integers(
            0, 256, size=(batch, seq_len)
        ).astype(np.int32),
        token_sharding(mesh),
    )
    for _ in range(2):
        state, loss = step(state, tokens)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = step(state, tokens)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    from tpudist.utils import chip_peak_flops, mfu, transformer_train_flops

    flops = transformer_train_flops(
        batch=batch, seq_len=seq_len, d_model=d_model, n_layers=n_layers,
        d_ff=module.d_ff, vocab=module.vocab, window=window,
    )
    util = mfu(flops, dt / steps, data_size * seq_shards, chip_peak_flops())
    return {
        "seq_len": seq_len,
        "seq_shards": seq_shards,
        "tokens_per_sec": round(batch * seq_len * steps / dt, 1),
        "model_flops_per_step": flops,
        "mfu_pct": round(util * 100, 2) if util is not None else None,
        "window": window,
        "block_per_chip": seq_len // seq_shards,
        "regime": "virtual-cpu" if devices[0].platform == "cpu" else "hardware",
    }


def main(argv=None) -> list:
    p = argparse.ArgumentParser()
    p.add_argument("--seq-lens", default="512,1024")
    p.add_argument("--seq-shards", default="1,2,4")
    p.add_argument("--batch", default=4, type=int)
    p.add_argument("--steps", default=8, type=int)
    p.add_argument("--d-model", default=128, type=int)
    p.add_argument("--n-layers", default=2, type=int)
    p.add_argument("--sliding-window", default=None, type=int,
                   help="sliding-window attention: the ring stops at the "
                        "window, so tokens/sec should hold as seq grows")
    args = p.parse_args(argv)
    if args.sliding_window is not None and args.sliding_window < 1:
        p.error(f"--sliding-window must be >= 1, got {args.sliding_window}")

    results = []
    for s in (int(x) for x in args.seq_lens.split(",")):
        for r in (int(x) for x in args.seq_shards.split(",")):
            try:
                res = measure(s, r, batch=args.batch, steps=args.steps,
                              d_model=args.d_model, n_layers=args.n_layers,
                              window=args.sliding_window)
            except ValueError as e:
                print(f"# skip seq={s} shards={r}: {e}", file=sys.stderr)
                continue
            results.append(res)
            print(json.dumps(res))
    return results


if __name__ == "__main__":
    main()
