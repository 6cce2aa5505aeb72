#!/usr/bin/env python3
"""DP scaling-efficiency harness.

The reference publishes no benchmarks (SURVEY.md §6); the north-star target
set for this repo is samples/sec/chip with ≥80% data-parallel scaling
efficiency as the mesh grows.  This harness measures the toy workload's
throughput at a ladder of data-parallel world sizes on whatever devices are
present and reports efficiency relative to the single-device rung.

On a real pod every rung uses distinct chips and the numbers are true
scaling measurements.  On a CPU host with virtual devices
(``--xla_force_host_platform_device_count=8``) the rungs share one physical
machine — the harness still validates the mechanics end-to-end (and the
tests run it that way), but throughput ratios are not hardware truth; the
report marks which regime produced it.

Output: one JSON line per rung + a summary line, e.g.
  {"world_size": 8, "samples_per_sec": ..., "per_chip": ...,
   "efficiency_vs_1": 0.97, ...}

Usage:
  python benchmarks/scaling.py [--iters 64] [--batch-per-chip 256]
  python benchmarks/scaling.py --world-sizes 1,2,4,8
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def measure_rung(devices, *, batch_per_chip: int, window: int, chunks: int,
                 warmup: int = 3) -> dict:
    """Throughput of the reference workload (two ToyMLPs, Adam, demo.py hot
    loop) data-parallel over ``devices``, scanned-window methodology
    (identical to bench.py so rungs are comparable)."""
    from tpudist.data import make_toy_data
    from tpudist.models import create_toy_model
    from tpudist.runtime.mesh import AXIS_DATA
    from tpudist.train import init_model_states, make_scanned_train_step

    mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    mx, px = create_toy_model(kx)
    my, py = create_toy_model(ky)
    models = {"model_X": (mx.apply, px), "model_Y": (my.apply, py)}
    tx = optax.adam(1e-3)
    states = init_model_states(models, tx)
    step = make_scanned_train_step({k: f for k, (f, _) in models.items()}, tx, mesh)

    batch = batch_per_chip * len(devices)
    data = make_toy_data(seed=0)
    repl = NamedSharding(mesh, P())
    x_all = jax.device_put(data.x, repl)
    y_all = jax.device_put(data.y, repl)
    idx = jax.device_put(
        np.random.default_rng(0).integers(
            0, len(data), size=(window, batch)
        ).astype(np.int32),
        repl,
    )

    for _ in range(warmup):
        states, losses = step(states, x_all, y_all, idx)
    jax.block_until_ready(losses)
    t0 = time.perf_counter()
    for _ in range(chunks):
        states, losses = step(states, x_all, y_all, idx)
    jax.block_until_ready(losses)
    dt = time.perf_counter() - t0

    sps = batch * window * chunks / dt
    return {
        "world_size": len(devices),
        "batch_per_chip": batch_per_chip,
        "samples_per_sec": round(sps, 1),
        "per_chip": round(sps / len(devices), 1),
    }


def main(argv=None) -> list:
    p = argparse.ArgumentParser()
    p.add_argument("--world-sizes", default=None,
                   help="comma list; default: 1,2,4,… up to all devices")
    p.add_argument("--batch-per-chip", default=256, type=int)  # demo.py:145
    p.add_argument("--window", default=32, type=int)
    p.add_argument("--chunks", default=16, type=int)
    args = p.parse_args(argv)

    devices = jax.devices()
    if args.world_sizes:
        sizes = [int(s) for s in args.world_sizes.split(",")]
    else:
        sizes, n = [], 1
        while n <= len(devices):
            sizes.append(n)
            n *= 2
    virtual = devices[0].platform == "cpu"

    results = []
    base_per_chip = None
    for n in sizes:
        if n > len(devices):
            print(f"# skipping world_size {n}: only {len(devices)} devices",
                  file=sys.stderr)
            continue
        r = measure_rung(devices[:n], batch_per_chip=args.batch_per_chip,
                         window=args.window, chunks=args.chunks)
        if base_per_chip is None:
            base_per_chip = r["per_chip"]
        r["efficiency_vs_1"] = round(r["per_chip"] / base_per_chip, 3)
        r["regime"] = "virtual-cpu" if virtual else "hardware"
        results.append(r)
        print(json.dumps(r))

    if results:
        top = results[-1]
        # The ≥80% efficiency target is a statement about hardware scaling;
        # virtual-cpu rungs share one machine's cores, so their ratios only
        # validate mechanics — report no verdict there.
        print(json.dumps({
            "summary": "dp_scaling",
            "max_world_size": top["world_size"],
            "efficiency_vs_1": top["efficiency_vs_1"],
            "target": 0.8,
            "meets_target": (top["efficiency_vs_1"] >= 0.8
                             if top["regime"] == "hardware" else None),
            "regime": top["regime"],
        }))
    return results


if __name__ == "__main__":
    main()
