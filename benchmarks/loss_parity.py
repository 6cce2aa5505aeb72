#!/usr/bin/env python3
"""Loss-parity harness: every entry-point equivalent must train the toy
problem to matching loss ("all four entry points reach
matching loss" — the reference's cross-backend eyeball comparison,
SURVEY.md §4.2, as an automated report).

Runs each entry point in-process with a fixed seed and budget, collects
final losses, and reports the spread.  Ideal MSE for the toy task is 0.25
(y = 0.5·ε + x² with ε ~ N(0,1): irreducible variance 0.25²·4 — see
``tpudist/data/toy.py``); "matching" means every entry point lands within
``--tolerance`` of the best.

Usage:  python benchmarks/loss_parity.py [--iters 300] [--tolerance 0.15]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

ENTRY_POINTS = {
    # name -> (example file, extra argv)
    "demo_dp": ("demo", []),
    "demo_dp_host_metrics": ("demo", ["--backend", "host"]),
    "demo_mpi_bootstrap": ("demo_mpi_bootstrap", []),
    "demo_model_split": ("demo_model_split", []),
    # batch matched to the other entry points (its lightning-shape default
    # of 128 halves the sample budget per iteration — a workload difference,
    # not the numerics difference this harness exists to catch)
    "demo_trainer": ("demo_trainer", ["--batch_size", "256"]),
}


def run_entry(name: str, extra, iters: int, seed: int) -> dict:
    import re
    import contextlib
    import io

    import tpudist.runtime.bootstrap as bs

    spec = importlib.util.spec_from_file_location(
        name, REPO / "examples" / f"{ENTRY_POINTS[name][0]}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    argv_save = sys.argv
    sys.argv = ["prog", "--dry_run", "--total_iterations", str(iters),
                "--seed", str(seed), "--log_every", str(iters), *extra]
    bs._INITIALIZED_CTX = None
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(io.StringIO()):
            mod.main()
    finally:
        sys.argv = argv_save
    out = buf.getvalue()
    losses = [float(v) for v in re.findall(r"'model_[XY]': ([0-9.eE+-]+)", out)]
    if not losses:
        raise RuntimeError(f"{name}: no final losses in output:\n{out[-500:]}")
    return {"entry_point": name, "final_losses": losses,
            "mean_loss": sum(losses) / len(losses)}


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--iters", default=300, type=int)
    p.add_argument("--seed", default=0, type=int)
    p.add_argument("--tolerance", default=0.15, type=float,
                   help="max allowed mean-loss gap to the best entry point")
    args = p.parse_args(argv)

    results = []
    for name, (_, extra) in ENTRY_POINTS.items():
        r = run_entry(name, extra, args.iters, args.seed)
        results.append(r)
        print(json.dumps(r))

    best = min(r["mean_loss"] for r in results)
    worst = max(r["mean_loss"] for r in results)
    summary = {
        "summary": "loss_parity",
        "best_mean_loss": round(best, 4),
        "worst_mean_loss": round(worst, 4),
        "spread": round(worst - best, 4),
        "tolerance": args.tolerance,
        "parity": worst - best <= args.tolerance,
        "ideal_mse": 0.25,
    }
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
