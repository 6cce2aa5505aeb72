"""Readings the limits of ``correct`` are set from, on the chip, at the
cell's own size, many seeds in one process:

    python3 -m cellbench.calibrate --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3

For every seed the timed step is driven from the seeded state through the
cell's ``check.steps`` steps and compared with the float32 reference (the
sound readings); for every control seed the reference computed in
``reference.CONTROL`` (fp8, the nearest precision below a bf16
configuration) is put in the program's place (the control's readings).  One
JSON object a line.  A training cell's readings need no measured window.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import tempfile
import time
from pathlib import Path

from cellbench import checks, reference
from cellbench.run import HERE, load_cell, load_json, require_tpu


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell, config = load_cell(args.workload)
    devices, _ = require_tpu(cell["chips"], load_json(HERE / "peaks.json"))
    runner = importlib.import_module(f"cellbench.runners.{cell['runner']}")
    job = runner.Job(cell, config, devices)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in sorted(set(seeds) | set(control)):
        with tempfile.TemporaryDirectory(prefix="cellbench_corpus_") as tmp:
            batches = job.loader(seed, Path(tmp))
            first = [next(batches) for _ in range(cell["check"]["steps"])]
        out = {"cell": cell["name"], "seed": seed}
        t0 = time.perf_counter()
        ref = job.reference_readings(seed, first)
        out["reference_s"] = time.perf_counter() - t0
        if seed in seeds:
            t0 = time.perf_counter()
            state, program = job.first_steps(seed, first)
            del state
            out["program_s"] = time.perf_counter() - t0
            out["sound"] = checks.train_gaps(program, ref)
            out["losses"] = [program["losses"], ref["losses"]]
        if seed in control:
            t0 = time.perf_counter()
            low = job.reference_readings(seed, first, mode=reference.CONTROL)
            out["control_s"] = time.perf_counter() - t0
            out["control"] = checks.train_gaps(low, ref)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
