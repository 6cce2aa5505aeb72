"""One process, one cell, once:

    python3 -m cellbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``workloads/<cell>.json`` names its
configuration (``configs/<config>.json``) and its runner
(``runners/<runner>.py``); every file of ``layer_metrics/`` names a reader
(``readers/*.py``).  This file holds no cell, model, runner or metric name.
It refuses anything but the TPUs of ``peaks.json``, as many as the cell
asks for; there is no CPU fallback.  The last line of its standard output
is the one JSON object of the benchmark's contract.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is counted from here, before jax loads

import argparse
import importlib
import json
import shutil
import sys
from pathlib import Path
from typing import NamedTuple, Optional

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
SCRATCH = REPO / ".cellbench"   # git-ignored: traces of the traced run


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple:
    """``(cell, config)`` from the data files; the cell's file name is its
    name in ``BENCHMARK.json``."""
    path = HERE / "workloads" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no such cell: {path} does not exist")
    cell = load_json(path)
    cell["name"] = name
    return cell, load_json(HERE / "configs" / f"{cell['config']}.json")


def require_tpu(chips: int, peaks: dict) -> tuple:
    """``(devices, peak)``: the first ``chips`` devices, or exit non-zero."""
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: jax reports {devices[0].platform} "
                         f"({kind}); this benchmark has no CPU fallback")
    if kind not in peaks:
        raise SystemExit(f"device_kind {kind!r} is not in peaks.json "
                         f"({sorted(peaks)}): add it with its source")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, jax reports "
                         f"{len(devices)}")
    return devices[:chips], peaks[kind]


class Reading(NamedTuple):
    """What a per-layer reader is given."""
    cell: dict
    config: dict
    peak: dict
    counters: dict
    spans: dict      # host span name -> [seconds], one entry per step
    reds: dict       # chip -> trace_reduce.DeviceReduction (traced run)


def layer_metrics(reading: Reading) -> dict:
    """Every metric file of ``layer_metrics/`` that is for this cell (its
    ``cells`` is absent or names it) and whose reader finds something to
    read in this run: ``name -> {"value", "unit"}``.  A reader returns
    ``None`` where there is nothing to read; one that raises ends the run."""
    out = {}
    for path in sorted((HERE / "layer_metrics").glob("*.json")):
        spec = load_json(path)
        if reading.cell["name"] not in spec.get("cells",
                                                [reading.cell["name"]]):
            continue
        module, fn = spec["reader"].split(":")
        try:
            value = getattr(importlib.import_module(module), fn)(reading)
        except Exception as e:
            raise SystemExit(
                f"per-layer metric {spec['name']!r} ({path.name}): its reader "
                f"{spec['reader']} raised {type(e).__name__}: {e}") from e
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def end_to_end(manifest: dict, cell_name: str, values: dict) -> dict:
    """The end-to-end metrics ``BENCHMARK.json`` declares for this cell."""
    out = {}
    for m in manifest["end_to_end"]:
        if cell_name in m.get("workloads", [cell_name]):
            out[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return out


def result_line(outcome: dict, *, manifest: dict, cell: dict, config: dict,
                peak: dict, devices, trace: bool) -> dict:
    """The contract's object from a runner's outcome."""
    import jax

    from cellbench import trace_reduce

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": jax.device_count(),
              "memory_peak_bytes": int(outcome["memory_peak_bytes"])}
    line = {"correct": bool(outcome["correct"]),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"])}
    if not trace:
        line["metrics"] = end_to_end(manifest, cell["name"],
                                     outcome["end_to_end"])
        line["device"] = device
        return line
    loaded = trace_reduce.load(trace_reduce.find_xplane(outcome["trace_dir"]))
    reds = trace_reduce.reduce_trace(
        loaded, **outcome.get("trace_hints", {}))
    if not reds:
        raise SystemExit("the trace holds no whole step on any device")
    line["metrics"] = layer_metrics(Reading(
        cell, config, peak, outcome["counters"], outcome["spans"], reds))
    n = len(reds)
    device["busy_s"] = sum(r.busy_ns for r in reds.values()) / n / 1e9
    device["window_s"] = sum(r.window_ns for r in reds.values()) / n / 1e9
    line["device"] = device
    line["breakdown"] = trace_reduce.breakdown(
        reds, loaded, outcome["host_span_names"])
    return line


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, config = load_cell(args.workload)
    manifest = load_json(REPO / "BENCHMARK.json")
    devices, peak = require_tpu(cell["chips"], load_json(HERE / "peaks.json"))
    print(f"[run] cell={cell['name']} config={cell['config']} "
          f"runner={cell['runner']} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} device={devices[0].device_kind} "
          f"x{len(devices)}", flush=True)
    if args.trace:
        shutil.rmtree(SCRATCH / "trace" / cell["name"], ignore_errors=True)
    runner = importlib.import_module(f"cellbench.runners.{cell['runner']}")
    outcome = runner.run(cell=cell, config=config, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         devices=devices, t0=T0, scratch=SCRATCH)
    line = result_line(outcome, manifest=manifest, cell=cell, config=config,
                       peak=peak, devices=devices, trace=bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
