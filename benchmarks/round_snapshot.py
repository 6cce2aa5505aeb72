#!/usr/bin/env python3
"""Round-end artifact snapshotter: freeze the DP-scaling and loss-parity
reports into ``SCALING_r{NN}.json`` / ``PARITY_r{NN}.json`` at the repo
root so round-over-round regressions outside the bench.py headline are
visible (each file is the harness's JSON lines verbatim).

- scaling runs on an 8-device virtual CPU mesh in a subprocess pinned
  to the CPU (``JAX_PLATFORMS=cpu``; the parent never touches a
  backend); rung ratios there validate mechanics, not hardware truth,
  and are labeled ``regime: virtual-cpu``.
- parity also runs on the virtual mesh (demo_model_split needs a 2-wide
  model axis): five entry points, fixed
  seed, final-loss spread — a numerics check, platform-independent.

Usage: python benchmarks/round_snapshot.py [--round N] [--iters 300]
Round defaults to (highest existing BENCH_r*.json round) + 1 — the round
currently being built.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_VIRTUAL_STUB = """
import os
# the virtual-mesh children run on the CPU and never claim a chip
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_num_cpu_devices", 8)
import sys
sys.path.insert(0, {repo!r})
sys.argv = ["bench"]
import importlib.util
spec = importlib.util.spec_from_file_location(
    {name!r}, {repo!r} + "/benchmarks/" + {name!r} + ".py")
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.main({argv!r})
"""


def detect_round() -> int:
    try:
        from benchmarks._round import current_round
    except ImportError:
        from _round import current_round

    return current_round()


def _stamp_artifact_header(path: Path, family: str, rnd: int) -> None:
    """Stamp the ``{"artifact": {schema, family, round}}`` header into an
    artifact this snapshot just wrote — declared metadata beats filename
    parsing (``tpudist.plan.artifacts`` validates it against both).
    Idempotent; existing header fields win."""
    try:
        text = path.read_text()
    except OSError:
        return
    header = {"schema": 1, "family": family, "round": rnd}
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict):
        declared = obj.get("artifact")
        obj["artifact"] = {**header, **declared} \
            if isinstance(declared, dict) else header
        path.write_text(json.dumps(obj, indent=1) + "\n")
        return
    if isinstance(obj, list):
        return  # plain-array artifacts: the loader wraps them as rows
    # JSONL: prepend one header line unless the first line already is one
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if lines and '"artifact"' in lines[0]:
        return
    path.write_text(json.dumps({"artifact": header}) + "\n" + text)


def run_lines(cmd: list[str], timeout: int,
              env: dict | None = None) -> list[dict]:
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{cmd[:2]} failed rc={proc.returncode}:\n{proc.stderr[-2000:]}")
    rows = []
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            rows.append(json.loads(line))
    if not rows:
        raise RuntimeError(f"{cmd[:2]}: no JSON rows in output")
    return rows


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--round", default=None, type=int)
    p.add_argument("--iters", default=300, type=int,
                   help="loss-parity training budget per entry point")
    args = p.parse_args(argv)
    rnd = args.round if args.round is not None else detect_round()

    for label, name, argv in (
        ("SCALING", "scaling", []),
        ("PARITY", "loss_parity", ["--iters", str(args.iters)]),
    ):
        rows = run_lines(
            [sys.executable, "-c",
             _VIRTUAL_STUB.format(repo=str(REPO), name=name, argv=argv)],
            timeout=1800,
        )
        if label == "SCALING":
            # Second regime: TRUE multi-process rungs through the tpurun
            # agent (r4 verdict #3 — the virtual rows alone misread as a
            # scaling collapse).  Detailed artifact:
            # SCALING_MULTIPROC_r{NN}.json; its rung lines merge here.
            # A multiproc failure must not void the completed virtual
            # rows or abort the PARITY pass — record it as a row.
            mp_out = REPO / f"SCALING_MULTIPROC_r{rnd:02d}.json"
            try:
                rows += run_lines(
                    [sys.executable, str(REPO / "benchmarks"
                                         / "scaling_multiproc.py"),
                     "--iters", "32", "--out", str(mp_out)],
                    timeout=900,
                )
            except Exception as e:
                rows.append({"regime": "multiprocess-cpu",
                             "error": repr(e)})
            if mp_out.exists():
                _stamp_artifact_header(mp_out, "SCALING_MULTIPROC", rnd)
        out = REPO / f"{label}_r{rnd:02d}.json"
        out.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        _stamp_artifact_header(out, label, rnd)
        print(f"{out.name}: {json.dumps(rows[-1])}")

    # Serving joins the round scoreboard: serve_bench writes its own
    # artifact (rate rungs + block-size sweep + overhead split); smoke
    # scale here — real numbers come from hardware rounds.  A serving
    # failure must not void the completed SCALING/PARITY snapshots.
    import os

    serve_out = REPO / f"BENCH_SERVE_r{rnd:02d}.json"
    try:
        # --multiproc 2: the tpurun-launched multi-process serve rung
        # (2 disaggregated workers, each SPMD over a 2-device emulated
        # mesh, serialized KV handoff) freezes into the same artifact.
        # --spec: the speculative-decode sweep (draft size x K vs the
        # non-spec device-busy floor) joins the round scoreboard too.
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "serve_bench.py"),
             "--smoke", "--multiproc", "2", "--devices-per-proc", "2",
             "--spec", "--out", str(serve_out)],
            timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        # surface the last MEASUREMENT row, not the trailing
        # {"wrote": ...} status line serve_bench prints after it
        data = [r for r in rows if "wrote" not in r] or rows
        print(f"{serve_out.name}: {json.dumps(data[-1])}")
    except Exception as e:
        serve_out.write_text(json.dumps(
            {"regime": "cpu-smoke", "error": repr(e)}) + "\n")
        print(f"{serve_out.name}: error {e!r}")

    # Elastic world-size rung (PR 12): goodput retained under a mid-run
    # rank kill — elastic-resume vs fixed-size-restart vs no-fault
    # baseline, through real tpurun-launched multi-process runs.
    # Failure-isolated like the serve snapshot.
    elastic_out = REPO / f"BENCH_ELASTIC_r{rnd:02d}.json"
    try:
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "elastic_bench.py"),
             "--out", str(elastic_out)],
            timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        print(f"{elastic_out.name}: {json.dumps(rows[-1])}")
    except Exception as e:
        elastic_out.write_text(json.dumps(
            {"regime": "multiprocess-cpu", "error": repr(e)}) + "\n")
        print(f"{elastic_out.name}: error {e!r}")

    # Observability rung (PR 13): measured metrics+trace overhead twin
    # plus the chaos cross-pool trace acceptance booleans, frozen as
    # BENCH_OBS_r{NN}.json.  Failure-isolated like the serve snapshot.
    obs_out = REPO / f"BENCH_OBS_r{rnd:02d}.json"
    try:
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "obs_bench.py"),
             # --max-new 48: ≥6 decode blocks per request, so the twin's
             # per-handle TPOT amortizes block-boundary quantization (at
             # the smoke default of 10 a µs-scale host delta can cost a
             # whole extra dispatch block and read as a 2x outlier)
             "--smoke", "--pairs", "7", "--max-new", "48",
             "--out", str(obs_out)],
            timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        data = [r for r in rows if "wrote" not in r] or rows
        print(f"{obs_out.name}: {json.dumps(data[-1])}")
    except Exception as e:
        obs_out.write_text(json.dumps(
            {"regime": "cpu-smoke", "error": repr(e)}) + "\n")
        print(f"{obs_out.name}: error {e!r}")

    # Graceful-degradation rung (host-RAM KV tier / preemption / SLO
    # shedding): resume-vs-re-prefill TTFT, protected-tenant attainment
    # under overload, preemption twin — frozen as
    # BENCH_SESSION_r{NN}.json.  Failure-isolated like the serve
    # snapshot.
    session_out = REPO / f"BENCH_SESSION_r{rnd:02d}.json"
    try:
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "session_bench.py"),
             "--smoke", "--out", str(session_out)],
            timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        data = [r for r in rows if "wrote" not in r] or rows
        print(f"{session_out.name}: {json.dumps(data[-1])}")
    except Exception as e:
        session_out.write_text(json.dumps(
            {"regime": "cpu-smoke", "error": repr(e)}) + "\n")
        print(f"{session_out.name}: error {e!r}")

    # Per-tenant adapter rung (paged multi-LoRA pool): adapters-per-
    # batch decode-throughput sweep vs base-only + oracle byte-identity
    # + churn compile pins, frozen as BENCH_ADAPTER_r{NN}.json.
    # Failure-isolated like the serve snapshot.
    adapter_out = REPO / f"BENCH_ADAPTER_r{rnd:02d}.json"
    try:
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "adapter_bench.py"),
             "--out", str(adapter_out)],
            timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        data = [r for r in rows if "wrote" not in r] or rows
        print(f"{adapter_out.name}: {json.dumps(json.loads(data[-1]))}")
    except Exception as e:
        adapter_out.write_text(json.dumps(
            {"regime": "cpu-smoke", "error": repr(e)}) + "\n")
        print(f"{adapter_out.name}: error {e!r}")

    # Fleet-router rung (PR 16): affinity vs round-robin on resume-TTFT
    # and prefix-cache hit rate, plus the replica-kill migration
    # booleans — frozen as BENCH_ROUTER_r{NN}.json.  Failure-isolated
    # like the serve snapshot.
    router_out = REPO / f"BENCH_ROUTER_r{rnd:02d}.json"
    try:
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "router_bench.py"),
             "--smoke", "--out", str(router_out)],
            timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        data = [r for r in rows if "wrote" not in r] or rows
        print(f"{router_out.name}: {json.dumps(data[-1])}")
    except Exception as e:
        router_out.write_text(json.dumps(
            {"regime": "cpu-smoke", "error": repr(e)}) + "\n")
        print(f"{router_out.name}: error {e!r}")

    # Online draft-distillation rung (PR 17): the distribution-shift
    # flywheel — frozen-draft acceptance decay vs gated-hot-swap
    # recovery, swap-latency + gate timelines, byte-identity and
    # compile-pin booleans — frozen as BENCH_DISTILL_r{NN}.json.
    # Failure-isolated like the serve snapshot.
    distill_out = REPO / f"BENCH_DISTILL_r{rnd:02d}.json"
    try:
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "distill_bench.py"),
             "--smoke", "--out", str(distill_out)],
            timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        data = [r for r in rows if "wrote" not in r] or rows
        print(f"{distill_out.name}: {json.dumps(json.loads(data[-1]))}")
    except Exception as e:
        distill_out.write_text(json.dumps(
            {"regime": "cpu-smoke", "error": repr(e)}) + "\n")
        print(f"{distill_out.name}: error {e!r}")

    # Structured-output rung (PR 18): mixed constrained/unconstrained
    # batch — constrained-vs-free per-token overhead, free-lane
    # byte-identity, grammar-churn compile pins — frozen as
    # BENCH_GRAMMAR_r{NN}.json.  Failure-isolated like the serve
    # snapshot.
    grammar_out = REPO / f"BENCH_GRAMMAR_r{rnd:02d}.json"
    try:
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "grammar_bench.py"),
             "--out", str(grammar_out)],
            timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        data = [r for r in rows if "wrote" not in r] or rows
        print(f"{grammar_out.name}: {json.dumps(data[-1])}")
    except Exception as e:
        grammar_out.write_text(json.dumps(
            {"regime": "cpu-smoke", "error": repr(e)}) + "\n")
        print(f"{grammar_out.name}: error {e!r}")

    # Decode per-op attribution (VERDICT Weak #2): trace the bf16 fused
    # decode loop and freeze the table naming the non-matmul residual.
    # Failure-isolated like the serve snapshot.
    prof_out = REPO / f"DECODE_PROFILE_r{rnd:02d}.json"
    try:
        run_lines(
            [sys.executable,
             str(REPO / "benchmarks" / "profile_summary.py"),
             "--capture-decode", "--out", str(prof_out)],
            timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        print(f"{prof_out.name}: written")
    except Exception as e:
        prof_out.write_text(json.dumps({"error": repr(e)}) + "\n")
        print(f"{prof_out.name}: error {e!r}")

    # Planner honesty rung (the measurement-driven planner PR): predict
    # every candidate from the round's frozen artifacts, measure them
    # live, freeze the error band the planner quotes on every report.
    # plan_bench writes its own declared header.  Failure-isolated like
    # the serve snapshot.
    plan_out = REPO / f"PLAN_r{rnd:02d}.json"
    try:
        rows = run_lines(
            [sys.executable, str(REPO / "benchmarks" / "plan_bench.py"),
             "--round", str(rnd), "--out", str(plan_out)],
            timeout=1800,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        print(f"{plan_out.name}: {json.dumps(rows[-1])}")
    except Exception as e:
        plan_out.write_text(json.dumps({"error": repr(e)}) + "\n")
        print(f"{plan_out.name}: error {e!r}")

    # Every artifact this snapshot wrote carries the declared header the
    # plan loader validates (declared metadata beats filename parsing);
    # error-path stubs get stamped too, so a failed bench still declares
    # what it was.
    for family, path in (
        ("BENCH_SERVE", serve_out), ("BENCH_ELASTIC", elastic_out),
        ("BENCH_OBS", obs_out), ("BENCH_SESSION", session_out),
        ("BENCH_ADAPTER", adapter_out), ("BENCH_ROUTER", router_out),
        ("BENCH_DISTILL", distill_out), ("BENCH_GRAMMAR", grammar_out),
        ("DECODE_PROFILE", prof_out),
    ):
        if path.exists():
            _stamp_artifact_header(path, family, rnd)


if __name__ == "__main__":
    main()
