#!/usr/bin/env python3
"""Planner honesty loop: predict-vs-measure on live rung geometries.

The planner (``tpudist.plan``) claims it can rank configs from the
frozen artifacts.  This bench closes the loop: on each rung geometry it

1. MEASURES the base candidate (``dp`` for training, the dense-``K=8``
   engine for serving) plus a micro-measured all-reduce bandwidth and
   feeds both in as a :class:`tpudist.plan.Calibration`,
2. PREDICTS every candidate through the same ``plan_training`` /
   ``plan_serving`` entry points the auto modes call,
3. MEASURES every candidate for real — training steps through the same
   step factories ``Trainer._fit_lm`` builds, serving rungs through a
   live ``InferenceServer`` driven by ``serve_bench.run_rate`` — and
4. freezes per-config ``predicted_s`` / ``measured_s`` / ``error_frac``
   plus the predicted-best-vs-measured-best verdict into
   ``PLAN_r{NN}.json``.

The frozen ``error_band`` (max/p50 ``error_frac``) is what
``planner._error_band`` quotes on every future plan report: the
planner's predictions come with the measured size of their own error.

Rung geometries (two per workload, so a ranking that only works at one
scale is caught): training on 4- and 8-device virtual CPU meshes
(subprocess-pinned); serving on two engine
geometries (slots x max_len).  Virtual-CPU rungs validate the planner's
MECHANICS — the match verdict and error band are real measurements of
the cost model on this host, not hardware truth.

Usage: python benchmarks/plan_bench.py [--round N] [--out PATH]
                                       [--iters N] [--requests N]
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: Measured-vs-measured tie tolerance for the match verdict: the plan
#: is correct when its pick measures within this fraction of the true
#: floor.  Sized to this host's observed run-to-run variance — the
#: near-tied sharded-family configs (fsdp vs zero1) flip ordering
#: across runs by up to ~8%, so a tighter verdict would grade noise,
#: not the planner.
MATCH_RTOL = 0.10

_STUB = """
import os
# BOTH pins are required: jax.config for this process's first backend
# resolution, and the env var for every code path that re-resolves from
# the environment.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count={devices}")
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", {devices})
import sys
sys.path.insert(0, {repo!r})
sys.argv = ["plan_bench"]
import importlib.util
spec = importlib.util.spec_from_file_location(
    "plan_bench", {repo!r} + "/benchmarks/plan_bench.py")
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.main({argv!r})
"""


_ROUND_RE = __import__("re").compile(r"^[A-Z][A-Z0-9_]*_r(\d+)\.json$")


def detect_round() -> int:
    """One past the highest round ANY family has frozen.  The plain
    ``BENCH_r*`` counter (benchmarks/_round.py) lags the per-family
    artifacts by many rounds in this tree; writing PLAN under its number
    would fail the artifact loader's stale check against the newest
    BENCH_SERVE round."""
    rounds = [int(m.group(1)) for p in REPO.glob("*_r*.json")
              if (m := _ROUND_RE.match(p.name))]
    return (max(rounds) + 1) if rounds else 1


# -- training rung ------------------------------------------------------


def _train_candidates(n_devices):
    from tpudist.plan import TrainCandidate

    cands = [TrainCandidate("dp"), TrainCandidate("fsdp"),
             TrainCandidate("zero1")]
    if n_devices >= 4:
        # the facade's pp default: stages=2, one microbatch per stage
        cands.append(TrainCandidate("pp", stages=2, microbatches=2))
    return cands


def _make_train_runner(cand, flax_mod, params, tx, tokens):
    """Compiled step runner for one candidate, built EXACTLY the way
    ``Trainer._fit_lm`` builds it (same factories, same sharding
    derivation) — the bench measures what the plan enacts.  Returns a
    closure ``run(iters) -> seconds_per_step`` over persistent state."""
    import jax

    from tpudist.train import init_lm_state, make_lm_train_step, \
        token_sharding

    if cand.strategy == "pp":
        from tpudist.parallel import (
            make_pp_lm_train_step,
            pp_state_sharding,
            stack_block_params,
        )
        from tpudist.runtime.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(data=-1, stage=cand.stages),
                         axis_names=("data", "stage"))
        state = init_lm_state(stack_block_params(params, cand.stages), tx)
        sharding = pp_state_sharding(mesh, state)
        state = jax.device_put(state, sharding)
        step = make_pp_lm_train_step(
            mesh, flax_mod, tx, n_stages=cand.stages,
            num_microbatches=cand.microbatches or cand.stages,
            schedule="1f1b", state_sharding=sharding)
    else:
        from tpudist.runtime.mesh import data_parallel_mesh

        mesh = data_parallel_mesh()
        state = init_lm_state(params, tx)
        sharding = None
        if cand.strategy in ("fsdp", "zero1"):
            from tpudist.parallel import fsdp_sharding, zero1_sharding

            sharding = (fsdp_sharding(mesh, state)
                        if cand.strategy == "fsdp"
                        else zero1_sharding(mesh, state))
            state = jax.device_put(state, sharding)
        step = make_lm_train_step(flax_mod.apply, tx, mesh,
                                  state_sharding=sharding)

    toks = jax.device_put(tokens, token_sharding(mesh))
    state, loss = step(state, toks)  # compile
    jax.block_until_ready(loss)
    box = [state]

    def run(iters: int) -> float:
        st = box[0]
        t0 = time.perf_counter()
        for _ in range(iters):
            st, loss = step(st, toks)
        jax.block_until_ready((st, loss))
        dt = (time.perf_counter() - t0) / iters
        box[0] = st
        return dt

    return run


def _interleaved_measure(runners: dict, iters: int,
                         reps: int = 3) -> dict:
    """Per-candidate best seconds/step, timed ROUND-ROBIN: each rep
    cycles through every candidate before the next rep starts, so host
    load drift hits all candidates equally instead of biasing whichever
    one ran during a quiet minute (back-to-back blocks measured up to
    ~20% cross-candidate skew on this box)."""
    best = {name: float("inf") for name in runners}
    for _ in range(reps):
        for name, run in runners.items():
            best[name] = min(best[name], run(iters))
    return best


def _collective_bandwidth() -> "float | None":
    """Micro-measured all-reduce bandwidth on the data mesh, in the same
    units the cost model divides by (``wire_bytes / bw``): ring-factor
    bytes moved per second."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpudist.runtime.mesh import data_parallel_mesh

    n = jax.device_count()
    if n < 2:
        return None
    mesh = data_parallel_mesh()
    m = 1 << 18  # 1 MiB of f32 per shard
    x = jnp.ones((n, m), jnp.float32)
    f = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P(), check_vma=False))
    jax.block_until_ready(f(x))  # compile
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    ring_bytes = 2.0 * (n - 1) / n * (m * 4)
    return ring_bytes / max(dt, 1e-9)


def _calibrate_state_ratio(tx, iters: int) -> float:
    """Measured zero1/dp step ratio on a PROXY workload (half the bench
    model: different size, same host) — the transferable calibration
    the cost model's ``state_shard_ratio`` quotes.  Predicting the
    TARGET workload's fsdp/zero1 from a proxy measurement is the test:
    circular it is not."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudist.models import create_transformer
    from tpudist.plan import TrainCandidate

    proxy_mod, proxy_params = create_transformer(
        jax.random.PRNGKey(1), seq_len=16, vocab=64, d_model=32,
        n_layers=2, n_heads=2, d_ff=64)
    host = jax.device_get(proxy_params)
    toks = np.random.default_rng(1).integers(
        0, 64, size=(8, 16)).astype(np.int32)
    runners = {
        c.strategy: _make_train_runner(
            c, proxy_mod, jax.tree.map(jnp.asarray, host), tx, toks)
        for c in (TrainCandidate("dp"), TrainCandidate("zero1"))}
    best = _interleaved_measure(runners, iters)
    return best["zero1"] / best["dp"]


def _rung_training(n_devices: int, iters: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpudist.models import create_transformer
    from tpudist.plan import (
        Calibration,
        TrainWorkload,
        load_artifacts,
        plan_training,
    )

    assert jax.device_count() == n_devices, (
        jax.device_count(), n_devices)
    cfg = dict(vocab=128, d_model=64, n_layers=4, n_heads=4, d_ff=128)
    seq, batch = 32, 8
    flax_mod, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=seq, **cfg)
    tx = optax.adam(1e-3)
    tokens = np.random.default_rng(0).integers(
        0, cfg["vocab"], size=(batch, seq)).astype(np.int32)

    cands = _train_candidates(n_devices)
    # each candidate's first (donating) step consumes its state buffers,
    # so every candidate starts from a fresh copy of the host params
    host_params = jax.device_get(params)
    runners = {
        c.name: _make_train_runner(
            c, flax_mod, jax.tree.map(jnp.asarray, host_params), tx,
            tokens)
        for c in cands}
    measured = _interleaved_measure(runners, iters)

    pb = sum(int(leaf.size) * leaf.dtype.itemsize
             for leaf in jax.tree.leaves(host_params))
    wl = TrainWorkload(
        param_bytes=float(pb),
        flops_per_step=6.0 * (pb / 4.0) * batch * seq,
        n_devices=n_devices, global_batch=batch, lm=True,
        precision="fp32",
        device_kind=jax.devices()[0].device_kind or "cpu")
    calib = Calibration(base_s=measured["dp"],
                        collective_bytes_per_s=_collective_bandwidth(),
                        state_shard_ratio=_calibrate_state_ratio(
                            tx, max(5, iters // 2)))
    report = plan_training(wl, load_artifacts(), candidates=cands,
                           calibration=calib)
    predicted_best = report.pick().candidate.name

    configs = []
    for pc in report.ranked:
        name = pc.candidate.name
        meas = measured[name]
        configs.append({
            "name": name,
            "predicted_s": round(pc.estimate.seconds, 6),
            "measured_s": round(meas, 6),
            "error_frac": round(
                abs(pc.estimate.seconds - meas) / meas, 4),
        })
    measured_best = min(measured, key=measured.get)
    floor = measured[measured_best]
    match = measured[predicted_best] <= floor * (1 + MATCH_RTOL) + 1e-9
    return {
        "kind": "training",
        "regime": "virtual-cpu",
        "geometry": {"platform": jax.default_backend(),
                     "n_devices": n_devices},
        "iters": iters,
        "base": "dp",
        "collective_bytes_per_s": calib.collective_bytes_per_s,
        "configs": configs,
        "predicted_best": predicted_best,
        "measured_best": measured_best,
        "match": bool(match),
    }


# -- serving rung -------------------------------------------------------


def _serve_candidates(slots):
    from tpudist.plan import ServeCandidate

    return [
        ServeCandidate(decode_block=8, slots=slots),
        ServeCandidate(decode_block=1, slots=slots),
        ServeCandidate(decode_block=8, spec_layers=1, spec_k=4,
                       slots=slots),
        ServeCandidate(decode_block=8, spec_layers=1, spec_k=8,
                       slots=slots),
    ]


def _measure_serve(module, params, cand, slots, n_requests, vocab):
    """Live TPOT/TTFT for one engine config: real ``InferenceServer``,
    burst load through ``serve_bench.run_rate``."""
    import numpy as np

    from tpudist.serve import InferenceServer, ServeConfig

    try:
        from benchmarks import serve_bench
    except ImportError:
        import serve_bench

    kw = dict(num_slots=slots, queue_limit=max(16, 2 * n_requests),
              prefill_pad=8, decode_block=cand.decode_block)
    if cand.spec_layers is not None:
        kw.update(spec=True, spec_draft_layers=cand.spec_layers,
                  spec_k=cand.spec_k)
    server = InferenceServer(module, params, ServeConfig(**kw),
                             install_signal_handler=False).start()
    try:
        # warm both prefill pad buckets + the decode/draft buckets so
        # the timed rung measures steady state, not compiles
        for plen in (6, 12):
            prompt = (np.arange(plen) % vocab).astype(np.int32)
            server.submit(prompt, max_new=32, seed=0).wait()
        row = serve_bench.run_rate(
            server, rate_rps=1e9, n_requests=n_requests, vocab=vocab,
            prompt_lens=(6, 12), max_news=(32, 32), seed=1)
    finally:
        server.close()
    return row


def _rung_serving(slots: int, max_len: int, n_requests: int) -> dict:
    import jax

    from tpudist.models import create_transformer
    from tpudist.plan import Calibration, load_artifacts, plan_serving
    from tpudist.plan.planner import engine_workload

    cfg = dict(vocab=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
               max_len=max_len)
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=16, **cfg)

    cands = _serve_candidates(slots)
    measured = {c.name: _measure_serve(module, params, c, slots,
                                       n_requests, cfg["vocab"])
                for c in cands}

    base_name = cands[0].name  # dense K=8 anchors the calibration
    wl = engine_workload(module, params, n_devices=1, slots=slots)
    calib = Calibration(base_s=measured[base_name]["tpot_s_p50"])
    report = plan_serving(wl, load_artifacts(), candidates=cands,
                          calibration=calib)
    predicted_best = report.pick().candidate.name

    configs = []
    for pc in report.ranked:
        name = pc.candidate.name
        row = measured[name]
        meas = row["tpot_s_p50"]
        configs.append({
            "name": name,
            "predicted_s": round(pc.estimate.seconds, 6),
            "measured_s": meas,
            "error_frac": round(
                abs(pc.estimate.seconds - meas) / meas, 4)
            if meas else None,
            "predicted_ttft_s": round(pc.ttft.seconds, 6)
            if pc.ttft is not None else None,
            "measured_ttft_s": row.get("ttft_s_p50"),
        })
    tpots = {n: r["tpot_s_p50"] for n, r in measured.items()
             if r["tpot_s_p50"]}
    measured_best = min(tpots, key=tpots.get)
    floor = tpots[measured_best]
    match = tpots.get(predicted_best, float("inf")) \
        <= floor * (1 + MATCH_RTOL) + 1e-9
    return {
        "kind": "serving",
        "regime": "cpu-smoke",
        "geometry": {"platform": jax.default_backend(), "n_devices": 1},
        "slots": slots,
        "max_len": max_len,
        "n_requests": n_requests,
        "base": base_name,
        "configs": configs,
        "predicted_best": predicted_best,
        "measured_best": measured_best,
        "match": bool(match),
    }


# -- orchestration ------------------------------------------------------


def _error_band(rungs) -> "dict | None":
    fracs = [c["error_frac"] for r in rungs
             for c in r.get("configs", [])
             if isinstance(c.get("error_frac"), (int, float))]
    if not fracs:
        return None
    return {"max_frac": round(max(fracs), 4),
            "p50_frac": round(statistics.median(fracs), 4),
            "n_configs": len(fracs),
            "n_rungs": sum(1 for r in rungs if "configs" in r)}


def _run_rung(devices: int, rung_argv: list, timeout: int = 900) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c",
         _STUB.format(devices=devices, repo=str(REPO), argv=rung_argv)],
        capture_output=True, text=True, timeout=timeout, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError(
            f"rung {rung_argv} failed rc={proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"rung {rung_argv}: no JSON row in output")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--round", default=None, type=int)
    p.add_argument("--out", default=None)
    p.add_argument("--iters", default=30, type=int,
                   help="timed training steps per candidate")
    p.add_argument("--requests", default=10, type=int,
                   help="requests per serving rung")
    # internal: run ONE rung in this process (the parent pins the
    # virtual device count before jax imports via _STUB)
    p.add_argument("--_rung", choices=("training", "serving"),
                   default=None, help=argparse.SUPPRESS)
    p.add_argument("--devices", default=8, type=int,
                   help=argparse.SUPPRESS)
    p.add_argument("--slots", default=4, type=int,
                   help=argparse.SUPPRESS)
    p.add_argument("--max-len", default=64, type=int,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args._rung == "training":
        print(json.dumps(_rung_training(args.devices, args.iters)))
        return 0
    if args._rung == "serving":
        print(json.dumps(_rung_serving(args.slots, args.max_len,
                                       args.requests)))
        return 0

    rnd = args.round if args.round is not None else detect_round()
    out = Path(args.out) if args.out else REPO / f"PLAN_r{rnd:02d}.json"

    train_rungs, serve_rungs = [], []
    for nd in (4, 8):
        spec = ["--_rung", "training", "--devices", str(nd),
                "--iters", str(args.iters)]
        try:
            row = _run_rung(nd, spec)
        except Exception as e:  # failure-isolated per rung
            row = {"kind": "training", "geometry": {"n_devices": nd},
                   "error": repr(e)}
        train_rungs.append(row)
        print(json.dumps(row))
    for slots, max_len in ((2, 64), (4, 96)):
        spec = ["--_rung", "serving", "--slots", str(slots),
                "--max-len", str(max_len),
                "--requests", str(args.requests)]
        try:
            row = _run_rung(1, spec)
        except Exception as e:
            row = {"kind": "serving",
                   "geometry": {"slots": slots, "max_len": max_len},
                   "error": repr(e)}
        serve_rungs.append(row)
        print(json.dumps(row))

    good = [r for r in train_rungs + serve_rungs if "configs" in r]
    platform = next((r["geometry"].get("platform") for r in good), "cpu")
    doc = {
        # the header artifacts.py validates: declared metadata beats
        # filename parsing.  Geometry declares only the platform — the
        # per-rung device counts live inside each rung (the PLAN file
        # spans several).
        "artifact": {"schema": 1, "family": "PLAN", "round": rnd,
                     "geometry": {"platform": platform}},
        "training": {"rungs": train_rungs,
                     "error_band": _error_band(train_rungs)},
        "serving": {"rungs": serve_rungs,
                    "error_band": _error_band(serve_rungs)},
        "summary": {
            "match_rtol": MATCH_RTOL,
            "all_match": bool(good) and all(r.get("match")
                                            for r in good),
            "rungs_ok": len(good),
            "rungs_failed": len(train_rungs + serve_rungs) - len(good),
        },
    }
    out.write_text(json.dumps(doc, indent=1) + "\n")
    print(json.dumps({"wrote": out.name,
                      "all_match": doc["summary"]["all_match"],
                      "training_band": doc["training"]["error_band"],
                      "serving_band": doc["serving"]["error_band"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
