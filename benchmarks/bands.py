#!/usr/bin/env python3
"""Multi-run measurement bands for the headline bench rows (VERDICT r4
next #4: single noisy runs were being narrated as stable facts).

Methodology, per row family (stated per row in the artifact):

- per-step LM rows: ONE ``bench_lm`` invocation with ``repeats=N`` —
  one compile, N raw timings of the 5-step loop on the same executable,
  so the band is execution noise, not compile variance;
- scanned rows: N invocations of ``bench_lm_scanned`` with its default
  min-of-3 statistic — the scan path's published number.  Its band is a
  band of MINIMA and therefore tighter by construction than the raw
  per-step bands; the artifact labels it so the two families are never
  read as the same statistic;
- decode rows: N invocations of ``bench_decode`` (its published
  best-of-3-gens statistic), labeled likewise.

Each invocation APPENDS a session to ``BANDS_r{NN}.json`` (NN = the
round being built, ``benchmarks/_round.py``) and re-pools all sessions
per row (median + [min, max] over every sample) — a later session
adds evidence instead of overwriting it.

Cross-round carry-forward (VERDICT #8: each round used to restart its
bands from zero samples, so early-round rows were narrated off 3-sample
bands while 9 perfectly valid samples sat in the previous round's
artifact): sessions from the prior round's artifact are imported into
the new round IF their ``code_hash`` — a digest of the measured code
paths (bench.py, models/ops/train/flops) — matches the current tree, so
a kernel or step-function change quietly invalidates old samples
instead of polluting the pool.  Carried sessions keep a ``carried_from``
marker and every pooled row lists per-session provenance, so a reader
can always tell which samples are fresh and which rode in.
"""

from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import bench  # noqa: E402


def measurement_code_hash() -> str:
    """Digest of the code that produces band samples: a change anywhere
    in the measured paths (harness, model/kernel code, the train step,
    the FLOPs accounting) invalidates prior-round samples for pooling.
    Deliberately coarse — a one-line comment edit also rotates the hash;
    false invalidation costs a few re-measured samples, false REUSE
    costs a silently wrong band."""
    import hashlib

    h = hashlib.sha256()
    files = [REPO / "bench.py", REPO / "tpudist" / "utils" / "flops.py"]
    for sub in ("models", "ops", "train"):
        files += sorted((REPO / "tpudist" / sub).glob("*.py"))
    for f in files:
        if f.exists():
            h.update(f.name.encode())
            h.update(f.read_bytes())
    return h.hexdigest()[:12]


def carry_forward(artifact: dict, prior_path: Path, code_hash: str) -> dict:
    """Import the prior round's sessions whose ``code_hash`` matches the
    current tree (module doc).  Already-carried sessions keep their
    ORIGINAL provenance marker, so a chain of unchanged rounds stays
    attributed to the round that measured it.  Returns a summary dict
    (stored in the artifact so exclusions are visible, not silent)."""
    info = {"from": prior_path.name, "carried": 0, "excluded_stale": 0}
    try:
        prior = json.loads(prior_path.read_text())
        sessions = prior["sessions"]
    except Exception as e:
        info["error"] = f"unreadable prior artifact: {e!r}"
        return info
    have = {(s.get("carried_from"), s.get("label"))
            for s in artifact["sessions"]}
    for s in sessions:
        if s.get("code_hash") != code_hash:
            # stale code version (or a pre-carry-forward artifact with
            # no hash at all): its samples measured different code
            info["excluded_stale"] += 1
            continue
        origin = s.get("carried_from") or prior_path.name
        if (origin, s.get("label")) in have:
            continue  # re-invocation: already carried
        artifact["sessions"].append({**s, "carried_from": origin})
        info["carried"] += 1
    return info


def _band(values):
    vals = [v for v in values if v is not None]
    if not vals:
        return {"runs": list(values), "median": None, "min": None,
                "max": None}
    return {"runs": list(values), "median": statistics.median(vals),
            "min": min(vals), "max": max(vals)}


def lm_rows(repeats: int, **cfg) -> dict:
    """One compile, ``repeats`` raw timings (bench_lm's repeats param)."""
    import jax

    row = bench.bench_lm(steps=5, repeats=repeats, **cfg)
    c = row["config"]
    peak = row.get("peak_bf16_flops_per_chip")
    n_chips = jax.local_device_count()  # bench_lm's own per-chip divisor
    toks, mfus = [], []
    for ms in row.get("step_ms_runs", [row["step_ms"]]):
        toks.append(round(c["batch"] * c["seq_len"] / (ms / 1e3)
                          / n_chips, 1))
        mfus.append(round(100 * row["model_flops_per_step"]
                          / (ms / 1e3) / (n_chips * peak), 2)
                    if peak else None)
    return {"statistic": "raw 5-step timings, one shared compile",
            "config": c,
            "tokens_per_sec_per_chip_runs": toks,
            "mfu_pct_vs_bf16_peak_runs": mfus}


def pool(sessions) -> dict:
    """Per-row bands over every session's samples."""
    # The decode roofline divides by ONE chip kind's HBM bandwidth; an
    # artifact whose sessions were measured on different kinds has no
    # single valid ceiling — refuse to stamp one rather than quietly
    # using the first session's chip for everyone's samples.
    kinds = sorted({s["device_kind"] for s in sessions
                    if s.get("device_kind")})
    device_kind = kinds[0] if len(kinds) == 1 else None
    merged: dict = {}
    for s in sessions:
        for name, row in s.get("rows", {}).items():
            if "error" in row or "superseded" in row:
                # superseded: the row's measurement CONFIG changed in a
                # later session (e.g. the scanned arm's donate_state
                # fix); raw samples stay in the session record, but the
                # pooled band must not mix configurations.
                continue
            slot = merged.setdefault(
                name, {"statistic": row.get("statistic"),
                       "config": row.get("config"), "samples": {},
                       "provenance": []})
            # per-row provenance: which session contributed, and whether
            # its samples were measured THIS round or carried forward
            prov = {"session": s.get("label"),
                    "carried_from": s.get("carried_from"),
                    "device_kind": s.get("device_kind")}
            if prov not in slot["provenance"]:
                slot["provenance"].append(prov)
            for key, vals in row.items():
                if key.endswith("_runs"):
                    slot["samples"].setdefault(key[:-5], []).extend(vals)
    pooled = {
        name: {"statistic": slot["statistic"], "config": slot["config"],
               "provenance": slot["provenance"],
               **{k: _band(v) for k, v in slot["samples"].items()}}
        for name, slot in merged.items()
    }
    # Decode rows carry a pooled roofline percentage (the ceiling is
    # deterministic per config, so it belongs next to the pooled median,
    # not only inside per-session medians).
    for row in pooled.values():
        cfg = row.get("config") or {}
        band = row.get("tokens_per_sec")
        if not (band and band["median"]
                and {"prompt_len", "max_new"} <= set(cfg)):
            continue  # not a decode row: no roofline field either way
        if len(kinds) > 1:
            row["pct_of_roofline_pooled_median"] = None
            row["roofline_note"] = (
                "sessions span device kinds "
                f"{kinds}: no single HBM ceiling applies to the pooled "
                "median — re-pool per kind for a roofline percentage")
            continue
        from tpudist.utils.flops import HBM_BYTES_PER_S, decode_roofline

        nbytes = 2 if cfg.get("precision") == "bf16" else 4
        roof = decode_roofline(
            batch=cfg["batch"], prompt_len=cfg["prompt_len"],
            max_new=cfg["max_new"], d_model=cfg["d_model"],
            n_layers=cfg["n_layers"], d_ff=cfg["d_ff"],
            vocab=cfg["vocab"], param_bytes=nbytes, cache_bytes=nbytes,
            # the sessions' chip, not the pooling host's (pooling may
            # run on a CPU box over TPU-measured sessions)
            hbm_bytes_per_s=HBM_BYTES_PER_S.get(device_kind))
        if roof:
            row["pct_of_roofline_pooled_median"] = round(
                100 * band["median"]
                / roof["ceiling_tokens_per_sec"], 1)
    return pooled


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--repeats", type=int, default=3)
    try:
        from benchmarks._round import current_round
    except ImportError:
        from _round import current_round

    p.add_argument("--out", default=str(
        REPO / f"BANDS_r{current_round():02d}.json"))
    p.add_argument("--configs", default="dense,long,d1024_b8,d1024_b16,"
                                        "scanned_dense,scanned_d1024,decode,"
                                        "decode_bf16")
    p.add_argument("--session", default=None,
                   help="label for this session (default: seq number)")
    p.add_argument("--carry-from", default="auto",
                   help="prior-round BANDS artifact to import matching-"
                        "code sessions from ('auto': BANDS_r{NN-1}; "
                        "'none': disable)")
    args = p.parse_args(argv)
    want = set(args.configs.split(","))

    out_path = Path(args.out)
    if out_path.exists():
        try:
            artifact = json.loads(out_path.read_text())
            assert "sessions" in artifact
        except Exception:
            # NEVER silently reset accumulated band history: back the
            # unparseable file up and start fresh, loudly.
            backup = out_path.with_suffix(".corrupt")
            out_path.replace(backup)
            print(json.dumps({"warning": f"unparseable {out_path.name} "
                              f"moved to {backup.name}; starting a fresh "
                              "artifact"}), flush=True)
            artifact = {"sessions": [], "pooled": {}}
    else:
        artifact = {"sessions": [], "pooled": {}}

    code_hash = measurement_code_hash()
    artifact["code_hash"] = code_hash
    if args.carry_from != "none":
        prior_path = (REPO / f"BANDS_r{current_round() - 1:02d}.json"
                      if args.carry_from == "auto"
                      else Path(args.carry_from))
        if (prior_path.exists()
                and prior_path.resolve() != out_path.resolve()):
            artifact["carry_forward"] = carry_forward(
                artifact, prior_path, code_hash)
            print(json.dumps({"carry_forward":
                              artifact["carry_forward"]}), flush=True)

    def write_artifact():
        # atomic: a kill mid-write must not truncate the accumulated file
        tmp = out_path.with_suffix(".tmp")
        tmp.write_text(json.dumps(artifact, indent=2) + "\n")
        tmp.replace(out_path)

    import jax

    fresh = [s for s in artifact["sessions"] if not s.get("carried_from")]
    session = {"label": args.session or f"s{len(fresh) + 1}",
               "device_kind": jax.devices()[0].device_kind,
               "repeats": args.repeats, "code_hash": code_hash, "rows": {}}
    artifact["sessions"].append(session)

    def run(name, fn):
        if name not in want:
            return
        t0 = time.perf_counter()
        try:
            session["rows"][name] = fn()
        except Exception as e:  # a wedged section must not void the rest
            session["rows"][name] = {"error": repr(e)}
        session["rows"][name]["wall_s"] = round(time.perf_counter() - t0, 1)
        artifact["pooled"] = pool(artifact["sessions"])
        print(json.dumps({name: session["rows"][name]}), flush=True)
        write_artifact()

    run("dense", lambda: lm_rows(
        args.repeats, name="dense_bf16", batch=8, seq_len=2048, d_model=512,
        n_layers=4, n_heads=8, d_ff=2048, vocab=256, precision="bf16"))
    run("long", lambda: lm_rows(
        args.repeats, name="long_context_bf16", batch=4, seq_len=8192,
        d_model=256, n_layers=4, n_heads=4, d_ff=1024, vocab=256,
        precision="bf16"))
    run("d1024_b8", lambda: lm_rows(
        args.repeats, name="mfu_d1024_bf16", batch=8, seq_len=2048,
        d_model=1024, n_layers=8, n_heads=8, d_ff=4096, vocab=256,
        precision="bf16"))
    run("d1024_b16", lambda: lm_rows(
        args.repeats, name="mfu_d1024_bf16_b16", batch=16, seq_len=2048,
        d_model=1024, n_layers=8, n_heads=8, d_ff=4096, vocab=256,
        precision="bf16"))

    def scanned(name, **cfg):
        rows = [bench.bench_lm_scanned(name=name, skip_plain=True, **cfg)
                for _ in range(args.repeats)]
        return {"statistic": ("min-of-3 per sample (the scan path's "
                              "published statistic) — tighter than the "
                              "raw per-step bands by construction"),
                "config": rows[0]["config"],
                "mfu_pct_vs_bf16_peak_runs":
                    [r["mfu_pct_vs_bf16_peak"] for r in rows]}

    run("scanned_dense", lambda: scanned(
        "dense_bf16_scanned", batch=8, seq_len=2048, d_model=512,
        n_layers=4, n_heads=8, d_ff=2048, vocab=256, scan_k=8))
    run("scanned_d1024", lambda: scanned(
        "mfu_d1024_bf16_b16_scanned", batch=16, seq_len=2048, d_model=1024,
        n_layers=8, n_heads=8, d_ff=4096, vocab=256, scan_k=4))

    def decode(precision="fp32"):
        rows = [bench.bench_decode(precision=precision)
                for _ in range(args.repeats)]
        roof = rows[0].get("roofline")
        vals = [r["value"] for r in rows]
        med = statistics.median(vals)
        return {"statistic": "best-of-3 internal gens per sample "
                             "(bench_decode's published statistic); "
                             "device runs are traced busy-time rates",
                "config": rows[0]["config"],
                "tokens_per_sec_runs": vals,
                "tokens_per_sec_device_runs":
                    [r.get("tokens_per_sec_device") for r in rows],
                "pct_of_roofline_median": round(
                    100 * med / roof["ceiling_tokens_per_sec"], 1)
                if roof else None}

    run("decode", decode)
    run("decode_bf16", lambda: decode(precision="bf16"))
    # re-pool unconditionally: carried-forward sessions must reach the
    # pooled bands even when this invocation ran zero configs
    artifact["pooled"] = pool(artifact["sessions"])
    write_artifact()  # even a zero-row session leaves a valid artifact
    return 0


if __name__ == "__main__":
    sys.exit(main())
