"""Benchmark-harness mechanics on the virtual mesh: each harness must run
end-to-end and emit well-formed JSON (real numbers come from hardware)."""

import sys

import pytest


class TestBands:
    def test_pool_merges_sessions_and_computes_decode_roofline(self):
        from benchmarks.bands import pool

        sessions = [
            {"device_kind": "TPU v5 lite",
             "rows": {"dense": {
                "statistic": "raw", "config": {"batch": 8},
                "mfu_pct_vs_bf16_peak_runs": [20.0, 22.0]}}},
            {"rows": {"dense": {
                "statistic": "raw", "config": {"batch": 8},
                "mfu_pct_vs_bf16_peak_runs": [24.0]},
                "bad": {"error": "boom"},
                "decode": {
                    "statistic": "best-of-3", "config": {
                        "batch": 8, "prompt_len": 16, "max_new": 240,
                        "d_model": 512, "n_layers": 4, "d_ff": 2048,
                        "vocab": 256, "precision": "bf16"},
                    "tokens_per_sec_runs": [40000.0, 50000.0, None]}}},
        ]
        pooled = pool(sessions)
        band = pooled["dense"]["mfu_pct_vs_bf16_peak"]
        assert band["runs"] == [20.0, 22.0, 24.0]
        assert band["median"] == 22.0
        assert "bad" not in pooled  # errored rows never pollute the pool
        dec = pooled["decode"]
        # bf16 precision -> 2-byte roofline (ceiling ~187.7k on v5e)
        assert dec["pct_of_roofline_pooled_median"] == pytest.approx(
            100 * 45000.0 / 187747.6, abs=0.1)

    def test_mixed_device_kinds_refuse_pooled_roofline(self):
        """Sessions measured on different chip kinds share no HBM
        ceiling: the pooled decode roofline must refuse (None + note),
        not silently use the first session's bandwidth (ADVICE r5)."""
        from benchmarks.bands import pool

        decode_row = {
            "statistic": "best-of-3", "config": {
                "batch": 8, "prompt_len": 16, "max_new": 240,
                "d_model": 512, "n_layers": 4, "d_ff": 2048,
                "vocab": 256, "precision": "bf16"},
            "tokens_per_sec_runs": [40000.0]}
        pooled = pool([
            {"device_kind": "TPU v5 lite", "rows": {"decode": decode_row}},
            {"device_kind": "TPU v4", "rows": {"decode": dict(decode_row)}},
        ])
        dec = pooled["decode"]
        assert dec["pct_of_roofline_pooled_median"] is None
        assert "TPU v4" in dec["roofline_note"]
        # band samples still pool (the refusal is roofline-only)
        assert dec["tokens_per_sec"]["runs"] == [40000.0, 40000.0]

    def test_corrupt_artifact_backed_up_not_reset(self, tmp_path):
        """A truncated artifact must be preserved as .corrupt, never
        silently overwritten (accumulated band history is evidence)."""
        from benchmarks.bands import main

        out = tmp_path / "BANDS.json"
        out.write_text('{"sessions": [{"label": "old"')  # truncated
        rc = main(["--configs", "none", "--out", str(out),
                   "--session", "t"])
        assert rc == 0
        assert (tmp_path / "BANDS.corrupt").exists()
        import json as _json

        fresh = _json.loads(out.read_text())
        assert [s["label"] for s in fresh["sessions"]] == ["t"]

    def test_carry_forward_keyed_by_code_hash_with_provenance(
            self, tmp_path):
        """VERDICT #8: a new round's artifact imports the prior round's
        sessions — but ONLY those whose code hash matches the current
        tree (a kernel/harness change silently invalidates old samples),
        and every pooled row says which sessions (fresh vs carried) its
        band came from."""
        import json as _json

        from benchmarks.bands import main, measurement_code_hash

        row = {"statistic": "raw", "config": {"batch": 8},
               "mfu_pct_vs_bf16_peak_runs": [20.0, 22.0]}
        prior = tmp_path / "BANDS_r98.json"
        prior.write_text(_json.dumps({"sessions": [
            {"label": "good", "device_kind": "cpu", "repeats": 2,
             "code_hash": measurement_code_hash(), "rows": dict(row=row)},
            {"label": "stale", "device_kind": "cpu", "repeats": 2,
             "code_hash": "deadbeef0000", "rows": dict(row=row)},
        ], "pooled": {}}))
        out = tmp_path / "BANDS_r99.json"
        rc = main(["--configs", "none", "--out", str(out),
                   "--session", "fresh", "--carry-from", str(prior)])
        assert rc == 0
        rec = _json.loads(out.read_text())
        # the matching session rode in, the stale one was excluded LOUDLY
        assert rec["carry_forward"]["carried"] == 1
        assert rec["carry_forward"]["excluded_stale"] == 1
        by_label = {s["label"]: s for s in rec["sessions"]}
        assert by_label["good"]["carried_from"] == "BANDS_r98.json"
        assert "stale" not in by_label
        assert "carried_from" not in by_label["fresh"]
        # pooled bands include the carried samples, with provenance
        pooled_row = rec["pooled"]["row"]
        assert pooled_row["mfu_pct_vs_bf16_peak"]["runs"] == [20.0, 22.0]
        assert pooled_row["provenance"] == [
            {"session": "good", "carried_from": "BANDS_r98.json",
             "device_kind": "cpu"}]
        # re-invocation must not duplicate the carried session
        rc = main(["--configs", "none", "--out", str(out),
                   "--session", "fresh2", "--carry-from", str(prior)])
        assert rc == 0
        rec2 = _json.loads(out.read_text())
        assert [s["label"] for s in rec2["sessions"]
                if s.get("carried_from")] == ["good"]
        assert rec2["pooled"]["row"]["mfu_pct_vs_bf16_peak"]["runs"] \
            == [20.0, 22.0]

    def test_carry_forward_chain_preserves_origin(self, tmp_path):
        """A session carried r5→r6 and again r6→r7 stays attributed to
        the artifact that MEASURED it, not the one it last rode in."""
        import json as _json

        from benchmarks.bands import carry_forward, measurement_code_hash

        ch = measurement_code_hash()
        mid = tmp_path / "BANDS_r98.json"
        mid.write_text(_json.dumps({"sessions": [
            {"label": "old", "code_hash": ch,
             "carried_from": "BANDS_r97.json", "rows": {}}]}))
        artifact = {"sessions": []}
        info = carry_forward(artifact, mid, ch)
        assert info["carried"] == 1
        assert artifact["sessions"][0]["carried_from"] == "BANDS_r97.json"


class TestSameWindowPair:
    """bench.py's fp32/bf16 pairing rule: a speedup is only ever quoted
    for two rows measured in the SAME invocation; anything else is
    explicitly voided, never silently stale (r5 verdict Weak #3: a
    cross-session pair showed bf16 1.7x 'slower')."""

    def test_pairs_when_both_measured_this_window(self):
        import bench

        results = {"a_fp32": {"step_ms": 200.0, "unit": "ms/step"},
                   "a_bf16": {"step_ms": 100.0, "unit": "ms/step"}}
        bench.same_window_pair(results, ["a_fp32", "a_bf16"],
                               "a_pair", "a_fp32", "a_bf16")
        pair = results["a_pair"]
        assert pair["bf16_speedup"] == 2.0
        assert pair["step_ms_fp32"] == 200.0
        assert "error" not in pair

    def test_inverted_for_rates(self):
        import bench

        results = {"d": {"value": 10000.0}, "d_bf16": {"value": 20000.0}}
        bench.same_window_pair(results, ["d", "d_bf16"], "d_pair",
                               "d", "d_bf16", field="value", invert=True)
        assert results["d_pair"]["bf16_speedup"] == 2.0

    def test_voided_when_one_side_is_stale(self):
        """The failure mode the satellite kills: one side measured in a
        PREVIOUS window (present in results, absent from measured_now)
        must void the pair, not quote a cross-window ratio."""
        import bench

        results = {"a_fp32": {"step_ms": 200.0},  # stale, merged from disk
                   "a_bf16": {"step_ms": 340.0}}  # fresh
        bench.same_window_pair(results, ["a_bf16"], "a_pair",
                               "a_fp32", "a_bf16")
        assert "error" in results["a_pair"]
        assert "same-window" in results["a_pair"]["error"]

    def test_voided_when_a_side_errored(self):
        import bench

        results = {"a_fp32": {"error": "timeout"}, "a_bf16": {"step_ms": 1.0}}
        bench.same_window_pair(results, ["a_fp32", "a_bf16"], "a_pair",
                               "a_fp32", "a_bf16")
        assert "error" in results["a_pair"]


class TestServeBench:
    @pytest.fixture(scope="class")
    def smoke(self, tmp_path_factory):
        """The CPU smoke's artifact, written ONCE: the load generator's
        offered-load row and every always-on rung; a test a section."""
        import json as _json

        from benchmarks.serve_bench import main

        out = tmp_path_factory.mktemp("serve_bench") / "BENCH_SERVE.json"
        rc = main(["--smoke", "--out", str(out), "--requests", "4",
                   "--rates", "burst", "--blocks", "1,4"])
        assert rc == 0
        rec = _json.loads(out.read_text())
        assert rec["regime"] == "cpu-smoke"
        return rec

    def test_smoke_writes_artifact_with_required_columns(self, smoke):
        """CI-smoke acceptance: the load generator runs on CPU and the
        offered-load row carries TTFT/TPOT percentiles, the
        dispatch-overhead split (wall vs device-busy TPOT),
        throughput and occupancy."""
        (row,) = smoke["rows"]
        assert row["offered_rps"] == "burst"
        assert row["completed"] == 4 and row["tokens_out"] > 0
        for col in ("achieved_tokens_per_s", "ttft_s_p50", "ttft_s_p95",
                    "tpot_s_p50", "tpot_s_p95", "occupancy_mean_cum",
                    # the overhead split: wall TPOT vs device-busy TPOT
                    "tpot_busy_s", "dispatches_per_token",
                    "host_sync_s_per_token", "decode_blocks",
                    "decode_tokens"):
            assert row[col] is not None, col
        # block decode amortizes dispatch: strictly fewer dispatches
        # than decoded tokens at the default block size
        assert row["dispatches_per_token"] < 1.0

    def test_smoke_churn_never_recompiles(self, smoke):
        # continuous batching's whole point: request churn never
        # recompiles; decode_block's cache is the bounded bucket set
        cc = smoke["server_stats"]["compile_counts"]
        assert cc["insert_batch"] in (1, -1)
        assert cc["evict"] in (1, -1)
        assert cc["prefill_extend"] in (0, 1, -1)  # smoke prompts fit one chunk
        assert cc["decode_block"] == -1 or 1 <= cc["decode_block"] <= 4

    def test_smoke_block_sweep_isolates_fusion(self, smoke):
        # the block-size sweep isolates fusion: K=1 is the per-iteration
        # dispatch regime (tokens/dispatch = batch occupancy, at most
        # num_slots=2 in smoke), K=4 fuses a further ~4x on top
        sweep = {e["decode_block"]: e for e in smoke["block_sweep"]}
        assert set(sweep) == {1, 4}
        assert sweep[1]["dispatches_per_token"] >= 1.0 / 2
        assert (sweep[4]["dispatches_per_token"]
                < sweep[1]["dispatches_per_token"])
        assert sweep[4]["decode_blocks"] < sweep[1]["decode_blocks"]

    def test_smoke_serving_report_quotes_kv(self, smoke):
        """The merged telemetry serving section."""
        sv = smoke["serving_report"]
        assert sv and sv["requests_finished"] >= 5  # warmup + 4
        assert sv["occupancy_mean"] is not None
        assert sv["decode_tokens"] > 0 and sv["tokens_per_dispatch"] >= 1.0
        # the serving report quotes the KV capacity story: block
        # occupancy, resident bytes, and decode bytes/token
        kv = sv["kv"]
        assert kv["bytes_resident_peak"] > 0
        assert kv["read_bytes_per_token"] > 0

    def test_smoke_paged_capacity_rung(self, smoke):
        # paged-capacity rung: 4x the slots at EQUAL pool bytes (the
        # CPU-smoke proxy for equal HBM bytes-resident), and the paged
        # arm actually runs more concurrent sequences than the dense
        # arm's hard slot cap
        cap = smoke["paged_capacity"]
        assert cap["slots_ratio"] == 4.0
        assert cap["equal_pool_bytes"]
        assert cap["pool_bytes_paged"] == cap["pool_bytes_dense"]
        assert cap["peak_concurrent_paged"] > cap["peak_concurrent_dense"]
        assert (cap["paged_4x"]["completed"]
                == cap["dense"]["completed"] == 12)

    def test_smoke_kv_dtype_sweep(self, smoke):
        # int8-KV sweep: resident bytes per cached position collapse
        # (int8 + per-block scales vs f32 ≈ 3.8x; ≥ 2x is the "halved
        # bytes/token" acceptance floor, met even against bf16)
        kvs = smoke["kv_dtype_sweep"]
        assert kvs["native_over_int8_bytes"] >= 2.0
        assert kvs["rows"][1]["kv"]["quantized"] is True
        assert kvs["rows"][1]["completed"] == kvs["rows"][0]["completed"]

    def test_smoke_attn_kernel_twin(self, smoke):
        # attn-kernel twin rung (always-on, like capacity): gather vs
        # the Pallas paged-attention kernel at high occupancy — the
        # kernel path must stream FEWER decode KV bytes per token
        # (live-KV accounting vs the gather path's pool-geometry view)
        tw = smoke["attn_kernel_twin"]
        assert tw["kernel"]["kv"]["attn_kernel"] == "paged"
        assert tw["gather"]["kv"]["attn_kernel"] == "gather"
        assert tw["kernel"]["completed"] == tw["gather"]["completed"]
        assert tw["read_bytes_per_token_kernel"] > 0
        assert tw["kernel_beats_gather_bytes"] is True
        assert tw["bytes_ratio_gather_over_kernel"] > 1.0

    def test_smoke_kernel_family_twin(self, smoke):
        # kernel-family twin rungs (always-on): each fused path vs its
        # in-graph twin on the same saturated burst; the prefill pair's
        # acceptance claim is byte-based — the in-kernel writes beat
        # the gather path's dense sweep + pad-span scatter
        fam = smoke["kernel_family_twin"]
        for pair in ("prefill", "sample", "rope_qkv"):
            assert fam[pair]["base"]["completed"] \
                == fam[pair]["fused"]["completed"], pair
            assert fam[pair]["tokens_per_s_fused"] > 0, pair
        assert fam["prefill"]["fused"]["kv"]["prefill_kernel"] is True
        assert fam["prefill"]["base"]["kv"]["prefill_kernel"] is False
        assert fam["prefill"]["prefill_write_bytes_kernel"] > 0
        assert fam["prefill"]["kernel_beats_gather_prefill_bytes"] is True
        assert fam["sample"]["fused"]["kv"]["sample_kernel"] is True
        assert fam["rope_qkv"]["fused"]["kv"]["fused_rope"] is True

    def test_smoke_mesh_rung(self, tmp_path):
        """The --mesh rung (single-process emulated-device mode): the
        offered-load rows serve off an SPMD 1x2 engine with the overlap
        routing on, the artifact records the mesh + sharded-param
        accounting, and the compile pins hold — mesh shapes change
        shardings, never programs."""
        from benchmarks.serve_bench import main

        out = tmp_path / "BENCH_SERVE_MESH.json"
        rc = main(["--smoke", "--out", str(out), "--requests", "3",
                   "--rates", "burst", "--blocks", "1", "--skip-sweeps",
                   "--mesh", "1x2", "--tp-overlap", "ring"])
        assert rc == 0
        import json as _json

        rec = _json.loads(out.read_text())
        assert rec["config"]["mesh"] == "1x2"
        (row,) = rec["rows"]
        assert row["completed"] == 3 and row["tokens_out"] > 0
        spmd = rec["server_stats"]["spmd"]
        assert spmd["mesh"] == {"data": 1, "model": 2}
        assert spmd["tp_overlap"] == "ring"
        assert spmd["param_bytes_per_device"] < spmd["param_bytes_total"]
        cc = rec["server_stats"]["compile_counts"]
        assert cc["insert_batch"] in (1, -1)
        assert cc["evict"] in (1, -1)

    def test_smoke_disagg_rung(self, tmp_path):
        """The --disagg rung (single-process mode): rows serve through
        the prefill/decode-disaggregated coordinator with serialized KV
        handoff, the handoff columns land, and the embedded serving
        report carries the per-pool TTFT/TPOT split."""
        from benchmarks.serve_bench import main

        out = tmp_path / "BENCH_SERVE_DISAGG.json"
        rc = main(["--smoke", "--out", str(out), "--requests", "3",
                   "--rates", "burst", "--blocks", "1", "--skip-sweeps",
                   "--disagg", "--handoff", "serial"])
        assert rc == 0
        import json as _json

        rec = _json.loads(out.read_text())
        assert rec["config"]["disagg"] and rec["config"]["handoff"] == \
            "serial"
        (row,) = rec["rows"]
        assert row["completed"] == 3 and row["tokens_out"] > 0
        assert row["handoffs"] > 0 and row["handoff_bytes"] > 0
        assert row["handoff_wait_s_p50"] is not None
        cc = rec["server_stats"]["decode_pool"]["compile_counts"]
        assert cc["import_lane"] in (1, -1)
        # the embedded report splits the phases by pool
        pools = rec["serving_report"]["pools"]
        assert pools["handoffs"] > 0
        assert pools["prefill"]["ttft"] is not None
        assert pools["decode"]["tpot"] is not None

    def test_multiproc_serve_rung(self):
        """The tpurun-launched multi-process serve rung: 2 workers x
        2 emulated devices each, disaggregated + serialized handoff,
        merged per-pool serving report embedded."""
        from benchmarks.serve_bench import run_multiproc_serve

        row = run_multiproc_serve(n_procs=2, devices_per_proc=2,
                                  requests=3, mesh="1x2")
        assert "error" not in row, row
        assert row["n_procs"] == 2 and len(row["ranks"]) == 2
        assert row["agg_tokens_per_s"] > 0
        assert row["handoffs_total"] > 0
        for r in row["ranks"]:
            assert r["n_devices"] == 2
            assert r["spmd"]["mesh"] == {"data": 1, "model": 2}
        sv = row["serving_report"]
        assert sv and sv["pools"]["handoffs"] == row["handoffs_total"]
        assert sv["pools"]["prefill"]["ttft"] is not None
        assert sv["pools"]["decode"]["tpot"] is not None

    def test_decode_profile_capture(self, tmp_path):
        """--capture-decode: the bf16 decode loop traces and the per-op
        table names the non-matmul residual (VERDICT Weak #2), and the
        speculative path's draft / verify / rollback phases are traced
        separately."""
        from benchmarks.profile_summary import main

        out = tmp_path / "DECODE_PROFILE.json"
        rc = main(["--capture-decode", "--decode-blocks", "2",
                   "--out", str(out)])
        assert rc == 0
        import json as _json

        rec = _json.loads(out.read_text())
        assert rec["config"]["dtype"] == "bf16"
        assert rec["total_us"] > 0
        assert rec["residual_pct"] is not None
        assert rec["residual_groups"], "residual table must name groups"
        assert abs(rec["matmul_pct"] + rec["residual_pct"] - 100.0) < 0.1
        sp = rec["spec"]
        for phase in ("draft", "verify"):
            assert sp[phase]["total_us"] > 0, phase
            assert sp[phase]["groups"], phase
        # rollback is cursor arithmetic: its attributed-op time is a
        # sliver of either forward's
        assert sp["rollback"]["op_us_excl_other"] < \
            sp["verify"]["op_us_excl_other"]
        # paged decode phases: gather vs the Pallas kernel traced
        # separately, so the artifact splits paged-kernel time from the
        # residual fusion/layout ops (kernel_us/kernel_pct name the
        # "custom (pallas/kernels)" group's share on device traces)
        pg = rec["paged"]
        for arm in ("gather", "kernel"):
            assert pg[arm]["total_us"] > 0, arm
            assert pg[arm]["groups"], arm
            assert "kernel_us" in pg[arm] and "kernel_pct" in pg[arm]
        # kernel-family phase rows: each fused path traced separately
        # against the gather prefill baseline
        fam = rec["family"]
        for phase in ("prefill.gather", "prefill.kernel",
                      "sample.kernel", "rope_qkv.kernel", "lora.kernel"):
            assert fam[phase]["total_us"] > 0, phase
            assert fam[phase]["groups"], phase

    def test_smoke_spec_sweep(self, tmp_path):
        """The --spec sweep: tied + distilled draft rungs over repeat
        traffic, accepted-tokens/pass and acceptance-rate columns, the
        single-model device-busy floor quoted per rung, and the mixed
        spec/non-spec traffic rung.  CPU-smoke asserts mechanics (the
        distilled draft reaches high acceptance on its workload; the
        below-floor claim is for the compute-dominated frozen artifact,
        not this µs-scale model)."""
        from benchmarks.serve_bench import main

        out = tmp_path / "BENCH_SERVE_SPEC.json"
        rc = main(["--smoke", "--out", str(out), "--requests", "4",
                   "--rates", "burst", "--blocks", "1", "--skip-sweeps",
                   "--spec", "--draft-layers", "1", "--draft-k", "2,4",
                   "--spec-distill", "120"])
        assert rc == 0
        import json as _json

        rec = _json.loads(out.read_text())
        assert rec["config"]["spec"]
        sw = rec["spec_sweep"]
        assert sw["workload"]["repeat_traffic"]
        # the floor is the non-spec engine's device-busy seconds per
        # sequential decode step
        assert sw["floor"]["busy_per_step_s"] > 0
        assert sw["floor"]["decode_steps"] > 0
        rows = {(r["draft"], r["k"]): r for r in sw["rows"]}
        assert set(rows) == {("tied-1", 2), ("tied-1", 4),
                             ("distilled-1", 2), ("distilled-1", 4)}
        for r in sw["rows"]:
            assert r["spec_blocks"] > 0
            assert r["accepted_per_pass"] is not None
            assert r["acceptance_rate"] is not None
            assert r["tpot_busy_floor_s"] == sw["floor"]["busy_per_step_s"]
            assert r["spec_draft_s"] >= 0 and r["spec_verify_s"] > 0
        # a draft distilled on the serving distribution accepts most of
        # its proposals; the zero-training tied draft accepts fewer
        assert (rows[("distilled-1", 4)]["acceptance_rate"]
                > rows[("tied-1", 4)]["acceptance_rate"])
        assert rows[("distilled-1", 4)]["acceptance_rate"] > 0.5
        # full acceptance at K=4 emits ~5 tokens per lane per pass
        assert rows[("distilled-1", 4)]["accepted_per_pass"] > 2.0
        # mixed rung: opted-out + sampled requests complete in-batch
        assert sw["mixed"]["completed"] > 0
        assert sw["mixed"]["spec_blocks"] > 0

    def test_smoke_paged_int8_rungs_compile_pinned(self, tmp_path):
        """The --paged/--kv-dtype rungs: offered-load rows served off
        the paged int8 engine, and the jit-cache compile counts stay
        pinned with paging enabled (block-table churn must not
        recompile — the whole point of in-graph indirection)."""
        from benchmarks.serve_bench import main

        out = tmp_path / "BENCH_SERVE_PAGED.json"
        rc = main(["--smoke", "--out", str(out), "--requests", "4",
                   "--rates", "burst", "--blocks", "1,4", "--skip-sweeps",
                   "--paged", "--kv-dtype", "int8"])
        assert rc == 0
        import json as _json

        rec = _json.loads(out.read_text())
        assert rec["config"]["paged"] and rec["config"]["kv_dtype"] == "int8"
        (row,) = rec["rows"]
        assert row["completed"] == 4 and row["tokens_out"] > 0
        assert row["kv"]["paged"] and row["kv"]["quantized"]
        assert row["kv"]["bytes_per_pos"] < 512  # int8, not f32
        # zero recompilation under churn, paging enabled: same pins as
        # the dense engine (one compile per program, decode_block one
        # per power-of-two bucket actually used)
        cc = rec["server_stats"]["compile_counts"]
        assert cc["insert_batch"] in (1, -1)
        assert cc["evict"] in (1, -1)
        assert cc["prefill_extend"] in (0, 1, -1)
        assert cc["decode_block"] == -1 or 1 <= cc["decode_block"] <= 4


class TestLossParity:
    def test_all_entry_points_match(self):
        from benchmarks.loss_parity import main

        summary = main(["--iters", "120", "--tolerance", "0.5"])
        assert summary["parity"], summary
        # Everyone should be in the toy problem's convergence basin.
        assert summary["worst_mean_loss"] < 1.5, summary


class TestFlopsAccounting:
    def test_transformer_flops_formula(self):
        from tpudist.utils import transformer_train_flops

        # One layer, no attention-vs-ffn surprises: check against the
        # hand-expanded formula for small numbers.  Causal attention counts
        # the exact live pairs s(s+1)/2 (each token attends itself + past).
        b, s, d, f, v, L = 2, 8, 4, 16, 10, 1
        causal_pairs = s * (s + 1) / 2
        fwd = L * (8 * b * s * d * d + 4 * b * causal_pairs * d
                   + 4 * b * s * d * f) + 2 * b * s * d * v
        got = transformer_train_flops(batch=b, seq_len=s, d_model=d,
                                      n_layers=L, d_ff=f, vocab=v)
        assert got == 3.0 * fwd
        # Full attention raises the pair count to s^2.
        full = transformer_train_flops(batch=b, seq_len=s, d_model=d,
                                       n_layers=L, d_ff=f, vocab=v,
                                       causal=False)
        assert full - got == 3.0 * 4 * b * (s * s - causal_pairs) * d
        # Sliding window clamps it to the band: first w tokens ramp up,
        # the rest attend exactly w keys.
        w = 3
        band_pairs = w * (w + 1) / 2 + (s - w) * w
        windowed = transformer_train_flops(batch=b, seq_len=s, d_model=d,
                                           n_layers=L, d_ff=f, vocab=v,
                                           window=w)
        assert got - windowed == 3.0 * 4 * b * (causal_pairs - band_pairs) * d
        # fwd_only is exactly a third of the train count.
        assert transformer_train_flops(batch=b, seq_len=s, d_model=d,
                                       n_layers=L, d_ff=f, vocab=v,
                                       fwd_only=True) == fwd

    def test_mfu_and_peak(self):
        from tpudist.utils import chip_peak_flops, mfu

        # Virtual CPU devices have no recorded peak -> MFU is None.
        assert chip_peak_flops() is None
        assert mfu(1e12, 0.1, 1, None) is None
        # With an explicit peak the ratio is exact.
        assert mfu(1e12, 0.1, 1, 1e13) == pytest.approx(1.0)
        assert mfu(1e12, 0.1, 4, 1e13) == pytest.approx(0.25)

class TestNumericsGate:
    """bench.py's on-chip kernel gate, exercised here in interpret mode
    (the real run asserts the same cases on the TPU before any timing)."""

    def test_gate_passes_and_reports_all_cases(self):
        import bench

        report = bench.numerics_gate(interpret=True, quick=True)
        assert set(report) == {"dense", "window", "gqa", "gqa_window"}
        for case in report.values():
            assert case["max_rel_err"] < 1e-2
            assert {"loss", "dq", "dk", "dv"} <= set(case)

    def test_gate_raises_on_mismatch(self, monkeypatch):
        import bench
        from tpudist import ops

        real = ops.flash_attention

        def corrupted(q, k, v, *a, **kw):
            return real(q, k, v, *a, **kw) * 1.5  # a "miscompiled" kernel

        corrupted.supports_gqa = True
        monkeypatch.setattr(ops, "flash_attention", corrupted)
        with pytest.raises(AssertionError, match="numerics gate FAILED"):
            bench.numerics_gate(interpret=True, quick=True)


class TestFlopsWindowContract:
    def test_window_without_causal_raises(self):
        from tpudist.utils.flops import attention_live_pairs

        with pytest.raises(ValueError, match="window requires causal"):
            attention_live_pairs(16, causal=False, window=4)


class TestProfileSummary:
    def test_synthetic_trace_groups_and_filters(self, tmp_path):
        """Chrome-trace events bucket into op groups; host python frames
        and metadata events are excluded from device self-time."""
        import gzip
        import json as _json

        sys.path.insert(0, "benchmarks")
        from benchmarks.profile_summary import summarize

        events = [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:TPU:0 TensorCore"}},
            {"ph": "X", "pid": 7, "ts": 700.0, "name": "fusion.3",
             "dur": 300.0},
            {"ph": "X", "pid": 7, "ts": 0.0, "name": "dot_general.1",
             "dur": 600.0},
            {"ph": "X", "pid": 7, "ts": 1100.0, "name": "all-reduce.2",
             "dur": 100.0},
            {"ph": "X", "pid": 7, "ts": 0.0, "name": "$loop.py:10 run",
             "dur": 999.0},
            {"ph": "X", "pid": 9, "ts": 0.0, "name": "host_thread_junk",
             "dur": 999.0},
        ]
        f = tmp_path / "x.trace.json.gz"
        with gzip.open(f, "wt") as fh:
            _json.dump({"traceEvents": events}, fh)
        s = summarize(tmp_path)
        assert s["total_us"] == 1000.0
        assert s["groups"]["matmul (MXU)"]["pct"] == 60.0
        assert s["groups"]["collectives"]["pct"] == 10.0
        names = [r["name"] for r in s["top_ops"]]
        assert "$loop.py:10 run" not in names
        assert "host_thread_junk" not in names

    def test_nested_spans_count_self_time_once(self, tmp_path):
        """A wrapper span enclosing ops on the same track contributes only
        its EXCLUSIVE time — nested device time is never double-counted."""
        import gzip
        import json as _json

        from benchmarks.profile_summary import summarize

        events = [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:TPU:0"}},
            # wrapper [0, 1000) encloses dot [100, 700) and fusion
            # [700, 950): wrapper self = 1000 − 600 − 250 = 150
            {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0,
             "name": "while.9", "dur": 1000.0},
            {"ph": "X", "pid": 7, "tid": 1, "ts": 100.0,
             "name": "dot_general.1", "dur": 600.0},
            {"ph": "X", "pid": 7, "tid": 1, "ts": 700.0,
             "name": "fusion.2", "dur": 250.0},
        ]
        f = tmp_path / "x.trace.json.gz"
        with gzip.open(f, "wt") as fh:
            _json.dump({"traceEvents": events}, fh)
        s = summarize(tmp_path)
        assert s["total_us"] == 1000.0
        by_name = {r["name"]: r["us"] for r in s["top_ops"]}
        assert by_name["while.9"] == 150.0
        assert by_name["dot_general.1"] == 600.0

    def test_wrapper_tracks_excluded_when_ops_track_exists(self, tmp_path):
        """TPU traces duplicate device time on parallel tracks (XLA
        Modules / Steps / XLA Ops); attribution uses the ops track only."""
        import gzip
        import json as _json

        from benchmarks.profile_summary import summarize

        events = [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "name": "thread_name", "pid": 7, "tid": 1,
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "name": "thread_name", "pid": 7, "tid": 2,
             "args": {"name": "Steps"}},
            {"ph": "M", "name": "thread_name", "pid": 7, "tid": 3,
             "args": {"name": "XLA Ops"}},
            {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0,
             "name": "jit_step(123)", "dur": 1000.0},
            {"ph": "X", "pid": 7, "tid": 2, "ts": 0.0,
             "name": "0", "dur": 1000.0},
            {"ph": "X", "pid": 7, "tid": 3, "ts": 0.0,
             "name": "dot_general.1", "dur": 900.0},
            {"ph": "X", "pid": 7, "tid": 3, "ts": 900.0,
             "name": "fusion.1", "dur": 100.0},
        ]
        f = tmp_path / "x.trace.json.gz"
        with gzip.open(f, "wt") as fh:
            _json.dump({"traceEvents": events}, fh)
        s = summarize(tmp_path)
        assert s["total_us"] == 1000.0  # not 3000: one track, counted once
        names = [r["name"] for r in s["top_ops"]]
        assert "jit_step(123)" not in names and "0" not in names
        assert s["groups"]["matmul (MXU)"]["pct"] == 90.0

    def test_unlabeled_device_pid_keeps_plain_summation(self, tmp_path):
        """The ops-track filter is per-pid: a device pid that never labels
        an 'XLA Ops' thread is NOT filtered against another pid's ops
        track (multi-chip traces need not label every device's threads —
        dropping the unlabeled chips would silently undercount them)."""
        import gzip
        import json as _json

        from benchmarks.profile_summary import summarize

        events = [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "name": "process_name", "pid": 8,
             "args": {"name": "/device:TPU:1"}},
            # pid 7 labels its ops track; wrapper on tid 1 is excluded
            {"ph": "M", "name": "thread_name", "pid": 7, "tid": 3,
             "args": {"name": "XLA Ops"}},
            {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0,
             "name": "jit_step(1)", "dur": 500.0},
            {"ph": "X", "pid": 7, "tid": 3, "ts": 0.0,
             "name": "dot_general.1", "dur": 500.0},
            # pid 8 has NO labeled ops track — its ops must still count
            {"ph": "X", "pid": 8, "tid": 9, "ts": 0.0,
             "name": "fusion.7", "dur": 500.0},
        ]
        f = tmp_path / "x.trace.json.gz"
        with gzip.open(f, "wt") as fh:
            _json.dump({"traceEvents": events}, fh)
        s = summarize(tmp_path)
        assert s["total_us"] == 1000.0  # 500 (pid 7 ops) + 500 (pid 8)
        names = {r["name"] for r in s["top_ops"]}
        assert "fusion.7" in names and "jit_step(1)" not in names

    def test_overlapping_span_charges_only_overlap(self, tmp_path):
        """A malformed span that starts inside its 'parent' but ends after
        it subtracts only the overlapping part from the parent's self
        time — not its full duration."""
        import gzip
        import json as _json

        from benchmarks.profile_summary import summarize

        events = [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:TPU:0"}},
            # parent [0, 1000); child [800, 1200) overhangs by 200:
            # parent self = 1000 − 200 (overlap only) = 800
            {"ph": "X", "pid": 7, "tid": 1, "ts": 0.0,
             "name": "while.9", "dur": 1000.0},
            {"ph": "X", "pid": 7, "tid": 1, "ts": 800.0,
             "name": "dot_general.1", "dur": 400.0},
        ]
        f = tmp_path / "x.trace.json.gz"
        with gzip.open(f, "wt") as fh:
            _json.dump({"traceEvents": events}, fh)
        s = summarize(tmp_path)
        by_name = {r["name"]: r["us"] for r in s["top_ops"]}
        assert by_name["while.9"] == 800.0
        assert by_name["dot_general.1"] == 400.0
        assert s["total_us"] == 1200.0

    def test_empty_dir_reports_error(self, tmp_path):
        from benchmarks.profile_summary import summarize

        assert "error" in summarize(tmp_path)


class TestRoofline:
    """Analytic roofline for the d1024 MFU rungs (VERDICT r3 #2's
    'prove the ceiling' half)."""

    def test_all_rungs_compute_bound_and_b32_needs_remat(self):
        import importlib.util
        from pathlib import Path as _P

        spec = importlib.util.spec_from_file_location(
            "roofline", _P(__file__).resolve().parent.parent
            / "benchmarks" / "roofline.py")
        rl = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rl)

        from tpudist.utils.flops import PEAK_BF16_FLOPS, transformer_train_flops

        peak = PEAK_BF16_FLOPS["TPU v5 lite"]
        n_params = rl.param_count(**rl.GEOM)
        assert 100e6 < n_params < 110e6  # the d1024/L8/ff4096 geometry
        for tag, batch, remat in rl.RUNGS:
            flops = transformer_train_flops(batch=batch, **rl.GEOM)
            act = rl.activation_bytes(batch=batch, remat=remat, **rl.GEOM)
            w = rl.weight_traffic_bytes(n_params, remat=remat)
            t_c = flops / peak
            t_h = (act + w) / rl.HBM_BYTES_PER_S
            assert t_c > 4 * t_h, (tag, t_c, t_h)  # strongly compute-bound
        # plain b32 exceeds the HBM budget; the remat rung fits
        mem_plain = n_params * 18 + rl.activation_bytes(
            batch=32, remat=False, **rl.GEOM) / 2
        mem_remat = n_params * 18 + rl.activation_bytes(
            batch=32, remat=True, **rl.GEOM) / 2
        assert mem_plain > rl.HBM_CAPACITY * 0.9
        assert mem_remat < rl.HBM_CAPACITY * 0.5

    def test_decode_roofline_bandwidth_accounting(self):
        """Decode ceiling = batch / (bytes-per-token-step / HBM BW) with
        weights streamed once per step and the KV cache once per sequence
        — and the lm_decode bench config's ceiling sits in the band the
        hand calculation gives (~94k tok/s on v5e at fp32)."""
        from tpudist.utils.flops import decode_roofline, transformer_param_count

        roof = decode_roofline(
            batch=8, prompt_len=16, max_new=240, d_model=512, n_layers=4,
            d_ff=2048, vocab=256, param_bytes=4, cache_bytes=4,
            hbm_bytes_per_s=8.19e11)
        n_params = transformer_param_count(
            d_model=512, n_layers=4, d_ff=2048, vocab=256, max_len=256)
        assert roof["n_params"] == n_params
        assert roof["weight_bytes_per_step"] == n_params * 4
        # mean context = 16 + 241/2; KV = batch·layers·2·L·d·4B
        mean_ctx = 16 + 241 / 2
        assert roof["kv_bytes_per_step_avg"] == int(
            8 * 4 * 2 * mean_ctx * 512 * 4)
        expect = 8 / ((roof["weight_bytes_per_step"]
                       + roof["kv_bytes_per_step_avg"]) / 8.19e11)
        assert abs(roof["ceiling_tokens_per_sec"] - expect) < 1.0
        assert 80_000 < roof["ceiling_tokens_per_sec"] < 110_000
        # unknown chip (CPU virtual mesh) → None, not a bogus number
        assert decode_roofline(
            batch=8, prompt_len=16, max_new=240, d_model=512, n_layers=4,
            d_ff=2048, vocab=256, hbm_bytes_per_s=0) is None

    def test_paged_prefill_roofline_tracks_live_kv(self):
        """The kernel-family PR's prefill rung: analytic KV bytes per
        prompt token — the kernel path's reads are monotone in live-KV
        fraction (it walks the committed prefix) and sit below the
        gather path everywhere, while gather's dense-view reads are
        flat in occupancy."""
        import importlib.util
        from pathlib import Path as _P

        spec = importlib.util.spec_from_file_location(
            "roofline", _P(__file__).resolve().parent.parent
            / "benchmarks" / "roofline.py")
        rl = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rl)

        row = rl.paged_prefill_row()
        assert row["rung"] == "paged_prefill"
        assert row["bound"] == "bandwidth"
        assert row["kernel_tracks_live_kv"] is True
        assert row["gather_flat_in_occupancy"] is True
        assert row["kernel_below_gather_everywhere"] is True
        # spot-check the accounting at f = 0.5: prefix blocks × kv
        # bytes/pos over the pad-sized chunk
        cfg = row["config"]
        kv_pos = 2 * cfg["n_layers"] * cfg["d_model"] * cfg["dtype_bytes"]
        at_half = [r for r in row["rows"]
                   if r["live_kv_fraction"] == 0.5][0]
        live = cfg["max_len"] // 2
        assert at_half["read_bytes_per_prompt_token_kernel"] == int(
            -(-live // cfg["kv_block"]) * cfg["kv_block"] * kv_pos
            / cfg["prefill_pad"])
        assert at_half["read_bytes_per_prompt_token_gather"] == int(
            (1 + cfg["prefill_pad"]) * cfg["max_len"] * kv_pos
            / cfg["prefill_pad"])


class TestPlanBench:
    """The frozen planner-validation artifact (plan_bench): every rung
    must carry predicted-vs-measured rows and the error band the
    planner quotes at plan time."""

    def test_frozen_plan_artifact_fields(self):
        import json as _json
        from pathlib import Path as _P

        frozen = sorted(_P(__file__).resolve().parent.parent.glob(
            "PLAN_r*.json"))
        if not frozen:
            pytest.skip("no frozen PLAN artifact yet")
        doc = _json.loads(frozen[-1].read_text())
        hdr = doc["artifact"]
        assert hdr["schema"] == 1 and hdr["family"] == "PLAN"
        assert hdr["round"] == int(frozen[-1].stem.split("_r")[-1])
        for wl in ("training", "serving"):
            sec = doc[wl]
            assert sec["rungs"], wl
            for rung in sec["rungs"]:
                assert rung["predicted_best"] and rung["measured_best"]
                assert isinstance(rung["match"], bool)
                for row in rung["configs"]:
                    assert row["predicted_s"] > 0
                    assert row["measured_s"] > 0
                    assert row["error_frac"] >= 0
            band = sec["error_band"]
            assert 0 <= band["max_frac"]
            assert band["n_configs"] >= band["n_rungs"] >= 1
        smry = doc["summary"]
        assert isinstance(smry["all_match"], bool)
        assert smry["rungs_ok"] >= 1 and 0 < smry["match_rtol"] < 1

    def test_round_detection_scans_all_families(self):
        """BENCH_r* counter lags the per-family artifacts — the round
        stamp must come from the max across every *_rNN.json family."""
        import importlib.util
        from pathlib import Path as _P

        repo = _P(__file__).resolve().parent.parent
        spec = importlib.util.spec_from_file_location(
            "plan_bench", repo / "benchmarks" / "plan_bench.py")
        pb = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pb)
        rnd = pb.detect_round()
        existing = max(
            int(m.group(1))
            for p in repo.glob("*_r*.json")
            if (m := pb._ROUND_RE.match(p.name)))
        assert rnd == existing + 1


def test_documents_name_only_scripts_that_exist():
    """Every ``benchmarks/<name>.py`` and every root record
    ``<NAME>_rNN.json`` the documents name is in the tree (a family named
    by pattern, ``_r{NN}`` / ``_r*`` / ``_rNN``, has at least one record):
    a document that sells a script or cites a record that went is the
    drift ISSUE 46 found."""
    import re
    from pathlib import Path as _P

    repo = _P(__file__).resolve().parent.parent
    missing = []
    for doc in ("README.md", "benchmarks/README.md", "docs/ARCHITECTURE.md",
                "docs/MIGRATION.md", "PARITY.md"):
        text = (repo / doc).read_text()
        scripts = set(re.findall(r"benchmarks/(\w+\.py)", text))
        if doc == "benchmarks/README.md":
            # its table names its neighbours bare
            scripts |= set(re.findall(r"`(\w+\.py)`", text))
        missing += [f"{doc}: benchmarks/{name}" for name in sorted(scripts)
                    if not (repo / "benchmarks" / name).exists()
                    and not (repo / name).exists()]
        records = set(re.findall(
            r"\b([A-Z][A-Z0-9_]*_r(?:\d+|\{NN\}|NN|\*))\.json", text))
        for record in sorted(records):
            family, _, rnd = record.rpartition("_r")
            pattern = f"{family}_r{rnd if rnd.isdigit() else '[0-9]*'}.json"
            if not list(repo.glob(pattern)):
                missing.append(f"{doc}: {record}.json")
    assert not missing, missing
