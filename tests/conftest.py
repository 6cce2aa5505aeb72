"""Test harness: an 8-device virtual CPU mesh in one process.

SURVEY.md §4: the reference had zero automated tests (the demos were the
tests).  JAX lets us do better — ``--xla_force_host_platform_device_count=8``
simulates an 8-device mesh in-process, so DP/model-split/trainer semantics,
sampler sharding, seeding, and checkpointing are ordinary pytest units.
Env vars must be set before jax initializes its backends, hence here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests never write a compile cache into the checkout as a side effect
# of calling initialize(); the cache rule has its own tests
# (test_runtime.py), which set what they need.
os.environ.setdefault("TPUDIST_COMPILATION_CACHE", "off")

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def dp_mesh():
    from tpudist.runtime.mesh import data_parallel_mesh

    return data_parallel_mesh()


@pytest.fixture()
def dm_mesh():
    from tpudist.runtime.mesh import data_model_mesh

    return data_model_mesh(model_size=2)


# ---------------------------------------------------------------------------
# Wall-clock split: the heavy convergence/integration smokes are marked
# ``slow`` and EXCLUDED from the default selection (pyproject addopts
# ``-m "not slow"``).  The default selection is tier-1: the driver runs it
# with six xdist workers (``-n 6 --dist loadfile``, the command of
# ``/root/TESTS_LAST_RUN.json``) under a limit of 1,470 s, and a run that
# is cut counts only as far as it got.  The last whole run, PR 46's on
# its own tree, on 8 cores: 931 s wall, 4,906 s of test time, 1,670
# tests (1,055 / 5,725 / 1,670 in the driver's run on the tree before).
# A test in the slow lane is a test no one runs (its last recorded run is
# ``SLOW_SUITE_r04.txt``): make a slow test cheaper before moving it here.
# ``pytest -m slow`` runs the rest; ``pytest -m "slow or not slow"`` runs
# everything.  Patterns are nodeid substrings, grouped here (not per-file
# decorators) so the whole selection policy is auditable in one place.
_SLOW_PATTERNS = (
    # multi-process integration (real subprocess rendezvous)
    "test_multiprocess.py",
    # subprocess kill/restart chaos harness (fast single-process fault
    # tests stay default in test_faults.py / test_watchdog.py)
    "test_chaos.py",
    # driver-shaped end-to-end smokes
    "test_graft_entry.py::test_dryrun_multichip",
    # benchmark-harness end-to-end runs
    "TestLossParity",
    "TestNumericsGate::test_gate_passes_and_reports_all_cases",
    # entry-point / trainer convergence smokes
    "TestLongContextExample",
    "TestWindowedRingExample",
    "Test3DParallelExample",
    "test_trainer_checkpoint_resume",
    "test_trainer_bf16",
    "test_trainer_convergence",
    "TestTpurun::test_restart_then_success",
    # heavy model-family convergence runs (each regime keeps a quick
    # parity/unit twin in the default selection)
    "TestPipelineParallelTransformer::test_pp_apply_rope_remat",
    "TestPipelineParallelTransformer::test_pp_training_matches_replicated",
    "TestLMTraining::test_loss_decreases_on_dp_sp_mesh",
    "TestMoETransformer::test_moe_lm_trains",
    "TestMoETransformer::test_moe_aux_stats",
    "TestMixedPrecision::test_bf16_lm_trains_ring",
    "TestMixedPrecision::test_bf16_forward_close_to_f32",
    "TestTensorParallelTransformer::test_tp_training_matches_replicated",
    "TestAttentionInterchangeability::test_dense_flash_ring_agree",
    "TestGQA::test_gqa_trains_with_ring",
    "TestFSDP::test_loss_matches_replicated",
    "Test1F1BSchedule::test_1f1b_trains",
    "Test1F1BSchedule::test_gpipe_schedule_selectable",
    "test_loss_and_update_parity_with_gpipe[8]",
    # serving: sustained-load dynamics (late join / backpressure / drain
    # under load); the fast slot/scheduler/server cases stay default
    "TestServeUnderLoad",
    # fleet-recovery chaos drives (each builds multi-worker disagg
    # servers and kills workers mid-flight; the fast envelope +
    # requeue-bookkeeping units stay default in test_serve_recovery.py)
    "TestWorkerLossChaos",
    # cross-pool trace chaos drive (multi-worker disagg + kill; the
    # fast lifeline/schema/export units stay default in test_trace.py)
    "TestTraceChaos",
    # host-tier preemption/session matrices + disagg park/resume e2e
    # (each cell builds servers; the dense greedy drives, the tier/
    # scheduler/controller units, and the parked-deadline regression
    # stay default in test_host_tier.py)
    "TestPreemptMatrix",
    "TestSessionMatrix",
    "TestDisaggHostTier",
    # sharded-serving sweeps: full mesh-shape × engine-mode oracle
    # matrix + disagg server e2e (the fast engine-level mesh/handoff
    # oracles stay default in TestServeSpmd)
    "TestServeMeshOracleSweep",
    "TestDisaggServer",
    # per-tenant adapter matrices: mesh/spec/kernel oracle sweeps, the
    # sampled stream-independence sweep (2 engines + per-request solo
    # drives), the cross-engine handoff re-bind drive, and the
    # disagg/host-tier re-bind e2e (the registry units, the dense/paged
    # greedy churn oracles, churn compile pins, and the dense-greedy
    # server representative stay default in test_serve_adapters.py)
    "TestAdapterMatrix",
    "TestAdapterDisaggTier",
    "TestAdapterOracle::test_sampled_streams_layout_independent",
    "TestAdapterHandoffUnit::test_export_import_rebinds_by_name",
    # structured-output oracle twins: the paged / speculative / adapter
    # arms each rebuild+recompile an engine (the dense mixed-batch
    # oracle, the registry refcount drive, the carry drives, and the
    # whole server surface stay default in test_constrain.py)
    "TestConstrainedDecodeOracle::test_mixed_batch_walks_and_free_lane_bit_exact[paged]",
    "TestConstrainedDecodeOracle::test_spec_arm_walks_with_logprobs",
    "TestConstrainedDecodeOracle::test_adapter_arm_walks",
    # fleet-router heavies: the sampled chaos-kill twin, the stash-off
    # degrade drive, and the live drain migration (the routing/probe/
    # spill units, the routed byte-identity reference, the greedy
    # chaos kill + corrupt-stash degrade, and the whole-fleet death
    # drive stay default in test_router.py)
    "test_mid_serve_kill_rehomes_byte_identical[sampled]",
    "TestReplicaDeathChaos::test_missing_stash",
    "TestRoutedServing::test_drain_replica_migrates_sessions_live",
    # serve_bench mesh/disagg/multiproc smokes + the decode trace
    # capture (each builds servers / spawns tpurun workers)
    "TestServeBench::test_smoke_mesh_rung",
    "TestServeBench::test_smoke_disagg_rung",
    "TestServeBench::test_multiproc_serve_rung",
    "TestServeBench::test_decode_profile_capture",
    # TP-serving decode-path comm-audit lowers
    "test_regime[serve_decode",
    # generation / checkpoint long chains
    "test_greedy_decodes_the_chain",
    "test_generate_with_filters_runs",
    "test_tp_sharded_lm_checkpoint_restores_replicated",
    "test_resume_matches_unbroken_run",
    # compile-heavy parity twins (each has a faster sibling in default:
    # e.g. the non-rope ring agreement, per-hop fwd kernels, small-window
    # variants) — moved out to hold tier-1's limit (see the top)
    "TestRoPE::test_ring_agrees_with_dense_under_rope",
    "test_loss_and_update_parity_with_gpipe[4]",
    "TestMixedPrecision::test_bf16_moe_stays_bf16",
    "TestMoETransformer::test_sharded_matches_dense_reference",
    "TestRingAttention::test_gradients_match_reference",
    "TestRingAttention::test_flash_kernel_gradients_match_reference",
    "TestRingAttention::test_inner_block_matches_reference",
    "TestRingAttention::test_sliding_window_gqa_ring_composed",
    "TestRingAttention::test_sliding_window_ring_gradients",
    "TestEndToEnd::test_trains_on_corpus_file",
    "test_scanned_resume_parity",
    "test_scanned_matches_per_step",
    "TestPipelineParallelTransformer::test_pp_apply_matches_sequential",
    "TestTpurun::test_env_contract",
    "TestGradAccumulation::test_matches_full_batch",
    "TestGeneration::test_temperature_sampling_valid",
    "TestOptimAndEvalStep::test_warmup_cosine_trains",
    "TestDecodeConsistency::test_cache_matches_full_forward",
    "test_save_restore_roundtrip",
    "TestFSDP::test_composes_with_tp",
    "TestFSDP::test_state_actually_sharded",
    "TestMoE::test_balance_weight_trains_toward_uniform",
    "TestMoE::test_matches_dense_routing",
    "TestMoE::test_balance_loss_measures_skew",
    "test_dp_matches_single_device",
    "test_convergence_smoke",
    "TestGQA::test_full_kv_heads_is_mha",
    "TestComposedMesh::test_dp_times_sp_attention",
    "TestPipeline::test_gradients_match_sequential",
    "TestTensorParallel::test_gradients_match_dense",
    "TestPipelineParallelTransformer::test_pp_apply_honors_sliding_window",
    "TestTpurun::test_peer_workers_killed_on_failure",
    "TestTpurun::test_node_rank_offsets_global_rank",
    "TestTpurun::test_exhausted_restarts_fail",
    "TestFlashAttention::test_backward_bf16",
    "test_flash_kernel_bf16_partials_stay_f32",
    "test_real_sigterm_preempts_training_subprocess",
    "test_loop_saves_and_exits_on_preemption_then_resumes",
    "test_completed_run_not_mislabeled_preempted",
    "test_run_bayes_end_to_end_minimizes",
    # compressed-grad-reduce convergence smoke (the fast
    # rejects-incompatible twin stays default)
    "TestCompressedGradReduce::test_tracks_f32_training",
    # comm-audit transformer lowers (compile-heavy; the dp/model-split
    # regimes + parser units stay in the default lane)
    "test_regime[dp_sp",
    "test_regime[dp_ep_moe]",
    "test_regime[fsdp]",
    "test_regime[dp_pp",
    # overlap-family transformer lowers (the small tp_mlp regimes and
    # the overlap numerics/knob tests stay default)
    "test_regime[fsdp_overlap",
    # unrolled-ring compile-count pinning (repeated jitted steps)
    "TestOverlapCompilePinning",
    # pipeline-demo e2e convergence runs (quick twins in default:
    # TestShardParity loss/grad parity, the 2-stage 1F1B smoke)
    "test_demo_pipeline[1f1b-1]",
    "test_demo_pipeline[interleaved-2]",
    # cross-topology checkpoint restore (default keeps the manager units;
    # the tp-sharded restore sibling is already slow)
    "test_interleaved_pp_checkpoint_restores_contiguous",
    # zigzag e2e convergence smokes (value/grad parity twins stay default)
    "TestZigzagRingExample::test_demo_runs_and_converges",
    "TestZigzagRing::test_lm_trains_end_to_end_via_standard_step",
    # 4-strategy facade parity chain (4 full train-step compiles; the
    # per-strategy sharding/smoke twins stay default)
    "TestTrainerStrategies::test_lm_strategies_loss_parity",
    # spec-decode heavy variants, relocated to hold the default lane
    # under the tier-1 wall budget after the observability tests joined
    # it (the same discipline as the paged-kernel variants below): the
    # default lane keeps the K=2 sampled dense-vs-paged stream
    # equivalence, the full greedy byte-identity sweep, and the
    # churn compile pins; these siblings extend to K∈{4,8} sampled and
    # the cross-mesh pin matrix
    "TestSpecOracle::test_sampled_stream_equivalence_dense_vs_paged[4]",
    "TestSpecOracle::test_sampled_stream_equivalence_dense_vs_paged[8]",
    "TestSpecCompilePins::test_compile_counts_flat_across_mesh_shapes",
    # the serve_bench spec-decode sweep smoke (~80s: distills a draft +
    # runs the rung matrix); the non-spec serve_bench smokes stay default
    "TestServeBench::test_smoke_spec_sweep",
    # paged-kernel engine-level variants (each builds+compiles fresh
    # engines; the default lane keeps the op-level equivalence sweep,
    # the f32 gather-vs-kernel-vs-oracle byte-identity drive, the
    # churn compile pins, and the server e2e — full kernel coverage at
    # ~half the wall cost; these siblings extend it to int8/sampled/
    # spec/handoff/mesh)
    "TestKernelEngine::test_greedy_byte_identity_vs_gather_and_oracle[int8]",
    "TestKernelEngine::test_sampled_streams_match_gather",
    "TestKernelEngine::test_spec_verify_through_kernel",
    "TestKernelEngine::test_handoff_adopted_lane_continues_byte_identical",
    "TestKernelEngine::test_compile_counts_flat_across_mesh_shapes",
    # kernel-family engine heavies (same discipline: each cell drives
    # fresh engines through full churn; the default lane keeps every
    # op-level kernel-vs-reference sweep, the f32 prefill
    # byte-identity + oracle + byte-accounting drive, the
    # paged-sampled fused-sampling representative, the all-four-
    # kernels full-stack greedy drive, the churn compile pins, and
    # the knob validation — these siblings extend to int8 prefill,
    # the remaining sampling cells, the spec arm, and the cross-mesh
    # pin matrix)
    "TestKernelFamilyEngine::test_prefill_kernel_greedy_byte_identity[int8]",
    "TestKernelFamilyEngine::test_fused_sampling_streams_identical[paged-greedy]",
    "TestKernelFamilyEngine::test_fused_sampling_streams_identical[dense-sampled]",
    "TestKernelFamilyEngine::test_fused_sampling_streams_identical[dense-greedy]",
    "TestKernelFamilyEngine::test_spec_through_kernel_prefill",
    "TestKernelFamilyEngine::test_compile_counts_flat_across_mesh_shapes",
    # LM facade resume chain (three compiled fits)
    "test_lm_checkpoint_resume_matches_unbroken",
)


def pytest_collection_modifyitems(config, items):
    matched = set()
    for item in items:
        for p in _SLOW_PATTERNS:
            if p in item.nodeid:
                item.add_marker(pytest.mark.slow)
                matched.add(p)
    # Self-audit on FULL collections: a renamed test must not silently
    # drop its pattern and rejoin tier-1.  "Full" = bare
    # `pytest` OR args that only restate the configured testpaths (the
    # README's `pytest tests/ -q` is a full collection too).
    args = {a.rstrip("/") for a in (config.getoption(
        "file_or_dir", default=None) or [])}
    testpaths = {t.rstrip("/") for t in config.getini("testpaths")}
    narrowed = (config.getoption("ignore", default=None)
                or config.getoption("ignore_glob", default=None)
                or config.getoption("deselect", default=None)
                or config.getoption("keyword", default=None))
    if (not args or args <= testpaths) and not narrowed:
        stale = [p for p in _SLOW_PATTERNS if p not in matched]
        if stale:
            raise pytest.UsageError(
                f"_SLOW_PATTERNS entries matched no collected test "
                f"(renamed/removed?): {stale}")
