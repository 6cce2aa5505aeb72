"""Env-var parsing shared by the runtime knobs (watchdog deadline, host
fabric timeout, init retry/backoff).  Kept dependency-free: the watchdog
imports this and must stay importable without jax."""

from __future__ import annotations

import os
from typing import Optional


def env_float(name: str, default: Optional[float] = None) -> Optional[float]:
    """``float(os.environ[name])``, falling back to ``default`` when the
    var is unset, empty, or unparseable (a typo'd knob must never take a
    job down — the default is always a safe behavior)."""
    v = os.environ.get(name)
    if not v:
        return default
    try:
        return float(v)
    except ValueError:
        return default


def env_positive_float(name: str,
                       default: Optional[float] = None) -> Optional[float]:
    """Like :func:`env_float`, with ``<= 0`` meaning "explicitly disabled"
    (maps to ``default``) — the contract of the deadline/timeout knobs."""
    v = env_float(name, None)
    return default if v is None or v <= 0 else v


def env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    """``int(os.environ[name])``, falling back to ``default`` when the var
    is unset, empty, or unparseable."""
    v = os.environ.get(name)
    if not v:
        return default
    try:
        return int(v)
    except ValueError:
        return default


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean env knob: unset → ``default``; set to ``0``/``false``/
    ``off``/``no``/empty (case-insensitive) → False; anything else →
    True.  The contract of the on/off switches (``TPUDIST_TELEMETRY``)."""
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("", "0", "false", "off", "no")


def env_rank(default: Optional[int] = None) -> Optional[int]:
    """This process's global rank from the launcher env contracts, in
    precedence order (tpudist > torchrun > SLURM) — the ONE resolution
    chain shared by crash-record attribution and fault-injection gating,
    so they can never disagree about which rank a process is."""
    for var in ("TPUDIST_PROCESS_ID", "RANK", "SLURM_PROCID"):
        v = os.environ.get(var)
        if v:
            try:
                return int(v)
            except ValueError:
                continue
    return default


#: The inventory of every ``TPUDIST_*`` environment knob the package
#: reads — name → one-line contract.  This registry is the gate that
#: keeps knobs from shipping undocumented: ``tests/test_env_inventory.py``
#: asserts (a) every ``TPUDIST_*`` name referenced anywhere in the
#: package appears here, and (b) every name here is documented in
#: ``docs/ARCHITECTURE.md``.  Add the entry and the doc row with the
#: code, or the suite fails.
ENV_VARS = {
    # launch contract (set by launch/tpurun; consumed by runtime.bootstrap)
    "TPUDIST_COORDINATOR": "host:port of process 0's coordination service",
    "TPUDIST_NUM_PROCESSES": "world size of the launch contract",
    "TPUDIST_PROCESS_ID": "this process's global rank",
    "TPUDIST_LOCAL_RANK": "rank within the node",
    "TPUDIST_LOCAL_WORLD_SIZE": "processes per node",
    "TPUDIST_RUN_ID": "job-scoped rendezvous/run id",
    "TPUDIST_RESTART_COUNT": "tpurun restart generation (0 on first launch)",
    "TPUDIST_ERROR_FILE": "crash-record path template (%r → rank)",
    "TPUDIST_TMPDIR": "job-local scratch directory",
    # robustness knobs
    "TPUDIST_WATCHDOG_S": "hang-watchdog stall deadline in seconds (<=0 off)",
    "TPUDIST_HOST_TIMEOUT_S": "host-fabric collective deadline in seconds",
    "TPUDIST_INIT_RETRIES": "jax.distributed.initialize retry budget",
    "TPUDIST_INIT_BACKOFF_S": "initialize retry base backoff seconds",
    "TPUDIST_FAULT": "chaos fault-injection grammar (runtime.faults)",
    # serving (tpudist.serve — ServeConfig.from_env)
    "TPUDIST_SERVE_SLOTS": "continuous-batching KV-cache slot count",
    "TPUDIST_SERVE_QUEUE": "serving request-queue bound (backpressure)",
    "TPUDIST_SERVE_MAX_NEW": "default per-request output-token budget",
    "TPUDIST_SERVE_PREFILL_PAD": "prefill chunk length (pad per compiled chunk)",
    "TPUDIST_SERVE_DEADLINE_S": "default per-request deadline seconds (<=0 off)",
    "TPUDIST_SERVE_DECODE_BLOCK": "max fused decode tokens per dispatch (K)",
    "TPUDIST_SERVE_PAGED": "paged KV cache: block pool + per-slot tables",
    "TPUDIST_SERVE_KV_BLOCK": "tokens per KV block (must divide max_len)",
    "TPUDIST_SERVE_KV_BLOCKS": "KV pool size in blocks (default: dense-equivalent)",
    "TPUDIST_SERVE_KV_INT8": "int8 KV storage with per-block dequant scales",
    "TPUDIST_SERVE_PREFIX_CACHE": "shared-prefix LRU cache bound in blocks (0 off)",
    "TPUDIST_SERVE_ATTN_KERNEL":
        "decode attention on the paged cache: gather (dense view per "
        "dispatch) | paged (Pallas kernel, in-kernel block-table walk)",
    "TPUDIST_SERVE_PREFILL_KERNEL":
        "paged-prefill flash kernel: block table walked AND written "
        "in-kernel (requires TPUDIST_SERVE_PAGED)",
    "TPUDIST_SERVE_SAMPLE_KERNEL":
        "fused in-kernel sampling tail: temperature + top-k/top-p + "
        "grammar mask + greedy argmax in one pass",
    "TPUDIST_SERVE_FUSED_ROPE":
        "fused RoPE+QKV projection kernel on the kernel arms "
        "(requires ATTN_KERNEL=paged and/or PREFILL_KERNEL)",
    "TPUDIST_SERVE_LORA_KERNEL":
        "in-kernel LoRA gather-matmul from the adapter pool "
        "(requires ADAPTERS and a kernel arm)",
    "TPUDIST_SERVE_MESH":
        "serving mesh shape 'DxM' (data x model; '1' = single device)",
    "TPUDIST_SERVE_TP_OVERLAP":
        "TP decode collective-matmul routing: off|ring|bidir "
        "(falls back to TPUDIST_OVERLAP)",
    "TPUDIST_SERVE_DISAGG": "prefill/decode disaggregation (separate pools)",
    "TPUDIST_SERVE_PREFILL_WORKERS": "prefill-pool worker count (disagg)",
    "TPUDIST_SERVE_DECODE_WORKERS": "decode-pool worker count (disagg)",
    "TPUDIST_SERVE_PREFILL_SLOTS":
        "slots per prefill worker (disagg; default: the decode slot count)",
    "TPUDIST_SERVE_HANDOFF":
        "KV handoff transport: device (in-mesh) | serial (byte transfer)",
    "TPUDIST_SERVE_HANDOFF_QUEUE": "bounded pending-KV-handoff queue length",
    "TPUDIST_SERVE_RECOVER":
        "self-healing disagg fleet: dead-worker lanes replay on survivors "
        "(default on; 0 = worker death aborts outstanding work)",
    "TPUDIST_SERVE_POOL_RESIZE":
        "iterations of sustained handoff-queue backpressure before the "
        "prefill slot budget shrinks by one (0 = off)",
    "TPUDIST_SERVE_HEALTH_STALE_S":
        "/healthz engine-heartbeat staleness threshold in seconds "
        "(default 300 — must exceed the first-dispatch XLA compile)",
    # host-RAM KV tier + overload control (serve/host_tier.py, overload.py)
    "TPUDIST_SERVE_HOST_TIER":
        "host-RAM KV session tier: park idle/preempted lanes in host "
        "memory, resume without recompute (default off)",
    "TPUDIST_HOST_TIER_BYTES":
        "host-tier byte budget (default 1 GiB; LRU spill beyond it)",
    "TPUDIST_HOST_TIER_TTL_S":
        "idle parked-session expiry in seconds (<=0/unset = LRU only)",
    "TPUDIST_SERVE_PREEMPT":
        "priority preemption: a higher-priority arrival parks a "
        "lower-priority decode lane in the host tier (default on; "
        "effective only with the host tier enabled)",
    "TPUDIST_SERVE_SHED":
        "SLO-aware load shedding off the live per-tenant attainment "
        "gauges (default off; needs TPUDIST_SLO_* targets + metrics)",
    "TPUDIST_SERVE_SHED_ATTAINMENT":
        "protected-class attainment floor that trips shedding "
        "(default 0.9)",
    "TPUDIST_SERVE_SHED_PRIORITY":
        "protected priority class: requests at or above it are never "
        "shed (default 1)",
    "TPUDIST_SERVE_FAIR_SHARE":
        "per-tenant token-rate fairness multiplier — reject a tenant "
        "above this multiple of its equal share once the queue is half "
        "full (0/unset = off)",
    # fleet router (serve/router.py — RouterConfig.from_env)
    "TPUDIST_ROUTER_REPLICAS":
        "fleet size for env-driven multi-replica rigs (default 2; the "
        "router itself takes an explicit replica list)",
    "TPUDIST_ROUTER_PROBE_S":
        "per-replica health-probe interval in seconds (default 0.05)",
    "TPUDIST_ROUTER_PROBE_FAILURES":
        "consecutive probe failures before a replica is marked dead "
        "(default 3; dead replicas re-probe on exponential backoff)",
    "TPUDIST_ROUTER_RETRIES":
        "per-request re-home budget after a replica dies mid-serve "
        "(default 2; exhaustion finishes the request replica_lost)",
    "TPUDIST_ROUTER_RETRY_BACKOFF_S":
        "re-home retry backoff base in seconds (default 0.05; doubles "
        "per failed attempt)",
    "TPUDIST_ROUTER_SPILL":
        "overflow spills to a sibling replica (paying a re-prefill) "
        "instead of rejecting while any replica has headroom "
        "(default on; 0 = reject on the affinity target's answer)",
    "TPUDIST_ROUTER_STASH":
        "router-side parked-package stash: finished session turns are "
        "exported so replica death migrates the session to a survivor "
        "(default on; 0 = death degrades sessions to full re-prefill)",
    "TPUDIST_ROUTER_POLICY":
        "routing policy: affinity (session -> prefix -> least-loaded, "
        "default) | rr (round-robin comparison arm)",
    # per-tenant adapters (serve/adapters.py + models/lora.py)
    "TPUDIST_SERVE_ADAPTERS":
        "per-tenant adapters: paged multi-LoRA factor pool + per-slot "
        "adapter ids, batched gathered decode (default off)",
    "TPUDIST_SERVE_ADAPTER_BLOCKS":
        "adapter-pool capacity in blocks — one resident adapter each "
        "(default 8; LRU-evicts cold adapters on load)",
    "TPUDIST_SERVE_ADAPTER_RANK":
        "LoRA rank r shared by every adapter in the pool (default 8)",
    # measurement-driven planner (tpudist/plan/)
    "TPUDIST_SERVE_AUTO":
        "env spelling of ServeConfig.auto: plan unpinned serving knobs "
        "against the frozen measurement artifacts (default off)",
    "TPUDIST_PLAN_DIR":
        "planner artifact directory (default: the repo root, where the "
        "harnesses' *_rNN.json records lie)",
    "TPUDIST_PLAN_TOPN":
        "rows the plan report prints per workload (default 0 = all)",
    "TPUDIST_PLAN_STALE_ROUNDS":
        "rounds behind the newest artifact before a family is rejected "
        "as stale evidence (default 20)",
    "TPUDIST_PLAN_STRICT":
        "1 = missing/rejected artifact families raise PlanArtifactError "
        "instead of degrading to the analytic model (default off)",
    # structured output (tpudist/constrain/)
    "TPUDIST_SERVE_CONSTRAIN":
        "structured output: per-request grammar/json_schema asks compile "
        "to token FSAs masking decode in-graph (default off)",
    "TPUDIST_CONSTRAIN_BLOCKS":
        "grammar-pool capacity in table blocks — one resident compiled "
        "grammar each (default 4; LRU-evicts unpinned grammars)",
    "TPUDIST_CONSTRAIN_STATES":
        "automaton state cap per compiled grammar — fixes the dense "
        "mask/transition table height (default 64; bigger grammars "
        "reject invalid_grammar)",
    "TPUDIST_SERVE_LOGPROBS":
        "engine-wide top-n logprobs width per emitted token (default 0 "
        "= off; per-request submit(logprobs=n) asks are slices of it)",
    "TPUDIST_SERVE_SPEC":
        "speculative decoding: draft proposes K, target verifies in one pass",
    "TPUDIST_SERVE_SPEC_K": "drafted tokens per speculative block",
    "TPUDIST_SERVE_SPEC_DRAFT_LAYERS":
        "tied-draft depth (target's first N layers; 0 = half the depth)",
    # online draft distillation (tpudist/distill/)
    "TPUDIST_DISTILL_CAPTURE":
        "live-traffic capture ring for draft distillation (default off; "
        "1 = tap finished streams into the bounded buffer)",
    "TPUDIST_DISTILL_BUFFER_TOKENS":
        "capture-ring token budget — oldest streams evict past it "
        "(default 65536)",
    "TPUDIST_DISTILL_SAMPLE":
        "capture every Nth finished stream (default 1 = all; sampled-out "
        "streams are counted, never silently dropped)",
    "TPUDIST_DISTILL_INTERVAL_S":
        "background distillation round cadence in seconds (default 30)",
    "TPUDIST_DISTILL_STEPS":
        "trainer steps per distillation round (default 40)",
    "TPUDIST_DISTILL_MIN_TOKENS":
        "captured-token floor before a round will train (default 256)",
    "TPUDIST_DISTILL_HOLDOUT":
        "held-out fraction of captured streams reserved for the swap "
        "gate's acceptance eval (default 0.25)",
    "TPUDIST_DISTILL_SWAP_MARGIN":
        "hysteresis: candidate must beat the serving draft's measured "
        "acceptance by this margin to hot-swap (default 0.02)",
    "TPUDIST_DISTILL_LR":
        "distillation learning rate (default 3e-3)",
    "TPUDIST_DISTILL_PER_ADAPTER":
        "bias rounds toward the heaviest captured adapter when it is "
        "resident in the adapter registry (default off)",
    # telemetry & goodput
    "TPUDIST_TELEMETRY": "telemetry arm switch (default on; 0/false = off)",
    "TPUDIST_TELEMETRY_DIR": "where per-rank telemetry JSONL + reports land",
    "TPUDIST_TELEMETRY_RING": "in-memory telemetry ring size (records)",
    # live observability plane (metrics / trace / statusz)
    "TPUDIST_METRICS":
        "live metrics registry feed from the span/event seams "
        "(default on; 0 = post-hoc telemetry only)",
    "TPUDIST_METRICS_PORT":
        "scrape endpoint port for /metrics /healthz /statusz "
        "(unset = off; 0 = ephemeral port for CI)",
    "TPUDIST_METRICS_ADDR":
        "scrape endpoint bind address (default 127.0.0.1 — the "
        "documents are unauthenticated; 0.0.0.0 is an explicit opt-in)",
    "TPUDIST_TRACE":
        "per-request trace lifeline spans (req_queue/req_prefill/"
        "req_handoff/req_decode; default on; 0 = trace_ids only)",
    "TPUDIST_SLO_TTFT_MS":
        "declared time-to-first-token SLO target in ms (<=0/unset = "
        "none) -> live attainment gauges + report slo section",
    "TPUDIST_SLO_TPOT_MS":
        "declared time-per-output-token SLO target in ms (<=0/unset = "
        "none) -> live attainment gauges + report slo section",
    # parallel execution strategy
    "TPUDIST_OVERLAP":
        "collective-matmul overlap mode: off|ring|bidir (default off)",
    # caches, the train loop's window
    "TPUDIST_COMPILATION_CACHE": "off = disable the persistent XLA compile cache (placed by JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)",
    "TPUDIST_CACHE": "native data-loader build cache base dir",
    "TPUDIST_SYNC_EVERY": "train-loop scan window / metric sync cadence",
    # sweep harness contract (launch/sweep.py)
    "TPUDIST_SWEEP_METRIC_FILE": "where a sweep trial writes its objective",
    "TPUDIST_SWEEP_RESULTS": "sweep results.jsonl path for the report CLI",
    "TPUDIST_SWEEP_INDEX": "trial index within the sweep",
    "TPUDIST_SWEEP_CONFIG": "the trial's resolved config (repr)",
}
