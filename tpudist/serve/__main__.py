"""``python -m tpudist.serve`` — self-contained serving demo.

Builds a small randomly-initialized ``TransformerLM``, starts the
continuous-batching server, pushes a burst of concurrent requests with
heterogeneous prompt/output lengths through it, streams tokens, drains,
and prints a JSON summary (per-request TTFT/latency + server stats).
Runs on CPU in seconds — the quick-start for the serving subsystem; the
real measurement harness is ``benchmarks/serve_bench.py``.

``--replicas N`` (N >= 2) runs the same burst through the fleet router
instead: N replica servers behind :class:`tpudist.serve.FleetRouter`,
with the routing/failover stats in the summary — the multi-replica
quick-start (``tests/test_router.py`` holds the routing and failover
drives).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpudist.serve",
        description="continuous-batching serving demo (random weights)")
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--queue", type=int, default=64)
    p.add_argument("--max-new", type=int, default=16,
                   help="output-length ceiling; each request draws from "
                        "[2, max-new]")
    p.add_argument("--prompt-len", type=int, default=12,
                   help="prompt-length ceiling; each request draws from "
                        "[1, prompt-len]")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--decode-block", type=int, default=8,
                   help="max fused decode tokens per device dispatch (K)")
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--replicas", type=int, default=1,
                   help="run N replica servers behind the fleet router "
                        "(1 = single server, no router)")
    p.add_argument("--telemetry-dir", default=None,
                   help="where serving spans land (default: "
                        "TPUDIST_TELEMETRY_DIR or runs/telemetry)")
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from tpudist import telemetry
    from tpudist.models import create_transformer
    from tpudist.serve import (FleetRouter, InferenceServer, RouterConfig,
                               ServeConfig)

    if args.telemetry_dir:
        telemetry.start(args.telemetry_dir)
    module, params = create_transformer(
        jax.random.PRNGKey(args.seed), seq_len=16, vocab=args.vocab,
        d_model=args.d_model, n_layers=args.n_layers,
        n_heads=max(2, args.d_model // 32), d_ff=4 * args.d_model,
        max_len=args.max_len)
    # chunked prefill admits prompts up to max_len - max_new; the pad is
    # just the chunk size — half the prompt ceiling, so the demo's longer
    # prompts actually exercise the chunked-prefill path
    prefill_pad = max(1, min(args.prompt_len // 2, args.max_len // 2))
    cfg = ServeConfig(num_slots=args.slots, queue_limit=args.queue,
                      max_new=args.max_new, prefill_pad=prefill_pad,
                      decode_block=args.decode_block,
                      host_tier=args.replicas > 1)
    if args.replicas > 1:
        # the multi-replica rig: N servers sharing the (tiny random)
        # weights, the router in front — sessions park in each
        # replica's host tier so death/drain can migrate them
        replicas = [InferenceServer(module, params, cfg,
                                    install_signal_handler=False).start()
                    for _ in range(args.replicas)]
        front = FleetRouter(replicas, RouterConfig()).start()
    else:
        front = InferenceServer(module, params, cfg)
        front.start()

    import time

    from tpudist.serve import AdmissionError

    rng = np.random.default_rng(args.seed)
    handles = []
    # prompts range past the pad (chunked prefill) but stay admissible
    # under the budget rule plen + max_new <= max_len
    plen_cap = max(1, min(args.prompt_len, args.max_len - args.max_new))
    for i in range(args.requests):
        plen = int(rng.integers(1, plen_cap + 1))
        max_new = int(rng.integers(2, args.max_new + 1))
        prompt = rng.integers(0, args.vocab, size=plen).astype(np.int32)
        stop_burst = False
        while True:
            try:
                handles.append(front.submit(
                    prompt, max_new=max_new, temperature=args.temperature,
                    seed=i))
                break
            except AdmissionError as e:
                if e.reason != "queue_full":
                    # only backpressure is transient; "draining" (e.g. the
                    # engine loop died) would spin here forever
                    print(f"[serve demo] submit stopped: {e.reason}",
                          file=sys.stderr)
                    stop_burst = True
                    break
                time.sleep(0.01)  # bounded queue doing its job: wait
        if stop_burst:
            break
    for h in handles:
        h.wait()
    stats = front.stats()
    front.close()
    report = telemetry.finish()

    rows = [{
        "id": getattr(h, "id", None),
        "prompt_len": int(len(getattr(h, "prompt", None)
                              if args.replicas > 1 else h.request.prompt)),
        "tokens_out": len(h.tokens),
        "reason": h.finish_reason,
        **({"replica": h.replica} if args.replicas > 1 else {}),
        "ttft_ms": round(h.ttft_s * 1e3, 2) if h.ttft_s else None,
        "tpot_ms": round(h.tpot_s * 1e3, 2) if h.tpot_s else None,
    } for h in handles]
    print(json.dumps({"requests": rows, "stats": stats,
                      "telemetry_report": bool(report)}, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
