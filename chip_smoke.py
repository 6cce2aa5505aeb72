#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpudist still starts on the chip.

Run from the repo root on a machine with a TPU (through the chip tool):

    python chip_smoke.py             # one chip: train, demo, serve, kernels
    python chip_smoke.py --chips 4   # four chips: FSDP + ring vs one device

One process, no child that needs the chip, no platform set here: the
script asks JAX which device it got and exits non-zero unless it is a
TPU.  Each phase drives the program through the entry points a user
calls, at the full width of the widest model the repo has history for
(the d1024 / 8x128 / ff4096 / L8 / seq2048 / b8 bf16 decoder LM), with
random weights made from a seed, and checks its output by the repo's own
means.  Nothing is caught to continue: the first failing check ends the
run non-zero.  The last line of stdout is the fixed result line.

What it proves: the program compiles and runs on this chip, the Pallas
kernels are in the compiled programs and agree with their references,
training lowers the loss, a checkpoint round-trips, the server answers
and matches ``generate()``.  What it does not: any speed.  The seconds
it prints are smoke observations (one run, compile included where
said), never benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Sequence

REPO = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Widths:
    """One model + serving-pool geometry.  ``FULL`` is what the chip runs;
    the CPU tests run every phase at ``TINY`` with kernels interpreted."""

    d_model: int = 1024
    n_heads: int = 8
    d_ff: int = 4096
    n_layers: int = 8
    seq: int = 2048
    batch: int = 8
    vocab: int = 256
    # the paged pool the serving kernels are checked against
    slots: int = 32
    kv_block: int = 16
    kv_blocks: int = 1024
    prefill_chunk: int = 128
    big_vocab: int = 32768   # sampling kernels: a real tokenizer's width
    lora_rank: int = 16
    flash_tiles: Sequence = ((512, 512), (1024, 1024))

    @property
    def dh(self) -> int:
        return self.d_model // self.n_heads

    def model_kwargs(self) -> dict:
        import jax.numpy as jnp

        return dict(vocab=self.vocab, d_model=self.d_model,
                    n_layers=self.n_layers, n_heads=self.n_heads,
                    d_ff=self.d_ff, max_len=self.seq, dtype=jnp.bfloat16)


FULL = Widths()
TINY = Widths(d_model=32, n_heads=2, d_ff=64, n_layers=2, seq=64, batch=8,
              vocab=32, slots=4, kv_block=8, kv_blocks=12, prefill_chunk=8,
              big_vocab=160, lora_rank=2, flash_tiles=((32, 32),))


def say(phase: str, **fields) -> None:
    """One phase line: ``[phase] key=value ...`` (floats to 4 significant)."""
    def fmt(v):
        return f"{v:.4g}" if isinstance(v, float) else str(v)

    print(f"[{phase}] " + " ".join(f"{k}={fmt(v)}" for k, v in fields.items()),
          flush=True)


def chain_tokens(w: Widths, seed: int):
    """Seeded increment-chain tokens ``[batch, seq]`` — fully predictable
    after the first position, so a few optimizer steps lower the loss."""
    import numpy as np

    start = np.random.default_rng(seed).integers(0, w.vocab, (w.batch, 1))
    return ((start + np.arange(w.seq)[None]) % w.vocab).astype(np.int32)


# ---------------------------------------------------------------------------
# phase: train


def phase_train(w: Widths, *, seed: int, steps: int = 10,
                expect_kernel: bool = True) -> list:
    """initialize -> mesh -> create_transformer -> init_lm_state ->
    make_lm_train_step; ``steps`` Adam steps; checkpoint round trip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpudist.checkpoint import (CheckpointConfig, CheckpointManager,
                                    abstract_like)
    from tpudist.models import create_transformer
    from tpudist.runtime import initialize
    from tpudist.runtime.mesh import data_parallel_mesh
    from tpudist.train import init_lm_state, make_lm_train_step, token_sharding

    initialize()
    mesh = data_parallel_mesh()
    module, params = create_transformer(
        jax.random.PRNGKey(seed), seq_len=w.seq, **w.model_kwargs())
    tx = optax.adam(1e-3)
    state = init_lm_state(params, tx)
    tokens = jax.device_put(chain_tokens(w, seed), token_sharding(mesh))
    t0 = time.perf_counter()
    step = make_lm_train_step(module.apply, tx, mesh).lower(
        state, tokens).compile()
    compile_s = time.perf_counter() - t0
    n_kernels = step.as_text().count("tpu_custom_call")
    if expect_kernel and not n_kernels:
        raise AssertionError(
            "the compiled train step holds no tpu_custom_call: attention "
            "gave way to an XLA formulation instead of the flash kernel")
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = step(state, tokens)
        jax.block_until_ready((state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"loss must stay finite and fall: {losses}")
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    say("train", mesh=dict(mesh.shape), params=n_params,
        compile_s=compile_s, flash_custom_calls=n_kernels,
        step_s_first=step_s[0], step_s_median=float(np.median(step_s[1:])),
        losses=[round(x, 4) for x in losses])

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        mgr = CheckpointManager(CheckpointConfig(
            directory=d, save_every=1, async_save=False))
        t0 = time.perf_counter()
        mgr.save(steps, state, {"iteration": steps})
        mgr.wait_until_finished()
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        restored, meta = mgr.restore(abstract_like(state))
        jax.block_until_ready(restored)
        restore_s = time.perf_counter() - t0
        mgr.close()
        same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                            state, restored)
        if not all(jax.tree.leaves(same)) or meta.get("iteration") != steps:
            raise AssertionError("checkpoint did not restore bit-identically")
    say("train", checkpoint="round-trip-identical", save_s=save_s,
        restore_s=restore_s)
    return losses


# ---------------------------------------------------------------------------
# phase: the reference's own workload (examples/demo.py --dry_run)


def phase_demo(*, seed: int, iterations: int = 300,
               group: str = "chip_smoke_demo"):
    """``examples/demo.py --dry_run`` in this process; loss read back from
    the metrics rows this very run committed.  The seed is passed on: left
    to itself the demo draws one anew each run, and the halving below is
    judged on one run."""
    import importlib.util

    rows_path = Path("runs") / group / "metrics.jsonl"
    rows_path.unlink(missing_ok=True)
    spec = importlib.util.spec_from_file_location(
        "tpudist_examples_demo", REPO / "examples" / "demo.py")
    demo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(demo)
    argv, sys.argv = sys.argv, [
        "demo.py", "--dry_run", "--total_iterations", str(iterations),
        "--group", group, "--seed", str(seed)]
    t0 = time.perf_counter()
    try:
        demo.main()
    finally:
        sys.argv = argv
    wall_s = time.perf_counter() - t0
    rows = [json.loads(x) for x in rows_path.read_text().splitlines()]
    curve = {m: [r[f"loss/{m}"] for r in rows if f"loss/{m}" in r]
             for m in ("model_X", "model_Y")}
    for m, c in curve.items():
        # per-step losses are noisy (batch 256): compare decile means
        n = max(len(c) // 10, 1)
        first, last = sum(c[:n]) / n, sum(c[-n:]) / n
        if len(c) != iterations or not last < 0.5 * first:
            raise AssertionError(
                f"demo {m}: {len(c)} rows for {iterations} iterations, "
                f"loss {first:.4g} -> {last:.4g} must at least halve")
        say("demo", model=m, iterations=iterations, wall_s=wall_s,
            loss_first_decile=first, loss_last_decile=last)
    return curve


# ---------------------------------------------------------------------------
# phase: serve


def phase_serve(w: Widths, *, seed: int, n_requests: int = 8,
                max_new_cap: int = 48):
    """InferenceServer, default ServeConfig, a mixed burst; one request's
    greedy stream equals ``generate()``'s; compile counts stay pinned."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudist.models import create_transformer, generate
    from tpudist.serve import InferenceServer, ServeConfig

    module, params = create_transformer(
        jax.random.PRNGKey(seed + 1), seq_len=16, **w.model_kwargs())
    cfg = ServeConfig()
    server = InferenceServer(module, params, cfg,
                             install_signal_handler=False).start()
    rng = np.random.default_rng(seed)
    plen_cap = max(2, min(w.seq // 4, w.seq - max_new_cap))
    t0 = time.perf_counter()
    handles = []
    for i in range(n_requests):
        prompt = rng.integers(0, w.vocab, int(rng.integers(1, plen_cap + 1)))
        handles.append(server.submit(
            prompt.astype(np.int32), seed=i,
            max_new=int(rng.integers(2, max_new_cap + 1))))
    for h in handles:
        if not h.wait(900):
            raise AssertionError(f"request {h.id} did not finish in 900 s")
    wall_s = time.perf_counter() - t0
    stats = server.stats()
    if not server.close(60):
        raise AssertionError("server did not close")
    reasons = [h.finish_reason for h in handles]
    if any(r not in ("length", "eos") for r in reasons):
        raise AssertionError(f"finish reasons: {reasons}")
    cc = stats["compile_counts"]
    # the pinned counts (verify skill): 1 insert_batch, at most
    # log2(decode_block)+1 decode buckets, 1 evict, 1 chunk-extend
    pinned = (cc["insert_batch"] == 1 and cc["evict"] == 1
              and cc["prefill_extend"] <= 1
              and 1 <= cc["decode_block"] <= cfg.decode_block.bit_length())
    if not pinned:
        raise AssertionError(f"compile counts moved: {cc}")
    # the byte-identity oracle: the longest prompt's greedy stream
    h = max(handles, key=lambda h: len(h.request.prompt))
    ref = generate(module, params, jnp.asarray(h.request.prompt)[None],
                   len(h.tokens))
    ref = np.asarray(ref)[0, len(h.request.prompt):].tolist()
    if h.tokens != ref:
        raise AssertionError(
            f"request {h.id}: served {h.tokens} != generate() {ref}")
    say("serve", requests=n_requests, reasons=sorted(set(reasons)),
        prompt_lens=[len(h.request.prompt) for h in handles],
        tokens_out=[len(h.tokens) for h in handles],
        burst_wall_s_compile_included=wall_s, compile_counts=cc,
        oracle=f"request {h.id} == generate() over {len(ref)} tokens")
    return handles


# ---------------------------------------------------------------------------
# phase: every Pallas kernel the repo keeps, natively, against its reference


@dataclasses.dataclass
class KernelCase:
    """``fn(*args, interpret=...)`` vs ``ref(*args)``; ``make(rng)`` builds
    the arguments, ``shapes`` describes them for a compile-only check.
    ``tol`` is one ``assert_allclose`` tolerance for every output or one
    per output (``atol=rtol=0`` is bit-identity); ``post`` maps both
    sides' outputs to what is compared."""

    name: str
    fn: Callable
    ref: Callable
    make: Callable
    tol: "dict | Sequence[dict]"
    post: "Callable | None" = None


def kernel_cases(w: Widths) -> list:
    """The kept kernels at ``w``'s widths: the paged pool at real size,
    bf16 compute, both pool dtypes; tolerances stated per case.

    bf16 cases: the kernel and its reference both round to bf16 but
    accumulate in different orders (online softmax vs one dense softmax),
    so outputs agree to bf16 resolution — ``atol 2e-2 / rtol 2e-2`` on
    O(1) values.  f32 elementwise kernels keep their tests' tolerances.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudist.ops.fused_linear import (fused_rope_qkv,
                                          fused_rope_qkv_reference,
                                          lora_delta, lora_delta_reference)
    from tpudist.ops.fused_sample import (fused_residual_prep,
                                          fused_residual_reference,
                                          fused_sample_prep,
                                          fused_sample_reference)
    from tpudist.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)
    from tpudist.ops.paged_prefill import (paged_prefill_attention,
                                           paged_prefill_reference)

    S, L, nb, bs = w.slots, w.n_layers, w.kv_blocks, w.kv_block
    nh = n_kv = w.n_heads
    dh, d, P = w.dh, w.d_model, w.prefill_chunk
    M = w.seq // bs
    bf16, f32 = jnp.bfloat16, jnp.float32
    BF16_TOL = dict(atol=2e-2, rtol=2e-2)
    EXACT = dict(atol=0, rtol=0)
    ULPS = dict(atol=0, rtol=1e-6)            # a few f32 ulps
    layer = L - 1

    def key(r):
        """The numpy stream seeds the device-side draws (the pools are
        hundreds of MB — made on the device, not shipped to it)."""
        return jax.random.PRNGKey(int(r.integers(2 ** 31)))

    def normal(r, shape, dtype, scale=1.0):
        return (jax.random.normal(key(r), shape, f32) * scale).astype(dtype)

    def pool(r, quant):
        shape = (L, nb, n_kv, bs, dh)
        if quant:
            return (jax.random.randint(key(r), shape, -127, 128, jnp.int8),
                    jax.random.randint(key(r), shape, -127, 128, jnp.int8),
                    jnp.asarray(r.uniform(0.005, 0.02, (L, nb, n_kv)), f32),
                    jnp.asarray(r.uniform(0.005, 0.02, (L, nb, n_kv)), f32))
        ones = jnp.ones((L, nb, n_kv), f32)
        return normal(r, shape, bf16), normal(r, shape, bf16), ones, ones

    def tables(r, pos0, span_extra):
        """Ragged block tables: live prefix mapped, sentinel ``nb`` past."""
        table = np.full((S, M), nb, np.int32)
        perm = r.permutation(nb)
        per = nb // S
        for b in range(S):
            live = min(-(-int(pos0[b] + span_extra[b]) // bs), M, per)
            table[b, :live] = perm[b * per: b * per + live]
        return table

    def make_decode(quant, s):
        def make(r):
            pk, pv, sk, sv = pool(r, quant)
            cap = min(M, nb // S) * bs
            pos0 = r.integers(0, cap - s + 1, S).astype(np.int32)
            pos0[0] = 0                       # a fresh lane
            table = tables(r, pos0, np.zeros(S, int))
            fill = np.zeros(S, np.int32)
            return (normal(r, (S, nh, s, dh), bf16), pk, pv, sk, sv,
                    jnp.asarray(table), jnp.asarray(pos0), jnp.asarray(fill),
                    normal(r, (S, n_kv, s, dh), bf16),
                    normal(r, (S, n_kv, s, dh), bf16))
        return make

    def make_prefill(quant):
        def make(r):
            pk, pv, sk, sv = pool(r, quant)
            Mw = min(M, (P - 1) // bs + 2)
            cap = min(M, nb // S) * bs
            pos0 = r.integers(0, max(cap - P - bs, 1), S).astype(np.int32)
            pos0[0] = 0
            if S > 2:
                pos0[2] = bs + 1              # partial first block
            clen = r.integers(1, P + 1, S).astype(np.int32)
            if S > 1:
                clen[1] = 0                   # a dead lane
            table = tables(r, pos0, np.where(clen > 0, P, 0))
            t0 = pos0 // bs
            n_t = np.where(clen > 0, (pos0 + clen - 1) // bs - t0 + 1, 0)
            logical = t0[:, None] + np.arange(Mw)[None]
            ids = np.take_along_axis(table, np.minimum(logical, M - 1), 1)
            live = (np.arange(Mw)[None] < n_t[:, None]) & (logical < M)
            wtable = np.where(live, ids, nb).astype(np.int32)
            return (normal(r, (S, nh, P, dh), bf16),
                    normal(r, (S, n_kv, P, dh), bf16),
                    normal(r, (S, n_kv, P, dh), bf16), pk, pv, sk, sv,
                    jnp.asarray(table), jnp.asarray(wtable),
                    jnp.asarray(pos0), jnp.asarray(clen))
        return make

    def make_sample(V, grammar):
        def make(r):
            args = [normal(r, (S, V), f32),
                    jnp.asarray(r.uniform(0.0, 1.5, S), f32).at[0].set(0.0)]
            if grammar:
                args += [jnp.asarray(r.random((3, 4, V)) > 0.3).at[2].set(True),
                         jnp.asarray(r.integers(0, 3, S), jnp.int32),
                         jnp.asarray(r.integers(0, 4, S), jnp.int32)]
            return tuple(args)
        return make

    def make_residual(V):
        def make(r):
            lt = normal(r, (S, 4, V), f32)
            # lane 0: draft == target -> the empty-residual fallback
            ld = normal(r, (S, 4, V), f32).at[0].set(lt[0])
            return lt, ld, jnp.asarray(r.uniform(0.3, 1.5, S), f32)
        return make

    def residual_post(outs):
        """Residual LOG-probabilities amplify a last-ulp softmax difference
        wherever target ~= draft, so lanes with a residual compare as
        probabilities; the fallback lane compares its logits."""
        pt, pd, lr = outs
        return pt, pd, jnp.exp(lr[1:]), lr[0]

    def make_rope(T, extra):
        def make(r):
            args = [normal(r, (S, T, d), bf16),
                    normal(r, (d, 3 * d), bf16, d ** -0.5),
                    jnp.asarray(r.integers(0, w.seq - T, S), jnp.int32)]
            if extra:
                args += [normal(r, (S, T, 3 * d), bf16, 0.1),
                         jnp.asarray(r.integers(0, 2, S), jnp.int32)]
            return tuple(args)
        return make

    def make_lora(T):
        def make(r):
            B, rank = 4, w.lora_rank
            return (normal(r, (S, T, d), bf16),
                    normal(r, (L, B, d, rank), f32, d ** -0.5),
                    normal(r, (L, B, rank, 3 * d), f32, rank ** -0.5),
                    jnp.asarray(r.integers(0, B + 1, S), jnp.int32))
        return make

    heads = dict(n_heads=nh, n_kv=n_kv, dh=dh)
    cases = []
    for quant in (False, True):
        tag = "int8" if quant else "bf16"
        for s in (1, 4):
            cases.append(KernelCase(
                f"paged_attention/{tag}/s{s}",
                lambda *a, interpret: paged_attention(
                    *a, layer=layer, interpret=interpret),
                lambda *a: paged_attention_reference(*a, layer=layer),
                make_decode(quant, s), BF16_TOL))
        # written blocks: both sides quantize the same merged tile with
        # the same amax/127 formula — scales to a few ulps, int8 codes to
        # one step (a last-ulp scale moves a code on a rounding boundary)
        blocks = dict(atol=1, rtol=0) if quant else BF16_TOL
        cases.append(KernelCase(
            f"paged_prefill/{tag}/P{P}",
            lambda *a, interpret: paged_prefill_attention(
                *a, layer=layer, interpret=interpret),
            lambda *a: paged_prefill_reference(*a, layer=layer),
            make_prefill(quant), (BF16_TOL, blocks, blocks, ULPS, ULPS)))
    for V in (w.vocab, w.big_vocab):
        for grammar in (False, True):
            cases.append(KernelCase(
                f"fused_sample/V{V}/{'grammar' if grammar else 'free'}",
                lambda *a, interpret: fused_sample_prep(
                    *a, interpret=interpret),
                fused_sample_reference, make_sample(V, grammar),
                (EXACT, ULPS, EXACT)))  # masked, scaled, greedy
        cases.append(KernelCase(
            f"fused_residual/V{V}",
            lambda *a, interpret: fused_residual_prep(*a, interpret=interpret),
            fused_residual_reference, make_residual(V),
            dict(atol=1e-8, rtol=1e-4), post=residual_post))
    for T in (1, P):
        for extra in (False, True):
            cases.append(KernelCase(
                f"fused_rope_qkv/T{T}/{'lora-extra' if extra else 'base'}",
                lambda *a, interpret: fused_rope_qkv(
                    *a, interpret=interpret, **heads),
                lambda *a: fused_rope_qkv_reference(*a, **heads),
                make_rope(T, extra), BF16_TOL))
        cases.append(KernelCase(
            f"lora_delta/T{T}",
            lambda *a, interpret: lora_delta(
                *a, layer=layer, interpret=interpret),
            lambda *a: lora_delta_reference(*a, layer=layer),
            make_lora(T), BF16_TOL))
    return cases


def check_case(case: KernelCase, out, ref) -> float:
    """Assert ``out`` matches ``ref`` under the case's stated tolerances;
    returns the worst absolute difference seen."""
    import jax
    import numpy as np

    post = case.post or (lambda outs: outs)
    outs, refs = jax.tree.leaves(post(out)), jax.tree.leaves(post(ref))
    if len(outs) != len(refs):
        raise AssertionError(f"{case.name}: {len(outs)} outputs vs "
                             f"{len(refs)} reference outputs")
    worst = 0.0
    for i, (a, b) in enumerate(zip(outs, refs)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if a.shape != b.shape or np.isnan(a).any():
            raise AssertionError(f"{case.name}[{i}]: shape {a.shape} vs "
                                 f"{b.shape}, or NaN")
        finite = np.isfinite(a) & np.isfinite(b)   # masked logits: -inf
        diff = np.abs(np.where(finite, a, 0) - np.where(finite, b, 0))
        worst = max(worst, float(diff.max(initial=0.0)))
        tol = case.tol if isinstance(case.tol, dict) else case.tol[i]
        np.testing.assert_allclose(a, b, err_msg=f"{case.name}[{i}]", **tol)
    return worst


def phase_kernels(w: Widths, *, seed: int, interpret: bool = False,
                  flash_gate: bool = True):
    """Every kept Pallas kernel at ``w`` against its in-repo reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudist.ops import (attention_reference, flash_attention,
                             flash_attention_packed)
    from tpudist.ops.attention import merge_heads

    report = {}
    if flash_gate:
        # mask / GQA / window semantics and both long-tile layouts (f32,
        # dh 64) — the repo's existing on-chip gate
        from bench import numerics_gate

        t0 = time.perf_counter()
        gate = numerics_gate(interpret=interpret, quick=interpret)
        say("kernels", case="flash numerics_gate", wall_s=time.perf_counter()
            - t0, max_rel_err={k: v["max_rel_err"] for k, v in gate.items()})
        report["numerics_gate"] = gate
    # flash at the model's own widths: bf16, dh 128, the tiles the train
    # step routes to, forward and all three backward kernels
    r = np.random.default_rng(seed)
    shape = (2, w.n_heads, w.seq, w.dh)
    q, k, v = (jnp.asarray(r.normal(size=shape), jnp.bfloat16)
               for _ in range(3))

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    want = jax.jit(jax.value_and_grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for bq, bk in w.flash_tiles:
        def loss_flash(q, k, v, bq=bq, bk=bk):
            return (flash_attention(q, k, v, True, bq, bk, interpret)
                    .astype(jnp.float32) ** 2).sum()

        t0 = time.perf_counter()
        got = jax.jit(jax.value_and_grad(loss_flash, argnums=(0, 1, 2)))(
            q, k, v)
        errs = [float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
                      .max() / jnp.abs(b.astype(jnp.float32)).max())
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        if not all(np.isfinite(errs)) or max(errs) > 2e-2:
            raise AssertionError(f"flash bf16 tiles {bq}/{bk}: max relative "
                                 f"errors loss/dq/dk/dv {errs} > 2e-2")
        say("kernels", case=f"flash/bf16/dh{w.dh}/tiles{bq}x{bk}/fwd+bwd",
            wall_s=time.perf_counter() - t0, max_rel_err=max(errs))
        report[f"flash_{bq}x{bk}"] = max(errs)

        # the packed entry (what Block's training arm calls at dh % 128 ==
        # 0) runs the same kernel bodies over the same tiles: the same bits
        def loss_packed(qkv, bq=bq, bk=bk):
            return (flash_attention_packed(qkv, w.n_heads, w.n_heads, True,
                                           bq, bk, interpret)
                    .astype(jnp.float32) ** 2).sum()

        t0 = time.perf_counter()
        loss, cotangent = jax.jit(jax.value_and_grad(loss_packed))(
            jnp.concatenate([merge_heads(a) for a in (q, k, v)], axis=-1))
        # (the loss sums o in another order; its cotangent 2·o, and dq, dk,
        # dv behind it, are element for element the head-major ones)
        same = bool(jnp.array_equal(
            cotangent,
            jnp.concatenate([merge_heads(g) for g in got[1]], axis=-1)))
        if not same or abs(float(loss) / float(got[0]) - 1) > 1e-5:
            raise AssertionError(f"packed flash tiles {bq}/{bk} differs from "
                                 "the head-major entry on the same values")
        say("kernels", case=f"flash_packed/bf16/dh{w.dh}/tiles{bq}x{bk}"
            "/fwd+bwd", wall_s=time.perf_counter() - t0,
            equals_head_major=same)
        report[f"flash_packed_{bq}x{bk}"] = 0.0
    for case in kernel_cases(w):
        args = case.make(np.random.default_rng(seed))
        t0 = time.perf_counter()
        out = jax.block_until_ready(case.fn(*args, interpret=interpret))
        worst = check_case(case, out, case.ref(*args))
        say("kernels", case=case.name, wall_s=time.perf_counter() - t0,
            max_abs_diff=worst, tol=case.tol)
        report[case.name] = worst
    return report


# ---------------------------------------------------------------------------
# --chips 4: the same LM step on a data=4 FSDP mesh and on a data=2 x seq=2
# ring-attention mesh, each against one device in this process


N_CHIPS = 4
# bf16 compute, different reduction orders: losses to 2% relative,
# parameter updates to 10% (update_error)
LOSS_RTOL, UPDATE_TOL = 2e-2, 0.1


def state_bytes_by_device(state) -> dict:
    """Bytes each device actually holds of ``state`` (addressable shards)."""
    import jax

    held: dict = {}
    for leaf in jax.tree.leaves(state):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


def update_error(p0, p_ref, p_got) -> float:
    """mean|got - ref| / mean|ref - start| over all parameters: how far two
    optimizer steps' UPDATES disagree (Adam moves every element by about
    the learning rate, so a bound on the parameters themselves would pass
    any update at all)."""
    import jax
    import jax.numpy as jnp

    num = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree.leaves(p_got), jax.tree.leaves(p_ref)))
    den = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree.leaves(p_ref), jax.tree.leaves(p0)))
    return num / den


def phase_multichip(w: Widths, *, seed: int, steps: int = 2,
                    interpret: bool = False, max_share: float = 0.3):
    """(a) ``data=4`` + ``fsdp_sharding`` (ZeRO-3 layout), (b) ``data=2 x
    seq=2`` + ``make_ring_attention``; identical global batch and
    parameters stepped on ONE device in the same process.  Stated
    tolerance (bf16 compute, different reduction orders): losses within
    ``LOSS_RTOL`` relative, parameter updates within ``UPDATE_TOL``
    (:func:`update_error`).  ``max_share`` bounds each device's part of
    the FSDP state (small leaves replicate, so a tiny model needs more
    room than the real one's ~1/4)."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from tpudist.models import create_transformer
    from tpudist.parallel import (fsdp_sharding, make_ring_attention,
                                  state_bytes_per_device)
    from tpudist.runtime import initialize
    from tpudist.runtime.mesh import AXIS_DATA, MeshConfig, make_mesh
    from tpudist.train import init_lm_state, make_lm_train_step, token_sharding

    initialize()
    devs = jax.devices()[:N_CHIPS]
    if len(devs) != N_CHIPS:
        raise AssertionError(f"needs {N_CHIPS} devices, JAX reports "
                             f"{len(devs)}")
    tx = optax.adam(1e-3)
    toks = chain_tokens(w, seed)
    module, params = create_transformer(
        jax.random.PRNGKey(seed), seq_len=w.seq, **w.model_kwargs())
    state0 = init_lm_state(params, tx)
    total = sum(x.nbytes for x in jax.tree.leaves(state0))

    def run(name, mesh, apply_fn, sharding_of, collectives):
        sharding = sharding_of(mesh)
        state = jax.device_put(state0, sharding or NamedSharding(
            mesh, PartitionSpec()))
        tokens = jax.device_put(toks, token_sharding(mesh))
        t0 = time.perf_counter()
        step = make_lm_train_step(
            apply_fn, tx, mesh, state_sharding=sharding,
            donate_state=False).lower(state, tokens).compile()
        compile_s = time.perf_counter() - t0
        text = step.as_text()
        missing = [c for c in collectives if c not in text]
        if missing:
            raise AssertionError(f"{name}: compiled step lacks {missing}")
        losses, step_s = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, loss = step(state, tokens)
            jax.block_until_ready((state, loss))
            step_s.append(time.perf_counter() - t0)
            losses.append(float(loss))
        held = state_bytes_by_device(state)
        say("multichip", run=name, mesh=dict(mesh.shape),
            compile_s=compile_s, step_s=[round(x, 4) for x in step_s],
            losses=[round(x, 5) for x in losses],
            flash_custom_calls=text.count("tpu_custom_call"),
            collectives={c: text.count(c) for c in collectives},
            state_MiB_by_device={k: round(v / 2**20, 1)
                                 for k, v in sorted(held.items())},
            token_shards=[tuple(s.data.shape)
                          for s in tokens.addressable_shards])
        return state, losses, held, tokens

    ref_state, ref_losses, _, _ = run(
        "one-device", make_mesh(MeshConfig(data=1), devices=devs[:1]),
        module.apply, lambda mesh: None, ())

    def compare(name, state, losses):
        np.testing.assert_allclose(losses, ref_losses, rtol=LOSS_RTOL,
                                   err_msg=f"{name} losses")
        err = update_error(state0.params, ref_state.params,
                           jax.device_put(state.params, devs[0]))
        if not err < UPDATE_TOL:
            raise AssertionError(f"{name}: parameter updates differ from "
                                 f"the one-device step by {err:.4g} "
                                 f"(tolerance {UPDATE_TOL})")
        say("multichip", run=name, vs="one-device",
            loss_rel_diff=float(np.max(np.abs(np.array(losses) / np.array(
                ref_losses) - 1))), update_error=err,
            tolerance=dict(loss_rtol=LOSS_RTOL, update=UPDATE_TOL))

    # (a) FSDP / ZeRO-3: every device holds about 1/n of the state
    mesh_a = make_mesh(MeshConfig(data=N_CHIPS), devices=devs)
    state, losses, held, _ = run(
        "fsdp-data4", mesh_a, module.apply,
        lambda mesh: fsdp_sharding(mesh, state0),
        ("all-gather", "all-reduce"))
    if sorted(held) != sorted(d.id for d in devs):
        raise AssertionError(f"fsdp: state shards on devices {sorted(held)} "
                             f"only, expected all of {[d.id for d in devs]}")
    # every device holds exactly what the layout says, about 1/n of it
    want = state_bytes_per_device(state0, fsdp_sharding(mesh_a, state0))
    if any(b != want for b in held.values()) or want > max_share * total:
        raise AssertionError(
            f"fsdp: devices hold {held} bytes of a {total}-byte state; "
            f"the layout says {want} each, at most {max_share} of it")
    compare("fsdp-data4", state, losses)

    # (b) data=2 x seq=2 ring attention: tokens split over both axes
    mesh_b = make_mesh(MeshConfig(data=N_CHIPS // 2, seq=2), devices=devs)
    ring = module.clone(attention_fn=make_ring_attention(
        mesh_b, causal=True, batch_axis=AXIS_DATA, interpret=interpret))
    state, losses, held, tokens = run(
        "ring-data2xseq2", mesh_b, ring.apply, lambda mesh: None,
        ("collective-permute", "all-reduce"))
    on = sorted({s.device.id for s in tokens.addressable_shards})
    want = (w.batch // (N_CHIPS // 2), w.seq // 2)
    if on != sorted(d.id for d in devs) or any(
            tuple(s.data.shape) != want for s in tokens.addressable_shards):
        raise AssertionError(f"ring: token shards {want} expected on every "
                             f"device, found devices {on}")
    if sorted(held) != sorted(d.id for d in devs):
        raise AssertionError(f"ring: state on devices {sorted(held)} only")
    compare("ring-data2xseq2", state, losses)


# ---------------------------------------------------------------------------


def describe_device(dev, n: int) -> dict:
    """Device line + the peaks table for its kind (unknown kind: error)."""
    from tpudist.utils.flops import chip_hbm_bytes_per_s, chip_peak_flops

    peak, hbm = chip_peak_flops(dev), chip_hbm_bytes_per_s(dev)
    if peak is None or hbm is None:
        raise AssertionError(
            f"device kind {dev.device_kind!r} is not in the peaks table "
            "(tpudist/utils/flops.py) — add it with its source, do not "
            "default it")
    say("device", platform=dev.platform, kind=dev.device_kind, count=n,
        peak_bf16_flops=peak, hbm_bytes_per_s=hbm)
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the four-chip mesh comparison")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this script proves the program on "
              "the chip and has nothing to say elsewhere", file=sys.stderr)
        return 1
    t_start = time.perf_counter()
    device = describe_device(devs[0], len(devs))
    from tpudist.data.native_loader import native_available
    from tpudist.runtime import enable_compilation_cache

    say("setup", compile_cache_dir=enable_compilation_cache(),
        native_loader_built=native_available(), jax=jax.__version__)
    if args.chips == 4:
        if len(devs) != 4:
            raise AssertionError(f"--chips 4 on {len(devs)} devices")
        phase_multichip(FULL, seed=args.seed)
    else:
        phase_train(FULL, seed=args.seed)
        phase_demo(seed=args.seed)
        phase_serve(FULL, seed=args.seed)
        phase_kernels(FULL, seed=args.seed)
    say("done", wall_s=time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
