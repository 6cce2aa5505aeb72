"""Pallas paged-attention decode kernel (tpudist/ops/paged_attention.py):
the kernel-vs-reference equivalence property sweep — paged × {f32, int8}
× decode-window s ∈ {1, 4, 8} × ragged occupancy (unmapped-sentinel
blocks, zero-live lanes, mid-window fills, GQA, sliding window) — plus
the engine-level contracts: kernel streams byte-identical to the gather
path and the sequential oracle under heterogeneous churn, a
freshly-adopted handoff lane continues byte-identically, compile pins
hold with the kernel enabled under churn and across mesh shapes, and
the spec verify runs through the same kernel.

Quoted tolerances (kernel vs gather-to-dense reference): the two share
the dequantization (``int8.astype(compute) * scale``), the −1e30 mask
constant, and f32 score/softmax math — the ONLY difference is
online-softmax accumulation order, so outputs agree to float rounding:
f32 pools within ``atol 5e-6 / rtol 1e-5``, int8 pools (dequantized
magnitudes up to ~25) within ``atol 5e-5 / rtol 1e-5``.  Greedy token
STREAMS are byte-identical (tests pin equality, not closeness).

Marker policy (``pallas``): everything here runs the kernel through the
Pallas INTERPRETER on CPU — tier-1 coverage of the exact walk/mask/
dequant code.  The native lowering is compiled for the v5e at the real
pool size in tests/test_chip_compile.py and run against the reference on
the chip by chip_smoke.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models import create_transformer, generate
from tpudist.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)
from tpudist.serve import InferenceServer, ServeConfig, SlotEngine

pytestmark = pytest.mark.pallas

#: quoted equivalence tolerances (see module docstring)
TOL = {"f32": dict(atol=5e-6, rtol=1e-5), "int8": dict(atol=5e-5, rtol=1e-5)}

CFG = dict(vocab=16, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_len=32)


@pytest.fixture(scope="module")
def model():
    return create_transformer(jax.random.PRNGKey(0), seq_len=16, **CFG)


def _case(S, nh, n_kv, s, dh, L, nb, bs, M, quant, seed, fill_max=0):
    """Random kernel inputs with RAGGED occupancy: per-slot cursors
    anywhere in [0, M*bs - s], tables sentinel-padded past each lane's
    live prefix (sentinel == nb, the unmapped marker)."""
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(S, nh, s, dh)), jnp.float32)
    if quant:
        pool_k = jnp.asarray(
            r.integers(-127, 128, size=(L, nb, n_kv, bs, dh)), jnp.int8)
        pool_v = jnp.asarray(
            r.integers(-127, 128, size=(L, nb, n_kv, bs, dh)), jnp.int8)
        sk = jnp.asarray(r.uniform(0.01, 0.2, size=(L, nb, n_kv)),
                         jnp.float32)
        sv = jnp.asarray(r.uniform(0.01, 0.2, size=(L, nb, n_kv)),
                         jnp.float32)
    else:
        pool_k = jnp.asarray(r.normal(size=(L, nb, n_kv, bs, dh)),
                             jnp.float32)
        pool_v = jnp.asarray(r.normal(size=(L, nb, n_kv, bs, dh)),
                             jnp.float32)
        sk = sv = jnp.ones((L, nb, n_kv), jnp.float32)
    pos0 = r.integers(0, M * bs - s + 1, size=S).astype(np.int32)
    pos0[0] = 0  # always include a zero-live lane (fresh/evicted slot)
    table = np.full((S, M), nb, np.int32)
    for b in range(S):
        live = -(-int(pos0[b]) // bs)
        table[b, :live] = r.choice(nb, size=live, replace=False)
    fill = (r.integers(0, fill_max + 1, size=S).astype(np.int32)
            if fill_max else np.zeros(S, np.int32))
    W = s + fill_max
    wk = jnp.asarray(r.normal(size=(S, n_kv, W, dh)), jnp.float32)
    wv = jnp.asarray(r.normal(size=(S, n_kv, W, dh)), jnp.float32)
    return (q, pool_k, pool_v, sk, sv, jnp.asarray(table),
            jnp.asarray(pos0), jnp.asarray(fill), wk, wv)


class TestKernelVsReference:
    @pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
    @pytest.mark.parametrize("s", [1, 4, 8])
    def test_property_sweep(self, quant, s):
        """The acceptance sweep: paged × {f32, int8} × window s ∈
        {1, 4, 8} × ragged occupancy incl. unmapped-sentinel blocks,
        every layer index, within the quoted tolerances."""
        tol = TOL["int8" if quant else "f32"]
        args = _case(S=4, nh=4, n_kv=2, s=s, dh=8, L=2, nb=9, bs=4, M=4,
                     quant=quant, seed=s)
        for layer in range(2):
            out = paged_attention(*args, layer=layer, interpret=True)
            ref = paged_attention_reference(*args, layer=layer)
            np.testing.assert_allclose(out, ref, **tol)

    def test_mid_window_fill(self):
        """Decode-scan steps t > 0: the window buffer already holds t
        committed-to-window tokens; the per-query mask must see them
        (col <= fill + i)."""
        args = _case(S=3, nh=4, n_kv=2, s=1, dh=8, L=2, nb=7, bs=4, M=4,
                     quant=False, seed=11, fill_max=3)
        for layer in range(2):
            out = paged_attention(*args, layer=layer, interpret=True)
            ref = paged_attention_reference(*args, layer=layer)
            np.testing.assert_allclose(out, ref, **TOL["f32"])

    @pytest.mark.parametrize("n_kv", [1, 2, 4])
    def test_gqa_group_shapes(self, n_kv):
        """Grouped-query attention runs natively: K/V blocks are
        fetched once per kv head, q rows of the whole group share the
        tile — every group width agrees with the reference."""
        args = _case(S=2, nh=4, n_kv=n_kv, s=2, dh=8, L=1, nb=7, bs=4,
                     M=3, quant=True, seed=n_kv)
        out = paged_attention(*args, layer=0, interpret=True)
        ref = paged_attention_reference(*args, layer=0)
        np.testing.assert_allclose(out, ref, **TOL["int8"])

    def test_sliding_window_mask(self):
        """The decode sliding-window lower bound composes with the
        block walk and the fused window mask."""
        args = _case(S=3, nh=4, n_kv=2, s=4, dh=8, L=2, nb=9, bs=4, M=4,
                     quant=False, seed=3)
        for w in (3, 7):
            out = paged_attention(*args, layer=1, window=w, interpret=True)
            ref = paged_attention_reference(*args, layer=1, window=w)
            np.testing.assert_allclose(out, ref, **TOL["f32"])


# ---------------------------------------------------------------------------
# engine level: the kernel arm of the slot-decode programs


def _prompt(plen, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG["vocab"], size=plen).astype(np.int32)


def _reqs():
    return [
        (_prompt(3, 0), 4),
        (_prompt(5, 1), 6),
        (_prompt(12, 2), 3),  # > prefill_pad 8: chunked prefill
        (_prompt(6, 3), 5),
    ]


def _reference(model, prompt, max_new):
    module, params = model
    out = generate(module, params, jnp.asarray(prompt)[None], max_new)
    return np.asarray(out)[0, len(prompt):].tolist()


def _drive(model, requests, *, num_slots=2, prefill_pad=8,
           temperature=0.0, seed=0, **engine_kw):
    """Continuous-batching churn (the test_serve oracle harness shape):
    FIFO admission, chunked prefill, decode via decode_auto."""
    module, params = model
    engine_kw.setdefault("paged", True)
    engine_kw.setdefault("kv_block", 4)
    eng = SlotEngine(module, params, num_slots=num_slots,
                     prefill_pad=prefill_pad, **engine_kw)
    pending = list(enumerate(requests))
    out = {rid: [] for rid, _ in pending}
    slot_rid, slot_budget = {}, {}

    def deliver(slot, toks):
        rid = slot_rid[slot]
        out[rid].extend(toks)
        assert len(out[rid]) <= slot_budget[slot]
        if len(out[rid]) >= slot_budget[slot]:
            eng.evict(slot)
            del slot_rid[slot], slot_budget[slot]

    while pending or eng.num_occupied:
        free, items = eng.free_slots(), []
        while free and pending:
            rid, (prompt, max_new) = pending.pop(0)
            slot = free.pop(0)
            slot_rid[slot], slot_budget[slot] = rid, max_new
            items.append((slot, prompt, temperature, seed, max_new))
        for slot, tok in eng.start_batch(items).items():
            if tok is not None:
                deliver(slot, [tok])
        for slot, tok in eng.advance_prefill().items():
            deliver(slot, [tok])
        if eng.num_active:
            _, blocks = eng.decode_auto()
            for slot, toks in list(blocks.items()):
                if slot in slot_rid:
                    deliver(slot, toks)
    return out, eng


class TestKernelEngine:
    @pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
    def test_greedy_byte_identity_vs_gather_and_oracle(self, model, int8):
        """The engine contract: kernel-path greedy streams are
        byte-identical to the gather path's AND the sequential
        oracle's, under heterogeneous churn incl. chunked prefill —
        and the honest read-bytes accounting satellite rides the same
        drive: the kernel path's decode bytes are live-KV-proportional,
        strictly below the gather path's pool-geometry charge."""
        og, eg = _drive(model, _reqs(), kv_int8=int8, attn_kernel="gather")
        ok, eng = _drive(model, _reqs(), kv_int8=int8, attn_kernel="paged")
        assert og == ok
        if not int8:  # int8's oracle is the gather path (same storage)
            for rid, (prompt, max_new) in enumerate(_reqs()):
                assert ok[rid] == _reference(model, prompt, max_new), rid
        # the pool drained cleanly (no leaked blocks under the kernel's
        # window commit)
        assert eng.alloc.free_blocks == eng.alloc.num_blocks
        # read-bytes accounting (same traffic, both paths just ran):
        # gather charges the full [slots, max_len] view per step
        rg = eg.decode_stats()["kv_read_bytes"]
        rk = eng.decode_stats()["kv_read_bytes"]
        assert 0 < rk < rg
        assert rg == eg.decode_stats()["steps"] * eg.num_slots \
            * eg.max_len * eg._bytes_per_pos()

    def test_sampled_streams_match_gather(self, model):
        """Per-request sampled streams are attention-path-independent
        (same fold_in substreams, logits agree within tolerance)."""
        a, _ = _drive(model, _reqs(), temperature=1.1, seed=7,
                      attn_kernel="gather")
        b, _ = _drive(model, _reqs(), temperature=1.1, seed=7,
                      attn_kernel="paged")
        assert a == b

    def test_spec_verify_through_kernel(self, model):
        """The speculative verify window (s = K+1 queries) runs through
        the SAME kernel: spec+kernel greedy streams are byte-identical
        to the sequential oracle (which test_serve_spec pins the
        gather path to — transitively the paths agree), and speculation
        actually accepts."""
        b, eng = _drive(model, _reqs(), spec_draft=1, spec_k=4,
                        attn_kernel="paged")
        for rid, (prompt, max_new) in enumerate(_reqs()):
            assert b[rid] == _reference(model, prompt, max_new), rid
        st = eng.spec_stats()
        assert st["blocks"] > 0 and st["tokens"] > st["blocks"]

    def test_handoff_adopted_lane_continues_byte_identical(self, model):
        """A freshly-adopted handoff lane (fresh table row, cold
        mid-stream import) decodes on through the kernel byte-identical
        to the sequential oracle — the ragged case where the adopted
        row's blocks are freshly allocated and the cursor is
        mid-sequence."""
        module, params = model
        src = SlotEngine(module, params, num_slots=2, prefill_pad=8,
                         paged=True, kv_block=4)
        dst = SlotEngine(module, params, num_slots=2, prefill_pad=8,
                         paged=True, kv_block=4, attn_kernel="paged")
        p = _prompt(5, 11)
        toks = [src.start_batch([(0, p, 0.0, 0, 8)])[0]]
        _, b = src.decode_block(max_k=2)
        toks += b[0]
        dst.import_slot(1, src.export_slot(0))
        while dst.counts[1] < dst.budget[1]:
            _, b = dst.decode_block()
            toks += b[1]
        assert toks[:8] == _reference(model, p, 8)

    def test_compile_counts_pinned_under_churn(self, model):
        """Churn never recompiles the kernel programs: the same pin set
        as the gather engine (decode_block bounded by the pow2 bucket
        walk, one compile for everything else)."""
        _, eng = _drive(model, _reqs() * 2, attn_kernel="paged")
        cc = eng.compile_counts()
        assert cc["insert_batch"] == 1
        assert cc["prefill_extend"] == 1
        assert cc["evict"] == 1
        assert 1 <= cc["decode_block"] <= 4

    def test_compile_counts_flat_across_mesh_shapes(self, model, devices):
        """Mesh shapes change shardings, never programs: identical
        jit-cache sizes at 1x1 and 1x2 with the kernel enabled, output
        byte-identical (the kernel's interpret lowering partitions like
        any XLA program)."""
        outs, counts = {}, {}
        for mesh in (None, "1x2"):
            out, eng = _drive(model, _reqs(), attn_kernel="paged",
                              mesh=mesh)
            outs[mesh], counts[mesh] = out, eng.compile_counts()
        assert outs[None] == outs["1x2"]
        assert counts[None] == counts["1x2"]

    def test_kernel_requires_paged(self, model):
        module, params = model
        with pytest.raises(ValueError, match="paged"):
            SlotEngine(module, params, num_slots=2, attn_kernel="paged")
        with pytest.raises(ValueError, match="attn_kernel"):
            SlotEngine(module, params, num_slots=2, paged=True,
                       kv_block=4, attn_kernel="nope")


class TestKernelServer:
    def test_server_e2e_and_kv_report(self, model, tmp_path):
        """InferenceServer on the kernel path: requests complete, the
        kv stats carry attn_kernel, and the aggregated serving report's
        kv section records which path produced read_bytes."""
        from tpudist import telemetry

        module, params = model
        telemetry.finish(write_report=False)
        telemetry.start(tmp_path)
        srv = InferenceServer(
            module, params,
            ServeConfig(num_slots=2, paged=True, kv_block=4,
                        attn_kernel="paged", prefill_pad=8),
            install_signal_handler=False).start()
        hs = [srv.submit(_prompt(4 + i, i), max_new=4) for i in range(3)]
        for h in hs:
            h.wait()
        assert all(h.finish_reason == "length" for h in hs)
        assert srv.stats()["kv"]["attn_kernel"] == "paged"
        srv.close()
        report = telemetry.finish()
        kv = report["serving"]["kv"]
        assert kv["attn_kernel"] == "paged"
        assert kv["read_bytes_per_token"] > 0
