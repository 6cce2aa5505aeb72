"""Pallas kernel family II (tpudist/ops/): paged-prefill flash attention
with in-kernel KV block writes, fused in-kernel sampling, fused
RoPE+QKV, and the in-kernel LoRA gather-matmul — the kernel-vs-reference
equivalence sweeps ({f32, int8} × ragged occupancy × GQA widths ×
windows) plus the engine-level contracts: every fused path's greedy
token streams are byte-identical to its in-graph twin AND the
sequential oracle under heterogeneous churn (chunked prefill included),
sampled streams are identical under the fold_in substream contract,
compile pins stay flat (one batched kernel-prefill program serves
insert AND one-hot chunk extends), and the honest prefill byte
accounting charges the kernel path chunk-proportional writes.

Quoted tolerances, same derivation as tests/test_paged_attention.py:
the kernel and the gather-to-dense reference share the dequantization
(``int8.astype(compute) * scale``), the mask constant, and f32 score
math — the only difference is online-softmax accumulation order — so
attention outputs agree to float rounding: f32 pools within ``atol
5e-6 / rtol 1e-5``, int8 pools within ``atol 5e-5 / rtol 1e-5``.
Written KV blocks are BIT-identical (both sides quantize the identical
merged tile with the identical ``amax/127`` formula), and the fused
sampling / RoPE+QKV / LoRA kernels are exact in interpret mode (same
op order as their references) — those tests pin equality, not
closeness.

Marker policy (``pallas``): everything here runs through the Pallas
INTERPRETER on CPU — tier-1 coverage of the exact walk/merge/quantize
code.  The native lowering is covered where it can be: compiled for the
v5e at the real pool size in tests/test_chip_compile.py, and run against
the references on the chip by chip_smoke.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models import create_transformer, generate
from tpudist.ops.fused_linear import (
    fused_rope_qkv,
    fused_rope_qkv_reference,
    lora_delta,
    lora_delta_reference,
)
from tpudist.ops.fused_sample import (
    fused_residual_prep,
    fused_residual_reference,
    fused_sample_prep,
    fused_sample_reference,
)
from tpudist.ops.paged_prefill import (
    paged_prefill_attention,
    paged_prefill_reference,
)
from tpudist.serve import SlotEngine

pytestmark = pytest.mark.pallas

#: quoted equivalence tolerances (see module docstring)
TOL = {"f32": dict(atol=5e-6, rtol=1e-5), "int8": dict(atol=5e-5, rtol=1e-5)}

CFG = dict(vocab=16, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_len=32)


@pytest.fixture(scope="module")
def model():
    return create_transformer(jax.random.PRNGKey(0), seq_len=16, **CFG)


# ---------------------------------------------------------------------------
# kernel vs reference: paged prefill


def _wtable(table, pos0, clen, bs, M, Mw, nb):
    """The engine's write-table rule (``_Paged.write_tables``): physical
    ids of the ceil-span blocks covering ``[pos0, pos0+clen)``, sentinel
    ``nb`` past the span (and everywhere on a zero-``clen`` lane)."""
    t0 = pos0 // bs
    n_t = np.where(clen > 0, (pos0 + clen - 1) // bs - t0 + 1, 0)
    logical = t0[:, None] + np.arange(Mw)[None]
    ids = np.take_along_axis(np.asarray(table),
                             np.minimum(logical, M - 1), axis=1)
    live = (np.arange(Mw)[None] < n_t[:, None]) & (logical < M)
    return np.where(live, ids, nb).astype(np.int32)


def _prefill_case(S, nh, n_kv, dh, L, nb, bs, M, P, quant, seed):
    """Ragged prefill inputs: per-lane cursors anywhere in the arena
    (incl. a zero-live lane and a non-block-aligned cursor — the
    chunked-prefill partial-first-block merge), chunk lengths ragged
    incl. a zero-``clen`` (dead) lane, sentinel-padded write tables."""
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(S, nh, P, dh)), jnp.float32)
    kn = jnp.asarray(r.normal(size=(S, n_kv, P, dh)), jnp.float32)
    vn = jnp.asarray(r.normal(size=(S, n_kv, P, dh)), jnp.float32)
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
    pos0 = r.integers(0, (M - (P - 1) // bs - 1) * bs, size=S).astype(
        np.int32)
    pos0[0] = 0            # fresh lane
    if S > 2:
        pos0[2] = bs + 1   # partial first block: merge keeps the prefix
    clen = r.integers(1, P + 1, size=S).astype(np.int32)
    if S > 1:
        clen[1] = 0        # dead lane: all-sentinel write table
    table = np.full((S, M), nb, np.int32)
    perm = r.permutation(nb)
    Mw = min(M, (P - 1) // bs + 2)
    for b in range(S):
        span = -(-int(pos0[b] + (P if clen[b] else 0)) // bs) or 1
        table[b, :span] = perm[b * M:b * M + span]
    wt = _wtable(table, pos0, clen, bs, M, Mw, nb)
    return (q, kn, vn, pool_k, pool_v, sk, sv, jnp.asarray(table),
            jnp.asarray(wt), jnp.asarray(pos0), jnp.asarray(clen))


def _check_prefill(args, quant, **kw):
    tol = TOL["int8" if quant else "f32"]
    out = paged_prefill_attention(*args, interpret=True, **kw)
    ref = paged_prefill_reference(*args, **kw)
    np.testing.assert_allclose(out[0], ref[0], **tol)  # attention o
    for a, b in zip(out[1:3], ref[1:3]):               # written blocks
        if np.asarray(a).dtype == np.int8:
            np.testing.assert_array_equal(a, b)        # bit-identical
        else:
            np.testing.assert_allclose(a, b, **TOL["f32"])
    for a, b in zip(out[3:], ref[3:]):                 # dequant scales
        np.testing.assert_allclose(a, b, **TOL["f32"])


class TestPagedPrefillVsReference:
    @pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
    def test_property_sweep(self, quant):
        """{f32, int8} × ragged occupancy (fresh lane, dead lane,
        partial first block) × every layer index, within the quoted
        tolerances; written blocks bit-identical on int8."""
        args = _prefill_case(S=4, nh=4, n_kv=2, dh=8, L=2, nb=24, bs=4,
                             M=6, P=8, quant=quant, seed=3)
        for layer in range(2):
            _check_prefill(args, quant, layer=layer)

    @pytest.mark.parametrize("n_kv", [1, 2, 4])
    def test_gqa_group_shapes(self, n_kv):
        """Every GQA group width agrees: K/V blocks fetched once per kv
        head, the group's q rows share the tile."""
        args = _prefill_case(S=3, nh=4, n_kv=n_kv, dh=8, L=1, nb=18,
                             bs=4, M=6, P=8, quant=True, seed=n_kv)
        _check_prefill(args, True, layer=0)

    def test_sliding_window_mask(self):
        """The sliding-window bound composes with the prefix walk AND
        the chunk's causal self-attention block."""
        args = _prefill_case(S=3, nh=2, n_kv=2, dh=8, L=2, nb=18, bs=4,
                             M=6, P=8, quant=False, seed=7)
        _check_prefill(args, False, layer=1, window=5)


# ---------------------------------------------------------------------------
# kernel vs reference: fused sampling tail


class TestFusedSampleVsReference:
    def _case(self, seed=0, S=3, V=33):
        r = np.random.default_rng(seed)
        logits = jnp.asarray(r.normal(size=(S, V)), jnp.float32)
        temps = jnp.asarray([0.0, 0.7, 1.3], jnp.float32)
        gallow = jnp.asarray(r.random((3, 4, V)) > 0.3).at[2].set(True)
        gidx = jnp.asarray([0, 2, 1], jnp.int32)
        gstate = jnp.asarray([1, 0, 3], jnp.int32)
        return logits, temps, gallow, gidx, gstate

    @pytest.mark.parametrize("tk,tp", [(0, 0.0), (5, 0.0), (0, 0.9),
                                       (7, 0.8)])
    def test_masked_scaled_greedy_exact(self, tk, tp):
        """All three outputs are EXACT (same op order as the in-graph
        tail): masked logits, temperature-scaled-and-filtered logits,
        greedy argmax — across top-k/top-p combinations with the
        grammar-mask gather riding the scalar-prefetched (gidx, gstate)
        coordinates."""
        args = self._case(seed=tk * 10 + int(tp * 10))
        out = fused_sample_prep(*args, top_k=tk, top_p=tp, interpret=True)
        ref = fused_sample_reference(*args, top_k=tk, top_p=tp)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)

    def test_no_grammar_path(self):
        logits, temps, *_ = self._case(seed=9)
        out = fused_sample_prep(logits, temps, interpret=True)
        ref = fused_sample_reference(logits, temps)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)

    def test_residual_prep_exact(self):
        """The speculative-verify sibling: both softmaxes and the
        residual logits (incl. the empty-residual ``lt/temp`` fallback
        when target == draft) are exact, so accept/reject decisions
        and residual draws downstream are bit-identical."""
        r = np.random.default_rng(4)
        lt = jnp.asarray(r.normal(size=(3, 4, 17)), jnp.float32)
        ld = jnp.asarray(r.normal(size=(3, 4, 17)), jnp.float32)
        temps = jnp.asarray([0.0, 0.9, 1.4], jnp.float32)
        for draft in (ld, lt):  # lt==ld → empty residual fallback
            out = fused_residual_prep(lt, draft, temps, interpret=True)
            ref = fused_residual_reference(lt, draft, temps)
            for a, b in zip(out, ref):
                np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# kernel vs reference: fused RoPE+QKV and the LoRA gather-matmul


class TestFusedLinearVsReference:
    def _case(self, seed, S=3, T=4, nh=4, n_kv=2, dh=8):
        r = np.random.default_rng(seed)
        d, kv = nh * dh, n_kv * dh
        h = jnp.asarray(r.normal(size=(S, T, d)), jnp.float32)
        w = jnp.asarray(r.normal(size=(d, d + 2 * kv)) * 0.05, jnp.float32)
        offs = jnp.asarray([0, 3, 11], jnp.int32)
        extra = jnp.asarray(r.normal(size=(S, T, d + 2 * kv)) * 0.1,
                            jnp.float32)
        on = jnp.asarray([1, 0, 1], jnp.int32)
        return h, w, offs, extra, on, dict(n_heads=nh, n_kv=n_kv, dh=dh)

    @pytest.mark.parametrize("rope", [True, False], ids=["rope", "norope"])
    @pytest.mark.parametrize("with_extra", [False, True],
                             ids=["base", "lora-extra"])
    def test_rope_qkv_matches(self, rope, with_extra):
        """Projection + per-slot-offset rotation (+ the pre-rotation
        LoRA delta under its ``on`` mask) agree with the reference to
        float rounding across rope on/off."""
        h, w, offs, extra, on, kw = self._case(rope + 2 * with_extra)
        e, o = (extra, on) if with_extra else (None, None)
        out = fused_rope_qkv(h, w, offs, e, o, rope=rope, interpret=True,
                             **kw)
        ref = fused_rope_qkv_reference(h, w, offs, e, o, rope=rope, **kw)
        for a, b in zip(out, ref):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)

    def test_lora_delta_exact_and_sentinel(self):
        """The in-kernel factor-block gather-matmul is exact (same
        ``(x·A)·B`` contraction order) incl. sentinel ids clamping into
        a real block (the caller's ``on`` mask discards those lanes)."""
        h, _, _, _, _, kw = self._case(5)
        r = np.random.default_rng(6)
        L, B, rank, dout = 2, 5, 2, 12
        d = kw["n_heads"] * kw["dh"]
        pa = jnp.asarray(r.normal(size=(L, B, d, rank)), jnp.float32)
        pb = jnp.asarray(r.normal(size=(L, B, rank, dout)), jnp.float32)
        ids = jnp.asarray([0, B, 3], jnp.int32)  # B = sentinel
        for layer in range(L):
            out = lora_delta(h, pa, pb, ids, layer=layer, interpret=True)
            ref = lora_delta_reference(h, pa, pb, ids, layer=layer)
            np.testing.assert_array_equal(out, ref)


# ---------------------------------------------------------------------------
# engine level: the kernel family behind the dispatch seams


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
           temperature=0.0, seed=0, adapter_names=None, **engine_kw):
    """Continuous-batching churn (the test_paged_attention harness
    shape): FIFO admission, chunked prefill, decode via decode_auto."""
    module, params = model
    engine_kw.setdefault("paged", True)
    engine_kw.setdefault("kv_block", 4)
    eng = SlotEngine(module, params, num_slots=num_slots,
                     prefill_pad=prefill_pad, **engine_kw)
    if adapter_names:
        from tpudist.models.lora import make_adapter_factors

        for i, name in enumerate(sorted({a for a in adapter_names if a})):
            eng.load_adapter(name, make_adapter_factors(
                jax.random.PRNGKey(100 + i), module,
                engine_kw.get("adapter_rank", 8)))
    pending = list(enumerate(requests))
    out = {rid: [] for rid, _ in pending}
    slot_rid, slot_budget = {}, {}

    def deliver(slot, toks):
        rid = slot_rid[slot]
        out[rid].extend(toks)
        if len(out[rid]) >= slot_budget[slot]:
            eng.evict(slot)
            del slot_rid[slot], slot_budget[slot]

    while pending or eng.num_occupied:
        free, items = eng.free_slots(), []
        while free and pending:
            rid, (prompt, max_new) = pending.pop(0)
            slot = free.pop(0)
            slot_rid[slot], slot_budget[slot] = rid, max_new
            if adapter_names:
                items.append((slot, prompt, temperature, seed, max_new,
                              (), None, adapter_names[rid]))
            else:
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


class TestKernelFamilyEngine:
    @pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
    def test_prefill_kernel_greedy_byte_identity(self, model, int8):
        """The prefill-kernel contract: greedy streams byte-identical
        to the gather path AND the sequential oracle under churn incl.
        chunked prefill, the pool drains cleanly, and the honest
        prefill accounting charges the kernel path chunk-proportional
        writes while the gather path pays the dense lane sweep."""
        og, eg = _drive(model, _reqs(), kv_int8=int8)
        ok, ek = _drive(model, _reqs(), kv_int8=int8, prefill_kernel=True)
        assert og == ok
        if not int8:
            for rid, (prompt, max_new) in enumerate(_reqs()):
                assert ok[rid] == _reference(model, prompt, max_new), rid
        assert ek.alloc.free_blocks == ek.alloc.num_blocks
        # write accounting: both paths charge writes, the kernel path
        # strictly less (blocks actually covered by chunks vs the
        # static pad span), and the kernel path's reads charge the
        # walked prefix, strictly below the gather path's dense sweep
        assert 0 < ek.prefill_write_bytes_total \
            < eg.prefill_write_bytes_total
        assert 0 <= ek.prefill_read_bytes_total \
            < eg.prefill_read_bytes_total
        # the knob is stamped through kv_stats (→ serve_kv_config)
        assert ek.kv_stats()["prefill_kernel"] is True
        assert ek.kv_stats()["prefill_read_bytes"] \
            == ek.prefill_read_bytes_total

    @pytest.mark.parametrize("paged,temp", [
        (True, 0.9), (True, 0.0), (False, 0.9), (False, 0.0),
    ], ids=["paged-sampled", "paged-greedy", "dense-sampled",
            "dense-greedy"])
    def test_fused_sampling_streams_identical(self, model, paged, temp):
        """The fused tail's streams are byte-identical to the unfused
        tail for greedy AND sampled temperatures (the categorical draw
        stays in-graph on the kernel's scaled logits — same fold_in
        substream), on the paged and dense engines.  The paged-sampled
        cell is the default-lane representative; the siblings are
        slow-lane (tests/conftest.py)."""
        kw = dict() if paged else dict(paged=False)
        a, _ = _drive(model, _reqs(), temperature=temp, **kw)
        b, _ = _drive(model, _reqs(), temperature=temp,
                      sample_kernel=True, **kw)
        assert a == b

    def test_full_stack_greedy_byte_identity(self, model):
        """All four kernels at once (prefill + fused sampling + fused
        RoPE+QKV + in-kernel LoRA on the paged decode arm) with mixed
        adapter/base lanes: streams byte-identical to the all-in-graph
        engine."""
        names = ["ad0", None, "ad1", "ad0"]
        a, _ = _drive(model, _reqs(), attn_kernel="paged", adapters=True,
                      adapter_names=names)
        b, _ = _drive(model, _reqs(), attn_kernel="paged", adapters=True,
                      adapter_names=names, prefill_kernel=True,
                      sample_kernel=True, fused_rope=True,
                      lora_kernel=True)
        assert a == b

    def test_compile_counts_pinned_under_churn(self, model):
        """Churn never recompiles: ONE batched kernel-prefill program
        serves the admission batch and every one-hot chunk extend
        (insert_batch == 1, prefill_extend == 1 — chunked prefill adds
        no second program shape), decode bounded by the pow2 buckets."""
        _, eng = _drive(model, _reqs() * 2, attn_kernel="paged",
                        prefill_kernel=True, sample_kernel=True,
                        fused_rope=True)
        cc = eng.compile_counts()
        assert cc["insert_batch"] == 1
        assert cc["prefill_extend"] == 1
        assert cc["evict"] == 1
        assert 1 <= cc["decode_block"] <= 4

    def test_spec_through_kernel_prefill(self, model):
        """Speculative decoding rides the kernel prefill + fused
        residual prep: sampled streams identical to the in-graph spec
        engine (the fused pass bit-matches both softmaxes, so
        accept/reject decisions and residual draws agree)."""
        a, _ = _drive(model, _reqs(), spec_draft=1, temperature=0.5,
                      attn_kernel="paged")
        b, eng = _drive(model, _reqs(), spec_draft=1, temperature=0.5,
                        attn_kernel="paged", prefill_kernel=True,
                        sample_kernel=True)
        assert a == b
        assert eng.spec_stats()["blocks"] > 0

    def test_compile_counts_flat_across_mesh_shapes(self, model, devices):
        """Mesh shapes change shardings, never programs: identical
        jit-cache sizes and byte-identical streams at 1x1 and 1x2 with
        the whole family enabled."""
        outs, counts = {}, {}
        for mesh in (None, "1x2"):
            out, eng = _drive(model, _reqs(), attn_kernel="paged",
                              prefill_kernel=True, sample_kernel=True,
                              fused_rope=True, mesh=mesh)
            outs[mesh], counts[mesh] = out, eng.compile_counts()
        assert outs[None] == outs["1x2"]
        assert counts[None] == counts["1x2"]

    def test_knob_validation(self, model):
        """Each knob's requirements fail loudly, naming its env var."""
        module, params = model
        with pytest.raises(ValueError, match="PREFILL_KERNEL"):
            SlotEngine(module, params, num_slots=2, prefill_kernel=True)
        with pytest.raises(ValueError, match="FUSED_ROPE"):
            SlotEngine(module, params, num_slots=2, paged=True,
                       kv_block=4, fused_rope=True)
        with pytest.raises(ValueError, match="LORA_KERNEL"):
            SlotEngine(module, params, num_slots=2, paged=True,
                       kv_block=4, attn_kernel="paged", lora_kernel=True)
        with pytest.raises(ValueError, match="LORA_KERNEL"):
            SlotEngine(module, params, num_slots=2, paged=True,
                       kv_block=4, adapters=True, lora_kernel=True)
