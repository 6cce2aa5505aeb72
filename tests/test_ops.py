"""Pallas kernel correctness tests (interpreter mode on the CPU mesh),
checked against the dense XLA references — the pattern SURVEY.md §4
prescribes for doing better than the reference's zero-test strategy."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops import (
    attention_reference,
    flash_attention,
    flash_attention_packed,
    flash_attention_with_lse,
)

# the module: ``tpudist.ops.flash_attention`` names the function it exports
kernels = importlib.import_module("tpudist.ops.flash_attention")


class TestFlashAttention:
    def _qkv(self, seq=256, batch=2, heads=2, d=64, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return tuple(
            jax.random.normal(k, (batch, heads, seq, d), jnp.float32) for k in ks
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = self._qkv()
        out = flash_attention(q, k, v, causal, 128, 128, True)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_small_seq_clamps_blocks(self):
        q, k, v = self._qkv(seq=64)
        out = flash_attention(q, k, v, False, 128, 128, True)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_reference(self):
        q, k, v = self._qkv(seq=128)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, 64, 64, True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)

    @pytest.mark.parametrize("bq,bk", [(64, 128), (128, 64), (64, 256), (256, 64)])
    def test_unequal_blocks_causal(self, bq, bk):
        """The causal dead-block DMA-elision index map depends on
        block_q != block_k arithmetic ((i+1)*bq-1)//bk — cover both
        wide-K and wide-Q tiles."""
        q, k, v = self._qkv(seq=256)
        out = flash_attention(q, k, v, True, bq, bk, True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_indivisible_seq_raises(self):
        q, k, v = self._qkv(seq=100)
        with pytest.raises(ValueError, match="divide"):
            flash_attention(q, k, v, False, 64, 64, True)

    @pytest.mark.parametrize("causal,bq,bk",
                             [(False, 64, 64), (True, 64, 128), (True, 128, 64)])
    def test_pallas_backward_block_shapes(self, causal, bq, bk):
        """The Pallas dq (KV-innermost) and dk/dv (Q-innermost) kernels use
        different dead-block remap arithmetic — cover non-causal plus both
        unequal-block causal orientations."""
        q, k, v = self._qkv(seq=256)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, bq, bk, True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 1)])
    def test_gqa_native_kv(self, causal, hq, hkv):
        """Grouped-query K/V consumed without repeat: fwd + all grads match
        the repeated-KV dense reference; dk/dv come back at kv-head count
        (the dkv kernel's group×q-tile accumulation sweep)."""
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (2, hq, 128, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, hkv, 128, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, hkv, 128, 32), jnp.float32)
        g = hq // hkv

        def rep(t):
            return jnp.repeat(t, g, axis=1)

        out = flash_attention(q, k, v, causal, 64, 64, True)
        ref = attention_reference(q, rep(k), rep(v), causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

        gf = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal, 64, 64, True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(
            lambda q, k, v: jnp.sum(
                attention_reference(q, rep(k), rep(v), causal=causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        assert gf[1].shape == k.shape and gf[2].shape == v.shape
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("window", [16, 100, 128])
    def test_sliding_window(self, window):
        """Sliding-window band (q − k < window): fwd + grads match the
        dense banded reference, including windows that don't align with
        tile edges — both band edges elide dead tiles."""
        q, k, v = self._qkv(seq=256, d=32)
        out = flash_attention(q, k, v, True, 64, 64, True, window)
        ref = attention_reference(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

        gf = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, True, 64, 64, True, window) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(
            lambda q, k, v: jnp.sum(
                attention_reference(q, k, v, causal=True,
                                    window=window) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_sliding_window_gqa(self):
        """Window composes with grouped-query K/V."""
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (1, 4, 128, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32)
        out = flash_attention(q, k, v, True, 64, 64, True, 32)
        ref = attention_reference(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                                  causal=True, window=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_window_requires_causal(self):
        q, k, v = self._qkv(seq=64)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, False, 64, 64, True, 16)

    def test_gqa_indivisible_heads_raises(self):
        q = jnp.zeros((1, 4, 64, 16))
        kv = jnp.zeros((1, 3, 64, 16))
        with pytest.raises(ValueError, match="multiple of kv heads"):
            flash_attention(q, kv, kv, False, 64, 64, True)

    def test_backward_bf16(self):
        """Mixed-precision discipline in the backward: bf16 MXU operands,
        f32 accumulation, grads emitted in bf16 — matches the dense
        reference run at the same input precision to bf16 tolerance."""
        q, k, v = (a.astype(jnp.bfloat16) for a in self._qkv(seq=128))

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, True, 64, 64, True).astype(jnp.float32)
                ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(
                attention_reference(q, k, v, causal=True).astype(jnp.float32)
                ** 2
            )

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=0.15, rtol=0.15,
            )


# (heads, kv heads, head_dim, seq, block_q, block_k, causal, window, dtype)
PACKED = {
    "mha": (4, 4, 32, 128, 64, 64, True, None, jnp.float32),
    "gqa": (4, 2, 32, 128, 64, 64, True, None, jnp.float32),
    "mqa-window": (4, 1, 32, 128, 32, 64, True, 48, jnp.float32),
    "non-causal": (2, 2, 32, 128, 64, 32, False, None, jnp.float32),
    "bq-below-seq-dh128-bf16": (4, 2, 128, 256, 64, 128, True, None,
                                jnp.bfloat16),
}


class TestFlashAttentionPacked:
    """The packed entry reads q, k, v out of one ``[b, s, (h + 2·kv)·dh]``
    array and writes ``[b, s, h·dh]``: the same kernel bodies over the
    same tiles in the same order as the head-major entry, so the same
    bits, forward and backward."""

    @pytest.mark.parametrize("case", sorted(PACKED))
    def test_packed_equals_head_major_bit_for_bit(self, case):
        from tpudist.ops.attention import merge_heads, split_heads

        h, kv, d, seq, bq, bk, causal, window, dtype = PACKED[case]
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (2, seq, (h + 2 * kv) * d), dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (2, seq, h * d),
                              jnp.float32)

        def packed(qkv):
            return flash_attention_packed(qkv, h, kv, causal, bq, bk, True,
                                          window)

        def head_major(qkv, attention=flash_attention):
            q, k, v = split_heads(qkv, h, kv)
            if attention is attention_reference:
                group = h // kv
                return merge_heads(attention_reference(
                    q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1),
                    causal=causal, window=window))
            return merge_heads(attention(q, k, v, causal, bq, bk, True,
                                         window))

        def cotangent(fn):
            return jax.grad(lambda x: jnp.sum(
                fn(x).astype(jnp.float32) * w))(qkv)

        out, g = packed(qkv), cotangent(packed)
        assert out.shape == (2, seq, h * d) and g.shape == qkv.shape
        assert g.dtype == qkv.dtype
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(head_major(qkv), np.float32))
        np.testing.assert_array_equal(
            np.asarray(g, np.float32),
            np.asarray(cotangent(head_major), np.float32))
        # and against the dense reference, at the flash tests' tolerances
        tol = (dict(atol=0.15, rtol=0.15) if dtype == jnp.bfloat16
               else dict(atol=5e-5, rtol=5e-5))
        ref = functools.partial(head_major, attention=attention_reference)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref(qkv), np.float32), **tol)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(cotangent(ref), np.float32),
                                   **tol)

    def test_a_width_that_holds_no_whole_heads_raises(self):
        with pytest.raises(ValueError, match="does not hold"):
            flash_attention_packed(jnp.zeros((1, 64, 100)), 4, 2, True, 64,
                                   64, True)


# (q heads, kv heads, head_dim, layout, tiles a side): 256-wide tiles cut
# into 64-wide squares, the cells' two head shapes
DIAGONAL = {
    f"{name}-{layout}-{tiles}-tiles": (h, kv, d, layout, tiles)
    for name, h, kv, d, layouts in [
        ("mha-dh128", 2, 2, 128, ("head-major", "packed")),
        ("gqa8-dh256", 8, 1, 256, ("packed",))]
    for layout in layouts for tiles in (2, 4)
}
# (causal, block_q, block_k, window): what a tile on the diagonal is NOT cut
# for, each a path some caller takes
WHOLE = {
    "window": (True, 128, 128, 96),
    "window-wider-than-a-tile": (True, 64, 64, 100),
    "ring-hop-shifted-band": (False, 128, 128, (None, 64)),
    "ring-hop-band-behind-the-shard": (False, 64, 64, (None, -70)),
    "ring-hop-band-at-a-tile-edge": (False, 64, 64, (None, 128)),
    "ring-hop-unequal-blocks": (False, 64, 128, (None, 100)),
    "band-with-both-edges": (False, 128, 128, (-32, 160)),
    "band-with-no-live-tile-in-some-rows": (False, 64, 64, (100, 200)),
    "window-unequal-blocks-wide-q": (True, 128, 64, 64),
    "window-unequal-blocks-wide-k": (True, 64, 128, 128),
    "unequal-blocks-wide-k": (True, 64, 128, None),
    "unequal-blocks-wide-q": (True, 128, 64, None),
    "not-causal": (False, 128, 128, None),
    "sub-does-not-divide": (True, 80, 80, None),
}


class TestDiagonalTilesBySquares:
    """A tile on the diagonal of the plain causal band goes by ``sub``-wide
    squares, and the squares above the diagonal are never computed: the
    same numbers as the dense reference, forward, ``lse`` and all three
    gradients; every other band keeps the whole-tile branch, bit for bit."""

    @pytest.mark.parametrize("case", sorted(DIAGONAL))
    def test_values_lse_and_gradients_match_the_reference(self, case,
                                                          monkeypatch):
        from tpudist.ops.attention import merge_heads, split_heads

        h, kv, d, layout, tiles = DIAGONAL[case]
        block, sub, seq = 256, 64, 256 * tiles
        qkv = jax.random.normal(jax.random.PRNGKey(tiles),
                                (1, seq, (h + 2 * kv) * d), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(9), (1, seq, h * d))
        cut = []   # one call a kernel traced: forward, dq, dk/dv
        real = kernels._diagonal_strips
        monkeypatch.setattr(
            kernels, "_diagonal_strips",
            lambda *a: cut.append(a) or real(*a))

        def flash(qkv):
            if layout == "packed":
                return flash_attention_packed(qkv, h, kv, True, block, block,
                                              True, None, sub)
            return merge_heads(flash_attention(
                *split_heads(qkv, h, kv), True, block, block, True, None,
                sub))

        def reference(qkv):
            q, k, v = split_heads(qkv, h, kv)
            return merge_heads(attention_reference(
                q, jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1),
                causal=True))

        def cotangent(fn):
            return jax.grad(lambda x: jnp.sum(fn(x) * w))(qkv)

        np.testing.assert_allclose(flash(qkv), reference(qkv), atol=2e-5,
                                   rtol=2e-5)
        assert cut == [(block, sub)]
        # the gradient holds dq, dk and dv side by side
        np.testing.assert_allclose(cotangent(flash), cotangent(reference),
                                   atol=1e-4, rtol=1e-4)
        assert cut[1:] == [(block, sub)] * 3   # forward again, dq, dk/dv
        q, k, v = split_heads(qkv, h, kv)
        if layout == "packed":
            lse = kernels._packed_fwd(qkv, h, kv, True, block, block, True,
                                      None, sub)[1][2].reshape(1, h, seq)
        else:
            lse = flash_attention_with_lse(q, k, v, True, block, block, True,
                                           False, None, sub)[1]
        scores = jnp.einsum("bhqd,bhkd->bhqk", q,
                            jnp.repeat(k, h // kv, 1)) * d ** -0.5
        scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores,
                           -jnp.inf)
        np.testing.assert_allclose(
            lse, jax.scipy.special.logsumexp(scores, axis=-1), atol=2e-5,
            rtol=2e-5)

    @pytest.mark.parametrize("case", sorted(WHOLE))
    def test_every_other_band_keeps_the_whole_tile_branch_bit_for_bit(
            self, case, monkeypatch):
        causal, bq, bk, window = WHOLE[case]
        seq = 240 if bq == 80 else 256
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (1, 4, seq, 32), jnp.float32)
        k, v = (jax.random.normal(key, (1, 2, seq, 32), jnp.float32)
                for key in ks[1:3])
        w = jax.random.normal(ks[3], q.shape)
        monkeypatch.setattr(
            kernels, "_diagonal_strips",
            lambda *a: pytest.fail(f"{case} cut a tile: {a}"))

        def run(sub):
            def both(q, k, v):
                out, lse = flash_attention_with_lse(
                    q, k, v, causal, bq, bk, True, False, window, sub)
                return jnp.sum(out * w) + jnp.sum(jnp.where(
                    lse > -1e29, lse, 0.0)), (out, lse)
            return jax.value_and_grad(both, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)

        whole = run(0)
        for got, want in zip(jax.tree.leaves(run(32)),
                             jax.tree.leaves(whole)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and the grid that follows the band gives what the sweep over
        # every tile gave (the parent's schedule: every step from tile 0,
        # the dead ones skipped): the live tiles in the same order
        seen = []
        real = kernels.band_grid

        def every_tile(nq, nkv, *band):
            seen.append((real(nq, nkv, *band)[:2], (nkv, nq)))
            return nkv, nq, nq * nkv, 0

        monkeypatch.setattr(kernels, "band_grid", every_tile)
        monkeypatch.setattr(kernels, "_first_live_kv", lambda *a: 0)
        monkeypatch.setattr(kernels, "_first_live_q", lambda *a: 0)
        for got, want in zip(jax.tree.leaves(run(0)),
                             jax.tree.leaves(whole)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # a band with an upper edge that leaves tiles out has a shorter grid
        assert all(band <= full for band, full in seen), seen
        if case in ("ring-hop-band-behind-the-shard",
                    "band-with-no-live-tile-in-some-rows",
                    "window-unequal-blocks-wide-q",
                    "window-wider-than-a-tile"):
            assert all(band[0] < full[0] for band, full in seen), seen


# (block, sub) and the windows of the band's grid: under the block, the
# block, 1.5 and 2.5 blocks, and past the end of the 256 positions
BAND_BLOCK, BAND_SUB, BAND_SEQ = 64, 16, 256
BAND = {
    f"{name}-{heads}-{layout}": (window, h, kv, layout)
    for name, window in [("under", 32), ("block", 64), ("1.5-blocks", 96),
                         ("2-blocks", 128), ("2.5-blocks", 160),
                         ("past-the-end", 512)]
    for heads, h, kv in [("mha", 2, 2), ("gqa9", 9, 1)]
    for layout in ("head-major", "packed")
}


class TestTheGridFollowsTheBand:
    """A sliding window's three kernels sweep the run of live tiles and no
    more, a window as wide as whole tiles takes its two edge tiles by
    squares, and forward, dq, dk and dv are the dense reference's."""

    @pytest.mark.parametrize("case", sorted(BAND))
    def test_values_and_gradients_match_the_reference(self, case,
                                                      monkeypatch):
        from tpudist.ops.attention import merge_heads, split_heads

        window, h, kv, layout = BAND[case]
        block, sub, seq, d = BAND_BLOCK, BAND_SUB, BAND_SEQ, 32
        qkv = jax.random.normal(jax.random.PRNGKey(h),
                                (1, seq, (h + 2 * kv) * d), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(9), (1, seq, h * d))
        grids, cut = [], []
        real_call = kernels.pl.pallas_call
        monkeypatch.setattr(
            kernels.pl, "pallas_call",
            lambda *a, **k: grids.append(k["grid"]) or real_call(*a, **k))
        for strips in ("_diagonal_strips", "_far_edge_strips"):
            real = getattr(kernels, strips)
            monkeypatch.setattr(
                kernels, strips,
                lambda *a, _name=strips, _real=real: cut.append(_name)
                or _real(*a))

        def flash(qkv):
            if layout == "packed":
                return flash_attention_packed(qkv, h, kv, True, block, block,
                                              True, window, sub)
            return merge_heads(flash_attention(
                *split_heads(qkv, h, kv), True, block, block, True, window,
                sub))

        def reference(qkv):
            q, k, v = split_heads(qkv, h, kv)
            return merge_heads(attention_reference(
                q, jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1),
                causal=True, window=window))

        def cotangent(fn):
            return jax.grad(lambda x: jnp.sum(fn(x) * w))(qkv)

        np.testing.assert_allclose(flash(qkv), reference(qkv), atol=2e-5,
                                   rtol=2e-5)
        # the gradient holds dq, dk and dv side by side
        np.testing.assert_allclose(cotangent(flash), cotangent(reference),
                                   atol=1e-4, rtol=1e-4)
        # forward, forward again under grad, dq, dk/dv: a query tile's
        # sweep is the window's width in tiles plus one, and a key tile's
        # the same a group member
        n = seq // block
        steps = min(n, -(-window // block) + 1)
        assert grids == [(h, n, steps)] * 3 + [(kv, n, steps * (h // kv))]
        # a window of whole tiles: both staircases in each of the four
        by_squares = window % block == 0
        assert cut == ["_diagonal_strips", "_far_edge_strips"] * (
            4 * by_squares)

    @pytest.mark.parametrize("seq,bq,bk,lo,hi", [
        (256, 64, 64, 0, 64), (256, 64, 64, 0, 96), (256, 64, 64, 0, 300),
        (256, 32, 64, 0, 64), (256, 128, 64, 0, 100), (256, 64, 64, None, 64),
        (256, 64, 64, None, -70), (256, 64, 64, -32, 160),
        (256, 64, 64, 100, 200), (256, 64, 64, 0, None),
        (256, 64, 128, 0, None), (256, 64, 64, None, None)])
    def test_a_sweep_is_the_run_of_live_tiles(self, seq, bq, bk, lo, hi):
        """First live to last live is exactly what ``_tile_live`` keeps, for
        a query tile's key tiles and a key tile's query tiles, and the grid
        axes are the longest runs."""
        nq, nkv = seq // bq, seq // bk
        live = np.array([[bool(kernels._tile_live(i, j, bq, bk, lo, hi))
                          for j in range(nkv)] for i in range(nq)])
        kv_steps, q_steps, n_live, _ = kernels.band_grid(nq, nkv, bq, bk, lo,
                                                         hi)
        assert n_live == live.sum()
        assert kv_steps == max(live.sum(1).max(), 1)
        assert q_steps == (nq if hi is None else max(live.sum(0).max(), 1))
        for i in np.flatnonzero(live.any(1)):
            first = int(kernels._first_live_kv(i, nkv, bq, bk, hi))
            last = int(kernels._last_live_kv(i, nkv, bq, bk, lo))
            assert list(np.flatnonzero(live[i])) == list(
                range(first, last + 1))
        if hi is not None:
            for j in np.flatnonzero(live.any(0)):
                first = int(kernels._first_live_q(j, nq, bq, bk, lo, hi))
                assert first == np.flatnonzero(live[:, j])[0]


class TestBlockwiseAttention:
    """The plain-XLA blockwise fallback (kernel-free platforms)."""

    def _qkv(self, seq=128, batch=2, heads=2, d=32, seed=3):
        import jax

        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return tuple(
            jax.random.normal(k, (batch, heads, seq, d), jnp.float32) for k in ks
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        from tpudist.ops import blockwise_attention

        q, k, v = self._qkv()
        out = blockwise_attention(q, k, v, causal=causal, block_k=32)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_reference(self, causal):
        from tpudist.ops import blockwise_attention

        q, k, v = self._qkv(seq=64)

        def loss_b(q, k, v):
            return jnp.sum(blockwise_attention(q, k, v, causal=causal,
                                               block_k=16) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

        gb = jax.grad(loss_b, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gb, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)
