"""Transformer family tests: the three attention implementations are
interchangeable, and the DP×SP train step actually learns."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from tpudist.models import create_transformer, lm_loss
from tpudist.ops import flash_attention
from tpudist.ops.attention import Tiles
from tpudist.parallel import make_ring_attention
from tpudist.runtime.mesh import AXIS_DATA, AXIS_SEQ, AXIS_STAGE
from tpudist.train import init_lm_state, make_lm_train_step, token_sharding

CFG = dict(vocab=32, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_len=128)


def _tokens(batch=4, seq=64, vocab=32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, size=(batch, seq)), jnp.int32)


class TestAttentionInterchangeability:
    def test_dense_flash_ring_agree(self, devices):
        """Same params, same tokens → same logits for all three attention
        implementations (dense XLA, Pallas flash, ring over a seq mesh)."""
        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_SEQ))
        tokens = _tokens()
        key = jax.random.PRNGKey(0)

        dense_mod, params = create_transformer(key, seq_len=64, **CFG)
        out_dense = dense_mod.apply(params, tokens)

        flash_mod, _ = create_transformer(
            key, seq_len=64,
            attention_fn=lambda q, k, v: flash_attention(q, k, v, True, 32, 32, True),
            **CFG,
        )
        out_flash = flash_mod.apply(params, tokens)

        ring_mod, _ = create_transformer(
            key, seq_len=64,
            attention_fn=make_ring_attention(mesh, causal=True,
                                             batch_axis=AXIS_DATA),
            **CFG,
        )
        out_ring = ring_mod.apply(params, tokens)

        np.testing.assert_allclose(np.asarray(out_dense), np.asarray(out_flash),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(out_dense), np.asarray(out_ring),
                                   atol=2e-4, rtol=2e-4)

    def test_causality(self):
        """Future tokens must not influence past logits."""
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            **CFG)
        t1 = _tokens(batch=1, seq=32)
        t2 = t1.at[0, -1].set((t1[0, -1] + 1) % 32)
        o1 = module.apply(params, t1)
        o2 = module.apply(params, t2)
        np.testing.assert_allclose(np.asarray(o1[0, :-1]), np.asarray(o2[0, :-1]),
                                   atol=1e-6)


class TestLMTraining:
    def _increment_batch(self, rng, batch, seq, vocab):
        start = rng.integers(0, vocab, size=(batch, 1))
        return jnp.asarray((start + np.arange(seq)[None]) % vocab, jnp.int32)

    def test_loss_decreases_on_dp_sp_mesh(self, devices):
        """DP×SP training drives the increment-chain task toward zero loss."""
        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_SEQ))
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32,
            attention_fn=make_ring_attention(mesh, causal=True,
                                             batch_axis=AXIS_DATA),
            **CFG,
        )
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        step = make_lm_train_step(module.apply, tx, mesh)
        rng = np.random.default_rng(0)
        shard = token_sharding(mesh)

        first = None
        for i in range(40):
            tokens = jax.device_put(
                self._increment_batch(rng, 8, 32, CFG["vocab"]), shard
            )
            state, loss = step(state, tokens)
            if first is None:
                first = float(loss)
        last = float(loss)
        assert last < first * 0.5, (first, last)

    def test_token_sharding_spec(self, devices):
        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_SEQ))
        assert token_sharding(mesh).spec == P(AXIS_DATA, AXIS_SEQ)

    def test_lm_loss_perfect_prediction(self):
        vocab = 8
        tokens = _tokens(batch=2, seq=16, vocab=vocab)
        logits = jax.nn.one_hot(
            jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1), vocab
        ) * 100.0
        assert float(lm_loss(logits, tokens)) < 1e-3


class TestTensorParallelTransformer:
    def test_tp_training_matches_replicated(self, devices):
        """DP×TP: same tokens, same init — TP-sharded training must produce
        the same losses as fully-replicated training (the XLA partitioner
        only changes WHERE compute runs)."""
        from tpudist.models.transformer import transformer_tp_sharding
        from tpudist.runtime.mesh import AXIS_MODEL

        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_MODEL))
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            **CFG)
        tx = optax.adam(1e-3)
        rng = np.random.default_rng(0)
        batches = [
            jnp.asarray(rng.integers(0, CFG["vocab"], size=(8, 32)), jnp.int32)
            for _ in range(5)
        ]

        # Replicated run.
        state = init_lm_state(params, tx)
        step = make_lm_train_step(module.apply, tx, mesh)
        ref_losses = []
        for b in batches:
            state, loss = step(state, jax.device_put(b, token_sharding(mesh)))
            ref_losses.append(float(loss))

        # TP-sharded run from the same init.
        _, params2 = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                        **CFG)
        state2 = init_lm_state(params2, tx)
        sharding = transformer_tp_sharding(mesh, state2)
        state2 = jax.device_put(state2, sharding)
        step_tp = make_lm_train_step(module.apply, tx, mesh,
                                     state_sharding=sharding)
        tp_losses = []
        for b in batches:
            state2, loss = step_tp(state2, jax.device_put(b, token_sharding(mesh)))
            tp_losses.append(float(loss))

        np.testing.assert_allclose(tp_losses, ref_losses, atol=1e-4, rtol=1e-4)

    def test_tp_weights_actually_sharded(self, devices):
        from tpudist.models.transformer import transformer_tp_sharding
        from tpudist.runtime.mesh import AXIS_MODEL

        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_MODEL))
        _, params = create_transformer(jax.random.PRNGKey(0), seq_len=32, **CFG)
        sharded = jax.device_put(params, transformer_tp_sharding(mesh, params))
        qkv = sharded["params"]["block_0"]["qkv"]["kernel"]
        assert qkv.sharding.spec == jax.sharding.PartitionSpec(None, AXIS_MODEL)
        # 3*d_model=192 columns over 4 model shards -> 48-wide local shards.
        assert qkv.addressable_shards[0].data.shape == (CFG["d_model"], 48)
        proj = sharded["params"]["block_0"]["proj"]["kernel"]
        assert proj.sharding.spec == jax.sharding.PartitionSpec(AXIS_MODEL, None)


class TestMoETransformer:
    def test_sharded_matches_dense_reference(self, devices):
        """Expert-parallel MoE FFN (all_to_all over the model axis) equals
        the dense per-token-all-experts reference when nothing overflows
        capacity."""
        from tpudist.models.transformer import moe_expert_fn
        from tpudist.parallel import make_moe
        from tpudist.runtime.mesh import AXIS_MODEL

        mesh = Mesh(np.asarray(devices).reshape(4, 2),
                    axis_names=(AXIS_DATA, AXIS_MODEL))
        moe_fn = make_moe(mesh, moe_expert_fn, batch_axis=AXIS_DATA,
                          capacity_factor=4.0)
        cfg = dict(CFG, n_experts=2)
        sharded_mod, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, moe_fn=moe_fn, **cfg)
        dense_mod, _ = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, **cfg)
        tokens = _tokens(batch=8, seq=32)
        out_sharded = sharded_mod.apply(params, tokens)
        out_dense = dense_mod.apply(params, tokens)
        np.testing.assert_allclose(np.asarray(out_sharded),
                                   np.asarray(out_dense),
                                   atol=2e-4, rtol=2e-4)

    def test_moe_lm_trains(self, devices):
        from tpudist.models.transformer import moe_expert_fn
        from tpudist.parallel import make_moe
        from tpudist.runtime.mesh import AXIS_MODEL

        mesh = Mesh(np.asarray(devices).reshape(4, 2),
                    axis_names=(AXIS_DATA, AXIS_MODEL))
        moe_fn = make_moe(mesh, moe_expert_fn, batch_axis=AXIS_DATA,
                          capacity_factor=2.0)
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, moe_fn=moe_fn,
            **dict(CFG, n_experts=2))
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        step = make_lm_train_step(module.apply, tx, mesh)
        rng = np.random.default_rng(0)
        shard = token_sharding(mesh)
        first = None
        for _ in range(30):
            start = rng.integers(0, CFG["vocab"], size=(8, 1))
            tokens = jax.device_put(
                jnp.asarray((start + np.arange(32)[None]) % CFG["vocab"],
                            jnp.int32), shard)
            state, loss = step(state, tokens)
            if first is None:
                first = float(loss)
        assert float(loss) < first * 0.7, (first, float(loss))

    def test_moe_aux_stats(self, devices):
        """aux=True surfaces routing stats (sown intermediates) host-side:
        dropped_fraction in [0,1], expert_load a distribution over experts."""
        from tpudist.models.transformer import moe_expert_fn
        from tpudist.parallel import make_moe
        from tpudist.runtime.mesh import AXIS_MODEL

        mesh = Mesh(np.asarray(devices).reshape(4, 2),
                    axis_names=(AXIS_DATA, AXIS_MODEL))
        moe_fn = make_moe(mesh, moe_expert_fn, batch_axis=AXIS_DATA,
                          capacity_factor=2.0)
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, moe_fn=moe_fn,
            **dict(CFG, n_experts=2))
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        step = make_lm_train_step(module.apply, tx, mesh, aux=True,
                                  donate_state=False)
        tokens = jax.device_put(_tokens(batch=8, seq=32),
                                token_sharding(mesh))
        state, loss, aux = step(state, tokens)
        assert set(aux) == {"moe_dropped_fraction", "moe_expert_load",
                            "moe_balance_loss"}
        dropped = float(aux["moe_dropped_fraction"])
        load = np.asarray(aux["moe_expert_load"])
        assert 0.0 <= dropped <= 1.0
        assert load.shape == (2,)
        np.testing.assert_allclose(load.sum(), 1.0, atol=1e-5)
        assert 0.9 <= float(aux["moe_balance_loss"]) <= 2.0
        # moe_balance_weight > 0 with aux=False: grads include the balance
        # term, the 2-tuple contract and reported-loss semantics hold.
        bal_step = make_lm_train_step(module.apply, tx, mesh,
                                      moe_balance_weight=0.01)
        bstate, bloss = bal_step(init_lm_state(params, tx), tokens)
        assert np.isfinite(float(bloss))

        # Dense (non-MoE) model sows nothing: aux comes back empty.
        dense_mod, dense_params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, **CFG)
        dense_step = make_lm_train_step(dense_mod.apply, tx, mesh, aux=True)
        _, _, dense_aux = dense_step(
            init_lm_state(dense_params, tx), tokens)
        assert dense_aux == {}


class TestGQA:
    def test_full_kv_heads_is_mha(self):
        """n_kv_heads == n_heads produces the identical model (same param
        shapes, same logits) as leaving it unset."""
        tokens = _tokens(batch=2, seq=32)
        mha, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                         **CFG)
        gqa, params_g = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                           n_kv_heads=CFG["n_heads"], **CFG)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), params, params_g)
        np.testing.assert_array_equal(np.asarray(mha.apply(params, tokens)),
                                      np.asarray(gqa.apply(params_g, tokens)))

    def test_kv_projection_smaller_and_causal(self):
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            n_kv_heads=1, **CFG)
        dh = CFG["d_model"] // CFG["n_heads"]
        kern = params["params"]["block_0"]["qkv"]["kernel"]
        assert kern.shape == (CFG["d_model"], CFG["d_model"] + 2 * dh)
        tokens = _tokens(batch=2, seq=32)
        out = module.apply(params, tokens)
        tokens2 = tokens.at[:, -1].set((tokens[:, -1] + 1) % CFG["vocab"])
        out2 = module.apply(params, tokens2)
        np.testing.assert_allclose(np.asarray(out[:, :-1]),
                                   np.asarray(out2[:, :-1]),
                                   atol=1e-5, rtol=1e-5)

    def test_invalid_kv_heads_raises(self):
        with pytest.raises(ValueError, match="divide"):
            create_transformer(jax.random.PRNGKey(0), seq_len=32,
                               n_kv_heads=3, **CFG)

    def test_gqa_decode_matches_forward_and_shrinks_cache(self):
        from tpudist.models import decode_logits, make_decode_step

        cfg = dict(CFG, n_heads=4)
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, n_kv_heads=2, rope=True, **cfg)
        tokens = _tokens(batch=2, seq=32)
        np.testing.assert_allclose(
            np.asarray(decode_logits(module, params, tokens)),
            np.asarray(module.apply(params, tokens).astype(jnp.float32)),
            atol=1e-4, rtol=1e-4)
        init_cache, _ = make_decode_step(module, params)
        cache = init_cache(2)
        k = cache["block_0"]["k"]
        assert k.shape[1] == 2  # n_kv_heads, not n_heads

    def test_gqa_trains_with_ring(self, devices):
        mesh = Mesh(np.asarray(devices).reshape(4, 2),
                    axis_names=(AXIS_DATA, AXIS_SEQ))
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, n_kv_heads=2, rope=True,
            attention_fn=make_ring_attention(mesh, causal=True,
                                             batch_axis=AXIS_DATA),
            **CFG)
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        step = make_lm_train_step(module.apply, tx, mesh)
        rng = np.random.default_rng(0)
        shard = token_sharding(mesh)
        first = None
        for _ in range(30):
            start = rng.integers(0, CFG["vocab"], size=(8, 1))
            toks = jax.device_put(
                jnp.asarray((start + np.arange(32)[None]) % CFG["vocab"],
                            jnp.int32), shard)
            state, loss = step(state, toks)
            if first is None:
                first = float(loss)
        assert float(loss) < first * 0.7, (first, float(loss))


class TestGradAccumulation:
    def test_matches_full_batch(self, devices):
        """accum_steps=4 == full-batch step: identical reported loss and
        near-identical updated params (summation order only)."""
        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            **CFG)
        tx = optax.adam(1e-3)
        tokens = jax.device_put(_tokens(batch=16, seq=32),
                                token_sharding(mesh))

        full = make_lm_train_step(module.apply, tx, mesh, donate_state=False)
        acc = make_lm_train_step(module.apply, tx, mesh, donate_state=False,
                                 accum_steps=4)
        s_full, l_full = full(init_lm_state(params, tx), tokens)
        s_acc, l_acc = acc(init_lm_state(params, tx), tokens)
        np.testing.assert_allclose(float(l_full), float(l_acc),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(s_full.params),
                        jax.tree.leaves(s_acc.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_indivisible_batch_raises(self, devices):
        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            **CFG)
        tx = optax.adam(1e-3)
        step = make_lm_train_step(module.apply, tx, mesh, accum_steps=3)
        tokens = jax.device_put(_tokens(batch=16, seq=32),
                                token_sharding(mesh))
        with pytest.raises(ValueError, match="accum"):
            step(init_lm_state(params, tx), tokens)


class TestRoPE:
    def test_causality_and_no_pos_table(self):
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            rope=True, **CFG)
        assert "pos_embed" not in params["params"]
        tokens = _tokens(batch=2, seq=32)
        out = module.apply(params, tokens)
        # future-token perturbation cannot change past logits
        tokens2 = tokens.at[:, -1].set((tokens[:, -1] + 1) % CFG["vocab"])
        out2 = module.apply(params, tokens2)
        np.testing.assert_allclose(np.asarray(out[:, :-1]),
                                   np.asarray(out2[:, :-1]),
                                   atol=1e-5, rtol=1e-5)

    def test_relative_encoding(self):
        """RoPE scores depend on relative offsets: a sequence prefixed by
        padding produces the same causal attention pattern shifted — check
        via the model's shift property on a repeating input."""
        from tpudist.ops import rope_rotate

        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 8, 16))
        qr, kr = rope_rotate(q), rope_rotate(k)
        # score(i, j) after rotation equals score computed with both
        # positions shifted by the same amount: rotate a length-16 copy
        # where rows occupy positions 8..15 instead of 0..7.
        pad = jnp.zeros_like(q)
        q16 = jnp.concatenate([pad, q], axis=2)
        k16 = jnp.concatenate([pad, k], axis=2)
        qr16, kr16 = rope_rotate(q16), rope_rotate(k16)
        s_base = jnp.einsum("bhqd,bhkd->bhqk", qr, kr)
        s_shift = jnp.einsum("bhqd,bhkd->bhqk", qr16[:, :, 8:], kr16[:, :, 8:])
        np.testing.assert_allclose(np.asarray(s_base), np.asarray(s_shift),
                                   atol=1e-4, rtol=1e-4)

    def test_ring_agrees_with_dense_under_rope(self, devices):
        """Rotation happens in the global view, so seq-sharded ring
        attention and dense agree on a rope model."""
        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_SEQ))
        tokens = _tokens(batch=4, seq=64)
        dense_mod, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=64, rope=True, **CFG)
        ring_mod, _ = create_transformer(
            jax.random.PRNGKey(0), seq_len=64, rope=True,
            attention_fn=make_ring_attention(mesh, causal=True,
                                             batch_axis=AXIS_DATA),
            **CFG)
        np.testing.assert_allclose(
            np.asarray(dense_mod.apply(params, tokens)),
            np.asarray(ring_mod.apply(params, tokens)),
            atol=2e-4, rtol=2e-4)


class TestMixedPrecision:
    def test_bf16_forward_close_to_f32(self):
        """Same f32 master params: bf16 compute tracks the f32 logits
        within bf16 resolution (~3 decimal digits of the logit scale)."""
        tokens = _tokens(batch=4, seq=32)
        mod32, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                           **CFG)
        mod16, _ = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                      dtype=jnp.bfloat16, **CFG)
        out32 = mod32.apply(params, tokens)
        out16 = mod16.apply(params, tokens)
        assert out16.dtype == jnp.bfloat16
        scale = float(jnp.abs(out32).max())
        err = float(jnp.abs(out32 - out16.astype(jnp.float32)).max())
        assert err < 0.05 * max(scale, 1.0), (err, scale)

    def test_bf16_moe_stays_bf16(self):
        """The MoE FFN honors the compute dtype end-to-end (no silent f32
        promotion of the residual stream)."""
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, dtype=jnp.bfloat16,
            **dict(CFG, n_experts=2))
        out = module.apply(params, _tokens(batch=4, seq=32))
        assert out.dtype == jnp.bfloat16

    def test_bf16_lm_trains_ring(self, devices):
        """bf16 compute composed with dp×sp ring attention: params stay f32
        masters and the loss still drops."""
        mesh = Mesh(np.asarray(devices).reshape(4, 2),
                    axis_names=(AXIS_DATA, AXIS_SEQ))
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32,
            attention_fn=make_ring_attention(mesh, causal=True,
                                             batch_axis=AXIS_DATA),
            dtype=jnp.bfloat16, **CFG)
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        step = make_lm_train_step(module.apply, tx, mesh)
        rng = np.random.default_rng(0)
        shard = token_sharding(mesh)
        first = None
        for _ in range(30):
            start = rng.integers(0, CFG["vocab"], size=(8, 1))
            tokens = jax.device_put(
                jnp.asarray((start + np.arange(32)[None]) % CFG["vocab"],
                            jnp.int32), shard)
            state, loss = step(state, tokens)
            if first is None:
                first = float(loss)
        assert float(loss) < first * 0.7, (first, float(loss))
        # master weights never left f32
        assert all(
            leaf.dtype == jnp.float32
            for leaf in jax.tree.leaves(state.params)
        )


def _run_example(name, argv, tmp_path, monkeypatch, capsys):
    """In-process example run on the virtual mesh (test_entrypoints pattern)."""
    import importlib.util
    import sys
    from pathlib import Path

    examples = Path(__file__).resolve().parent.parent / "examples"
    sys.path.insert(0, str(examples))
    try:
        spec = importlib.util.spec_from_file_location(name, examples / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)

        monkeypatch.chdir(tmp_path)
        monkeypatch.setattr(sys, "argv", ["prog"] + argv)
        import tpudist.runtime.bootstrap as bs

        bs._INITIALIZED_CTX = None
        mod.main()
    finally:
        sys.path.remove(str(examples))
    out = capsys.readouterr().out
    assert "final lm loss" in out
    return float(out.split("final lm loss:")[1].split()[0])


class TestLongContextExample:
    def test_demo_runs_and_converges(self, tmp_path, monkeypatch, capsys):
        final = _run_example("demo_long_context", [
            "--dry_run", "--seq_shards", "4", "--seq_len", "64",
            "--d_model", "64", "--total_iterations", "60",
            "--batch_size", "8", "--seed", "0", "--log_every", "20",
        ], tmp_path, monkeypatch, capsys)
        assert final < 2.0


class TestWindowedRingExample:
    def test_demo_runs_and_converges(self, tmp_path, monkeypatch, capsys):
        """--sliding_window composed with --seq_shards: the windowed ring
        trains the increment-chain task (fully learnable inside any
        window >= 2) end to end through the entry point."""
        final = _run_example("demo_long_context", [
            "--dry_run", "--seq_shards", "4", "--seq_len", "64",
            "--sliding_window", "24", "--d_model", "64",
            "--total_iterations", "60", "--batch_size", "8",
            "--seed", "0", "--log_every", "20",
        ], tmp_path, monkeypatch, capsys)
        assert final < 2.0


class TestZigzagRingExample:
    def test_demo_runs_and_converges(self, tmp_path, monkeypatch, capsys):
        """--zigzag: the causal-balanced ring layout trains the chain
        task end to end through the entry point (permuted stream +
        explicit positions + zigzag loss)."""
        final = _run_example("demo_long_context", [
            "--dry_run", "--seq_shards", "4", "--seq_len", "64",
            "--zigzag", "--d_model", "64", "--total_iterations", "60",
            "--batch_size", "8", "--seed", "0", "--log_every", "20",
        ], tmp_path, monkeypatch, capsys)
        assert final < 2.0

    def test_zigzag_flag_validation(self, tmp_path, monkeypatch, capsys):
        import pytest as _pytest

        with _pytest.raises(SystemExit, match="seq_shards"):
            _run_example("demo_long_context", [
                "--dry_run", "--zigzag", "--seq_len", "64",
                "--total_iterations", "1",
            ], tmp_path, monkeypatch, capsys)
        with _pytest.raises(SystemExit, match="excludes"):
            _run_example("demo_long_context", [
                "--dry_run", "--zigzag", "--seq_shards", "4",
                "--sliding_window", "16", "--seq_len", "64",
                "--total_iterations", "1",
            ], tmp_path, monkeypatch, capsys)


class Test3DParallelExample:
    def test_demo_runs_and_converges(self, tmp_path, monkeypatch, capsys):
        final = _run_example("demo_3d_parallel", [
            "--dry_run", "--seq_shards", "2", "--model_shards", "2",
            "--seq_len", "64", "--d_model", "64", "--total_iterations", "60",
            "--batch_size", "8", "--seed", "0", "--log_every", "20",
        ], tmp_path, monkeypatch, capsys)
        assert final < 2.0


class TestPipelineParallelTransformer:
    def _mesh(self, devices, n_stages=4):
        from tpudist.runtime.mesh import AXIS_STAGE

        return Mesh(
            np.asarray(devices).reshape(8 // n_stages, n_stages),
            axis_names=(AXIS_DATA, AXIS_STAGE),
        )

    def test_stack_unstack_roundtrip(self):
        from tpudist.parallel import stack_block_params, unstack_block_params

        _, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                       vocab=32, d_model=32, n_layers=4,
                                       n_heads=2, d_ff=64, max_len=32)
        pp = stack_block_params(params, n_stages=2)
        back = unstack_block_params(pp)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            params, back,
        )

    def test_pp_apply_matches_sequential(self, devices):
        """Pipelined forward == plain TransformerLM forward: the schedule
        only changes WHEN each block runs, never the math."""
        from tpudist.parallel import make_pp_lm_apply, stack_block_params

        mesh = self._mesh(devices)
        cfg = dict(vocab=32, d_model=32, n_layers=4, n_heads=2, d_ff=64,
                   max_len=32)
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            **cfg)
        tokens = _tokens(batch=8, seq=32)
        ref = module.apply(params, tokens)

        pp_apply = make_pp_lm_apply(mesh, module, n_stages=4,
                                    num_microbatches=2)
        out = pp_apply(stack_block_params(params, n_stages=4), tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_pp_apply_honors_sliding_window(self, devices):
        """A windowed model pipelined over stages must reproduce the
        unpipelined windowed forward (the stage blocks rebuild the
        windowed default attention), and must differ from the unwindowed
        forward (the window actually bites)."""
        from tpudist.parallel import make_pp_lm_apply, stack_block_params

        mesh = self._mesh(devices)
        cfg = dict(vocab=32, d_model=32, n_layers=4, n_heads=2, d_ff=64,
                   max_len=32)
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            sliding_window=7, **cfg)
        tokens = _tokens(batch=8, seq=32)
        ref = module.apply(params, tokens)
        pp_apply = make_pp_lm_apply(mesh, module, n_stages=4,
                                    num_microbatches=2)
        out = pp_apply(stack_block_params(params, n_stages=4), tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        dense = module.clone(sliding_window=None).apply(params, tokens)
        assert float(jnp.max(jnp.abs(ref - dense))) > 1e-4

    def test_pp_apply_rope_remat(self, devices):
        """RoPE (no pos table) + stage remat through the pipeline path."""
        from tpudist.parallel import make_pp_lm_apply, stack_block_params

        mesh = self._mesh(devices)
        cfg = dict(vocab=32, d_model=32, n_layers=4, n_heads=2, d_ff=64,
                   max_len=32)
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            rope=True, **cfg)
        tokens = _tokens(batch=8, seq=32)
        ref = module.apply(params, tokens)
        pp_apply = make_pp_lm_apply(mesh, module, n_stages=4,
                                    num_microbatches=2, remat=True)
        pp_params = stack_block_params(params, n_stages=4)
        out = pp_apply(pp_params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        # differentiable with remat on
        g = jax.grad(lambda p: float(0) + lm_loss(pp_apply(p, tokens),
                                                  tokens))(pp_params)
        assert float(jnp.abs(jax.tree.leaves(g["blocks"])[0]).sum()) > 0

    def test_pp_training_matches_replicated(self, devices):
        """DP×PP training (template: TestTensorParallelTransformer): same
        tokens, same init — stage-sharded pipelined training must produce
        the same losses as fully-replicated training."""
        from tpudist.parallel import (
            make_pp_lm_apply,
            pp_state_sharding,
            stack_block_params,
        )

        mesh = self._mesh(devices)
        cfg = dict(vocab=32, d_model=32, n_layers=4, n_heads=2, d_ff=64,
                   max_len=32)
        module, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                            **cfg)
        tx = optax.adam(1e-3)
        rng = np.random.default_rng(0)
        batches = [
            jnp.asarray(rng.integers(0, 32, size=(8, 32)), jnp.int32)
            for _ in range(5)
        ]

        # Replicated run.
        state = init_lm_state(params, tx)
        step = make_lm_train_step(module.apply, tx, mesh)
        ref_losses = []
        for b in batches:
            state, loss = step(state, jax.device_put(b, token_sharding(mesh)))
            ref_losses.append(float(loss))

        # Pipelined run from the same init.
        _, params2 = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                        **cfg)
        pp_params = stack_block_params(params2, n_stages=4)
        state2 = init_lm_state(pp_params, tx)
        sharding = pp_state_sharding(mesh, state2)
        state2 = jax.device_put(state2, sharding)
        pp_apply = make_pp_lm_apply(mesh, module, n_stages=4,
                                    num_microbatches=2)
        step_pp = make_lm_train_step(pp_apply, tx, mesh,
                                     state_sharding=sharding)
        pp_losses = []
        for b in batches:
            state2, loss = step_pp(state2,
                                   jax.device_put(b, token_sharding(mesh)))
            pp_losses.append(float(loss))

        np.testing.assert_allclose(pp_losses, ref_losses, atol=1e-4, rtol=1e-4)

    def test_pp_blocks_actually_sharded(self, devices):
        from tpudist.parallel import pp_state_sharding, stack_block_params
        from tpudist.runtime.mesh import AXIS_STAGE

        mesh = self._mesh(devices)
        _, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                       vocab=32, d_model=32, n_layers=4,
                                       n_heads=2, d_ff=64, max_len=32)
        pp = stack_block_params(params, n_stages=4)
        sharded = jax.device_put(pp, pp_state_sharding(mesh, pp))
        qkv = sharded["blocks"]["qkv"]["kernel"]
        assert qkv.sharding.spec == P(AXIS_STAGE)
        # [4 stages, 1 layer, 32, 96] -> one stage's [1, 1, 32, 96] per shard.
        assert qkv.addressable_shards[0].data.shape == (1, 1, 32, 96)
        assert sharded["rest"]["head"]["kernel"].sharding.spec == P()


class TestCompressedGradReduce:
    """grad_reduce_dtype=bf16: the DP gradient all-reduce at half wire
    width (tpudist/train/lm.py).  Numerics must track the f32 path
    closely (master weights stay f32; only the reduce payload narrows);
    the audit asserts the halved payload (tests/test_comm_audit.py)."""

    def _setup(self, devices, **kw):
        from tpudist.runtime.mesh import AXIS_DATA

        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=1, n_heads=2, d_ff=64, max_len=16)
        tx = optax.adam(1e-2)
        state = init_lm_state(params, tx)
        step = make_lm_train_step(module.apply, tx, mesh,
                                  donate_state=False, **kw)
        return mesh, state, step

    def test_tracks_f32_training(self, devices):
        import jax.numpy as jnp

        mesh, state, step32 = self._setup(devices)
        _, state16_init, step16 = self._setup(
            devices, grad_reduce_dtype=jnp.bfloat16)
        shard = token_sharding(mesh)
        rng = np.random.default_rng(0)
        s32, s16 = state, state16_init
        l32 = l16 = None
        first = None
        for i in range(30):
            # Learnable chain pattern (next token = current + 1 mod V) —
            # uniform-random tokens would sit at the ln(V) entropy floor
            # and neither path could show training progress.
            start = rng.integers(0, 32, size=(16, 1))
            toks = jax.device_put(
                ((start + np.arange(16)[None]) % 32).astype(np.int32),
                shard)
            s32, l32 = step32(s32, toks)
            s16, l16 = step16(s16, toks)
            if first is None:
                # Step-0 loss: same params, same batch — bf16 narrowing
                # has not touched anything the loss reads yet.
                np.testing.assert_allclose(float(l32), float(l16),
                                           rtol=1e-5)
                first = float(l32)
        # Both train, and the compressed path lands within a few percent.
        assert float(l32) < first * 0.8
        assert float(l16) < first * 0.8
        assert abs(float(l16) - float(l32)) < 0.05 * float(l32), (
            float(l32), float(l16))

    def test_rejects_incompatible_compositions(self, devices):
        import jax.numpy as jnp

        from tpudist.parallel import fsdp_sharding
        from tpudist.runtime.mesh import AXIS_DATA, AXIS_SEQ

        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=1, n_heads=2, d_ff=64, max_len=16)
        tx = optax.adam(1e-2)
        state = init_lm_state(params, tx)
        sh = fsdp_sharding(mesh, state, min_size=64)
        with pytest.raises(ValueError, match="pure-DP"):
            make_lm_train_step(module.apply, tx, mesh,
                               grad_reduce_dtype=jnp.bfloat16,
                               state_sharding=sh)
        sp_mesh = Mesh(np.asarray(devices).reshape(4, 2),
                       axis_names=(AXIS_DATA, AXIS_SEQ))
        with pytest.raises(ValueError, match="data-only"):
            make_lm_train_step(module.apply, tx, sp_mesh,
                               grad_reduce_dtype=jnp.bfloat16)


class TestBlockWindowGuard:
    """Block.sliding_window only masks the decode cache; the training path
    must be given an attention_fn carrying a MATCHING window tag —
    otherwise the model would silently train full-causal and decode
    windowed (advisor finding, round 2)."""

    def test_untagged_attention_fn_raises(self):
        from tpudist.models.transformer import Block
        from tpudist.ops import attention_reference

        def untagged(q, k, v):
            return attention_reference(q, k, v, causal=True)

        block = Block(d_model=32, n_heads=4, d_ff=64, attention_fn=untagged,
                      sliding_window=8)
        x = jnp.zeros((2, 16, 32), jnp.float32)
        with pytest.raises(ValueError, match="sliding_window"):
            block.init(jax.random.PRNGKey(0), x)

    def test_matching_tag_passes(self):
        from tpudist.models.transformer import (
            Block, make_length_aware_attention)

        block = Block(d_model=32, n_heads=4, d_ff=64,
                      attention_fn=make_length_aware_attention(8),
                      sliding_window=8)
        x = jnp.zeros((2, 16, 32), jnp.float32)
        params = block.init(jax.random.PRNGKey(0), x)
        assert block.apply(params, x).shape == x.shape

    def test_ring_attention_carries_window_tag(self, devices):
        mesh = Mesh(np.asarray(devices[:4]), axis_names=(AXIS_SEQ,))
        ring = make_ring_attention(mesh, causal=True, window=8)
        assert ring.window == 8
        assert make_ring_attention(mesh, causal=True).window is None


class Test1F1BSchedule:
    """Hand-interleaved 1F1B pipeline schedule vs the GPipe autodiff path:
    same math, O(n_stages) residual memory instead of O(num_micro)."""

    CFG4 = dict(vocab=64, d_model=32, n_layers=4, n_heads=4, d_ff=64)

    def _mesh(self, devices):
        return Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_STAGE))

    def _states(self, mesh, tx):
        from tpudist.parallel import pp_state_sharding, stack_block_params

        module, params = create_transformer(jax.random.PRNGKey(0),
                                            seq_len=32, **self.CFG4)
        pp = stack_block_params(params, 4)
        state = init_lm_state(pp, tx)
        shard = pp_state_sharding(mesh, state)
        return module, jax.device_put(state, shard), shard

    @pytest.mark.parametrize("num_micro", [4, 8])
    def test_loss_and_update_parity_with_gpipe(self, devices, num_micro):
        from tpudist.parallel import make_pp_lm_apply, make_pp_lm_train_step

        mesh = self._mesh(devices)
        tx = optax.adam(1e-3)
        module, state, shard = self._states(mesh, tx)
        tokens = jax.device_put(_tokens(batch=2 * num_micro, seq=32),
                                token_sharding(mesh))

        apply_g = make_pp_lm_apply(mesh, module, n_stages=4,
                                   num_microbatches=num_micro)
        step_g = make_lm_train_step(apply_g, tx, mesh, donate_state=False,
                                    state_sharding=shard)
        step_f = make_pp_lm_train_step(
            mesh, module, tx, n_stages=4, num_microbatches=num_micro,
            schedule="1f1b", donate_state=False, state_sharding=shard)

        sg, lg = step_g(state, tokens)
        sf, lf = step_f(state, tokens)
        np.testing.assert_allclose(float(lg), float(lf),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(sg.params),
                        jax.tree.leaves(sf.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_gpipe_schedule_selectable_and_matches(self, devices):
        """schedule='gpipe' through the same entry returns the composed
        make_pp_lm_apply + make_lm_train_step step."""
        from tpudist.parallel import make_pp_lm_train_step

        mesh = self._mesh(devices)
        tx = optax.adam(1e-3)
        module, state, shard = self._states(mesh, tx)
        tokens = jax.device_put(_tokens(batch=8, seq=32),
                                token_sharding(mesh))
        step_g = make_pp_lm_train_step(
            mesh, module, tx, n_stages=4, num_microbatches=4,
            schedule="gpipe", donate_state=False, state_sharding=shard)
        step_f = make_pp_lm_train_step(
            mesh, module, tx, n_stages=4, num_microbatches=4,
            schedule="1f1b", donate_state=False, state_sharding=shard)
        _, lg = step_g(state, tokens)
        _, lf = step_f(state, tokens)
        np.testing.assert_allclose(float(lg), float(lf),
                                   rtol=1e-5, atol=1e-5)

    def test_1f1b_smoke_2stage(self, devices):
        """Default-lane fast twin of the parity test (r3 advisor: every
        feature keeps one smoke in the `not slow` selection): 2 stages,
        tiny model, one step — 1F1B loss matches GPipe."""
        from tpudist.parallel import (
            make_pp_lm_train_step,
            pp_state_sharding,
            stack_block_params,
        )

        mesh = Mesh(np.asarray(devices[:2]).reshape(1, 2),
                    axis_names=(AXIS_DATA, AXIS_STAGE))
        tx = optax.adam(1e-3)
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=8, vocab=16, d_model=16,
            n_layers=2, n_heads=2, d_ff=32, max_len=8)
        state = init_lm_state(stack_block_params(params, 2), tx)
        shard = pp_state_sharding(mesh, state)
        state = jax.device_put(state, shard)
        tokens = jax.device_put(_tokens(batch=2, seq=8, vocab=16),
                                token_sharding(mesh))
        losses = {}
        for schedule in ("gpipe", "1f1b"):
            step = make_pp_lm_train_step(
                mesh, module, tx, n_stages=2, num_microbatches=2,
                schedule=schedule, donate_state=False, state_sharding=shard)
            _, losses[schedule] = step(state, tokens)
        np.testing.assert_allclose(float(losses["gpipe"]),
                                   float(losses["1f1b"]),
                                   rtol=1e-5, atol=1e-5)

    def test_1f1b_trains(self, devices):
        from tpudist.parallel import make_pp_lm_train_step

        mesh = self._mesh(devices)
        tx = optax.adam(1e-3)
        module, state, shard = self._states(mesh, tx)
        step = make_pp_lm_train_step(
            mesh, module, tx, n_stages=4, num_microbatches=4,
            schedule="1f1b", state_sharding=shard)
        rng = np.random.default_rng(0)
        shard_tok = token_sharding(mesh)
        first = None
        for _ in range(60):
            start = rng.integers(0, 64, size=(8, 1))
            toks = jax.device_put(
                jnp.asarray((start + np.arange(32)[None]) % 64, jnp.int32),
                shard_tok)
            state, loss = step(state, toks)
            if first is None:
                first = float(loss)
        assert float(loss) < first * 0.7, (first, float(loss))

    def test_bad_schedule_and_moe_raise(self, devices):
        from tpudist.parallel import make_pp_lm_train_step

        mesh = self._mesh(devices)
        tx = optax.adam(1e-3)
        module, _, _ = self._states(mesh, tx)
        with pytest.raises(ValueError, match="gpipe|1f1b"):
            make_pp_lm_train_step(mesh, module, tx, n_stages=4,
                                  schedule="zb-h1")
        moe_mod = module.clone(n_experts=2)
        with pytest.raises(ValueError, match="MoE"):
            make_pp_lm_train_step(mesh, moe_mod, tx, n_stages=4,
                                  schedule="1f1b")

    def test_indivisible_batch_raises(self, devices):
        from tpudist.parallel import make_pp_lm_train_step

        mesh = self._mesh(devices)
        tx = optax.adam(1e-3)
        module, state, shard = self._states(mesh, tx)
        step = make_pp_lm_train_step(
            mesh, module, tx, n_stages=4, num_microbatches=3,
            schedule="1f1b", donate_state=False, state_sharding=shard)
        tokens = jax.device_put(_tokens(batch=8, seq=32),
                                token_sharding(mesh))
        with pytest.raises(ValueError, match="microbatches"):
            step(state, tokens)


class TestRemat:
    """TransformerLM(remat=True): jax.checkpoint per block — identical
    numerics, checkpoint equations actually present in the backward."""

    def test_numerics_identical_and_checkpoint_present(self, devices):
        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        cfg = dict(vocab=64, d_model=64, n_layers=2, n_heads=4, d_ff=128)
        toks = jax.device_put(_tokens(batch=8, seq=64, vocab=64),
                              token_sharding(mesh))
        tx = optax.adam(1e-3)
        results = {}
        for remat in (False, True):
            module, params = create_transformer(
                jax.random.PRNGKey(0), seq_len=64, remat=remat, **cfg)
            step = make_lm_train_step(module.apply, tx, mesh,
                                      donate_state=False)
            results[remat] = step(init_lm_state(params, tx), toks)

            def loss_of(p, module=module):
                return lm_loss(module.apply(p, toks), toks)

            jaxpr = str(jax.make_jaxpr(jax.grad(loss_of))(params))
            assert ("remat" in jaxpr or "checkpoint" in jaxpr) == remat
        (s0, l0), (s1, l1) = results[False], results[True]
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(s0.params),
                        jax.tree.leaves(s1.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_decode_ignores_remat(self):
        """The KV-cache decode path must not wrap blocks (mutable cache
        state inside jax.checkpoint is unsupported); remat models decode
        exactly like plain ones."""
        from tpudist.models import decode_logits

        cfg = dict(vocab=64, d_model=64, n_layers=2, n_heads=4, d_ff=128)
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, remat=True, **cfg)
        toks = _tokens(batch=2, seq=16, vocab=64)
        np.testing.assert_allclose(
            np.asarray(decode_logits(module, params, toks)),
            np.asarray(jax.jit(module.apply)(params, toks)),
            atol=1e-4, rtol=1e-4)


class TestRematPolicies:
    """remat_policy is a memory/FLOPs dial, never a numerics change."""

    def test_policies_numerically_identical(self):
        from tpudist.models import create_transformer

        cfg = dict(vocab=32, d_model=32, n_layers=2, n_heads=2, d_ff=64,
                   max_len=32)
        toks = _tokens(batch=2, seq=32)
        mod0, params = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                          **cfg)

        def grad_of(mod):
            return jax.jit(jax.grad(
                lambda p: float(0) + lm_loss(mod.apply(p, toks), toks)))(params)

        base = grad_of(mod0)
        for policy in ("nothing", "dots", "dots_no_batch"):
            g = grad_of(mod0.clone(remat=True, remat_policy=policy))
            for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(g)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)

    def test_unknown_policy_rejected(self):
        from tpudist.models import create_transformer

        mod, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=1, n_heads=2, d_ff=64, max_len=16)
        bad = mod.clone(remat=True, remat_policy="everything")
        with pytest.raises(ValueError, match="remat_policy"):
            bad.apply(params, _tokens(batch=1, seq=16))


# a row that lets a test's 128 positions reach the routes a chip's 2,048 do
_SHORT_TILES = Tiles(min_seq=128, block_q=64, block_k=64, block_k_long=64,
                     long_seq=8192, sub=32)


class _AsTpu:
    """The first CPU device, answering ``device_kind`` as a TPU would:
    what ``make_length_aware_attention`` asks before it picks a route."""

    platform = "tpu"
    device_kind = "TPU of the test"

    def __init__(self, device):
        self._device = device

    def __getattr__(self, name):
        return getattr(self._device, name)


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the attention closure the way a v5e would — from the test, not
    through an option of the program: the device kind reads as a TPU's
    with a row of its own in the table (the flash kernels from seq 128 with
    64-wide tiles), and the kernels the closure then picks run in the Pallas
    interpreter."""
    from tpudist.ops import attention

    def interpreted(kernel):
        def run(*args):
            return kernel(*args[:6], True, *args[7:])   # args[6]: interpret
        return run

    real = jax.devices()
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: [_AsTpu(real[0])] + real[1:])
    monkeypatch.setitem(attention.TILES, _AsTpu.device_kind, _SHORT_TILES)
    monkeypatch.setattr(attention, "flash_attention_packed",
                        interpreted(attention.flash_attention_packed))
    monkeypatch.setattr(attention, "flash_attention",
                        interpreted(attention.flash_attention))


@pytest.fixture
def layouts(tmp_path):
    """The ``attn_layout`` events recorded while the test runs, as
    ``(layout, reason)`` pairs."""
    from tpudist import telemetry
    from tpudist.telemetry import names

    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        yield lambda: [(r["layout"], r.get("reason")) for r in session.ring
                       if r["name"] == names.ATTN_LAYOUT]
    finally:
        telemetry.finish(write_report=False)


def _untagged(attention_fn):
    """The same attention as an injected ``attention_fn`` with no packed
    route: ``Block`` keeps its head-major path for it."""
    def fn(q, k, v):
        return attention_fn(q, k, v)
    fn.supports_gqa = True
    return fn


class TestPackedAttentionRoute:
    """``Block`` hands the default attention the fused projection's own
    ``[b, s, 3·d]`` output; the closure takes the packed flash kernels where
    it can see that they fit (TPU, long enough, ``dh % 128 == 0``) and the
    head-major route everywhere else, and says which in ``attn_layout``."""

    def _loss_and_grads(self, seq, attention_fn=None, **overrides):
        from tpudist.ops import default_attention

        cfg = dict(vocab=32, d_model=256, n_layers=2, n_heads=2, d_ff=256,
                   max_len=seq) | overrides
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=seq,
            attention_fn=attention_fn and _untagged(default_attention),
            **cfg)
        tokens = _tokens(batch=2, seq=seq)
        return jax.jit(jax.value_and_grad(
            lambda p: lm_loss(module.apply(p, tokens), tokens)))(params)

    @pytest.mark.parametrize("kv_heads", [None, 1], ids=["mha", "gqa"])
    def test_packed_route_equals_head_major_at_dh_128(self, as_tpu, layouts,
                                                      kv_heads):
        loss_p, grads_p = self._loss_and_grads(128, n_kv_heads=kv_heads)
        # init (always the default attention), then the differentiated
        # trace: one event a layer each
        assert layouts() == [("packed", None)] * 4
        loss_h, grads_h = self._loss_and_grads(128, attention_fn="untagged",
                                               n_kv_heads=kv_heads)
        assert layouts()[4:] == ([("packed", None)] * 2
                                 + [("head_major", "custom_fn")] * 2)
        np.testing.assert_allclose(loss_p, loss_h, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(grads_p), jax.tree.leaves(grads_h)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("reason", ["dh", "seq", "platform"])
    def test_other_shapes_and_platforms_stay_head_major(
            self, reason, request, layouts, monkeypatch):
        if reason == "platform":     # long enough, tiles fit, but a CPU
            from tpudist.ops import attention

            monkeypatch.setitem(attention.TILES,
                                jax.devices()[0].device_kind, _SHORT_TILES)
        else:
            request.getfixturevalue("as_tpu")
        seq = 64 if reason == "seq" else 128
        heads = 4 if reason == "dh" else 2            # dh 64 / dh 128
        loss, _ = self._loss_and_grads(seq, n_heads=heads)
        assert np.isfinite(loss)
        assert layouts() == [("head_major", reason)] * 4

    @pytest.mark.parametrize("kv_heads", [None, 2], ids=["mha", "gqa"])
    def test_rope_on_the_packed_view_equals_rope_head_major(self, kv_heads):
        """``Block`` rotates q's and k's heads on the ``[b, s, h, dh]`` view
        of the packed tensor; the head-major arm rotates ``[b, h, s, dh]``:
        the same angles on the same numbers."""
        from tpudist.ops import default_attention

        cfg = dict(CFG, rope=True, n_kv_heads=kv_heads)
        tokens = _tokens()
        packed_mod, params = create_transformer(jax.random.PRNGKey(0),
                                                seq_len=64, **cfg)
        head_mod, _ = create_transformer(
            jax.random.PRNGKey(0), seq_len=64,
            attention_fn=_untagged(default_attention), **cfg)
        np.testing.assert_array_equal(
            jax.jit(packed_mod.apply)(params, tokens),
            jax.jit(head_mod.apply)(params, tokens))
