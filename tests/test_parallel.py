"""Parallelism-strategy tests on the 8-device virtual mesh: every strategy
is checked numerically against its single-device dense reference, forward
AND backward (the construct must train, not just infer)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.ops import attention_reference
from tpudist.parallel import (
    MoEStats,
    init_mlp_params,
    make_moe,
    make_pipeline,
    make_ring_attention,
    make_tp_mlp,
    mlp_param_sharding,
)
from tpudist.runtime.mesh import AXIS_DATA, AXIS_MODEL, AXIS_SEQ, AXIS_STAGE


@pytest.fixture()
def seq_mesh(devices):
    return Mesh(np.asarray(devices), axis_names=(AXIS_SEQ,))


@pytest.fixture()
def model_mesh(devices):
    return Mesh(np.asarray(devices), axis_names=(AXIS_MODEL,))


@pytest.fixture()
def stage_mesh(devices):
    return Mesh(np.asarray(devices[:4]), axis_names=(AXIS_STAGE,))


class TestRingAttention:
    def _qkv(self, seq=64, batch=2, heads=4, d=16, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        shape = (batch, heads, seq, d)
        return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, seq_mesh, causal):
        q, k, v = self._qkv()
        ring = make_ring_attention(seq_mesh, causal=causal)
        out = ring(q, k, v)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_reference(self, seq_mesh):
        """The ring formulation must train: grads through ppermute + online
        softmax equal the dense-attention grads."""
        q, k, v = self._qkv(seq=32)
        ring = make_ring_attention(seq_mesh, causal=True)

        def loss_ring(q, k, v):
            return jnp.sum(ring(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)

    def test_sharded_inputs_stay_sharded(self, seq_mesh):
        """Device-placement check: with inputs laid out on the seq axis the
        output is seq-sharded too — no implicit gather of the long axis."""
        q, k, v = self._qkv()
        spec = NamedSharding(seq_mesh, P(None, None, AXIS_SEQ, None))
        q, k, v = (jax.device_put(x, spec) for x in (q, k, v))
        out = make_ring_attention(seq_mesh)(q, k, v)
        assert out.sharding.spec == P(None, None, AXIS_SEQ, None)

    @pytest.mark.parametrize("causal", [False, True])
    def test_inner_block_matches_reference(self, seq_mesh, causal):
        """Sub-blocked shard consumption (O(shard·inner) memory) is
        numerically identical, forward and backward."""
        q, k, v = self._qkv(seq=64)
        ring = make_ring_attention(seq_mesh, causal=causal, inner_block=4)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        g_ring = jax.grad(lambda q: jnp.sum(ring(q, k, v) ** 2))(q)
        g_ref = jax.grad(
            lambda q: jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)
        )(q)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                                   atol=5e-5, rtol=5e-5)

    def test_seq_not_divisible_raises(self, seq_mesh):
        q, k, v = self._qkv(seq=60)  # 60 % 8 != 0
        with pytest.raises(Exception):
            make_ring_attention(seq_mesh)(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_kernel_matches_reference(self, seq_mesh, causal):
        """The Pallas per-hop decomposition (flash_attention_with_lse +
        logsumexp merge, dead hops skipped via lax.cond) is numerically the
        same ring."""
        q, k, v = self._qkv(seq=64)
        ring = make_ring_attention(seq_mesh, causal=causal, kernel="flash",
                                   interpret=True)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_kernel_gradients_match_reference(self, seq_mesh, causal):
        """Grads flow through the merge AND through the lse cotangent path
        (the merge weights depend on each hop's lse), so this exercises the
        kernel VJP's delta−dL folding."""
        q, k, v = self._qkv(seq=32)
        ring = make_ring_attention(seq_mesh, causal=causal, kernel="flash",
                                   interpret=True)

        g_ring = jax.grad(
            lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(
                attention_reference(q, k, v, causal=causal) ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)

    def test_flash_kernel_bf16_partials_stay_f32(self, seq_mesh):
        """bf16 inputs: per-hop partials are emitted f32 (out_f32) so merge
        precision matches the xla path's f32 (m, l, o) carry — both rings
        must land within bf16 tolerance of the f32 dense reference."""
        q, k, v = (a.astype(jnp.bfloat16) for a in self._qkv(seq=64))
        ref = attention_reference(
            *(a.astype(jnp.float32) for a in (q, k, v)), causal=True
        )
        for kern in ("flash", "xla"):
            ring = make_ring_attention(seq_mesh, causal=True, kernel=kern,
                                       interpret=True)
            out = ring(q, k, v)
            assert out.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref),
                atol=0.03, rtol=0.03,
            )

    def test_flash_kernel_gqa_native(self, seq_mesh):
        """The flash ring consumes grouped-query K/V without repeating
        (advertised via supports_gqa): matches the repeated-KV dense
        reference, and K/V rotate the ring at kv-head width."""
        q, _, _ = self._qkv(seq=64, heads=4)
        _, k, v = self._qkv(seq=64, heads=2, seed=9)
        ring = make_ring_attention(seq_mesh, causal=True, kernel="flash",
                                   interpret=True)
        assert getattr(ring, "supports_gqa", False)
        out = ring(q, k, v)
        ref = attention_reference(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                                  causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("kernel", ["flash", "xla"])
    @pytest.mark.parametrize("window", [5, 12, 40])
    def test_sliding_window_ring(self, seq_mesh, kernel, window):
        """Windowed ring attention (both bodies) vs the dense banded
        reference: windows inside one shard (5 < 8), crossing a shard
        boundary (12), and spanning several shards (40).  The flash body
        expresses each off-diagonal hop as a statically-shifted band and
        skips hops beyond the window entirely."""
        q, k, v = self._qkv(seq=64)  # 8 devices -> 8-token shards
        ring = make_ring_attention(seq_mesh, causal=True, kernel=kernel,
                                   interpret=(kernel == "flash"),
                                   window=window)
        ref = attention_reference(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_sliding_window_ring_gradients(self, seq_mesh):
        q, k, v = self._qkv(seq=32)  # 4-token shards
        ring = make_ring_attention(seq_mesh, causal=True, kernel="flash",
                                   interpret=True, window=6)
        g_ring = jax.grad(
            lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(
                attention_reference(q, k, v, causal=True, window=6) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5)

    def test_sliding_window_gqa_ring_composed(self, seq_mesh):
        """All three kernel capabilities at once — grouped K/V, sliding
        window, ring decomposition — against the dense banded repeated-KV
        reference, forward and backward."""
        q, _, _ = self._qkv(seq=64, heads=4)
        _, k, v = self._qkv(seq=64, heads=2, seed=11)
        ring = make_ring_attention(seq_mesh, causal=True, kernel="flash",
                                   interpret=True, window=20)

        def rep(t):
            return jnp.repeat(t, 2, axis=1)

        ref_fn = lambda q, k, v: attention_reference(  # noqa: E731
            q, rep(k), rep(v), causal=True, window=20)
        np.testing.assert_allclose(
            np.asarray(ring(q, k, v)), np.asarray(ref_fn(q, k, v)),
            atol=2e-5, rtol=2e-5)
        g_ring = jax.grad(
            lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(ref_fn(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_flash_kernel_unfit_shard_falls_back(self, seq_mesh):
        """Shards that don't fit the kernel block contract (here 12 tokens
        per device with block 8) trace through the xla body instead of
        raising."""
        q, k, v = self._qkv(seq=96)  # 96/8 devices = 12-token shards
        ring = make_ring_attention(seq_mesh, causal=True, kernel="flash",
                                   block_q=8, block_k=8, interpret=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestTensorParallel:
    def _reference(self, params, x):
        h = jax.nn.gelu(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    def test_matches_dense(self, model_mesh):
        params = init_mlp_params(jax.random.PRNGKey(0), d_model=32, d_hidden=128)
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
        tp = make_tp_mlp(model_mesh)
        np.testing.assert_allclose(
            np.asarray(tp(params, x)), np.asarray(self._reference(params, x)),
            atol=1e-5, rtol=1e-5,
        )

    def test_gradients_match_dense(self, model_mesh):
        params = init_mlp_params(jax.random.PRNGKey(0), d_model=16, d_hidden=64)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
        tp = make_tp_mlp(model_mesh)
        g_tp = jax.grad(lambda p: jnp.sum(tp(p, x) ** 2))(params)
        g_ref = jax.grad(lambda p: jnp.sum(self._reference(p, x) ** 2))(params)
        for k in params:
            np.testing.assert_allclose(np.asarray(g_tp[k]), np.asarray(g_ref[k]),
                                       atol=1e-4, rtol=1e-4)

    def test_weights_actually_sharded(self, model_mesh):
        """w1 columns / w2 rows live on distinct devices (the "verify stages
        actually place on distinct chips" concern, SURVEY.md §7 hard part e)."""
        params = init_mlp_params(jax.random.PRNGKey(0), d_model=32, d_hidden=128)
        sharded = jax.device_put(params, mlp_param_sharding(model_mesh, params))
        assert sharded["w1"].sharding.spec == P(None, AXIS_MODEL)
        assert sharded["w2"].sharding.spec == P(AXIS_MODEL, None)
        # 128 hidden / 8 devices = 16-column shards per device.
        shard = sharded["w1"].addressable_shards[0]
        assert shard.data.shape == (32, 16)


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


class TestPipeline:
    def _stacked_params(self, n_stages, d, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), n_stages)
        return {
            "w": jnp.stack([jax.random.normal(k, (d, d)) / np.sqrt(d) for k in ks]),
            "b": jnp.zeros((n_stages, d)),
        }

    def _reference(self, stacked, x):
        for i in range(stacked["w"].shape[0]):
            x = _stage_fn({"w": stacked["w"][i], "b": stacked["b"][i]}, x)
        return x

    @pytest.mark.parametrize("num_micro", [4, 8])
    def test_matches_sequential(self, stage_mesh, num_micro):
        d = 16
        stacked = self._stacked_params(4, d)
        x = jax.random.normal(jax.random.PRNGKey(1), (32, d))
        pipe = make_pipeline(stage_mesh, _stage_fn, num_microbatches=num_micro)
        np.testing.assert_allclose(
            np.asarray(pipe(stacked, x)), np.asarray(self._reference(stacked, x)),
            atol=1e-5, rtol=1e-5,
        )

    @pytest.mark.parametrize("remat", [False, True])
    def test_gradients_match_sequential(self, stage_mesh, remat):
        """remat=True recomputes stage forwards in the backward — same
        gradients, O(boundaries) activation memory."""
        d = 8
        stacked = self._stacked_params(4, d)
        x = jax.random.normal(jax.random.PRNGKey(1), (16, d))
        pipe = make_pipeline(stage_mesh, _stage_fn, num_microbatches=4,
                             remat=remat)
        g_pipe = jax.grad(lambda p: jnp.sum(pipe(p, x) ** 2))(stacked)
        g_ref = jax.grad(lambda p: jnp.sum(self._reference(p, x) ** 2))(stacked)
        for k in stacked:
            np.testing.assert_allclose(np.asarray(g_pipe[k]), np.asarray(g_ref[k]),
                                       atol=1e-4, rtol=1e-4)

    def test_head_with_collective_raises_at_trace_time(self):
        """A user loss_fn containing a collective deadlocks the mesh at
        runtime (the head runs under a per-device-varying lax.cond), so
        head_grad_branches must refuse it at trace time with a clear
        error — not hang (ADVICE r4 #1)."""
        from tpudist.parallel.pipeline import head_grad_branches

        def bad_loss(out_p, a, aux):
            return jax.lax.pmean(jnp.sum(a @ out_p["w"]), "stage")

        head, _ = head_grad_branches(bad_loss)
        args = ({"w": jnp.ones((4, 4))}, jnp.ones((2, 4)), jnp.zeros((2,)))

        def run(a):
            return head((a[0], a[1], a[2]))

        mesh = Mesh(np.array(jax.devices()[:4]), ("stage",))
        with pytest.raises(ValueError, match="collective"):
            jax.eval_shape(
                jax.shard_map(run, mesh=mesh,
                                 in_specs=P(), out_specs=P(), check_vma=False),
                args)

    def test_head_collective_free_loss_passes(self):
        """The trace-time guard must not reject a legal (collective-free)
        loss_fn."""
        from tpudist.parallel.pipeline import head_grad_branches

        def ok_loss(out_p, a, aux):
            return jnp.sum((a @ out_p["w"]) ** 2)

        head, head_zeros = head_grad_branches(ok_loss)
        args = ({"w": jnp.ones((4, 4))}, jnp.ones((2, 4)), jnp.zeros((2,)))
        loss_and_grads = head(args)
        z = head_zeros(args)
        assert jax.tree.structure(loss_and_grads) == jax.tree.structure(z)


def _expert_fn(params, tokens):
    return jax.nn.relu(tokens @ params["w"]) @ params["wo"]


class TestMoE:
    def _params(self, d=16, hidden=32, n_experts=8, seed=0):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        return {
            "router": jax.random.normal(k1, (d, n_experts)),
            "experts": {
                "w": jax.random.normal(k2, (n_experts, d, hidden)) / np.sqrt(d),
                "wo": jax.random.normal(k3, (n_experts, hidden, d)) / np.sqrt(hidden),
            },
        }

    def _reference(self, params, x, capacity):
        """Dense routing with the same capacity-drop semantics."""
        probs = jax.nn.softmax(x @ params["router"], axis=-1)
        idx = jnp.argmax(probs, axis=-1)
        gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
        out = jnp.zeros_like(x)
        counts = {}
        for t in range(x.shape[0]):
            e = int(idx[t])
            counts[e] = counts.get(e, 0)
            if counts[e] < capacity:
                ex = jax.tree.map(lambda a, e=e: a[e], params["experts"])
                out = out.at[t].set(gate[t] * _expert_fn(ex, x[t][None])[0])
            counts[e] += 1
        return out

    def test_matches_dense_routing(self, model_mesh):
        d, tokens = 16, 64
        params = self._params(d=d)
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, d))
        capacity = int(1.25 * tokens / 8 + 0.5)
        moe = make_moe(model_mesh, _expert_fn)
        out, stats = moe(params, x)
        ref = self._reference(params, x, capacity)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        assert isinstance(stats, MoEStats)
        assert 0.0 <= float(stats.dropped_fraction) <= 1.0
        np.testing.assert_allclose(float(jnp.sum(stats.expert_load)), 1.0,
                                   atol=1e-6)

    def test_trains(self, model_mesh):
        """Router + experts receive nonzero gradients through the dispatch."""
        params = self._params()
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        moe = make_moe(model_mesh, _expert_fn)
        g = jax.grad(lambda p: jnp.sum(moe(p, x)[0] ** 2))(params)
        assert float(jnp.abs(g["router"]).sum()) > 0
        assert float(jnp.abs(g["experts"]["w"]).sum()) > 0

    def test_top2_matches_dense_topk(self, model_mesh):
        """k=2 at ample capacity == dense Mixtral-style computation: top-2
        experts per token, gates renormalized over the pair."""
        d, tokens = 16, 64
        params = self._params(d=d)
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, d))
        moe = make_moe(model_mesh, _expert_fn, k=2, capacity_factor=8.0)
        out, stats = moe(params, x)

        probs = jax.nn.softmax(x @ params["router"], axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, 2)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        ref = jnp.zeros_like(x)
        for c in range(2):
            for t in range(tokens):
                ex = jax.tree.map(lambda a: a[int(idx[t, c])],
                                  params["experts"])
                ref = ref.at[t].add(
                    gate_vals[t, c] * _expert_fn(ex, x[t][None])[0])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        # every assignment placed at this capacity
        assert float(stats.dropped_fraction) == 0.0

    def test_balance_loss_measures_skew(self, model_mesh):
        """Uniform routing → balance ≈ 1; collapsed routing → ≈ n_experts;
        and the loss is differentiable w.r.t. the router."""
        d, tokens, n = 16, 512, 8
        params = self._params(d=d, n_experts=n)
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, d))
        moe = make_moe(model_mesh, _expert_fn)

        params_uniform = dict(params, router=jnp.zeros((d, n)))
        _, s_uniform = moe(params_uniform, x)
        # zero logits: P_e exactly uniform, f_e whatever argmax ties give —
        # balance = n * sum(f * 1/n) = 1 exactly
        np.testing.assert_allclose(float(s_uniform.balance_loss), 1.0,
                                   atol=1e-5)

        # collapsed routing (all tokens to expert 0) at the dispatch level —
        # the router is linear in x, so synthetic logits express it directly
        from tpudist.parallel.moe import _topk_dispatch

        logits = jnp.zeros((tokens, n)).at[:, 0].set(30.0)
        _, _, s_skew = _topk_dispatch(logits, n, capacity=tokens, k=1)
        np.testing.assert_allclose(float(s_skew.balance_loss), n, rtol=1e-3)

        g = jax.grad(lambda p: moe(p, x)[1].balance_loss)(params)
        assert float(jnp.abs(g["router"]).sum()) > 0

    def test_balance_weight_trains_toward_uniform(self, model_mesh):
        """Optimizing balance_loss alone drives the router toward uniform
        dispatch (the mechanism the LM-loss weighting relies on)."""
        import optax

        d = 16
        params = self._params(d=d)
        # start skewed
        params["router"] = params["router"] * 0.1 + jnp.eye(d, 8) * 5.0
        x = jax.random.normal(jax.random.PRNGKey(1), (256, d))
        moe = make_moe(model_mesh, _expert_fn)
        tx = optax.adam(1e-1)
        opt = tx.init(params)
        first = None
        for _ in range(20):
            loss, g = jax.value_and_grad(
                lambda p: moe(p, x)[1].balance_loss)(params)
            upd, opt = tx.update(g, opt, params)
            params = optax.apply_updates(params, upd)
            if first is None:
                first = float(loss)
        assert float(loss) < first, (first, float(loss))


class TestComposedMesh:
    def test_dp_times_sp_attention(self, devices):
        """2×4 (data × seq) mesh: batch and sequence sharded simultaneously."""
        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_SEQ))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (4, 2, 32, 8)) for kk in ks)
        ring = make_ring_attention(mesh, causal=True, batch_axis=AXIS_DATA)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestFSDP:
    """ZeRO-3-style fully-sharded state: same math as replicated DP, 1/n
    state memory per chip."""

    def _setup(self, mesh):
        import optax

        from tpudist.models import create_transformer
        from tpudist.train import init_lm_state, make_lm_train_step, token_sharding

        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32,
            vocab=32, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_len=32,
        )
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 32, size=(8, 32)), jnp.int32)
        tokens = jax.device_put(tokens, token_sharding(mesh))
        return module, tx, state, tokens, make_lm_train_step

    def test_loss_matches_replicated(self, devices):
        from tpudist.parallel import fsdp_sharding

        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        module, tx, state, tokens, make_step = self._setup(mesh)

        repl_step = make_step(module.apply, tx, mesh, donate_state=False)
        fs = fsdp_sharding(mesh, state)
        fstate = jax.device_put(state, fs)
        fsdp_step = make_step(module.apply, tx, mesh, donate_state=False,
                              state_sharding=fs)
        for _ in range(3):
            state, loss_r = repl_step(state, tokens)
            fstate, loss_f = fsdp_step(fstate, tokens)
            np.testing.assert_allclose(float(loss_r), float(loss_f),
                                       rtol=2e-6, atol=2e-6)

    def test_state_actually_sharded(self, devices):
        from tpudist.parallel import fsdp_sharding, state_bytes_per_device

        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        module, tx, state, _, _ = self._setup(mesh)
        fs = fsdp_sharding(mesh, state)
        fstate = jax.device_put(state, fs)

        # A block kernel [64, 192] shards 192 -> 24 per device; its Adam
        # moments shard identically (they mirror the param tree).
        k = fstate.params["params"]["block_0"]["qkv"]["kernel"]
        assert k.sharding.spec != P()
        assert k.addressable_shards[0].data.size == k.size // 8
        mu = fstate.opt_state[0].mu["params"]["block_0"]["qkv"]["kernel"]
        assert mu.addressable_shards[0].data.size == mu.size // 8

        # Analytic accounting: near-1/8 of the replicated footprint (small
        # leaves replicate).
        total = sum(l.size * l.dtype.itemsize
                    for l in jax.tree.leaves(state))
        per_dev = state_bytes_per_device(state, fs)
        assert per_dev < total * 0.25, (per_dev, total)

    def test_composes_with_tp(self, devices):
        """merge_shardings: TP specs where they exist, FSDP elsewhere —
        trains on a (data, model) mesh."""
        import optax

        from tpudist.models import create_transformer
        from tpudist.models.transformer import transformer_tp_sharding
        from tpudist.parallel import fsdp_sharding, merge_shardings
        from tpudist.train import init_lm_state, make_lm_train_step, token_sharding

        mesh = Mesh(np.asarray(devices).reshape(4, 2),
                    axis_names=(AXIS_DATA, AXIS_MODEL))
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32,
            vocab=32, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_len=32,
        )
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        merged = merge_shardings(transformer_tp_sharding(mesh, state),
                                 fsdp_sharding(mesh, state))
        mstate = jax.device_put(state, merged)
        step = make_lm_train_step(module.apply, tx, mesh,
                                  state_sharding=merged)
        tokens = jax.device_put(
            jnp.asarray(np.random.default_rng(0).integers(0, 32, size=(8, 32)),
                        jnp.int32),
            token_sharding(mesh))
        first = None
        for _ in range(10):
            mstate, loss = step(mstate, tokens)
            if first is None:
                first = float(loss)
        assert float(loss) < first, (first, float(loss))
        # embeddings (TP-replicated) got the FSDP treatment
        emb = mstate.params["params"]["tok_embed"]["embedding"]
        assert emb.sharding.spec != P()


class TestZeRO1:
    """Weight-update sharding (arXiv:2004.13336 / ZeRO-1): params stay
    replicated, optimizer state shards over the data axis — same math as
    replicated DP at ~1/n optimizer memory."""

    def _setup(self, mesh):
        import optax

        from tpudist.models import create_transformer
        from tpudist.train import init_lm_state, make_lm_train_step, token_sharding

        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32,
            vocab=32, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_len=32,
        )
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 32, size=(8, 32)), jnp.int32)
        tokens = jax.device_put(tokens, token_sharding(mesh))
        return module, tx, state, tokens, make_lm_train_step

    def test_loss_matches_replicated(self, devices):
        from tpudist.parallel import zero1_sharding

        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        module, tx, state, tokens, make_step = self._setup(mesh)

        repl_step = make_step(module.apply, tx, mesh, donate_state=False)
        zs = zero1_sharding(mesh, state)
        zstate = jax.device_put(state, zs)
        z_step = make_step(module.apply, tx, mesh, donate_state=False,
                           state_sharding=zs)
        for _ in range(3):
            state, loss_r = repl_step(state, tokens)
            zstate, loss_z = z_step(zstate, tokens)
            np.testing.assert_allclose(float(loss_r), float(loss_z),
                                       rtol=2e-6, atol=2e-6)

    def test_params_replicated_opt_sharded(self, devices):
        from jax.sharding import PartitionSpec as P

        from tpudist.parallel import state_bytes_per_device, zero1_sharding

        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
        module, tx, state, _, _ = self._setup(mesh)
        zs = zero1_sharding(mesh, state)
        zstate = jax.device_put(state, zs)

        k = zstate.params["params"]["block_0"]["qkv"]["kernel"]
        assert all(a is None for a in tuple(k.sharding.spec)), k.sharding
        mu = zstate.opt_state[0].mu["params"]["block_0"]["qkv"]["kernel"]
        assert mu.sharding.spec != P()
        assert mu.addressable_shards[0].data.size == mu.size // 8

        # Memory ladder: zero1 strictly between replicated DP and fsdp.
        from tpudist.parallel import fsdp_sharding

        total = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))
        z_bytes = state_bytes_per_device(state, zs)
        f_bytes = state_bytes_per_device(state, fsdp_sharding(mesh, state))
        assert f_bytes < z_bytes < total, (f_bytes, z_bytes, total)
        # Adam state is 2/3 of the f32 total; sharding it 8x should land
        # well under half the replicated footprint.
        assert z_bytes < total * 0.5


class TestZigzagRing:
    """Causal-balanced zigzag ring layout: every (device, hop) costs the
    same two half-chunk blocks, vs the contiguous ring's (n+1)/2n
    aggregate efficiency."""

    def _qkv(self, S, B=2, H=2, D=16):
        key = jax.random.PRNGKey(0)
        return tuple(jax.random.normal(k, (B, H, S, D))
                     for k in jax.random.split(key, 3))

    def test_indices_roundtrip_and_layout(self):
        from tpudist.parallel import zigzag_indices

        pi = np.asarray(zigzag_indices(32, 4))
        # a permutation
        assert sorted(pi.tolist()) == list(range(32))
        # device 0's shard = half-chunks 0 and 7; device 3's = 3 and 4
        assert pi[:8].tolist() == list(range(0, 4)) + list(range(28, 32))
        assert pi[24:].tolist() == list(range(12, 16)) + list(range(16, 20))
        with pytest.raises(ValueError, match="half-chunks"):
            zigzag_indices(12, 8)

    @pytest.mark.parametrize("n,S", [(4, 64), (8, 64), (2, 32)])
    def test_value_and_grad_parity_vs_dense(self, devices, n, S):
        from tpudist.parallel import (make_zigzag_ring_attention,
                                      zigzag_indices)
        from tpudist.runtime.mesh import AXIS_SEQ

        mesh = Mesh(np.asarray(devices[:n]), (AXIS_SEQ,))
        q, k, v = self._qkv(S)
        pi = zigzag_indices(S, n)
        inv = jnp.argsort(pi)
        ring = make_zigzag_ring_attention(mesh)

        out = ring(q[..., pi, :], k[..., pi, :], v[..., pi, :])
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out[..., inv, :]),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)

        def loss_z(q, k, v):
            return (ring(q[..., pi, :], k[..., pi, :], v[..., pi, :])
                    ** 2).sum()

        def loss_r(q, k, v):
            return (attention_reference(q, k, v, causal=True)[..., pi, :]
                    ** 2).sum()

        gz = jax.grad(loss_z, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gz, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_live_work_is_balanced(self):
        """The schedule math: live half-chunk-block count per (device,
        hop) is constant across devices at every hop — the property the
        contiguous causal ring lacks."""
        for n in (2, 4, 8):
            for t in range(n):
                per_dev = []
                for i in range(n):
                    j = (i - t) % n
                    live = 1  # q_hi x k_lo(j): always fully live
                    if j <= i:
                        live += 1  # q_lo x k_lo
                    if j >= i:
                        live += 1  # q_hi x k_hi
                    per_dev.append(live)
                assert len(set(per_dev)) == 1, (n, t, per_dev)
                # hops beyond the diagonal cost exactly 2 blocks
                if t:
                    assert per_dev[0] == 2
        # contiguous ring, same accounting: hop t has n - t live devices
        # (aggregate (n+1)/2n) — recorded here as the contrast.
        n = 8
        contiguous_live = [sum(1 for i in range(n) if (i - t) % n <= i)
                           for t in range(n)]
        assert contiguous_live == [n - t for t in range(n)]

    def test_odd_shard_rejected(self, devices):
        from tpudist.parallel import ring_attention_shard_zigzag
        from tpudist.runtime.mesh import AXIS_SEQ

        mesh = Mesh(np.asarray(devices[:4]), (AXIS_SEQ,))
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 12, 8))
        with pytest.raises(ValueError, match="even"):
            jax.shard_map(
                lambda a, b, c: ring_attention_shard_zigzag(a, b, c),
                mesh=mesh,
                in_specs=(P(None, None, AXIS_SEQ, None),) * 3,
                out_specs=P(None, None, AXIS_SEQ, None),
                check_vma=True,
            )(q, q, q)

    def test_lm_trains_end_to_end_via_standard_step(self, devices):
        """Zigzag is first-class: permuted tokens + explicit positions +
        make_zigzag_lm_loss through the UNMODIFIED make_lm_train_step
        produce the same loss and parameter updates as natural-order
        training (per-token sublayers are order-free; only attention and
        the loss are layout-aware)."""
        import optax

        from tpudist.models import create_transformer
        from tpudist.parallel import (make_zigzag_lm_loss,
                                      make_zigzag_ring_attention,
                                      zigzag_indices)
        from tpudist.runtime.mesh import AXIS_DATA, AXIS_SEQ
        from tpudist.train import (init_lm_state, make_lm_train_step,
                                   token_sharding)

        n_sp, S = 4, 64
        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    (AXIS_DATA, AXIS_SEQ))
        pi = np.asarray(zigzag_indices(S, n_sp))

        mod_nat, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=S, vocab=32, d_model=32,
            n_layers=2, n_heads=2, d_ff=64, max_len=S)
        mod_zz = mod_nat.clone(
            attention_fn=make_zigzag_ring_attention(mesh,
                                                    batch_axis=AXIS_DATA))
        toks = np.random.default_rng(0).integers(
            0, 32, size=(8, S)).astype(np.int32)
        tx = optax.adam(1e-3)

        step_n = make_lm_train_step(mod_nat.apply, tx, mesh,
                                    donate_state=False)
        st_n, loss_n = step_n(init_lm_state(params, tx),
                              jax.device_put(toks, token_sharding(mesh)))

        pos = jnp.asarray(pi, jnp.int32)
        step_z = make_lm_train_step(
            lambda p, t: mod_zz.apply(p, t, pos), tx, mesh,
            donate_state=False, loss_fn=make_zigzag_lm_loss(S, n_sp))
        st_z, loss_z = step_z(init_lm_state(params, tx),
                              jax.device_put(toks[:, pi],
                                             token_sharding(mesh)))

        np.testing.assert_allclose(float(loss_n), float(loss_z),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(st_n.params),
                        jax.tree.leaves(st_z.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    def test_loss_with_targets_matches_lm_loss_on_natural_order(self):
        from tpudist.models import lm_loss, lm_loss_with_targets

        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
        toks = jnp.asarray(rng.integers(0, 32, size=(2, 16)), jnp.int32)
        # natural-order targets: next token, final position masked
        tgt = jnp.concatenate(
            [toks[:, 1:], jnp.full((2, 1), -1, jnp.int32)], axis=1)
        np.testing.assert_allclose(
            float(lm_loss(logits, toks)),
            float(lm_loss_with_targets(logits, tgt)), rtol=1e-6)

    def test_positions_guards(self):
        """Explicit positions are rejected under rope, decode, AND the
        default array-order attention (each silently wrong otherwise)."""
        from tpudist.models import create_transformer

        toks = jnp.zeros((1, 16), jnp.int32)
        pos = jnp.arange(16, dtype=jnp.int32)

        mod_r, params_r = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=1, n_heads=2, d_ff=64, max_len=16, rope=True)
        with pytest.raises(ValueError, match="learned position table"):
            mod_r.apply(params_r, toks, pos)

        mod_n, params_n = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=1, n_heads=2, d_ff=64, max_len=16)
        mod_d = mod_n.clone(decode=True)
        with pytest.raises(ValueError, match="learned position table"):
            mod_d.apply(params_n, toks, pos, mutable=["cache"])

        # default attention masks over array order: must refuse
        with pytest.raises(ValueError, match="layout-aware"):
            mod_n.apply(params_n, toks, pos)


class TestRingGQAWire:
    """GQA-native xla ring: the ring wire carries hkv-headed K/V — the
    HLO's collective-permutes must be group x smaller than MHA's."""

    def _hop_bytes(self, hkv):
        from tpudist.parallel import make_ring_attention
        from tpudist.runtime.mesh import AXIS_SEQ
        from tpudist.utils.hlo_audit import collect_collectives, profile

        n, B, H, S, D = 4, 2, 4, 64, 16
        mesh = Mesh(np.asarray(jax.devices()[:n]), (AXIS_SEQ,))
        ring = make_ring_attention(mesh, causal=True, kernel="xla")
        q = jnp.zeros((B, H, S, D), jnp.float32)
        k = jnp.zeros((B, hkv, S, D), jnp.float32)
        prof = profile(collect_collectives(ring, q, k, k))
        cp = prof["collective-permute"]
        return cp["count"], cp["bytes_total"]

    def test_gqa_halves_the_ring_wire(self):
        n_mha, bytes_mha = self._hop_bytes(hkv=4)
        n_gqa, bytes_gqa = self._hop_bytes(hkv=2)
        assert n_mha == n_gqa            # same hop structure
        assert bytes_gqa * 2 == bytes_mha  # half the heads -> half the wire
        # absolute check (forward program): (n-1) hops x (K+V) each of
        # [B, hkv, shard, D] f32
        n, B, D, shard, hkv = 4, 2, 16, 16, 2
        assert bytes_gqa == (n - 1) * 2 * B * hkv * shard * D * 4

    def test_gqa_value_and_grad_parity(self, devices):
        """Grouped K/V through the xla ring equals the repeated-KV dense
        reference — values and grads (the repeat happens post-hop)."""
        from tpudist.parallel import make_ring_attention
        from tpudist.runtime.mesh import AXIS_SEQ

        n, B, H, HKV, S, D = 4, 2, 4, 2, 64, 16
        mesh = Mesh(np.asarray(devices[:n]), (AXIS_SEQ,))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, S, D))
        k = jax.random.normal(ks[1], (B, HKV, S, D))
        v = jax.random.normal(ks[2], (B, HKV, S, D))
        ring = make_ring_attention(mesh, causal=True, kernel="xla")
        rep = lambda x: jnp.repeat(x, H // HKV, 1)

        np.testing.assert_allclose(
            np.asarray(ring(q, k, v)),
            np.asarray(attention_reference(q, rep(k), rep(v), causal=True)),
            rtol=2e-5, atol=2e-5)
        g1 = jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(
            lambda q, k, v: (attention_reference(
                q, rep(k), rep(v), causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_gqa_composes_with_window_on_xla_ring(self, devices):
        """GQA + sliding window + xla ring in one body: post-hop repeat
        must not disturb the band masking or the early ring exit."""
        from tpudist.parallel import make_ring_attention
        from tpudist.runtime.mesh import AXIS_SEQ

        n, B, H, HKV, S, D, W = 4, 2, 4, 2, 64, 16, 12
        mesh = Mesh(np.asarray(devices[:n]), (AXIS_SEQ,))
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, H, S, D))
        k = jax.random.normal(ks[1], (B, HKV, S, D))
        v = jax.random.normal(ks[2], (B, HKV, S, D))
        ring = make_ring_attention(mesh, causal=True, kernel="xla",
                                   window=W)
        rep = lambda x: jnp.repeat(x, H // HKV, 1)
        np.testing.assert_allclose(
            np.asarray(ring(q, k, v)),
            np.asarray(attention_reference(q, rep(k), rep(v), causal=True,
                                           window=W)),
            rtol=2e-5, atol=2e-5)

    def test_zigzag_eval_step_matches_natural(self, devices):
        """make_lm_eval_step(loss_fn=zigzag) on permuted batches equals
        the natural-order eval loss (the demo's --zigzag eval path)."""
        from tpudist.models import create_transformer
        from tpudist.parallel import (make_zigzag_lm_loss,
                                      make_zigzag_ring_attention,
                                      zigzag_indices)
        from tpudist.runtime.mesh import AXIS_DATA, AXIS_SEQ
        from tpudist.train import make_lm_eval_step, token_sharding

        n_sp, S = 4, 64
        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    (AXIS_DATA, AXIS_SEQ))
        pi = np.asarray(zigzag_indices(S, n_sp))
        mod_nat, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=S, vocab=32, d_model=32,
            n_layers=1, n_heads=2, d_ff=64, max_len=S)
        mod_zz = mod_nat.clone(
            attention_fn=make_zigzag_ring_attention(mesh,
                                                    batch_axis=AXIS_DATA))
        toks = np.random.default_rng(3).integers(
            0, 32, size=(8, S)).astype(np.int32)

        ev_n = make_lm_eval_step(mod_nat.apply, mesh)
        loss_n = ev_n(params, jax.device_put(toks, token_sharding(mesh)))

        pos = jnp.asarray(pi, jnp.int32)
        ev_z = make_lm_eval_step(
            lambda p, t: mod_zz.apply(p, t, pos), mesh,
            loss_fn=make_zigzag_lm_loss(S, n_sp))
        loss_z = ev_z(params, jax.device_put(toks[:, pi],
                                             token_sharding(mesh)))
        np.testing.assert_allclose(float(loss_n), float(loss_z),
                                   rtol=1e-5, atol=1e-5)
