"""Interleaved (virtual-stage) 1F1B: schedule properties + numerics.

The schedule simulator is pure Python — its properties (canonical V=1
timeline, bubble shrinking with V, O(V·D) bank depths, deadlock-free
convergence) are asserted directly.  Numerical parity runs the shard
body on the 8-device virtual mesh against straight-line autodiff, and
the LM entry point against the GPipe step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from tpudist.parallel.pipeline_interleaved import (
    deinterleave_block_params,
    interleave_block_params,
    interleaved_schedule,
    pipeline_interleaved_shard,
)


class TestSchedule:
    def test_v1_matches_canonical_1f1b_timeline(self):
        # Non-interleaved 1F1B on D stages: M + 2(D-1) pair-ticks, +1 for
        # the banked loss-cotangent hand-off.
        for D, M in [(2, 4), (4, 8), (4, 16), (8, 16)]:
            s = interleaved_schedule(D, 1, M)
            assert s.total_ticks == M + 2 * (D - 1) + 1, (D, M)

    def test_bubble_shrinks_with_chunks(self):
        # Wall-clock bubble = bubble_ticks x (chunk time ~ 1/V).
        D, M = 4, 16
        wall = [interleaved_schedule(D, v, M).bubble_ticks / v
                for v in (1, 2, 4)]
        assert wall[0] > wall[1] > wall[2], wall

    def test_bank_depth_constant_in_microbatches(self):
        D, V = 4, 2
        depths = {interleaved_schedule(D, V, m).act_depth
                  for m in (8, 16, 32)}
        assert len(depths) == 1, depths  # O(V*D), not O(M)

    def test_requires_microbatch_multiple_of_width(self):
        with pytest.raises(ValueError, match="multiple"):
            interleaved_schedule(4, 2, 6)

    def test_tables_are_consistent(self):
        s = interleaved_schedule(4, 2, 8)
        t = s.tables
        D, V, M = 4, 2, 8
        # every unit appears exactly once per device
        assert t["fwd_valid"].sum() == D * M * V
        assert t["bwd_valid"].sum() == D * M * V
        # loss taken exactly once per microbatch (on the last stage)
        assert t["take_loss"].sum() == M
        assert t["take_dx"].sum() == M
        # slots stay inside the banks
        assert t["fwd_slot"].max() < s.act_depth
        assert t["bwd_act_slot"].max() < s.act_depth
        assert t["bwd_cot_slot"].max() < s.cot_depth


class TestInterleaveLayout:
    def test_roundtrip_and_placement(self):
        D, V = 4, 2
        stack = jnp.arange(D * V)[:, None] * jnp.ones((1, 3))
        inter = interleave_block_params(stack, D)
        # device-major: position j = d*V + c holds global stage c*D + d
        got = np.asarray(inter[:, 0]).astype(int).tolist()
        want = [(j % V) * D + j // V for j in range(D * V)]
        assert got == want
        back = deinterleave_block_params(inter, D)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(stack))


class TestShardParity:
    """Shard body vs straight-line autodiff on the virtual mesh."""

    @pytest.mark.parametrize("D,V,M", [(4, 2, 8), (2, 4, 4), (4, 1, 8)])
    def test_loss_and_grads_match_reference(self, devices, D, V, M):
        S, d_model, micro = D * V, 8, 4
        Ws = jax.random.normal(jax.random.PRNGKey(0),
                               (S, 1, d_model, d_model)) * 0.3
        out_w = jax.random.normal(jax.random.PRNGKey(1), (d_model,))

        def stage_fn(p, x):
            for i in range(p.shape[0]):
                x = jnp.tanh(x @ p[i])
            return x

        def loss_fn(ow, act, aux):
            return jnp.mean((act @ ow - aux) ** 2)

        xs = jax.random.normal(jax.random.PRNGKey(2), (M, micro, d_model))
        aux = jax.random.normal(jax.random.PRNGKey(3), (M, micro))

        def ref_loss(Ws, ow, xs):
            total = 0.0
            for m in range(M):
                a = xs[m]
                for g in range(S):
                    a = stage_fn(Ws[g], a)
                total = total + loss_fn(ow, a, aux[m])
            return total

        ref_l, (ref_wg, ref_og, ref_dx) = jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2))(Ws, out_w, xs)

        sched = interleaved_schedule(D, V, M)
        mesh = Mesh(np.array(devices[:D]), ("stage",))

        def body(Wb, ow, xm, am):
            return pipeline_interleaved_shard(
                Wb, ow, xm, am, stage_fn=stage_fn, loss_fn=loss_fn,
                schedule=sched, axis_name="stage")

        loss_sum, cg, og, dx = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("stage"), P(), P(), P()),
            out_specs=(P(), P("stage"), P(), P()), check_vma=False))(interleave_block_params(Ws, D), out_w, xs, aux)

        np.testing.assert_allclose(float(loss_sum), float(ref_l), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(deinterleave_block_params(cg, D)),
            np.asarray(ref_wg), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(og), np.asarray(ref_og),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(ref_dx),
                                   rtol=1e-4, atol=1e-5)


class TestLMInterleaved:
    """make_pp_lm_train_step(schedule='interleaved') vs GPipe."""

    CFG8 = dict(vocab=64, d_model=32, n_layers=8, n_heads=4, d_ff=64)

    def test_loss_and_update_parity_with_gpipe(self, devices):
        from tpudist.models import create_transformer
        from tpudist.parallel import (make_pp_lm_train_step,
                                      pp_state_sharding,
                                      stack_block_params,
                                      stack_block_params_interleaved)
        from tpudist.runtime.mesh import AXIS_DATA, AXIS_STAGE
        from tpudist.train import init_lm_state, token_sharding

        D, V, M = 4, 2, 8
        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_STAGE))
        tx = optax.adam(1e-3)
        module, params = create_transformer(jax.random.PRNGKey(0),
                                            seq_len=32, **self.CFG8)
        tokens = np.random.default_rng(0).integers(
            0, 64, size=(2 * M, 32)).astype(np.int32)

        # GPipe reference over the contiguous 4-stage layout
        pp_g = stack_block_params(params, D)
        state_g = init_lm_state(pp_g, tx)
        shard_g = pp_state_sharding(mesh, state_g)
        step_g = make_pp_lm_train_step(
            mesh, module, tx, n_stages=D, num_microbatches=M,
            schedule="gpipe", donate_state=False, state_sharding=shard_g)

        pp_i = stack_block_params_interleaved(params, D, V)
        state_i = init_lm_state(pp_i, tx)
        shard_i = pp_state_sharding(mesh, state_i)
        step_i = make_pp_lm_train_step(
            mesh, module, tx, n_stages=D, num_microbatches=M,
            schedule="interleaved", n_chunks=V, donate_state=False,
            state_sharding=shard_i)

        toks = jax.device_put(tokens, token_sharding(mesh))
        sg, lg = step_g(jax.device_put(state_g, shard_g), toks)
        si, li = step_i(jax.device_put(state_i, shard_i), toks)
        np.testing.assert_allclose(float(lg), float(li),
                                   rtol=1e-5, atol=1e-5)
        # compare updated params in the common unstacked layout
        from tpudist.parallel import unstack_block_params

        back_g = unstack_block_params(
            {"blocks": sg.params["blocks"], "rest": sg.params["rest"]})
        back_i = unstack_block_params(
            {"blocks": deinterleave_block_params(si.params["blocks"], D),
             "rest": si.params["rest"]})
        for a, b in zip(jax.tree.leaves(back_g), jax.tree.leaves(back_i)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_n_chunks_requires_interleaved(self, devices):
        from tpudist.models import create_transformer
        from tpudist.parallel import make_pp_lm_train_step
        from tpudist.runtime.mesh import AXIS_DATA, AXIS_STAGE

        mesh = Mesh(np.asarray(devices).reshape(2, 4),
                    axis_names=(AXIS_DATA, AXIS_STAGE))
        module, _ = create_transformer(jax.random.PRNGKey(0), seq_len=32,
                                       **self.CFG8)
        with pytest.raises(ValueError, match="interleaved"):
            make_pp_lm_train_step(mesh, module, optax.adam(1e-3),
                                  n_stages=4, num_microbatches=8,
                                  schedule="1f1b", n_chunks=2)


    def test_format_timeline_smoke(self):
        from tpudist.parallel.pipeline_interleaved import format_timeline

        s = interleaved_schedule(2, 2, 4)
        txt = format_timeline(s)
        assert "D=2 V=2 M=4" in txt
        assert txt.count("dev") == 2
        # every unit appears: 4 micros x 2 chunks, F and B
        for m in range(4):
            for c in range(2):
                assert f"F{m}.{c}" in txt and f"B{m}.{c}" in txt
