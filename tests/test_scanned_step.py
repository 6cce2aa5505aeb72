"""The device-cached scan path must reproduce the per-step path exactly:
same batch order, same final params, same per-iteration logged losses."""

import jax
import numpy as np
import optax

from tpudist.data import ShardPlan, ShardedLoader, make_toy_data
from tpudist.models import create_toy_model
from tpudist.models.split_mlp import split_state_sharding
from tpudist.runtime.mesh import data_model_mesh
from tpudist.train import (
    TrainLoopConfig,
    init_model_states,
    make_multi_model_train_step,
    make_scanned_train_step,
    run_training,
)
from tpudist.utils.metrics import MetricsLogger


def _build(mesh, *, split=False, batch_size=64):
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    mx, px = create_toy_model(kx)
    my, py = create_toy_model(ky)
    models = {"model_X": (mx.apply, px), "model_Y": (my.apply, py)}
    tx = optax.adam(1e-3)
    states = init_model_states(models, tx)
    sharding = None
    if split:
        sharding = split_state_sharding(mesh, states)
        states = jax.device_put(states, sharding)
    apply_fns = {k: f for k, (f, _) in models.items()}
    step = make_multi_model_train_step(apply_fns, tx, mesh, state_sharding=sharding)
    chunk = make_scanned_train_step(apply_fns, tx, mesh, state_sharding=sharding)
    data = make_toy_data(seed=0)
    plan = ShardPlan(num_samples=len(data), num_shards=1, shard_id=0, seed=0)
    loader = ShardedLoader(data, batch_size=batch_size, plan=plan)
    return states, step, chunk, loader


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _losses_from(logger_rows):
    return [(r["loss/model_X"], r["loss/model_Y"]) for r in logger_rows]


class _CaptureLogger(MetricsLogger):
    def __init__(self):
        super().__init__(run=None, jsonl_path=None)
        self.rows = []

    def log(self, metrics, commit=True):
        self.rows.append(dict(metrics))


def test_scanned_matches_per_step(dp_mesh):
    cfg = TrainLoopConfig(total_iterations=25, progress_bar=False, sync_every=7)

    states_a, step, _, loader_a = _build(dp_mesh)
    log_a = _CaptureLogger()
    states_a, _ = run_training(states_a, step, loader_a, dp_mesh, log_a, cfg)

    states_b, _, chunk, loader_b = _build(dp_mesh)
    log_b = _CaptureLogger()
    states_b, _ = run_training(
        states_b, None, loader_b, dp_mesh, log_b, cfg, chunk_step_fn=chunk
    )

    assert len(log_a.rows) == len(log_b.rows) == 25
    np.testing.assert_allclose(
        _losses_from(log_a.rows), _losses_from(log_b.rows), rtol=1e-6
    )
    for a, b in zip(_leaves(states_a), _leaves(states_b)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_scanned_with_model_split(dm_mesh):
    cfg = TrainLoopConfig(total_iterations=10, progress_bar=False, sync_every=4)
    states, _, chunk, loader = _build(dm_mesh, split=True)
    log = _CaptureLogger()
    states, losses = run_training(
        states, None, loader, dm_mesh, log, cfg, chunk_step_fn=chunk
    )
    assert len(log.rows) == 10
    assert all(np.isfinite(v) for r in log.rows for v in r.values())


def test_scanned_resume_parity(dp_mesh):
    # resume at iteration 9 must continue the same data stream
    cfg = TrainLoopConfig(total_iterations=20, progress_bar=False, sync_every=5)
    states_a, _, chunk_a, loader_a = _build(dp_mesh)
    states_a, _ = run_training(
        states_a, None, loader_a, dp_mesh, None, cfg, chunk_step_fn=chunk_a
    )

    states_b, _, chunk_b, loader_b = _build(dp_mesh)
    cfg9 = TrainLoopConfig(total_iterations=9, progress_bar=False, sync_every=5)
    states_b, _ = run_training(
        states_b, None, loader_b, dp_mesh, None, cfg9, chunk_step_fn=chunk_b
    )
    states_b, _ = run_training(
        states_b, None, loader_b, dp_mesh, None, cfg,
        start_iteration=9, chunk_step_fn=chunk_b,
    )
    for a, b in zip(_leaves(states_a), _leaves(states_b)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_scanned_fallback_on_partial_batches(dp_mesh):
    # 512 % 96 != 0 → host path (no chunk), still completes
    states, step, chunk, loader = _build(dp_mesh, batch_size=96)
    cfg = TrainLoopConfig(total_iterations=8, progress_bar=False)
    states, losses = run_training(
        states, step, loader, dp_mesh, None, cfg, chunk_step_fn=chunk
    )
    assert all(np.isfinite(v) for v in losses.values())


def test_loop_config_resolves_sync_every(monkeypatch):
    """The scan window is the loop config's own: 256, or
    ``TPUDIST_SYNC_EVERY``, or what the caller passes."""
    monkeypatch.delenv("TPUDIST_SYNC_EVERY", raising=False)
    assert TrainLoopConfig().sync_every == 256
    monkeypatch.setenv("TPUDIST_SYNC_EVERY", "32")
    assert TrainLoopConfig().sync_every == 32
    assert TrainLoopConfig(sync_every=8).sync_every == 8


class TestScannedLMStep:
    """make_scanned_lm_train_step: K optimizer steps per dispatch, losses
    and final state bit-matching K plain steps."""

    def test_matches_k_plain_steps(self, devices):
        import numpy as np
        import optax
        from jax.sharding import Mesh

        from tpudist.models import create_transformer
        from tpudist.runtime.mesh import AXIS_DATA
        from tpudist.train import (chunk_token_sharding, init_lm_state,
                                   make_lm_train_step,
                                   make_scanned_lm_train_step,
                                   token_sharding)

        mesh = Mesh(np.asarray(devices), (AXIS_DATA,))
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=32, vocab=32, d_model=32,
            n_layers=1, n_heads=2, d_ff=64, max_len=32)
        tx = optax.adam(1e-3)
        K, B, S = 4, 8, 32
        toks = np.random.default_rng(0).integers(
            0, 32, size=(K, B, S)).astype(np.int32)

        st_p = init_lm_state(params, tx)
        plain = make_lm_train_step(module.apply, tx, mesh,
                                   donate_state=False)
        plain_losses = []
        for k in range(K):
            st_p, loss = plain(st_p, jax.device_put(toks[k],
                                                    token_sharding(mesh)))
            plain_losses.append(float(loss))

        st_s = init_lm_state(params, tx)
        chunk = make_scanned_lm_train_step(module.apply, tx, mesh,
                                           donate_state=False)
        st_s, losses = chunk(st_s, jax.device_put(
            toks, chunk_token_sharding(mesh)))
        np.testing.assert_allclose(np.asarray(losses), plain_losses,
                                   rtol=1e-6, atol=1e-6)
        for a, b in zip(jax.tree.leaves(st_p.params),
                        jax.tree.leaves(st_s.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_custom_loss_fn_threads(self, devices):
        import numpy as np
        import optax
        from jax.sharding import Mesh

        from tpudist.models import create_transformer
        from tpudist.runtime.mesh import AXIS_DATA
        from tpudist.train import (chunk_token_sharding, init_lm_state,
                                   make_scanned_lm_train_step)

        mesh = Mesh(np.asarray(devices), (AXIS_DATA,))
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=1, n_heads=2, d_ff=64, max_len=16)
        calls = []

        def loss_fn(logits, toks):
            from tpudist.models import lm_loss

            calls.append(1)
            return lm_loss(logits, toks)

        chunk = make_scanned_lm_train_step(
            module.apply, optax.adam(1e-3), mesh, loss_fn=loss_fn,
            donate_state=False)
        toks = np.zeros((2, 8, 16), np.int32)
        _, losses = chunk(init_lm_state(params, optax.adam(1e-3)),
                          jax.device_put(toks, chunk_token_sharding(mesh)))
        assert losses.shape == (2,)
        assert calls  # traced through the custom loss
