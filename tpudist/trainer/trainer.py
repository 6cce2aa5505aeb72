"""High-level Trainer facade — parity with PyTorch Lightning as used by the
reference (``demo_pytorch_lightning.py``, SURVEY.md §3.4).

The reference's ``LitToyModel`` holds two models (``:16-25``), sums their MSE
losses in ``training_step`` (``:27-33``) and returns one Adam per model from
``configure_optimizers`` (``:35-40``); ``pl.Trainer(gpus, num_nodes,
strategy='ddp', precision=32)`` owns the loop, device placement, and
distributed wiring (``:57-60``).

The TPU-native facade keeps that division of labor: the user supplies a
:class:`TrainerModule` (models + optimizers + loss); the :class:`Trainer`
owns the mesh, the compiled step, logging, and teardown.  ``strategy`` maps
onto mesh layout + state sharding (the Lightning ``strategy=`` flag analog,
``demo_pytorch_lightning.py:57-60``, opened to the full library — VERDICT
r4 weak #5):

- ``'dp'``       1-D data mesh, replicated state (≅ ``strategy='ddp'``)
- ``'dp_model'`` 2-D ``('data','model')`` mesh, user-supplied sharding
- ``'zero1'``    data mesh, optimizer state sharded over it
  (:func:`tpudist.parallel.zero1_sharding` — weight-update sharding)
- ``'fsdp'``     data mesh, params + optimizer state fully sharded
  (:func:`tpudist.parallel.fsdp_sharding` — ZeRO-3 layout)
- ``'pp'``       ``('data','stage')`` mesh, pipeline schedule
  (:class:`LMTrainerModule` only — blocks shard over stages)

``devices``/``num_nodes`` are *not* parameters — the mesh covers whatever the
launch contract provided, which is the multi-controller JAX model.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import optax

from tpudist.comm.collectives import MetricBackend
from tpudist.runtime.bootstrap import initialize, shutdown
from tpudist.runtime.mesh import data_model_mesh, data_parallel_mesh
from tpudist.runtime.seeding import resolve_shared_seed
from tpudist.train.loop import TrainLoopConfig, run_training
from tpudist.train.step import (
    init_model_states,
    make_multi_model_train_step,
    make_scanned_train_step,
    mse_loss,
)
from tpudist.utils.metrics import MetricsLogger, init_metrics


def _cast_tree(tree, dtype):
    """Cast float leaves only — integer inputs (token ids) and non-float
    leaves pass through untouched."""
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        else a, tree)


def _bf16_apply(f):
    """Mixed precision: fp32 master weights, bf16 compute — params cast at
    apply time so grads come back fp32 for the optimizer."""
    import jax.numpy as jnp

    def wrapped(p, x):
        return _cast_tree(
            f(_cast_tree(p, jnp.bfloat16), _cast_tree(x, jnp.bfloat16)),
            jnp.float32)
    return wrapped


class TrainerModule:
    """Subclass and override; the Lightning-``LightningModule`` analog."""

    def configure_models(self, rng: jax.Array) -> Dict[str, Tuple[Callable, object]]:
        """Return name → ``(apply_fn, params)``.  Called once on every
        process with the same ``rng`` (replicated init without broadcast)."""
        raise NotImplementedError

    def configure_optimizers(self):
        """Return one optax transformation, or a per-model dict — the
        ``configure_optimizers`` returning a list of Adams analog
        (``demo_pytorch_lightning.py:35-40``).  For LR schedules use
        :func:`tpudist.train.build_optimizer` (owning the optimizer is the
        module's job, the Lightning contract, so the Trainer does not read
        ``--lr_schedule`` itself)."""
        return optax.adam(1e-3)

    def loss(self, pred: jax.Array, target: jax.Array) -> jax.Array:
        """Per-model loss; the total logged loss is the sum over models
        (``training_step`` summing loss_X + loss_Y, ``:27-33``)."""
        return mse_loss(pred, target)

    def state_sharding(self, mesh, states):
        """Optional non-replicated state layout for ``strategy='dp_model'``
        (strategy-derived layouts — fsdp/zero1 — apply when this returns
        None)."""
        return None


class LMTrainerModule(TrainerModule):
    """Trainer module for the LM family — the contract that opens the
    Trainer to the transformer strategies (fsdp / zero1 / pp).

    The user supplies ONE flax language model via :meth:`configure_lm`;
    the loader passed to ``fit`` yields ``[batch, seq]`` int32 token
    arrays (re-iterated per epoch; an optional ``set_epoch(e)`` hook gets
    the DistributedSampler set_epoch call, ``demo.py:96-98``).
    """

    def configure_lm(self, rng: jax.Array):
        """Return ``(flax_module, params)`` — e.g. from
        :func:`tpudist.models.create_transformer`.  Called once on every
        process with the same ``rng`` (replicated init)."""
        raise NotImplementedError

    def configure_optimizers(self):
        """One optax transformation (the LM path has a single model, so a
        per-model dict is rejected)."""
        return optax.adam(1e-3)

    def loss(self, logits: jax.Array, tokens: jax.Array) -> jax.Array:
        """Next-token loss given ``apply(params, tokens) -> logits``.
        Ignored by ``strategy='pp'`` (the pipeline schedules own their
        fused vocab head — see ``tpudist.parallel.pipeline_lm``)."""
        from tpudist.train.lm import lm_loss

        return lm_loss(logits, tokens)


@dataclasses.dataclass
class Trainer:
    max_steps: int = 1000  # demo_pytorch_lightning.py:48 (1000 steps)
    # 'dp' | 'dp_model' | 'fsdp' | 'zero1' | 'pp' | 'auto' ('auto' =
    # measurement-driven pick, tpudist.plan — resolved at fit(); the
    # ranked report lands on self.plan and stamps into telemetry)
    strategy: str = "dp"
    model_parallel: int = 2
    # fsdp/zero1: leaves under this many elements stay replicated (the
    # gather overhead beats the memory win for small tensors).
    shard_min_size: int = 1024
    # pp (LMTrainerModule only): stage-axis width, schedule, microbatches
    # (default: one per stage; interleaved wants 2x).
    pipeline_stages: int = 2
    pp_schedule: str = "1f1b"  # 'gpipe' | '1f1b' | 'interleaved'
    pp_chunks: int = 2         # virtual chunks/device (interleaved only)
    microbatches: Optional[int] = None
    precision: str = "fp32"  # 'fp32' (reference precision=32) | 'bf16'
    log_every: int = 1
    metric_backend: MetricBackend = MetricBackend.ICI
    project: str = "tpudist"
    group: Optional[str] = None
    dry_run: bool = False
    seed: Optional[int] = 0  # None → rank-0 draw broadcast job-wide
    use_node_rank: bool = False
    progress_bar: bool = True
    # Checkpointing (the demos' --checkpoint_dir/--checkpoint_every/--resume
    # contract, reference dir layout job_submitter.sh:157-159): a directory
    # enables periodic saves; resume=True restores the latest step and
    # continues the loop from its saved iteration.
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    resume: bool = False

    def fit(self, module: TrainerModule, loader) -> Dict[str, float]:
        """Own the whole run: init runtime, build mesh + compiled step,
        train, tear down.  Returns the final per-model losses."""
        from tpudist.checkpoint import (
            resolve_checkpoint_location,
            setup_checkpointing,
        )

        # Resolve (and validate resume config) before any runtime side
        # effects — same env-contract resolution as the plain demos.
        ckpt_dir = resolve_checkpoint_location(
            self.checkpoint_dir, save_every=self.checkpoint_every,
            resume=self.resume,
        )
        initialize(use_node_rank=self.use_node_rank)
        seed = resolve_shared_seed(self.seed)
        if self.strategy == "auto":
            # measurement-driven resolution (tpudist.plan): score the
            # strategies this facade can enact against the frozen
            # artifacts, assign the winner onto self.strategy (+ pp
            # fields when pp wins).  self.plan keeps the full ranked
            # report; the loop stamps plan.stamp() into telemetry so
            # prediction-vs-actual is auditable from the run report.
            from tpudist.plan import resolve_trainer_auto

            self.plan = resolve_trainer_auto(self, module, seed)
        if isinstance(module, LMTrainerModule):
            return self._fit_lm(module, loader, ckpt_dir, seed)

        if self.strategy in ("dp", "fsdp", "zero1"):
            mesh = data_parallel_mesh()
        elif self.strategy == "dp_model":
            mesh = data_model_mesh(model_size=self.model_parallel)
        elif self.strategy == "pp":
            raise ValueError(
                "strategy='pp' needs an LMTrainerModule (transformer "
                "blocks shard over pipeline stages; the multi-model toy "
                "contract has no block stack)")
        else:
            raise ValueError(f"unknown strategy {self.strategy!r}")

        models = module.configure_models(jax.random.PRNGKey(seed))
        tx = module.configure_optimizers()
        states = init_model_states(models, tx)
        state_sharding = module.state_sharding(mesh, states)
        if state_sharding is None and self.strategy in ("fsdp", "zero1"):
            from tpudist.parallel import fsdp_sharding, zero1_sharding

            if self.strategy == "fsdp":
                state_sharding = fsdp_sharding(
                    mesh, states, min_size=self.shard_min_size)
            else:
                state_sharding = {
                    k: zero1_sharding(mesh, st, min_size=self.shard_min_size)
                    for k, st in states.items()}
        if state_sharding is not None:
            states = jax.device_put(states, state_sharding)

        apply_fns = {k: f for k, (f, _) in models.items()}
        if self.precision == "bf16":
            apply_fns = {k: _bf16_apply(f) for k, f in apply_fns.items()}
        step = make_multi_model_train_step(
            apply_fns, tx, mesh, loss_fn=module.loss, state_sharding=state_sharding
        )
        chunk_step = make_scanned_train_step(
            apply_fns, tx, mesh, loss_fn=module.loss, state_sharding=state_sharding
        )

        ckpt = None
        start_iteration = 0
        if ckpt_dir is not None:
            # mesh= routes resume through the reshard path: a checkpoint
            # saved at a different world size (elastic tpurun relaunch)
            # re-binds its logical shardings onto THIS mesh.
            ckpt, states, start_iteration = setup_checkpointing(
                states, ckpt_dir, save_every=self.checkpoint_every,
                resume=self.resume, mesh=mesh,
            )

        logger: MetricsLogger = init_metrics(
            project=self.project, group=self.group or "trainer", dry_run=self.dry_run
        )
        cfg = TrainLoopConfig(
            total_iterations=self.max_steps,
            log_every=self.log_every,
            metric_backend=self.metric_backend,
            progress_bar=self.progress_bar,
            plan_stamp=(self.plan.stamp()
                        if getattr(self, "plan", None) is not None
                        else None),
        )
        try:
            states, losses = run_training(
                states, step, loader, mesh, logger, cfg,
                ckpt=ckpt, start_iteration=start_iteration,
                chunk_step_fn=chunk_step,
            )
        finally:
            if ckpt is not None:
                ckpt.close()
        self.final_states = states
        # A SIGTERM-preempted run checkpointed and exited EARLY — the
        # caller must not mistake it for a completed fit (resume with
        # the same checkpoint_dir + resume=True to continue).
        from tpudist.runtime import preemption
        from tpudist.runtime.rank_logging import rank_print

        self.preempted = preemption.last_run_preempted()
        if self.preempted:
            rank_print("[trainer] preempted: checkpoint saved, fit "
                       "incomplete — rerun with resume=True to continue")
        return losses

    def _fit_lm(self, module: "LMTrainerModule", loader, ckpt_dir, seed):
        """LM-family fit: one transformer, strategy-derived state layout
        (dp / fsdp / zero1 / pp), token-batch loader."""
        from tpudist.checkpoint import setup_checkpointing
        from tpudist.train import init_lm_state, make_lm_train_step

        if self.strategy in ("dp", "fsdp", "zero1"):
            mesh = data_parallel_mesh()
        elif self.strategy == "pp":
            from tpudist.runtime.mesh import MeshConfig, make_mesh

            mesh = make_mesh(
                MeshConfig(data=-1, stage=self.pipeline_stages),
                axis_names=("data", "stage"))
        else:
            raise ValueError(
                f"strategy {self.strategy!r} not supported for "
                "LMTrainerModule (use dp/fsdp/zero1/pp; dp_model is the "
                "toy split-MLP layout)")

        flax_mod, params = module.configure_lm(jax.random.PRNGKey(seed))
        tx = module.configure_optimizers()
        if isinstance(tx, dict):
            raise ValueError(
                "LMTrainerModule.configure_optimizers must return one "
                "optax transformation (single model)")

        if self.strategy == "pp":
            if self.precision == "bf16":
                raise ValueError(
                    "strategy='pp' does not support precision='bf16' yet: "
                    "the pipeline schedules own their step construction "
                    "(tpudist.parallel.pipeline_lm) and the facade's "
                    "apply-time cast does not reach it — requesting it "
                    "must not silently train fp32")
            from tpudist.parallel import (
                make_pp_lm_train_step,
                pp_state_sharding,
                stack_block_params,
                stack_block_params_interleaved,
            )

            chunks = self.pp_chunks if self.pp_schedule == "interleaved" else 1
            micro = self.microbatches or self.pipeline_stages * (
                2 if self.pp_schedule == "interleaved" else 1)
            if chunks > 1:
                pp_params = stack_block_params_interleaved(
                    params, self.pipeline_stages, chunks)
            else:
                pp_params = stack_block_params(params, self.pipeline_stages)
            state = init_lm_state(pp_params, tx)
            sharding = pp_state_sharding(mesh, state)
            state = jax.device_put(state, sharding)
            step = make_pp_lm_train_step(
                mesh, flax_mod, tx, n_stages=self.pipeline_stages,
                num_microbatches=micro, schedule=self.pp_schedule,
                n_chunks=chunks, state_sharding=sharding)
        else:
            state = init_lm_state(params, tx)
            sharding = module.state_sharding(mesh, state)
            if sharding is None and self.strategy in ("fsdp", "zero1"):
                from tpudist.parallel import fsdp_sharding, zero1_sharding

                sharding = (
                    fsdp_sharding(mesh, state, min_size=self.shard_min_size)
                    if self.strategy == "fsdp"
                    else zero1_sharding(mesh, state,
                                        min_size=self.shard_min_size))
            if sharding is not None:
                state = jax.device_put(state, sharding)
            apply_fn = flax_mod.apply
            if self.precision == "bf16":
                apply_fn = _bf16_apply(apply_fn)
            step = make_lm_train_step(
                apply_fn, tx, mesh, state_sharding=sharding,
                loss_fn=module.loss)

        ckpt = None
        start_iteration = 0
        if ckpt_dir is not None:
            ckpt, state, start_iteration = setup_checkpointing(
                state, ckpt_dir, save_every=self.checkpoint_every,
                resume=self.resume, mesh=mesh,
            )
        logger: MetricsLogger = init_metrics(
            project=self.project, group=self.group or "trainer",
            dry_run=self.dry_run)
        try:
            state, losses = self._run_lm_loop(
                state, step, loader, mesh, logger, ckpt, start_iteration)
        finally:
            if ckpt is not None:
                ckpt.close()
        self.final_states = state
        from tpudist.runtime import preemption
        from tpudist.runtime.rank_logging import rank_print

        self.preempted = preemption.last_run_preempted()
        if self.preempted:
            rank_print("[trainer] preempted: checkpoint saved, fit "
                       "incomplete — rerun with resume=True to continue")
        return losses

    def _run_lm_loop(self, state, step, loader, mesh, logger, ckpt,
                     start_iteration):
        """Token-batch loop.  The preemption bracket and run-teardown
        ordering are the SHARED helpers in :mod:`tpudist.train.loop`
        (``preemption_scope`` / ``finalize_run``) — one copy of that
        contract for every loop in the framework."""
        import numpy as np

        from tpudist import telemetry
        from tpudist.train import token_sharding
        from tpudist.train.loop import (
            StepSpans,
            TrainLoopConfig,
            _data_wait_iter,
            _make_pbar,
            _preemption_check,
            finalize_run,
            preemption_scope,
        )

        # session ownership: only finish a session this loop started —
        # a pre-existing one belongs to the embedding process (e.g. a
        # serving process whose distill flywheel trains through here)
        owns_telemetry = telemetry.active() is None
        telemetry.ensure_started()
        if getattr(self, "plan", None) is not None:
            # auto-mode audit trail: the chosen plan + predictions land
            # in the same stream as the measured step spans
            telemetry.event("plan_selected", **self.plan.stamp())
        # live observability: scrape endpoint + step-time gauges flow
        # from the step spans via the metrics feed (TPUDIST_METRICS_PORT
        # gates the endpoint; no-op when unset)
        from tpudist.telemetry import statusz

        statusz.ensure_started()
        tele = telemetry.active()

        ts = token_sharding(mesh)
        batches = len(loader) if hasattr(loader, "__len__") else None
        if batches is None and start_iteration:
            # Fast-forwarding start_iteration batches through a loader with
            # no __len__ cannot recover the epoch boundary: the skip loop
            # would silently exhaust a shorter iterator (dying later with a
            # misleading "yielded no batches") and epoch-seeded shuffling
            # would replay epoch-0 data.  Fail at the resume site instead.
            raise ValueError(
                f"resume at iteration {start_iteration} requires a sized "
                "loader: the LM loop derives the epoch boundary from "
                "len(loader), which this loader does not provide — wrap it "
                "with a __len__ (e.g. a list or tpudist.data loader) or "
                "restart without resume")
        epoch = start_iteration // batches if batches else 0
        skip = start_iteration - epoch * (batches or 0)
        iteration = start_iteration
        loss = None
        preempted = False
        pbar = _make_pbar(
            TrainLoopConfig(total_iterations=self.max_steps,
                            progress_bar=self.progress_bar),
            initial=start_iteration)
        # finalize_run stays INSIDE the scope: the forced preemption save
        # must run with the SIGTERM handler still installed, or a second
        # signal during the grace window kills the process mid-save.
        with preemption_scope(ckpt is not None):
            with StepSpans(tele) as steps:
                while iteration < self.max_steps and not preempted:
                    if hasattr(loader, "set_epoch"):
                        loader.set_epoch(epoch)
                    it = iter(loader)
                    for _ in range(skip):
                        next(it, None)
                    skip = 0
                    advanced = False
                    for tokens in _data_wait_iter(it, tele):
                        advanced = True
                        if iteration >= self.max_steps:
                            break
                        state, loss = steps.run(
                            iteration, step, state, jax.device_put(
                                np.asarray(tokens, dtype=np.int32), ts))
                        iteration += 1
                        # The compiled LM step already reduces the loss over
                        # the GLOBAL batch, so there is no per-rank value for
                        # a host-fabric (metric_backend) reduction to merge —
                        # rank-0 logging of the step loss is the whole story.
                        if logger is not None and \
                                iteration % max(1, self.log_every) == 0:
                            logger.log({"loss/lm": float(loss)}, commit=True)
                        if pbar is not None:
                            pbar.update(1)
                        if ckpt is not None:
                            ckpt.maybe_save(iteration, state,
                                            {"iteration": iteration,
                                             "epoch": epoch})
                            if (iteration < self.max_steps
                                    and _preemption_check()):
                                preempted = True
                                break
                    if not advanced:
                        raise ValueError("LM loader yielded no batches")
                    if not preempted:
                        epoch += 1
            if pbar is not None:
                pbar.close()
            finalize_run(state, iteration=iteration, epoch=epoch,
                         preempted=preempted, ckpt=ckpt, logger=logger,
                         own_telemetry=owns_telemetry)
        return state, {"lm": float(loss) if loss is not None else None}

    @staticmethod
    def teardown():
        shutdown()
