"""The training loop.

Shape parity with ``training_demo`` (``demo.py:75-137``): a fixed iteration
budget spread over epochs (1000 iterations, ``demo.py:88,126-128``), per-epoch
``set_epoch`` reshuffle (``demo.py:96-98``), two models stepped per iteration,
rank-0 tqdm (``demo.py:91-92``), per-iteration global batch-weighted loss
logging (``demo.py:113-121``), and the teardown ordering — metrics logger
finished *before* the distributed runtime goes down (``demo.py:130-136``).

TPU-first deviation (SURVEY.md §3.1 "hot spots"): the reference performs a
synchronous CPU collective + wandb call inside every iteration.  Here the
compiled step returns device scalars; the host only blocks on them at the
logging cadence (``log_every``), keeping the metric path off the XLA critical
path while preserving per-iteration semantics at the default cadence of 1.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, Optional

import jax

from tpudist import telemetry
from tpudist.comm.collectives import MetricBackend, barrier
from tpudist.data.loader import ShardedLoader, shard_batch
from tpudist.telemetry import names
from tpudist.train.step import ModelState, batch_sharding
from tpudist.utils.envutil import env_int
from tpudist.utils.metrics import MetricsLogger


@dataclasses.dataclass
class TrainLoopConfig:
    total_iterations: int = 1000  # demo.py:88
    log_every: int = 1
    metric_backend: MetricBackend = MetricBackend.ICI
    metric_prefix: str = "loss/"
    progress_bar: bool = True
    # Device→host syncs are batched: losses are fetched (and, for the HOST
    # backend, cross-process reduced) once per ``sync_every`` iterations
    # instead of per step.  Log *rows* stay per-iteration (reference
    # semantics, demo.py:119-121); only the blocking fetch is deferred, so
    # the device stays ahead of the host (SURVEY.md §3.1 "hot spots").
    # 256 (vs the earlier 32): on a real v5e chip the toy step costs
    # ~41 µs inside a 512-long scan vs ~60 µs at window 32 (value-fetch-
    # synced timing) — longer windows amortize per-step overhead ~1.5x.
    # None = TPUDIST_SYNC_EVERY, else 256.
    sync_every: Optional[int] = None
    # Device-cached scan path: opt-out plus an HBM budget — the dataset is
    # replicated per device, so only datasets under this cap take the path.
    device_cache: bool = True
    device_cache_max_bytes: int = 256 * 1024 * 1024

    # Preemption-safe shutdown: when a SIGTERM arrived (see
    # tpudist.runtime.preemption — the demos/Trainer install the handler)
    # and a checkpoint manager is active, save at the next sync boundary
    # (all processes agree on it via the host fabric) and return early.
    preempt_save: bool = True

    # Plan stamp (tpudist.plan): when the run's configuration was chosen
    # by the measurement-driven planner (Trainer strategy="auto"), the
    # chosen config + predicted numbers as flat telemetry tags — emitted
    # as ONE plan_selected event once the loop's session is live, so the
    # report shows prediction next to the measured step time.
    plan_stamp: Optional[dict] = None

    # Hang watchdog (tpudist.runtime.watchdog): abort the process with
    # exit 124 + all-thread stack dump when no iteration/window completes
    # within this deadline, so tpurun's restart loop re-admits the group
    # instead of burning the allocation until scheduler timeout.  None =
    # resolve from TPUDIST_WATCHDOG_S (unset = disabled).  Size it above
    # the slowest legitimate gap between PETS — that includes a synchronous
    # checkpoint save and the end-of-run save drain / teardown barrier,
    # not just a step — the first deadline gets 10x slack for XLA
    # compilation.
    watchdog_timeout_s: Optional[float] = None

    def __post_init__(self):
        if self.sync_every is None:
            self.sync_every = env_int("TPUDIST_SYNC_EVERY", 256)


def _preemption_check() -> bool:
    from tpudist.runtime import preemption

    return preemption.check_all()


@contextlib.contextmanager
def preemption_scope(enabled: bool):
    """Per-run preemption bracket, shared by every training loop (the
    per-step and scanned paths here, the Trainer's LM loop): clear the
    sticky per-run record unconditionally — a later run without
    checkpointing must not inherit an earlier run's preempted status —
    install the SIGTERM handler when ``enabled``, and ALWAYS restore the
    process-wide handler on exit (a library must not leave one behind)."""
    from tpudist.runtime import preemption

    preemption.clear_last_run_preempted()
    installed = False
    if enabled:
        # Off the main thread install() degrades to a warned no-op (False).
        installed = preemption.install()
    try:
        yield
    finally:
        if installed:
            preemption.reset()


def finalize_run(states, *, iteration, epoch, preempted, ckpt, logger,
                 flush=None, own_telemetry: bool = True) -> None:
    """The run-teardown ordering CONTRACT (shared by every loop; parity
    with demo.py:130-136 — metrics finish before the end barrier):

    1. final checkpoint save — forced on preemption, because the boundary
       may coincide with a cadence save whose meta lacks the stamp;
    2. sticky preempted note (survives the handler reset in
       :func:`preemption_scope` — callers must be able to tell a
       partially-trained early exit from a completed run);
    3. queued metric rows flushed (``flush``), then ``logger.finish()``;
    4. the end-of-training barrier;
    5. the telemetry session finished — rank 0 merges every rank's and
       generation's JSONL into ``report.json``/``report.md`` so *every*
       run ends with a goodput report — but ONLY when this loop started
       the session (``own_telemetry``).  A loop embedded in a live
       process (the distillation flywheel training inside a serving
       process) must not tear down the host's session: that would
       silently stop every event/metric feed the moment the first
       background round completed.
    """
    if ckpt is not None:
        ckpt.save(iteration, states,
                  {"iteration": iteration, "epoch": epoch,
                   **({"preempted": True} if preempted else {})},
                  force=preempted)
        ckpt.wait_until_finished()
    if preempted:
        from tpudist.runtime import preemption

        preemption.note_run_preempted()
    if flush is not None:
        flush()
    if logger is not None:
        logger.finish()
    barrier("end_of_training")
    if own_telemetry:
        telemetry.finish()


def _data_wait_iter(source, tele):
    """Yield from ``source``, recording each blocking ``next()`` as a
    ``data_wait`` span — the consumer-side stall the goodput report's
    ``data`` component measures.  Plain passthrough when disarmed.

    Uses the stack-pushing ``span()`` form on purpose: a source that
    records its own ``data_wait`` leaves (``prefetch_to_device``) then
    nests under this span instead of double-counting the same stall."""
    if tele is None:
        yield from source
        return
    it = iter(source)
    while True:
        try:
            with tele.span("data_wait"):
                item = next(it)
        except StopIteration:
            return
        yield item


class StepSpans:
    """Times a loop's steps with one step in flight, as the loops run.

    A jitted step returns when it is ENQUEUED; timing the call times the
    enqueue (2 ms of a 255 ms step on a v5e).  A step's time is the gap
    between the arrivals of consecutive results, so::

        with StepSpans(tele) as steps:
            for i, batch in ...:
                state, loss = steps.run(i, step_fn, state, batch)

    dispatches step ``i`` and only then waits for the result of step
    ``i-1`` (the device always has the next step queued, exactly the
    pipelining the loops had).  What it records, armed:

    - ``step``: from one arrival to the next (from the enqueue when
      nothing was in flight); the first is ``compile`` and ends at its own
      arrival, so it covers the XLA compile.  Extra ``tags`` (the scanned
      loop's ``steps=k``) ride on it.  A run's ``step`` spans therefore
      sum to the loop's wall time, which is what the goodput table reads.
    - ``dispatch``: child of ``step``, the enqueue alone, inside a
      ``jax.profiler.StepTraceAnnotation("train", step_num=i)`` so a
      profiler trace groups the device's work by step.
    - whatever else the loop's thread records while a step is in flight
      (``data_wait``, ``metric_flush``, ``ckpt_save``) gets ``step`` as
      its parent: host work hidden behind the device, not more wall-clock.

    ``fn`` returns ``(state, *results)``; the wait is on the results, never
    the state, which the next dispatch may donate.  Disarmed
    (``tele is None``) ``run`` is the bare call: no wait, no record."""

    def __init__(self, tele):
        self._tele = tele
        self._scope = None     # entered ``step`` scope of the step in flight
        self._results = None   # ... and what its arrival is read from
        self._edge = 0.0       # monotonic start of that span
        self._tags = None
        self._first = True

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            self.drain()
        elif self._scope is not None:
            # unwind only: the results may be poisoned
            self._scope.__exit__(None, None, None)
            self._scope = self._results = None

    def _open(self, tags) -> None:
        self._scope = self._tele.scope(names.STEP)
        self._scope.__enter__()
        self._edge = time.monotonic()
        self._tags = tags or None

    def _close(self, results) -> None:
        jax.block_until_ready(results)
        now = time.monotonic()
        self._scope.__exit__(None, None, None)
        self._scope = None
        self._tele.record_span("compile" if self._first else "step",
                               self._edge, now - self._edge, self._tags)
        self._first = False

    def run(self, i: int, fn: Callable, *args, **tags):
        tele = self._tele
        if tele is None:
            return fn(*args)
        if self._scope is None:   # nothing in flight: starts with the enqueue
            self._open(tags)
        with jax.profiler.StepTraceAnnotation(names.STEP_ANNOTATION,
                                              step_num=i), \
                tele.span(names.DISPATCH):
            out = fn(*args)
        in_flight, self._results = self._results, out[1:]
        if self._first:
            self.drain()          # the compile span ends at its own arrival
        elif in_flight is not None:
            self._close(in_flight)
            self._open(tags)
        return out

    def drain(self) -> None:
        """Wait for the step in flight, if any, and close its span."""
        if self._scope is not None:
            self._close(self._results)
            self._results = None


def _make_pbar(config: TrainLoopConfig, initial: int = 0):
    if not config.progress_bar or jax.process_index() != 0:
        return None
    try:
        from tqdm import tqdm
    except ImportError:
        return None
    return tqdm(total=config.total_iterations, desc="train", initial=initial)


class _DeferredMetrics:
    """Collects per-iteration device losses; flushes them to the logger in
    batches — one blocking transfer per ``sync_every`` steps, identical
    logged values."""

    def __init__(self, logger, config: TrainLoopConfig):
        self.logger = logger
        self.config = config
        self._pending = []  # (iteration, batch_size, losses_device_dict)

    def add(self, iteration: int, batch_size: int, losses) -> None:
        self._pending.append((iteration, batch_size, losses))
        if len(self._pending) >= max(1, self.config.sync_every):
            self.flush()

    def flush(self) -> None:
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        # One transfer for the whole window.  The blocking fetch (which
        # absorbs whatever device compute the async dispatch ran ahead
        # of) is its own span so it never masquerades as idle time.
        with telemetry.span("metric_flush", rows=len(pending)):
            fetched = jax.device_get([losses for _, _, losses in pending])
        if self.config.metric_backend == MetricBackend.HOST:
            from tpudist.comm.collectives import host_allreduce_sum
            import numpy as np

            keys = sorted(fetched[0])
            local = np.array(
                [[float(f[k]) * bs for k in keys] for f, (_, bs, _) in zip(fetched, pending)],
                dtype=np.float64,
            )
            weights = np.array([[bs] * len(keys) for _, bs, _ in pending], np.float64)
            num, den = host_allreduce_sum((local, weights))
            fetched = [
                {k: num[i, j] / den[i, j] for j, k in enumerate(keys)}
                for i in range(len(pending))
            ]
        for (iteration, _, _), vals in zip(pending, fetched):
            self.logger.log(
                {f"{self.config.metric_prefix}{k}": float(v) for k, v in vals.items()},
                commit=True,
            )
        # live gauges, once per flush window (the scrape endpoint's view
        # of training progress; step-time sketches come from step spans)
        from tpudist.telemetry import metrics

        last_iter, _, _ = pending[-1]
        metrics.set_train_gauges(
            last_iter, {k: float(v) for k, v in fetched[-1].items()})


def run_training(
    states: Dict[str, ModelState],
    step_fn: Callable,
    loader: ShardedLoader,
    mesh,
    logger: Optional[MetricsLogger] = None,
    config: Optional[TrainLoopConfig] = None,
    per_process_batch_size: Optional[int] = None,
    ckpt=None,
    start_iteration: int = 0,
    chunk_step_fn: Optional[Callable] = None,
):
    """Run to the iteration budget; returns ``(final_states, final_losses)``.

    ``ckpt`` (a :class:`tpudist.checkpoint.CheckpointManager`) enables
    periodic saves on its ``save_every`` cadence; pass ``start_iteration``
    (from restored meta) to resume — the loop fast-forwards through the
    deterministic epoch shuffle so the data stream continues exactly where
    the saved run left off (set_epoch semantics, ``demo.py:96-98``).

    ``chunk_step_fn`` (from :func:`make_scanned_train_step`) switches to the
    device-cached scan path when the dataset fits in HBM: the whole dataset
    is uploaded once, ``sync_every`` iterations run as one XLA program, and
    only tiny index arrays cross the host↔device boundary per window.
    Numerics and log rows are identical to the per-step path.
    """
    config = config or TrainLoopConfig()
    from tpudist.runtime import faults, watchdog

    faults.arm_from_env()  # chaos harness: TPUDIST_FAULT grammar, no code changes
    # Session OWNERSHIP: a pre-existing session belongs to the caller
    # (a serving process running the distill flywheel, a test, a larger
    # job) — this loop records into it but must not finish it.
    owns_telemetry = telemetry.active() is None
    telemetry.ensure_started()  # goodput accounting: TPUDIST_TELEMETRY=0 disarms
    if config.plan_stamp:
        # auditable auto-mode: prediction lands in the same stream the
        # measured step times do (telemetry.aggregate's plan section)
        telemetry.event("plan_selected", **config.plan_stamp)
    # live observability: scrape endpoint (TPUDIST_METRICS_PORT gates it)
    # — step-time/goodput gauges flow from the step spans via the metrics
    # feed; the training loop adds its iteration/loss gauges at each
    # metric flush (never per step)
    from tpudist.telemetry import statusz

    statusz.ensure_started()
    wd = watchdog.from_config(
        config.watchdog_timeout_s, name="train_loop",
        first_deadline_s=(config.watchdog_timeout_s or
                          watchdog.timeout_from_env() or 0.0) * 10,
    )
    with preemption_scope(config.preempt_save and ckpt is not None):
        if wd is not None:
            wd.start()
        try:
            return _dispatch_training(
                states, step_fn, loader, mesh, logger, config,
                ckpt, start_iteration, chunk_step_fn, wd,
                own_telemetry=owns_telemetry)
        finally:
            if wd is not None:
                wd.stop()


def _dispatch_training(states, step_fn, loader, mesh, logger, config,
                       ckpt, start_iteration, chunk_step_fn, wd=None,
                       own_telemetry=True):
    from tpudist.runtime import faults

    if (
        chunk_step_fn is not None
        and config.device_cache
        and loader.plan.mode == "distributed"
        and loader.plan.samples_per_shard % loader.batch_size == 0
        and loader.dataset.x.nbytes + loader.dataset.y.nbytes
        <= config.device_cache_max_bytes
    ):
        return _run_scanned(
            states, chunk_step_fn, loader, mesh, logger, config, ckpt,
            start_iteration, wd, own_telemetry=own_telemetry
        )
    sharding = batch_sharding(mesh)
    # resume fast-forward: whole epochs are skipped arithmetically; only the
    # partial first epoch's batches are skipped via the loader (index-level,
    # nothing materialized).
    batches_per_epoch = len(loader)
    epoch = start_iteration // batches_per_epoch
    iteration = epoch * batches_per_epoch
    skip_in_epoch = start_iteration - iteration
    pbar = _make_pbar(config, initial=start_iteration)

    deferred = _DeferredMetrics(logger, config) if logger is not None else None
    tele = telemetry.active()
    last_losses = None
    preempted = False
    with StepSpans(tele) as steps:
        while iteration < config.total_iterations and not preempted:
            loader.set_epoch(epoch)
            iteration += skip_in_epoch
            skip, skip_in_epoch = skip_in_epoch, 0
            for x, y in _data_wait_iter(loader.iter_from(skip), tele):
                if iteration >= config.total_iterations:
                    break
                faults.inject_step(iteration)  # chaos: kill/sigterm@step
                bs = x.shape[0]
                gx, gy = shard_batch((x, y), sharding)
                states, losses = steps.run(iteration, step_fn, states, gx, gy)
                if wd is not None:
                    # Pet AFTER the step: the first pet must land past the XLA
                    # compile so the watchdog's first-deadline slack covers it.
                    wd.pet()
                last_losses = losses
                if deferred is not None and iteration % config.log_every == 0:
                    deferred.add(iteration, bs, losses)
                iteration += 1
                if ckpt is not None:
                    ckpt.maybe_save(
                        iteration, states, {"iteration": iteration, "epoch": epoch}
                    )
                    if wd is not None:
                        wd.pet()  # a save making I/O progress is not a hang
                if (config.preempt_save and ckpt is not None
                        and iteration < config.total_iterations
                        and iteration % max(1, config.sync_every) == 0
                        and _preemption_check()):
                    preempted = True
                    break
                if pbar is not None:
                    pbar.update(1)
            if not preempted:  # the preempted break leaves epoch mid-flight
                epoch += 1

    if pbar is not None:
        pbar.close()
    finalize_run(states, iteration=iteration, epoch=epoch,
                 preempted=preempted, ckpt=ckpt, logger=logger,
                 flush=deferred.flush if deferred is not None else None,
                 own_telemetry=own_telemetry)
    final_losses = (
        {k: float(jax.device_get(v)) for k, v in last_losses.items()}
        if last_losses is not None
        else {}
    )
    return states, final_losses


def _run_scanned(
    states, chunk_step_fn, loader, mesh, logger, config, ckpt,
    start_iteration, wd=None, own_telemetry=True
):
    """Device-cached scan loop (see ``run_training``).

    The per-epoch global permutation (DistributedSampler/set_epoch
    semantics) is precomputed host-side exactly as the host path derives
    it — global batch ``t`` of epoch ``e`` is the concatenation of every
    shard's ``t``-th batch, matching the layout
    ``make_array_from_process_local_data`` gives the host path — and only
    the int32 index windows are shipped to the device.
    """
    import dataclasses as _dc

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from tpudist.data.sharding import epoch_indices

    plan = loader.plan
    B = loader.batch_size
    repl = NamedSharding(mesh, PartitionSpec())
    x_np, y_np = loader.dataset.x, loader.dataset.y
    x_all = jax.make_array_from_callback(x_np.shape, repl, lambda i: x_np[i])
    y_all = jax.make_array_from_callback(y_np.shape, repl, lambda i: y_np[i])

    shard_plans = [_dc.replace(plan, shard_id=i) for i in range(plan.num_shards)]
    batches_per_epoch = plan.samples_per_shard // B

    def global_batches(epoch):
        per_shard = [epoch_indices(p, epoch) for p in shard_plans]
        for t in range(batches_per_epoch):
            yield np.concatenate([s[t * B : (t + 1) * B] for s in per_shard])

    pbar = _make_pbar(config, initial=start_iteration)

    total = config.total_iterations
    save_every = ckpt.config.save_every if ckpt is not None else 0
    iteration = start_iteration
    epoch = start_iteration // batches_per_epoch
    batch_in_epoch = start_iteration % batches_per_epoch
    gen = None
    pending_losses = []  # (first_iteration, device dict of (K,) losses)
    last_losses = None

    from tpudist.runtime import faults

    tele = telemetry.active()
    preempted = False
    with StepSpans(tele) as steps:
        while iteration < total:
            faults.inject_step(iteration)  # chaos: kill/sigterm at window edges
            # window length: sync cadence, save cadence, and budget boundaries
            k = min(max(1, config.sync_every), total - iteration)
            if save_every > 0:
                to_save = save_every - (iteration % save_every)
                k = min(k, to_save)
            idx_rows = []
            # host-side index/window assembly = the scanned path's data stall
            with telemetry.span(names.DATA_WAIT):
                while len(idx_rows) < k:
                    if gen is None:
                        gen = global_batches(epoch)
                        for _ in range(batch_in_epoch):
                            next(gen)
                        batch_in_epoch = 0
                    for row in gen:
                        idx_rows.append(row)
                        if len(idx_rows) == k:
                            break
                    else:
                        gen = None
                        epoch += 1
            idx = jax.device_put(np.stack(idx_rows).astype(np.int32), repl)
            # the unit of this loop's ``step`` span is a window of k steps
            states, losses = steps.run(iteration, chunk_step_fn, states,
                                       x_all, y_all, idx, steps=len(idx_rows))
            if wd is not None:
                # Pet AFTER the window: the first pet must land past the XLA
                # compile so the watchdog's first-deadline slack covers it.
                wd.pet()
            last_losses = losses
            if logger is not None:
                pending_losses.append((iteration, losses))
                if len(pending_losses) * k >= config.sync_every:
                    _flush_scanned(pending_losses, logger, config)
                    pending_losses = []
            iteration += len(idx_rows)
            if ckpt is not None:
                ckpt.maybe_save(iteration, states, {"iteration": iteration, "epoch": epoch})
                if wd is not None:
                    wd.pet()  # a save making I/O progress is not a hang
            if pbar is not None:
                pbar.update(len(idx_rows))
            # Window edges are the natural (all-process-agreed) preemption
            # boundaries of the scanned path.  A signal during the FINAL
            # window is not a preemption — the run completed.
            if (config.preempt_save and ckpt is not None
                    and iteration < total and _preemption_check()):
                preempted = True
                break

    if pbar is not None:
        pbar.close()
    finalize_run(states, iteration=iteration, epoch=epoch,
                 preempted=preempted, ckpt=ckpt, logger=logger,
                 flush=(lambda: _flush_scanned(pending_losses, logger,
                                               config))
                 if logger is not None else None,
                 own_telemetry=own_telemetry)
    final_losses = {}
    if last_losses is not None:
        fetched = jax.device_get(last_losses)
        final_losses = {k_: float(v[-1]) for k_, v in fetched.items()}
    return states, final_losses


def _flush_scanned(pending, logger, config):
    """Fetch queued (K,) loss windows in one transfer and emit per-iteration
    log rows (values are already global means — computed over the globally
    sharded batch inside the compiled window)."""
    if not pending:
        return
    with telemetry.span("metric_flush", rows=len(pending)):
        fetched = jax.device_get([losses for _, losses in pending])
    for (first_it, _), window in zip(pending, fetched):
        length = len(next(iter(window.values())))
        for j in range(length):
            if (first_it + j) % config.log_every == 0:
                logger.log(
                    {
                        f"{config.metric_prefix}{name}": float(vals[j])
                        for name, vals in window.items()
                    },
                    commit=True,
                )
    # live gauges, once per flush (the scanned-path twin of
    # _DeferredMetrics.flush — both loops keep the scrape view current)
    from tpudist.telemetry import metrics

    first_it, _ = pending[-1]
    last_window = fetched[-1]
    length = len(next(iter(last_window.values())))
    metrics.set_train_gauges(
        first_it + length - 1,
        {k: float(vals[-1]) for k, vals in last_window.items()})
