"""Runner ``train_lm``: one decoder-LM training job, as a user of tpudist
starts it, timed for a window.

The call sequence is ``chip_smoke.py``'s train phase (``initialize -> mesh
-> module -> init_lm_state -> make_lm_train_step``) with three differences
a real size forces: the weights are the benchmark's own, made on the device
from the seed (the architecture's ``init_weights``) and handed to the
program; the state is born already laid out (``jit`` with ``out_shardings``),
because a 1.4 B-parameter Adam state cannot exist on one chip first; and the
batches come from the program's loader over a seeded corpus file.

Nothing here knows an architecture: the module the step is built round, the
benchmark's weights in its parameter tree and back, and the reference's
forward pass are the configuration's module under ``archs/``
(``archs.load(config)``, by its ``model_type``).

One ``Job`` holds the compiled step; set-up drives THAT object from the
seeded state through the first ``check.steps`` steps (through the window's
own feed and call), takes the readings ``correct`` rests on, and hands the
same object and state to the window.  After the window the program's state
is freed and the plain reference follows the same steps on the same batches.
"""

from __future__ import annotations

import collections
import contextlib
import math
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from cellbench import archs, checks, corpus, reference
from tpudist.data.lm import make_lm_loader
from tpudist.parallel import fsdp_sharding
from tpudist.runtime import initialize
from tpudist.runtime.mesh import MeshConfig, make_mesh
from tpudist.train import init_lm_state, make_lm_train_step, token_sharding

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
HOST_SPANS = ("data_wait", "dispatch", "loss_fetch")
RECOVER_S = 3.0


def say(tag: str, **fields) -> None:
    def fmt(v):
        return f"{v:.6g}" if isinstance(v, float) else str(v)

    print(f"[{tag}] " + " ".join(f"{k}={fmt(v)}" for k, v in fields.items()),
          flush=True)


def norm_vector(leaves: list) -> jax.Array:
    """Per-tensor L2 norms of a program tree's ``named_leaves``."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves])


def projection_matrix(leaves: list) -> jax.Array:
    """Per-tensor ``reference.sign_projections`` of the same."""
    return jnp.stack([reference.sign_projections(x) for x in leaves])


# ---------------------------------------------------------------------------


class Job:
    """Mesh, module, optimizer and the compiled step of one cell; built
    once in a process, whatever the number of seeds it then runs."""

    def __init__(self, cell: dict, config: dict, devices):
        job = cell["job"]
        arch = archs.load(config)
        m = arch.dims(config)
        self.config, self.job, self.arch = config, job, arch
        self.chips = len(devices)
        self.seq = job["seq_len"]
        self.batch = job["per_chip_batch"] * self.chips
        if self.seq > m["seq"]:
            raise ValueError(f"seq_len {self.seq} is over the configuration's "
                             f"{m['seq']} positions")
        initialize()
        self.mesh = make_mesh(MeshConfig(data=self.chips), devices=devices)
        self.module = arch.build_module(config, job)
        self.lr = job["optimizer"]["learning_rate"]
        if job["optimizer"]["name"] != "adam":
            raise ValueError("the reference follows Adam only")
        self.tx = optax.adam(self.lr)

        def make_state(seed_words):
            return init_lm_state(
                arch.program_tree(config,
                                  arch.init_weights(config, seed_words)),
                self.tx)

        abstract = jax.eval_shape(make_state, reference.split_seed(0))
        layout = job["state_layout"]
        if layout == "fsdp":
            self.sharding = fsdp_sharding(self.mesh, abstract)
        elif layout == "replicated":
            repl = NamedSharding(self.mesh, PartitionSpec())
            self.sharding = jax.tree.map(lambda _: repl, abstract)
        else:
            raise ValueError(f"unknown state_layout {layout!r}")
        self.make_state = jax.jit(make_state, out_shardings=self.sharding)
        self.tok_sharding = token_sharding(self.mesh)
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract, self.sharding)
        tokens = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32,
                                      sharding=self.tok_sharding)
        t0 = time.perf_counter()
        self.step = make_lm_train_step(
            self.module.apply, self.tx, self.mesh,
            state_sharding=self.sharding if layout == "fsdp" else None,
            accum_steps=job["accum_steps"]).lower(state, tokens).compile()
        mem = self.step.memory_analysis()
        loaded_s = time.perf_counter() - t0
        text = self.step.as_text()
        calls = text.count("tpu_custom_call")
        say("job", mesh=dict(self.mesh.shape), batch=self.batch, seq=self.seq,
            step_compile_or_load_s=time.perf_counter() - t0,
            tpu_custom_calls=calls,
            argument_GB=getattr(mem, "argument_size_in_bytes", 0) / 1e9,
            temp_GB=getattr(mem, "temp_size_in_bytes", 0) / 1e9)
        want = job["custom_calls_per_layer"] * m["layers"]
        if calls != want:
            raise AssertionError(
                f"the compiled step holds {calls} tpu_custom_call, the cell "
                f"expects {want}: attention did not take the flash kernel")
        for c in job["collectives_in_step"]:
            if c not in text:
                raise AssertionError(f"the compiled step holds no {c}")
        say("job", step_loaded_s=loaded_s,
            as_text_and_checks_done_s=time.perf_counter() - t0)

        def norms(tree):
            leaves = arch.named_leaves(config, tree)
            return norm_vector(leaves), projection_matrix(leaves)

        self._norms = jax.jit(norms)
        self._delta = jax.jit(lambda params, words: self._norms(
            jax.tree.map(jnp.subtract, params, arch.program_tree(
                config, arch.init_weights(config, words)))))

    def feed(self, batch: np.ndarray) -> jax.Array:
        return jax.device_put(batch, self.tok_sharding)

    def loader(self, seed: int, directory: Path):
        """The program's LM loader over this seed's corpus file."""
        m = self.arch.dims(self.config)
        path = corpus.write_corpus(
            Path(directory) / "corpus.bin", self.job["corpus"],
            vocab=m["vocab"], seq_len=self.seq, seed=seed)
        _, batches, _ = make_lm_loader(
            path, seq_len=self.seq, batch_size=self.batch,
            seed=seed % (2 ** 31), dtype="uint16")
        return batches

    def first_steps(self, seed: int, batches: list):
        """``(state, readings)``: the seeded state driven through
        ``batches`` by the timed step, and what ``correct`` compares."""
        words = reference.split_seed(seed)
        t0 = time.perf_counter()
        state = self.make_state(words)
        jax.block_until_ready(state)
        say("setup", state_on_device_s=time.perf_counter() - t0)
        losses, grad_norms, grad_proj = [], None, None
        for batch in batches:
            state, loss = self.step(state, self.feed(batch))
            losses.append(float(loss))
            if grad_norms is None:
                # Adam's first moment after one step is (1 - b1) * gradient
                norms, proj = self._norms(state.opt_state[0].mu)
                grad_norms = np.asarray(norms) / (1.0 - reference.ADAM_B1)
                grad_proj = np.asarray(proj) / (1.0 - reference.ADAM_B1)
        say("setup", first_steps_done_s=time.perf_counter() - t0)
        update_norms, update_proj = jax.device_get(
            self._delta(state.params, words))
        say("setup", readings_done_s=time.perf_counter() - t0)
        return state, dict(losses=losses, grad_norms=grad_norms,
                           grad_proj=grad_proj, update_norms=update_norms,
                           update_proj=update_proj)

    def reference_readings(self, seed: int, batches: list,
                           mode: str = "f32") -> dict:
        """The plain reference over the same steps.  Over several chips its
        weights are laid out by plain ``NamedSharding`` (XLA's partitioner,
        none of tpudist's FSDP code) and each block of rows is one row a
        chip."""
        w_sh = t_sh = None
        if self.chips > 1:
            axis = self.mesh.axis_names[0]

            def lay(shape):
                spec = [None] * len(shape)
                dims = sorted(range(len(shape)), key=lambda d: -shape[d])
                if math.prod(shape) >= 2 ** 20:
                    for d in dims:
                        if shape[d] % self.chips == 0:
                            spec[d] = axis
                            break
                return NamedSharding(self.mesh, PartitionSpec(*spec))

            w_sh = {k: lay(s) for k, s in
                    self.arch.weight_shapes(self.config).items()}
            t_sh = NamedSharding(self.mesh, PartitionSpec(axis))
        return reference.train_readings(
            self.arch, self.config, seed, batches, lr=self.lr,
            rows_per_block=self.job["reference_rows_per_block"] * self.chips,
            mode=mode, weight_sharding=w_sh, token_sharding=t_sh)


class Spans:
    """Host spans on the host clock and, while a trace is being taken, on
    the profiler's clock too (``TraceAnnotation``)."""

    def __init__(self):
        self.seconds = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds[name].append(time.perf_counter() - t0)


class CompileCounter:
    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            self.n += 1


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def window(job: Job, state, batches, *, seconds: float, trace_dir,
           capture: dict):
    """Dispatch step i, then read the loss of step i-1 (as the Trainer's LM
    loop runs ahead of the device), until ``seconds`` have passed.  A step's
    time is the gap between consecutive loss arrivals.  With ``trace_dir``
    a profiler trace is taken around ``capture['steps']`` whole steps after
    ``capture['skip_steps']``; the steps it touches are flagged."""
    spans = Spans()
    losses, arrivals = [], []
    pending, dispatched = None, 0
    start_at = stop_at = -1
    if trace_dir:
        start_at = max(1, capture["skip_steps"])
        stop_at = start_at + capture["steps"] + 1
    captured = None

    def fetch():
        nonlocal pending
        with spans("loss_fetch"):
            losses.append(float(pending))
        arrivals.append(time.perf_counter())
        pending = None

    t_begin = time.perf_counter()
    while True:
        if dispatched == start_at:
            fetch()   # the device is idle: the capture starts between steps
            captured = [time.perf_counter(), None]
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # the spans below are enough
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        with spans("data_wait"):
            tokens = job.feed(next(batches))
        with spans("dispatch"):
            state, loss = job.step(state, tokens)
        dispatched += 1
        if pending is not None:
            fetch()
        pending = loss
        if dispatched == stop_at:
            fetch()
            jax.profiler.stop_trace()
            captured[1] = time.perf_counter()
        tracing = trace_dir is not None and (captured is None
                                             or captured[1] is None)
        if time.perf_counter() - t_begin >= seconds and not tracing:
            break
    if pending is not None:
        fetch()
    jax.block_until_ready(state)
    edges = np.array([t_begin] + arrivals)
    flagged = np.zeros(len(arrivals), bool)
    if captured:
        # the profiler's teardown goes on for a moment after stop_trace
        # returns (a 1.5 s step was seen right after it), so steps that
        # start within RECOVER_S of the capture's end are flagged too
        flagged = ((edges[1:] > captured[0])
                   & (edges[:-1] < captured[1] + RECOVER_S))
    return state, dict(losses=losses, step_s=np.diff(edges), flagged=flagged,
                       window_s=arrivals[-1] - t_begin, dispatched=dispatched,
                       spans=dict(spans.seconds))


def run(*, cell: dict, config: dict, seed: int, seconds: float, trace: bool,
        devices, t0: float, scratch: Path) -> dict:
    """One run of one cell.  Returns what ``cellbench.run`` prints."""
    say("setup", imports_done_s=time.perf_counter() - t0)
    job = Job(cell, config, devices)
    say("setup", job_built_s=time.perf_counter() - t0)
    check = cell["check"]
    compiles = CompileCounter()
    with tempfile.TemporaryDirectory(prefix="cellbench_corpus_") as tmp:
        batches = job.loader(seed, Path(tmp))
        first = [next(batches) for _ in range(check["steps"])]
        state, program = job.first_steps(seed, first)
        jax.block_until_ready(state)
        setup_s = time.perf_counter() - t0
        say("setup", setup_s=setup_s, first_losses=program["losses"])
        before = compiles.n
        trace_dir = None
        if trace:
            trace_dir = Path(scratch) / "trace" / cell["name"]
        state, w = window(job, state, batches, seconds=seconds,
                          trace_dir=trace_dir, capture=cell["trace"])
        compiles_in_window = compiles.n - before
    memory_peak = peak_bytes(devices)
    say("memory", **{k: v for k, v in (devices[0].memory_stats() or {}).items()
                     if "bytes" in k})
    del state   # the reference runs after the program's state is freed

    clean = ~w["flagged"]
    step_s = w["step_s"]
    tokens_per_step = job.batch * job.seq
    rate_steps = step_s[clean] if trace else step_s
    if not len(rate_steps):
        raise RuntimeError("no step of the window lies outside the capture: "
                           "the window is too short for a traced run")
    tokens_per_s_per_chip = (tokens_per_step * len(rate_steps)
                             / float(rate_steps.sum()) / job.chips)
    step_ms_p90 = float(np.percentile(rate_steps, 90) * 1e3)
    say("window", steps=len(step_s), steps_rated=len(rate_steps),
        window_s=w["window_s"], step_ms_median=float(
            np.median(rate_steps) * 1e3), step_ms_p90=step_ms_p90,
        step_ms_max=float(rate_steps.max() * 1e3),
        tokens_per_s_per_chip=tokens_per_s_per_chip,
        loss_first=w["losses"][0], loss_last=w["losses"][-1])

    t_ref = time.perf_counter()
    ref = job.reference_readings(seed, first)
    gaps = checks.train_gaps(program, ref)
    within, lines = checks.judge(gaps, check["limits"])
    for line in lines:
        print(line, flush=True)
    say("check", reference_s=time.perf_counter() - t_ref,
        program_losses=program["losses"], reference_losses=ref["losses"])
    losses = np.asarray(w["losses"])
    finite = np.isfinite(losses)
    # how far the loss fell is printed, not judged: over 21 seeds the second
    # half of a 30 s window lay 0.24-0.59 under the seeded losses, too close
    # to a step's own swing (0.2-0.3) for a floor that never refuses a sound
    # run; the three checked steps above are what shows that the step learns
    say("check", losses_finite=bool(finite.all()),
        compiles_in_window=compiles_in_window,
        loss_seeded=float(np.mean(program["losses"])),
        loss_second_half=float(losses[len(losses) // 2:].mean()),
        window_losses=[round(float(x), 4) for x in losses])
    correct = bool(within and finite.all() and compiles_in_window == 0)

    return dict(
        correct=correct, attempted=w["dispatched"],
        failed=int((~finite).sum()), memory_peak_bytes=memory_peak,
        end_to_end={"tokens_per_s_per_chip": tokens_per_s_per_chip,
                    "step_ms_p90": step_ms_p90, "setup_s": setup_s},
        counters={"compiles_in_window": compiles_in_window,
                  "steps": len(step_s), "tokens_per_step": tokens_per_step,
                  "per_chip_batch": job.job["per_chip_batch"],
                  "seq_len": job.seq, "chips": job.chips,
                  "tokens_per_s_per_chip": tokens_per_s_per_chip,
                  "memory_peak_bytes": memory_peak},
        spans=w["spans"], host_span_names=HOST_SPANS, trace_dir=trace_dir,
        trace_hints={"vocab": job.arch.dims(config)["vocab"]})
