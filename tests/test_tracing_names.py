"""The one vocabulary of names (``tpudist/telemetry/names.py``) where the
program writes it: scopes in the lowered LM step, spans on the profiler's
clock, the step helper's arrival-to-arrival ``step`` spans, and the compile
listener's spans and events.  (The kernel names in a compiled TPU step are
checked in ``test_chip_compile.py``, the one file that describes a chip.)"""

import dataclasses
import json
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from tpudist import telemetry
from tpudist.telemetry import names
from tpudist.train.loop import StepSpans


# ---------------------------------------------------------------------------
# (a) scopes in the lowered step


@pytest.fixture(scope="module")
def step_op_names():
    """Every ``op_name`` of a tiny ``make_lm_train_step`` lowered on CPU."""
    from tpudist.models import create_transformer
    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import init_lm_state, make_lm_train_step

    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=16, vocab=64, d_model=32, n_layers=2,
        n_heads=2, d_ff=64)
    tx = optax.adam(1e-3)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    step = make_lm_train_step(module.apply, tx, mesh, accum_steps=2)
    lowered = step.lower(init_lm_state(params, tx),
                         jnp.zeros((4, 16), jnp.int32))
    # (inside the accumulation scan's body the names start afresh, without
    # the ``jit(step)/`` prefix, so every location string is kept)
    found = set(re.findall(r'loc\("([^"]+)"',
                           lowered.as_text(debug_info=True)))
    # the pattern decoder's step: the mixers' and the expert layer's scopes
    from tpudist.models.hybrid import HybridLM, HybridSizes

    hybrid = HybridLM(
        vocab=64, layer_types=(names.LINEAR, names.FULL),
        sizes=HybridSizes(d_model=32, n_heads=2, n_kv_heads=1, head_dim=16,
                          rotary_dim=4, linear_key_heads=1,
                          linear_value_heads=2, linear_key_dim=8,
                          linear_value_dim=8, n_experts=4, held=2, top_k=2,
                          expert_width=16, shared_width=16), remat=True)
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = hybrid.init(jax.random.PRNGKey(0), tokens)
    lowered = make_lm_train_step(hybrid.apply, tx, mesh).lower(
        init_lm_state(params, tx), tokens)
    found |= set(re.findall(r'loc\("([^"]+)"',
                            lowered.as_text(debug_info=True)))
    # layers of one sublayer: the state-space mixer's scopes and the latent
    # projections round an expert layer's routed experts
    hybrid = HybridLM(
        vocab=64, layer_types=(names.STATE_SPACE, names.EXPERT_LAYER),
        sizes=dataclasses.replace(
            hybrid.sizes, one_sublayer=True, norm=names.PLAIN, ssm_heads=2,
            ssm_head_dim=8, ssm_state=8, ssm_chunk=32,
            scoring=names.SIGMOID_BIAS, routed_scale=2.5,
            expert_fn=names.RELU2, latent_width=16, shared_scored=False),
        remat=True)
    params = hybrid.init(jax.random.PRNGKey(0), tokens)
    lowered = make_lm_train_step(hybrid.apply, tx, mesh).lower(
        init_lm_state(params, tx), tokens)
    found |= set(re.findall(r'loc\("([^"]+)"',
                            lowered.as_text(debug_info=True)))
    # two softmax kinds behind a gate a head: a window layer's own scope,
    # and the gate's nested in both
    from tpudist.models.hybrid import SoftmaxSizes

    hybrid = HybridLM(
        vocab=64, layer_types=(names.FULL, names.WINDOW),
        sizes=dataclasses.replace(
            hybrid.sizes, one_sublayer=False, latent_width=None, ffn_width=32,
            attention=names.HEAD_GATED_ATTN, softmax_kinds=(
                (names.FULL, SoftmaxSizes(2, 1, rotary_dim=8)),
                (names.WINDOW, SoftmaxSizes(4, 1, window=16, rotary_dim=16)))),
        remat=True, feed_forwards=(names.DENSE_FFN, names.EXPERT_SHARE))
    params = hybrid.init(jax.random.PRNGKey(0), tokens)
    lowered = make_lm_train_step(hybrid.apply, tx, mesh).lower(
        init_lm_state(params, tx), tokens)
    found |= set(re.findall(r'loc\("([^"]+)"',
                            lowered.as_text(debug_info=True)))
    # a delta rule whose decay is a number a channel behind low-rank gates,
    # and latent attention: the two mixers' scopes and what nests in them
    hybrid = HybridLM(
        vocab=64, layer_types=(names.CHANNEL_LINEAR, names.LATENT),
        sizes=dataclasses.replace(
            hybrid.sizes, n_heads=2, linear_key_heads=2, linear_gate_rank=4,
            latent_rank=12, latent_key_dims=(24, 8), latent_value_dim=16),
        remat=True)
    params = hybrid.init(jax.random.PRNGKey(0), tokens)
    lowered = make_lm_train_step(hybrid.apply, tx, mesh).lower(
        init_lm_state(params, tx), tokens)
    return found | set(re.findall(r'loc\("([^"]+)"',
                                  lowered.as_text(debug_info=True)))


def _under(scope: str, op_name: str) -> bool:
    return re.search(rf"(^|[/(]){scope}([/)]|$)", op_name) is not None


@pytest.mark.parametrize("scope", names.SCOPES)
def test_the_lowered_step_carries_every_scope(step_op_names, scope):
    assert [n for n in step_op_names if _under(scope, n)], scope


def test_a_backward_op_carries_the_transpose_mark_and_its_sublayer(
        step_op_names):
    for scope in (names.ATTN, names.MLP, names.EMBED, names.HEAD,
                  names.LOSS, names.LINEAR_ATTN, names.DELTA_RULE,
                  names.MOE, names.EXPERTS, names.SHARED_EXPERT, names.SSM,
                  names.SSD_SCAN, names.LATENT_PROJ, names.WINDOW_ATTN,
                  names.HEAD_GATE, names.SSM_CONV, names.SSM_NORM, names.KDA,
                  names.KDA_GATE, names.LATENT_ATTN, names.LATENT_KV):
        assert [n for n in step_op_names
                if names.BACKWARD_MARK in n and _under(scope, n)], scope
    # the optimizer is not differentiated: no transposed op under it
    assert not [n for n in step_op_names
                if names.BACKWARD_MARK in n and _under(names.OPTIMIZER, n)]


@pytest.mark.parametrize("tokens, n_experts, held, k, moved", [
    (32, 4, 2, 2, ("gather",)), (512, 64, 1, 8, ("gather", "scatter-add"))],
    ids=["one_buffer", "windows"])
def test_the_combine_scope_nests_in_moe_forward_and_backward(
        tokens, n_experts, held, k, moved):
    """Inside the share layer's loop over blocks the lowered names start
    afresh, so the nesting is read off the compiled text, where the
    profiler takes an operation's scope from: ``moe_combine`` under ``moe``
    (it stays inside what ``moe`` reads), forward and backward; where the
    share takes windows, round the gathers into a window and the
    scatter-adds out of it."""
    from tpudist.parallel import moe

    key = jax.random.split(jax.random.PRNGKey(0), 5)
    d, width = 16, 8
    assert (moe.share_windows(tokens, k, held, n_experts)[1] > 1) == (
        "scatter-add" in moved)
    params = {"router": jax.random.normal(key[0], (d, n_experts)),
              "experts": {
        "gate": jax.random.normal(key[1], (held, d, width)),
        "up": jax.random.normal(key[2], (held, d, width)),
        "down": jax.random.normal(key[3], (held, width, d))}}
    x = jax.random.normal(key[4], (tokens, d))
    loss = lambda p, x: jnp.sum(jnp.sin(moe.expert_share(
        p, x, n_experts=n_experts, held=held, first_expert=1, k=k)[0]))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    # the gathers: a constant the compiler hoists out of the loop loses
    # the loop's names with it
    for op in moved:
        ops = [n for n in re.findall(r'op_name="([^"]+)"', text)
               if _under(names.MOE_COMBINE, n) and n.endswith("/" + op)]
        assert ops and all(_under(names.MOE, n) for n in ops), op
        assert {names.BACKWARD_MARK in n for n in ops} == {False, True}, op


def test_scopes_do_not_change_what_the_step_computes():
    """Scopes are metadata: the same state and tokens give the same loss
    and update with ``jax.named_scope`` turned into a no-op."""
    import contextlib
    from unittest import mock

    from tpudist.models import create_transformer
    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import init_lm_state, make_lm_train_step

    module, params = create_transformer(
        jax.random.PRNGKey(1), seq_len=8, vocab=32, d_model=16, n_layers=1,
        n_heads=2, d_ff=32)
    tx = optax.adam(1e-2)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    tokens = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % 32

    def one_step():
        step = make_lm_train_step(module.apply, tx, mesh, donate_state=False)
        return step(init_lm_state(params, tx), tokens)

    state_a, loss_a = one_step()
    with mock.patch.object(jax, "named_scope",
                           lambda name: contextlib.nullcontext()):
        state_b, loss_b = one_step()
    assert float(loss_a) == float(loss_b)
    for a, b in zip(jax.tree.leaves(state_a), jax.tree.leaves(state_b)):
        assert jnp.array_equal(a, b)


# ---------------------------------------------------------------------------
# (c) spans on the profiler's clock; the package without jax


def test_a_span_shows_on_the_host_rows_of_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=options)
        try:
            with telemetry.span("x_marks_the_span"):
                with telemetry.span(names.DISPATCH):
                    jnp.ones((8, 8)).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
    finally:
        telemetry.finish(write_report=False)
    (path,) = (tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU"]
    events = {e.name: e for line in host[0].lines for e in line.events
              if e.name in ("x_marks_the_span", names.DISPATCH)}
    assert set(events) == {"x_marks_the_span", names.DISPATCH}
    outer, inner = events["x_marks_the_span"], events[names.DISPATCH]
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)


@pytest.mark.parametrize("module", ["tpudist.telemetry",
                                    "tpudist.telemetry.names"])
def test_the_telemetry_package_still_does_not_import_jax(module):
    """Importing it does not, and neither does a span's look for the
    profiler's annotation: without jax it is the shared no-op."""
    code = (f"import sys, {module}; "
            "from tpudist.telemetry import spans; "
            "assert spans._trace_annotation('x') is spans._NULL_SPAN; "
            "assert 'jax' not in sys.modules, 'jax was imported'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# ---------------------------------------------------------------------------
# (d) the step helper


class FakeClock:
    """The one clock of a step-helper test, injected where the spans under
    test and the fake device read theirs (``time.monotonic``, ``time.time``)
    and where the test itself sleeps: time passes only where the test says
    so, whatever the host's load.  Everything else of ``time`` is the real
    module's."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self) -> float:
        return self.now

    def time(self) -> float:
        return 1.7e9 + self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture()
def clock(monkeypatch):
    from tpudist.telemetry import spans
    from tpudist.train import loop

    fake = FakeClock()
    monkeypatch.setattr(spans, "time", fake)
    monkeypatch.setattr(loop, "time", fake)
    return fake


class FakeDevice:
    """Runs one step at a time, ``step_s`` each by ``clock``; a result is
    ready when its step has finished, and waiting for it is counted."""

    def __init__(self, step_s: float, clock=time):
        self.step_s, self.free_at, self.waits = step_s, 0.0, 0
        self.clock = clock

    def step(self, state, _batch):
        self.free_at = max(self.free_at, self.clock.monotonic()) + self.step_s
        return state + 1, FakeResult(self, self.free_at)


class FakeResult:
    def __init__(self, device, ready_at):
        self.device, self.ready_at = device, ready_at

    def block_until_ready(self):
        self.device.waits += 1
        clock = self.device.clock
        clock.sleep(max(0.0, self.ready_at - clock.monotonic()))
        return self


def _spans(session, name):
    return [r for r in session.ring
            if r["kind"] == "span" and r["name"] == name]


def test_step_spans_sum_to_the_loops_wall_time_and_dispatch_does_not(
        tmp_path, clock):
    """On the injected clock the loader takes 1 ms a batch, an enqueue 0.5 ms
    and a device step 20 ms, and nothing else takes any time: the sums are
    exact, not within what a loaded host leaves of a ``time.sleep``."""
    session = telemetry.start(tmp_path, rank=0, generation=0)
    try:
        device, state, n = FakeDevice(0.02, clock), 0, 25

        def enqueue(state, batch):
            clock.sleep(0.0005)
            return device.step(state, batch)

        t0 = clock.monotonic()
        with StepSpans(session) as steps:
            for i in range(n):
                with session.span(names.DATA_WAIT):
                    clock.sleep(0.001)
                state, result = steps.run(i, enqueue, state, None,
                                          steps=1)
        wall = clock.monotonic() - t0
        assert state == n and isinstance(result, FakeResult)
        # the loader and the enqueue hide behind the device, but for the
        # first two batches: the first step is drained before the second
        assert wall == pytest.approx(2 * 0.0015 + n * 0.02, abs=1e-9)
        step, compile_ = _spans(session, names.STEP), _spans(session, names.COMPILE)
        assert len(compile_) == 1 and len(step) == n - 1
        assert all(s["steps"] == 1 for s in step)
        total = sum(s["dur"] for s in step + compile_)
        # all of the loop's time but those two batches' waits, which nothing
        # was in flight to hide
        assert total == pytest.approx(wall - 2 * 0.001, abs=1e-6)
        # every step but the drained last one took one device step
        assert sorted(s["dur"] for s in step)[len(step) // 2] \
            == pytest.approx(0.02, abs=1e-6)
        dispatch = _spans(session, names.DISPATCH)
        assert len(dispatch) == n
        assert sum(s["dur"] for s in dispatch) \
            == pytest.approx(n * 0.0005, abs=1e-6)
        # host work while a step is in flight is the step's child: detail
        # for the goodput sum, not a second copy of the wall-clock
        waits = _spans(session, names.DATA_WAIT)
        assert "parent" not in waits[0]            # nothing in flight yet
        assert {w.get("parent") for w in waits[2:]} == {"step"}
        assert {d.get("parent") for d in dispatch[2:]} == {"step"}
        # ... and nothing is left on the thread's span stack
        with session.span("after"):
            pass
        assert "parent" not in _spans(session, "after")[0]
    finally:
        telemetry.finish(write_report=False)


def test_step_spans_disarmed_add_no_sync():
    device, state = FakeDevice(0.005), 0
    with StepSpans(None) as steps:
        for i in range(5):
            state, _ = steps.run(i, device.step, state, None)
    assert state == 5 and device.waits == 0


def test_step_spans_unwind_when_the_loop_raises(tmp_path):
    session = telemetry.start(tmp_path, rank=0, generation=0)
    try:
        device = FakeDevice(0.001)
        with pytest.raises(RuntimeError, match="boom"):
            with StepSpans(session) as steps:
                for i in range(3):
                    steps.run(i, device.step, 0, None)
                raise RuntimeError("boom")
        with session.span("after"):
            pass
        assert "parent" not in _spans(session, "after")[0]
    finally:
        telemetry.finish(write_report=False)


def test_goodput_moves_a_steps_data_wait_out_of_step(tmp_path, clock):
    """The aggregator's half of the contract: a ``data_wait`` that lies in
    a ``step`` span is the same wall-clock under its own heading.  On the
    injected clock: ten batches of 4 ms, ten device steps of 10 ms."""
    from tpudist.telemetry.aggregate import aggregate_run

    session = telemetry.start(tmp_path, rank=0, generation=0)
    device, state = FakeDevice(0.01, clock), 0
    with StepSpans(session) as steps:
        for i in range(10):
            with session.span(names.DATA_WAIT):
                clock.sleep(0.004)
            state, _ = steps.run(i, device.step, state, None)
    telemetry.finish(write_report=False)
    report = aggregate_run(tmp_path)
    goodput = {k: v["s"] for k, v in report["goodput"].items()}
    assert goodput["data"] == pytest.approx(10 * 0.004, abs=1e-5)
    # ... and is no longer in ``step``: eight waits lay inside a step span
    # (the first two with nothing in flight: the first step is drained)
    assert goodput["step"] == pytest.approx(9 * 0.01 - 8 * 0.004, abs=1e-5)
    assert goodput["compile"] == pytest.approx(0.01, abs=1e-5)
    assert sum(goodput.values()) == pytest.approx(report["wall_clock_s"],
                                                  abs=1e-5)
    assert report["wall_clock_s"] == pytest.approx(2 * 0.004 + 10 * 0.01,
                                                   abs=1e-5)
    assert report["per_rank"][0]["overlap_s"] == 0.0


# ---------------------------------------------------------------------------
# (e) the compile listener


@pytest.fixture()
def cache_in(tmp_path, monkeypatch):
    """JAX's persistent cache on, in a fresh directory, with no floor, for
    one test; the listeners registered as ``initialize()`` registers them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from tpudist.runtime import compilation_cache

    monkeypatch.setenv("TPUDIST_COMPILATION_CACHE", "on")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    assert compilation_cache.enable_compilation_cache() == str(
        tmp_path / "cache")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    yield compilation_cache
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_the_compile_listener_records_spans_a_miss_and_then_a_hit(
        tmp_path, cache_in):
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        def fresh():
            # a new function object: the jit cache misses, the HLO is the same
            def poly(x):
                for k in range(40):
                    x = jnp.sin(x) * (k + 1.5) + jnp.tanh(x @ x)
                return x.sum()
            return jax.jit(poly)

        import numpy as np

        # (a host array: making a device array would compile a program too)
        x = np.full((64, 64), 0.3751, np.float32)
        before = cache_in.event_counts()
        fresh()(x).block_until_ready()
        mid = cache_in.event_counts()
        assert mid[names.COMPILE_CACHE_MISS] \
            == before[names.COMPILE_CACHE_MISS] + 1
        assert mid[names.COMPILE_CACHE_HIT] == before[names.COMPILE_CACHE_HIT]
        fresh()(x).block_until_ready()   # the second, process-local lookup
        after = cache_in.event_counts()
        assert after[names.COMPILE_CACHE_HIT] \
            == before[names.COMPILE_CACHE_HIT] + 1
        assert after[names.COMPILE_CACHE_MISS] == mid[names.COMPILE_CACHE_MISS]
        recorded = [r["name"] for r in session.ring]
        assert recorded.count(names.COMPILE_CACHE_MISS) == 1
        assert recorded.count(names.COMPILE_CACHE_HIT) == 1
        for name in (names.XLA_TRACE, names.XLA_LOWER,
                     names.XLA_BACKEND_COMPILE):
            spans = _spans(session, name)
            assert spans, name
            # detail for the goodput sum, started ``dur`` before it was told
            assert all(s["parent"] == names.XLA_PARENT for s in spans)
            assert all(s["dur"] >= 1e-3 for s in spans)
    finally:
        telemetry.finish(write_report=False)


def _poly(name: str, depth: int = 40):
    """A new function object under ``name``: the jit cache misses, the HLO
    and the module's name are the same, so the persistent cache can hit."""
    def poly(x):
        for k in range(depth):
            x = jnp.sin(x) * (k + 1.5) + jnp.tanh(x @ x)
        return x.sum()

    poly.__name__ = name
    return jax.jit(poly)


@pytest.mark.parametrize("armed", [True, False], ids=["session", "no_session"])
@pytest.mark.parametrize("case", [names.CACHE_MISS, names.CACHE_HIT,
                                  names.UNCACHED])
def test_a_backend_compile_says_what_it_was_and_what_it_costs_cold(
        tmp_path, cache_in, case, armed):
    """``xla_backend_compile`` is a compile or a load; the cache's own
    events, told on the compiling thread just before it, say which.  The
    process-wide record holds the same with or without a session."""
    import numpy as np

    if case == names.UNCACHED:   # compiled and not written: under the floor
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e6)
    program = f"poly_{case}_{armed}"
    x = np.full((64, 64), 0.3751, np.float32)
    session = (telemetry.start(tmp_path / "tele", rank=0, generation=0)
               if armed else None)
    try:
        assert telemetry.active() is session
        assert program not in cache_in.compile_seconds()
        # (deep enough to compile for a second or so where the entry's own
        # figure is compared: JAX keeps it in whole seconds)
        depth = 200 if case == names.CACHE_HIT else 40
        _poly(program, depth)(x).block_until_ready()
        first = cache_in.compile_seconds()[program]
        assert first["compiles"] == 1 and first["cache_hits"] == 0
        assert first["cold_compile_s"] == first["compile_or_load_s"] > 0
        assert first["trace_s"] > 0 and first["lower_s"] > 0
        row = first
        if case == names.CACHE_HIT:
            _poly(program, depth)(x).block_until_ready()
            both = cache_in.compile_seconds()[program]
            assert both["compiles"] == 2 and both["cache_hits"] == 1
            row = {k: both[k] - first[k] for k in both}
            # the entry carries the compile it saved, cut to whole seconds:
            # what the miss's span held, less the key, the serialising and
            # the write round it
            assert int(first["cold_compile_s"]) - 1 <= row["cold_compile_s"] \
                <= first["cold_compile_s"]
        if session is None:
            return
        spans = [r for r in session.ring if r.get("fun") == program]
        assert [r["name"] for r in spans].count(names.XLA_TRACE) \
            == [r["name"] for r in spans].count(names.XLA_LOWER) \
            == row["compiles"] + (case == names.CACHE_HIT)
        compiles = [r for r in spans
                    if r["name"] == names.XLA_BACKEND_COMPILE]
        assert [r["cache"] for r in compiles] == (
            [names.CACHE_MISS, names.CACHE_HIT] if case == names.CACHE_HIT
            else [case])
        last = compiles[-1]
        assert last["cold_s"] == pytest.approx(row["cold_compile_s"])
        assert last["dur"] == pytest.approx(row["compile_or_load_s"])
        if case == names.CACHE_HIT:
            assert 0 < last["load_s"] <= last["dur"]
            assert last["cold_s"] == int(last["cold_s"]) != last["dur"]
        else:
            assert "load_s" not in last
            assert last["cold_s"] == pytest.approx(last["dur"])
    finally:
        if armed:
            telemetry.finish(write_report=False)


def test_a_compile_on_a_second_thread_leaves_the_firsts_cache_events():
    """The cache's events wait for THEIR thread's compile to end: a compile
    that ends on another thread in between reads ``uncached``."""
    import threading

    from tpudist.runtime import compilation_cache as cache

    hit = next(e for e, n in names.XLA_CACHE_EVENTS.items()
               if n == names.COMPILE_CACHE_HIT)
    backend = next(e for e, n in names.XLA_DURATION_SPANS.items()
                   if n == names.XLA_BACKEND_COMPILE)
    cache._on_event(hit)
    cache._on_duration(names.XLA_CACHE_TIME_SAVED, 7.0)
    cache._on_duration(names.XLA_CACHE_RETRIEVAL, 0.5)
    other = threading.Thread(target=cache._on_duration, args=(backend, 2.0),
                             kwargs={"fun_name": "jit(on_the_second_thread)"})
    other.start()
    other.join(timeout=60)
    assert not other.is_alive()
    cache._on_duration(backend, 0.6, fun_name="jit(on_the_first_thread)")
    cache._on_duration(backend, 0.4, fun_name="jit(on_the_first_thread)")
    rows = cache.compile_seconds()
    assert rows["on_the_second_thread"] == dict(
        trace_s=0, lower_s=0, compile_or_load_s=2.0, cold_compile_s=2.0,
        compiles=1, cache_hits=0)
    # ... and taken by the compile they were for, not by the one after it
    assert rows["on_the_first_thread"] == dict(
        trace_s=0, lower_s=0, compile_or_load_s=1.0, cold_compile_s=7.9,
        compiles=2, cache_hits=1)


def test_compile_seconds_names_the_programs_that_cost_most(monkeypatch):
    """The record is bounded: past its size the cheapest program so far is
    summed under one key with every duration under the span floor."""
    from tpudist.runtime import compilation_cache as cache

    trace = next(e for e, n in names.XLA_DURATION_SPANS.items()
                 if n == names.XLA_TRACE)
    monkeypatch.setattr(cache, "_seconds", {})
    monkeypatch.setattr(cache, "_MAX_PROGRAMS", 3)
    for name, seconds in [("a", 0.5), ("b", 0.002), ("c", 0.3),
                          ("tiny", 0.0004), ("d", 0.1), ("b", 0.004)]:
        cache._on_duration(trace, seconds, fun_name=name)
    rows = cache.compile_seconds()
    assert {k: v["trace_s"] for k, v in rows.items()} == pytest.approx(
        {"a": 0.5, "c": 0.3, "b": 0.004, cache.OTHER_PROGRAMS: 0.1024})


@pytest.mark.parametrize("reduce_dtype", [None, jnp.bfloat16],
                         ids=["plain", "compressed"])
def test_every_xla_span_of_a_lowered_step_says_its_program(
        tmp_path, reduce_dtype):
    """``names.STEP_PROGRAM`` is what both builders call the function they
    jit, and what JAX reports for its trace and its lowering; the nested
    traces inside it carry their own names."""
    from tpudist.models import create_transformer
    from tpudist.runtime import compilation_cache as cache
    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import init_lm_state, make_lm_train_step

    cache.enable_compilation_cache()   # the listeners, as initialize() does
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=16, vocab=64, d_model=32, n_layers=2,
        n_heads=2, d_ff=64)
    tx = optax.adam(1e-3)
    state = init_lm_state(params, tx)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    tokens = jnp.zeros((4, 16), jnp.int32)
    before = cache.compile_seconds().get(names.STEP_PROGRAM,
                                         {"trace_s": 0, "lower_s": 0})
    session = telemetry.start(tmp_path, rank=0, generation=0)
    try:
        make_lm_train_step(module.apply, tx, mesh,
                           grad_reduce_dtype=reduce_dtype).lower(
            state, tokens)
        spans = [r for r in session.ring if r["kind"] == "span"
                 and r["name"] in names.XLA_DURATION_SPANS.values()]
    finally:
        telemetry.finish(write_report=False)
    assert spans and all(s["fun"] for s in spans)
    traces = [s for s in spans if s["name"] == names.XLA_TRACE]
    outermost = max(traces, key=lambda s: s["dur"])
    assert outermost["fun"] == names.STEP_PROGRAM
    assert all(outermost["t"] <= s["t"] + 1e-3 for s in traces)
    (lowering,) = [s for s in spans if s["name"] == names.XLA_LOWER
                   and s["fun"] == names.STEP_PROGRAM]
    after = cache.compile_seconds()[names.STEP_PROGRAM]
    assert after["trace_s"] - before["trace_s"] \
        == pytest.approx(outermost["dur"], abs=1e-6)
    assert after["lower_s"] - before["lower_s"] \
        == pytest.approx(lowering["dur"], abs=1e-6)


def test_the_report_lists_the_programs_the_compile_component_went_to(
        tmp_path, capsys):
    """``python -m tpudist.telemetry report`` over a recorded stream: seven
    programs as the listener writes them, the five that cost most listed."""
    from tpudist.telemetry.__main__ import main

    session = telemetry.start(tmp_path, rank=0, generation=0)
    t0 = time.monotonic()
    xla = dict(parent=names.XLA_PARENT)
    with session.span(names.COMPILE):
        for i, fun in enumerate(["make_state", "norms", "delta", "tiny_a",
                                 "tiny_b", "_flash_forward"]):
            session.record_span(names.XLA_TRACE, t0, 0.1 * (i + 1),
                                {"fun": fun}, **xla)
        session.record_span(names.XLA_TRACE, t0, 9.0,
                            {"fun": names.STEP_PROGRAM}, **xla)
        session.record_span(names.XLA_LOWER, t0, 6.0,
                            {"fun": names.STEP_PROGRAM}, **xla)
        session.record_span(
            names.XLA_BACKEND_COMPILE, t0, 12.5,
            {"fun": names.STEP_PROGRAM, "cache": names.CACHE_HIT,
             "load_s": 12.25, "cold_s": 131.25}, **xla)
        session.record_span(
            names.XLA_BACKEND_COMPILE, t0, 2.0,
            {"fun": "make_state", "cache": names.UNCACHED, "cold_s": 2.0},
            **xla)
    telemetry.finish(write_report=False)
    assert main(["report", str(tmp_path), "--json"]) == 0
    programs = json.loads(capsys.readouterr().out)["compile_programs"]
    assert [p["fun"] for p in programs] == [
        names.STEP_PROGRAM, "make_state", "_flash_forward", "tiny_b",
        "tiny_a"]
    assert programs[0] == dict(
        fun=names.STEP_PROGRAM, trace_lower_s=15.0, compile_or_load_s=12.5,
        load_s=12.25, cold_s=131.25, cache={names.CACHE_HIT: 1})
    assert programs[1]["cache"] == {names.UNCACHED: 1}
    assert programs[2]["cache"] == {}
    assert main(["report", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "| step | 15.000 | 12.500 | hit x1 | 12.250 | 131.250 |" in out
    assert "| _flash_forward | 0.600 | 0.000 | - |" in out
    assert "| norms |" not in out


def test_initialize_records_one_init_span_in_a_single_process(
        tmp_path, monkeypatch):
    from tpudist.runtime import bootstrap

    for var in ("RANK", "WORLD_SIZE", "SLURM_PROCID", "SLURM_NTASKS",
                "TPUDIST_PROCESS_ID", "TPUDIST_NUM_PROCESSES",
                "OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(bootstrap, "_INITIALIZED_CTX", None)
    session = telemetry.start(tmp_path, rank=0, generation=0)
    try:
        ctx = bootstrap.initialize()
        assert not ctx.is_distributed
        (init,) = _spans(session, names.INIT)
        assert init["world"] == 1 and "parent" not in init
    finally:
        telemetry.finish(write_report=False)
        monkeypatch.setattr(bootstrap, "_INITIALIZED_CTX", None)


# ---------------------------------------------------------------------------
# (f) the attention's operand layout, said once a call site when it is traced


def test_attn_layout_is_spelled_once_in_the_vocabulary():
    from pathlib import Path

    import tpudist

    package = Path(tpudist.__file__).resolve().parent
    spelled = [p.relative_to(package).as_posix()
               for p in sorted(package.rglob("*.py"))
               if f'"{names.ATTN_LAYOUT}"' in p.read_text()]
    assert spelled == ["telemetry/names.py"]


def test_attn_layout_fires_once_a_call_site_at_trace_time_and_not_a_step(
        tmp_path):
    from tpudist.models import create_transformer
    from tpudist.runtime.mesh import MeshConfig, make_mesh
    from tpudist.train import init_lm_state, make_lm_train_step

    layers = 3
    module, params = create_transformer(
        jax.random.PRNGKey(2), seq_len=8, vocab=32, d_model=16,
        n_layers=layers, n_heads=2, d_ff=32)
    tx = optax.adam(1e-2)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    tokens = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % 32
    state = init_lm_state(params, tx)
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        def events():
            return [r for r in session.ring if r["name"] == names.ATTN_LAYOUT]

        step = make_lm_train_step(module.apply, tx, mesh, donate_state=False)
        state, _ = step(state, tokens)       # traced here: one a layer
        assert len(events()) == layers
        # a CPU, and 8 tokens: the reason names the first test that failed
        assert all(r["kind"] == "event" and r["layout"] == names.HEAD_MAJOR
                   and r["reason"] == names.WHY_SEQ for r in events())
        # (the state a step returns is laid out on the mesh, the seeded one
        # was not: jit traces once more for it, and that trace speaks too)
        state, _ = step(state, tokens)
        traced = len(events())
        assert traced in (layers, 2 * layers)
        for _ in range(3):                   # the compiled step says nothing
            state, loss = step(state, tokens)
        jax.block_until_ready(loss)
        assert len(events()) == traced
    finally:
        telemetry.finish(write_report=False)


def test_a_windowed_calls_attn_layout_says_its_grid(tmp_path, monkeypatch):
    """Of a windowed call where the flash kernels run, the event says the
    window, the tiles, how the band-edge tiles are worked, what that
    computes, the key axis of the grid and the share of a head's grid steps
    that find a live tile (the laguna cell's sliding layers: 32 steps for
    31 live); the vocabulary's comment names every field."""
    from pathlib import Path
    from types import SimpleNamespace

    from tpudist.ops import attention

    monkeypatch.setattr(
        jax, "devices",
        lambda *a, **k: [SimpleNamespace(device_kind="TPU v5 lite")])
    monkeypatch.setattr(attention, "flash_attention_packed",
                        lambda qkv, h, kv, *a: qkv[..., : h * 128])
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        for window in (512, None):
            jax.eval_shape(
                lambda qkv: attention.attention_within(window).packed(
                    qkv, 36, 4),
                jax.ShapeDtypeStruct((1, 8192, 44 * 128), jnp.bfloat16))
        windowed, plain = [
            {k: v for k, v in r.items() if k not in ("t", "kind", "name", "dur", "gen", "rank")}
            for r in session.ring if r["name"] == names.ATTN_LAYOUT]
    finally:
        telemetry.finish(write_report=False)
    assert windowed == dict(
        layout=names.PACKED, diag_sub=256, computed_over_live=1.4999,
        window=512, tiles=[512, 512], grid_kv=2, live_steps_share=0.9688)
    assert plain == dict(layout=names.PACKED, diag_sub=256,
                         computed_over_live=1.0311)
    source = Path(names.__file__).read_text()
    comment = source[:source.index("ATTN_LAYOUT =")].rsplit("\n\n", 1)[1]
    for field in (*windowed, "reason"):
        assert f"``{field}=``" in comment, field


def test_the_pattern_decoder_says_its_layout_once_a_trace(tmp_path):
    """``mixer_layout`` (the layer kinds) once a trace of the decoder and
    ``moe_layout`` once an expert layer, at trace time and not a step."""
    from tpudist.models.hybrid import HybridLM, HybridSizes

    hybrid = HybridLM(
        vocab=64, layer_types=(names.LINEAR, names.FULL),
        sizes=HybridSizes(d_model=32, n_heads=2, n_kv_heads=1, head_dim=16,
                          rotary_dim=4, linear_key_heads=1,
                          linear_value_heads=2, linear_key_dim=8,
                          linear_value_dim=8, n_experts=4, held=2,
                          first_expert=2, top_k=2, expert_width=16,
                          shared_width=16))
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = hybrid.init(jax.random.PRNGKey(0), tokens)
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        apply = jax.jit(hybrid.apply)
        for _ in range(3):
            apply(params, tokens).block_until_ready()
        events = [r for r in session.ring if r.get("kind") == "event"]
    finally:
        telemetry.finish(write_report=False)
    mixers = [r for r in events if r["name"] == names.MIXER_LAYOUT]
    experts = [r for r in events if r["name"] == names.MOE_LAYOUT]
    assert len(mixers) == 1 and len(experts) == 2
    assert mixers[0]["kinds"] == [names.LINEAR, names.FULL]
    assert {(r["experts"], r["held"], r["first"], r["top_k"], r["dropless"],
             r["combine"]) for r in experts} == {
                 (4, 2, 2, 2, True, names.PICK_MAJOR)}


@pytest.mark.parametrize(
    "tokens, k, held, width, rows, blocks, window_rows, at_most, strip_rows,"
    " combine", [
        (8192, 22, 8, 1024, 180224, 1, 22528, 8, 2816, names.SCATTER_ADD),
        (16384, 10, 32, 2048, 81920, 2, 81920, 1, 81920, names.PICK_MAJOR)],
    ids=["8_of_512_held", "32_of_512_held"])
def test_moe_layout_says_the_windows_a_share_takes(
        tmp_path, tokens, k, held, width, rows, blocks, window_rows, at_most,
        strip_rows, combine):
    """The two cells' expert layers at their own shapes, traced only: a
    64th of the experts held takes its arrivals through windows of eight
    even shares (22,528 rows, at most 8 of them a block) and scatters them
    by strips of one (2,816 rows), a 16th keeps one buffer of the bound
    (``window_rows`` and ``strip_rows`` say the bound itself)."""
    from tpudist.parallel import moe

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    params = {"router": jax.ShapeDtypeStruct((width, 512), jnp.float32),
              "experts": {"up": shape(held, width, 64),
                          "down": shape(held, 64, width)}}
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        y, counts, windows, strips = jax.eval_shape(
            lambda p, x: moe.expert_share(
                p, x, n_experts=512, held=held, first_expert=0, k=k,
                expert_fn=moe.relu2_ffn), params, shape(tokens, width))
        said = [r for r in session.ring if r.get("name") == names.MOE_LAYOUT]
    finally:
        telemetry.finish(write_report=False)
    assert (y.shape, counts.shape, windows.shape, strips.shape) == (
        (tokens, width), (held,), (blocks,), (blocks,))
    (e,) = said
    assert (e["buffer_rows"], e["blocks"], e["window_rows"],
            e["windows_at_most"], e["strip_rows"], e["combine"],
            e["dropless"]) == (
                rows, blocks, window_rows, at_most, strip_rows, combine, True)


@pytest.mark.parametrize("feed_forward, remat, keeps, columns", [
    (names.DENSE_FFN, True, [names.MIXER_OUT, names.FFN_GATE, names.FFN_UP,
                             names.FFN_OUT], 32 + 2 * 48 + 32),
    (names.EXPERT_SHARE, True, [names.MIXER_OUT, names.ROUTER_LOGITS,
                                names.ROUTER_PICKS], 32 + 8 + 8),
    (names.DENSE_FFN, False, [], 0),
    (names.EXPERT_LAYER, True, [
        names.EXPERT_OUT, names.ROUTER_LOGITS, names.ROUTER_PICKS,
        names.LATENT_IN, names.SHARED_GATE, names.SHARED_UP],
     24 + 8 + 8 + 24 + 2 * 16),
    (names.CHANNEL_LINEAR, True, [names.MIXER_OUT, names.ROUTER_LOGITS,
                                  names.ROUTER_PICKS], 32 + 8 + 8)],
    ids=["dense_arm", "expert_share_arm", "no_remat",
         "one_sublayer_expert_layer", "a_decay_a_channel"])
def test_mixer_layout_says_what_a_rematerialised_layer_keeps(
        tmp_path, feed_forward, remat, keeps, columns):
    """``remat_keeps``: the names kept besides a layer's input;
    ``remat_kept_bytes_per_layer``: ``MIXER_OUT``'s ``tokens x d_model x
    itemsize`` and, in the dense arm, ``tokens x (2 x ffn_width + d_model)
    x itemsize`` more; in a decoder of one-sublayer layers an expert
    layer's ``EXPERT_OUT`` and ``LATENT_IN``, ``tokens x latent_width x
    itemsize`` each, its router's float32 logits (4 experts: 8 bf16
    columns), its 2 picks and their scores (int32 and float32: 8 bf16
    columns) and its gated shared expert's two first products'; in the
    expert-share arm of a two-sublayer layer ``MIXER_OUT`` and the router's
    two; and of the two kinds of layer here the delta-rule one alone
    ``DELTA_INVERSE``, behind ``names.KDA_KEEPS`` (what the mixer computes
    on the way to its scan: ``tokens x heads x 8 x itemsize`` bytes each
    here) where its decay is a number a channel."""
    from tpudist.models.hybrid import HybridLM, HybridSizes

    arms = dict(feed_forward=feed_forward)
    kinds = (names.LINEAR, names.FULL)
    if feed_forward == names.EXPERT_LAYER:
        arms = dict(feed_forward=names.EXPERT_SHARE, one_sublayer=True,
                    latent_width=24, shared_scored=False)
        kinds = (names.FULL, names.EXPERT_LAYER)
    if feed_forward == names.CHANNEL_LINEAR:
        arms = dict(feed_forward=names.EXPERT_SHARE, linear_gate_rank=4)
        kinds = (names.CHANNEL_LINEAR, names.FULL)
    hybrid = HybridLM(
        vocab=64, layer_types=kinds, dtype=jnp.bfloat16,
        remat=remat, sizes=HybridSizes(
            d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, rotary_dim=4,
            linear_key_heads=1, linear_value_heads=2, linear_key_dim=8,
            linear_value_dim=8, ffn_width=48,
            n_experts=4, held=2, top_k=2, expert_width=16, shared_width=16,
            **arms))
    tokens = jnp.zeros((2, 64), jnp.int32)
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        jax.eval_shape(hybrid.init, jax.random.PRNGKey(0), tokens)
        # (the decoder's own event: a KDA call site says one of its own)
        said = [r for r in session.ring
                if r.get("name") == names.MIXER_LAYOUT and "kinds" in r]
    finally:
        telemetry.finish(write_report=False)
    assert len(said) == 1
    held = 2 * 64 * columns * 2
    if remat and kinds[0] in (names.LINEAR, names.CHANNEL_LINEAR):
        # the layer whose mixer scans by the delta rule keeps its chunks'
        # inverse besides, float32 whatever the compute dtype: 2 heads x a
        # chunk of 64 numbers a position (a decay a channel before it what
        # its mixer hands the scan, 2 heads x 8 bf16 columns each); the
        # attention layer nothing more, so the event says both a layer
        channel = (list(names.KDA_KEEPS)
                   if kinds[0] == names.CHANNEL_LINEAR else [])
        keeps = [keeps + channel + [names.DELTA_INVERSE], keeps]
        held = [held + 2 * 64 * 2 * 64 * 4
                + len(channel) * 2 * 64 * 2 * 8 * 2, held]
    assert said[0]["remat_keeps"] == keeps
    assert said[0]["remat_kept_bytes_per_layer"] == held
