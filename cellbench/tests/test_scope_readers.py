"""The readers of ``readers/scopes.py`` and ``readers/program.py`` on the
trace cut from the first good traced run of ``cgpt590m-train-1chip`` with
the program's names in it (PR 25; chip 0, two whole steps, the op lines with
their ``tf_op`` kept on the event metadata, the python thread's host spans,
``lm_batch`` among them), and on the older four-chip trace, which has none
of the names: there every new reader gives ``None`` and does not raise."""

import gzip
import json
import shutil
from pathlib import Path

import pytest

from cellbench import run as harness
from cellbench import trace_reduce as tr
from cellbench.readers import device, program, scopes
from tpudist.telemetry import names

DATA = Path(__file__).resolve().parent / "data"
SCOPED = DATA / "trace_1chip_scoped"
CELL = "recorded-cell"
FLASH = ("flash_fwd_ms_per_step", "flash_bwd_dq_ms_per_step",
         "flash_bwd_dkv_ms_per_step")
PHASES = ("fwd_ms_per_step", "bwd_ms_per_step", "optimizer_ms_per_step")
SCOPE_READERS = FLASH + PHASES + ("scoped_device_pct",
                                  "attn_glue_ms_per_step")
PROGRAM_READERS = ("loader_ms_per_step", "runtime_init_s",
                   "step_trace_lower_s", "compile_cache_misses")


def reading(trace_root: Path, monkeypatch, counters=None):
    """A ``Reading`` whose traced run left ``trace_root`` where the harness
    leaves a cell's trace (``run.SCRATCH / "trace" / <cell>``)."""
    scratch = trace_root.parent / f"scratch_{trace_root.name}"
    (scratch / "trace").mkdir(parents=True, exist_ok=True)
    link = scratch / "trace" / CELL
    if not link.exists():
        link.symlink_to(trace_root, target_is_directory=True)
    monkeypatch.setattr(harness, "SCRATCH", scratch)
    reds = tr.reduce_trace(tr.load(tr.find_xplane(link)), vocab=50257)
    return harness.Reading({"name": CELL}, {}, {}, counters or {}, {}, reds)


@pytest.fixture(scope="session")
def scoped_trace_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("trace_1chip_scoped")
    out = root / "plugins" / "profile" / "recorded"
    out.mkdir(parents=True)
    with gzip.open(SCOPED / "1chip.xplane.pb.gz", "rb") as f, \
            open(out / "1chip.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    return root


@pytest.fixture(scope="session")
def expected():
    return json.loads((SCOPED / "expected.json").read_text())


@pytest.mark.parametrize("metric", SCOPE_READERS)
def test_a_scope_reader_gives_its_fixed_number(
        scoped_trace_dir, expected, monkeypatch, metric):
    r = reading(scoped_trace_dir, monkeypatch)
    assert getattr(scopes, metric)(r) == pytest.approx(
        expected["metrics"][metric], rel=1e-9)


def test_the_flash_kernels_sum_to_the_custom_calls(
        scoped_trace_dir, expected, monkeypatch):
    r = reading(scoped_trace_dir, monkeypatch)
    red = r.reds[0]
    assert (red.steps, red.custom_call_ns) == (
        expected["steps"], expected["custom_call_ns"])
    by_kernel = [getattr(scopes, m)(r) for m in FLASH]
    assert all(ms > 1.0 for ms in by_kernel)
    assert sum(by_kernel) == pytest.approx(
        red.custom_call_ns / red.steps / 1e6, rel=1e-9)
    assert sum(by_kernel) == pytest.approx(
        device.attn_kernel_ms_per_step(r), rel=1e-9)


def test_the_phases_and_the_unscoped_rest_sum_to_the_busy_time(
        scoped_trace_dir, expected, monkeypatch):
    r = reading(scoped_trace_dir, monkeypatch)
    red = r.reds[0]
    parts = [getattr(scopes, m)(r) for m in PHASES]
    rest = scopes.unscoped_ms_per_step(r)
    assert red.busy_ns == expected["busy_ns"]
    assert sum(parts) + rest == pytest.approx(
        red.busy_ns / red.steps / 1e6, rel=1e-9)
    assert scopes.scoped_device_pct(r) == pytest.approx(
        100.0 * sum(parts) / (sum(parts) + rest), rel=1e-9)
    assert scopes.scoped_device_pct(r) > 95
    # the backward pass costs about twice the forward; the optimizer, fused
    # into the weight-gradient fusions, shows only what was left outside
    assert 1.5 < parts[1] / parts[0] < 2.5 and parts[2] < 0.1 * parts[0]


def test_scopes_come_from_the_event_metadata_and_kernels_from_the_text(
        scoped_trace_dir):
    path = tr.find_xplane(scoped_trace_dir)
    by_name = scopes.op_scopes(str(path))[0]
    assert len(by_name) > 500
    assert all(s.startswith("jit(step)/") for s in by_name.values())
    chip = scopes.load(str(path))[0]
    assert {op.kernel for op in chip.ops} - {None} == set(names.FLASH_KERNELS)
    for op in chip.ops:
        if op.kernel:   # scope and kernel name agree, from two places
            assert f"/{names.ATTN}/{op.kernel}/" in op.scope
            assert (op.phase == "bwd") == (op.kernel != names.FLASH_FWD)
    # the host rows of the same trace carry the program's loader span
    host = tr.load(path).host
    assert [e for es in host.values() for e in es
            if e.name == names.LM_BATCH]


@pytest.mark.parametrize("metric", SCOPE_READERS)
def test_a_trace_without_the_names_reads_as_nothing(
        recorded_trace_dir, monkeypatch, metric):
    """``trace_fsdp4`` dates from before the names (96 ``shard_map.<n>``
    custom calls with empty ``kernel_metadata``, no ``tf_op``)."""
    r = reading(recorded_trace_dir, monkeypatch)
    assert r.reds   # the old readers still read it
    assert getattr(scopes, metric)(r) is None
    # all of its busy time is the unscoped rest
    assert scopes.unscoped_ms_per_step(r) == pytest.approx(
        sum(d.busy_ns / d.steps for d in r.reds.values()) / len(r.reds) / 1e6,
        rel=1e-3)


@pytest.mark.parametrize("metric", SCOPE_READERS)
def test_no_trace_at_all_reads_as_nothing(tmp_path, monkeypatch, metric):
    monkeypatch.setattr(harness, "SCRATCH", tmp_path)
    r = harness.Reading({"name": CELL}, {}, {}, {}, {}, {})
    assert getattr(scopes, metric)(r) is None


def test_a_file_that_is_no_trace_gives_no_scopes(tmp_path):
    junk = tmp_path / "junk.xplane.pb"
    junk.write_bytes(b"\xff\xff\xff\xff not a protobuf")
    assert scopes.op_scopes(str(junk)) == {}
    assert scopes.op_scopes(str(tmp_path / "missing.xplane.pb")) == {}


@pytest.mark.parametrize("metric", PROGRAM_READERS)
def test_program_readers_with_telemetry_disarmed(monkeypatch, metric):
    """The rehearsal's state (``conftest`` disarms telemetry): no session,
    so no span to read; the compile counter is the process's own."""
    from tpudist import telemetry

    assert telemetry.active() is None
    r = harness.Reading({"name": CELL}, {}, {}, {"steps": 3}, {}, {})
    value = getattr(program, metric)(r)
    if metric == "compile_cache_misses":
        assert value == 0
    else:
        assert value is None


def test_program_readers_read_the_active_sessions_ring(tmp_path):
    import time

    from tpudist import telemetry

    session = telemetry.start(tmp_path, rank=0, generation=0)
    try:
        now = time.monotonic()
        with session.span(names.INIT):
            time.sleep(0.002)
        # set-up: a 30 ms trace holding a nested 10 ms one, then 5 ms of
        # lowering; a trace after the window's first batch does not count
        session.record_span(names.XLA_TRACE, now - 1.00, 0.030)
        session.record_span(names.XLA_TRACE, now - 0.99, 0.010)
        session.record_span(names.XLA_LOWER, now - 0.90, 0.005)
        session.record_span(names.LM_BATCH, now - 0.80, 0.004)   # set-up's
        session.record_span(names.XLA_LOWER, now - 0.70, 0.002)
        for k, dur in enumerate((0.001, 0.003, 0.002)):          # window's
            session.record_span(names.LM_BATCH, now - 0.5 + 0.1 * k, dur)
        session.record_span(names.XLA_TRACE, now - 0.25, 0.050)
        r = harness.Reading({"name": CELL}, {}, {}, {"steps": 3}, {}, {})
        assert program.loader_ms_per_step(r) == pytest.approx(2.5)
        assert program.runtime_init_s(r) == pytest.approx(0.002, abs=0.002)
        assert program.step_trace_lower_s(r) == pytest.approx(0.037, abs=1e-5)
    finally:
        telemetry.finish(write_report=False)
