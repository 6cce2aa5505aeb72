"""The readers of ``readers/scopes.py`` and ``readers/program.py`` on the
trace cut from the first good traced run of ``cgpt590m-train-1chip`` with
the program's names in it (PR 25; chip 0, two whole steps, the op lines with
their ``tf_op`` kept on the event metadata, the python thread's host spans,
``lm_batch`` among them), and on the older four-chip trace, which has none
of the names: there every scope reader gives ``None`` and does not raise,
and attention's by-name readers of ``readers/device.py`` raise, since its
steps hold custom calls that carry no name."""

import json
from pathlib import Path

import pytest

from cellbench import flops
from cellbench import run as harness
from cellbench import trace_reduce as tr
from cellbench.readers import device, program, scopes
from tpudist.telemetry import names

DATA = Path(__file__).resolve().parent / "data"
HERE = DATA.parents[1]
SCOPED = DATA / "trace_1chip_scoped"
CELL = "recorded-cell"
FLASH = ("flash_fwd_ms_per_step", "flash_bwd_dq_ms_per_step",
         "flash_bwd_dkv_ms_per_step")
PHASES = ("fwd_ms_per_step", "bwd_ms_per_step", "optimizer_ms_per_step")
SCOPE_READERS = FLASH + PHASES + ("scoped_device_pct",
                                  "attn_glue_ms_per_step")
SHARES = ("flash_fwd_roofline", "flash_bwd_dq_roofline",
          "flash_bwd_dkv_roofline")
PROGRAM_READERS = ("loader_ms_per_step", "runtime_init_s",
                   "step_trace_lower_s", "compile_cache_misses")


def reading(trace_root: Path, monkeypatch, counters=None, config=None,
            peak=None):
    """A ``Reading`` whose traced run left ``trace_root`` where the harness
    leaves a cell's trace (``run.SCRATCH / "trace" / <cell>``)."""
    scratch = trace_root.parent / f"scratch_{trace_root.name}"
    (scratch / "trace").mkdir(parents=True, exist_ok=True)
    link = scratch / "trace" / CELL
    if not link.exists():
        link.symlink_to(trace_root, target_is_directory=True)
    monkeypatch.setattr(harness, "SCRATCH", scratch)
    reds = tr.reduce_trace(tr.load(tr.find_xplane(link)), vocab=50257)
    return harness.Reading({"name": CELL}, config or {}, peak or {},
                           counters or {}, {}, reds)


def recorded_cells_reading(trace_root: Path, monkeypatch):
    """The same with the configuration, job and chip the trace was recorded
    from (``cgpt590m-train-1chip``, TPU v5 lite)."""
    cell, config = harness.load_cell("cgpt590m-train-1chip")
    peak = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    return reading(trace_root, monkeypatch, config=config, peak=peak,
                   counters={"per_chip_batch": cell["job"]["per_chip_batch"],
                             "seq_len": cell["job"]["seq_len"]})


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
    """Attention's kernels read by name: the three by-kernel readings, the
    by-name total and the old reading (every Mosaic custom call) agree on
    this cell, all of whose 54 calls a step are flash kernels."""
    r = reading(scoped_trace_dir, monkeypatch)
    red = r.reds[0]
    assert (red.steps, red.custom_call_ns) == (
        expected["steps"], expected["custom_call_ns"])
    assert set(red.kernel_ns) == set(names.FLASH_KERNELS)
    by_kernel = [getattr(scopes, m)(r) for m in FLASH]
    assert all(ms > 1.0 for ms in by_kernel)
    assert sum(by_kernel) == pytest.approx(
        red.custom_call_ns / red.steps / 1e6, rel=1e-9)
    assert sum(by_kernel) == pytest.approx(
        device.attn_kernel_ms_per_step(r), rel=1e-9)
    assert red.group_ns["flash custom calls"] == red.custom_call_ns
    assert red.group_ns["other custom calls"] == 0


def test_the_yardsticks_kernel_names_are_the_programs():
    """``flops.py`` spells attention's kernel names once for the yardstick;
    this holds that copy against the program's vocabulary."""
    assert (flops.FLASH_FWD, flops.FLASH_BWD_DQ, flops.FLASH_BWD_DKV) == (
        names.FLASH_FWD, names.FLASH_BWD_DQ, names.FLASH_BWD_DKV)
    assert flops.FLASH_KERNELS == tuple(names.FLASH_KERNELS)
    assert tr.FLASH_KERNELS is flops.FLASH_KERNELS
    work = flops.flash_kernel_work(batch=1, seq=8, d_model=8, n_layers=1)
    assert tuple(work) == flops.FLASH_KERNELS


@pytest.mark.parametrize("metric", SHARES)
def test_a_kernels_roofline_share_gives_its_fixed_number(
        scoped_trace_dir, expected, monkeypatch, metric):
    r = recorded_cells_reading(scoped_trace_dir, monkeypatch)
    assert getattr(device, metric)(r) == pytest.approx(
        expected["metrics"][metric], rel=1e-9)


def test_the_shares_are_one_roofline_split_by_kernel(
        scoped_trace_dir, monkeypatch):
    """Each share is the kernel's least time over its own time, so their
    time-weighted mean is attention's share; each kernel owns a third of
    the algorithm's operations and none reads over 100."""
    r = recorded_cells_reading(scoped_trace_dir, monkeypatch)
    shares = [getattr(device, m)(r) for m in SHARES]
    ms = [getattr(scopes, m)(r) for m in FLASH]
    assert all(0 < x < 100 for x in shares)
    whole = device.attn_kernel_roofline(r)
    assert sum(x * t for x, t in zip(shares, ms)) / sum(ms) == pytest.approx(
        whole, rel=1e-9)
    # forward once and backward twice that; 4 + 8 bf16 tensors a layer
    b, s, d, layers = 4, 2048, 1536, 18
    work = flops.flash_kernel_work(batch=b, seq=s, d_model=d, n_layers=layers)
    total_flops = sum(w[0] for w in work.values())
    total_bytes = sum(w[1] for w in work.values())
    assert total_flops == pytest.approx(
        3 * layers * 4.0 * b * (s * (s + 1) / 2) * d, rel=1e-12)
    assert total_bytes == pytest.approx(layers * 12.0 * b * s * d * 2,
                                        rel=1e-12)
    least, bound = flops.roofline_seconds(total_flops, total_bytes, r.peak)
    assert bound == "compute"
    assert whole == pytest.approx(
        100.0 * least * 1e3 / device.attn_kernel_ms_per_step(r), rel=1e-9)


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


BY_NAME = SHARES + ("attn_kernel_ms_per_step", "attn_kernel_roofline")


@pytest.mark.parametrize("metric", SCOPE_READERS)
def test_a_trace_without_the_names_reads_as_nothing(
        recorded_trace_dir, monkeypatch, metric):
    """``trace_fsdp4`` dates from before the names (96 ``shard_map.<n>``
    custom calls with empty ``kernel_metadata``, no ``tf_op``)."""
    r = reading(recorded_trace_dir, monkeypatch)
    assert r.reds   # the older readers still read it
    assert getattr(scopes, metric)(r) is None
    # all of its busy time is the unscoped rest
    assert scopes.unscoped_ms_per_step(r) == pytest.approx(
        sum(d.busy_ns / d.steps for d in r.reds.values()) / len(r.reds) / 1e6,
        rel=1e-3)


@pytest.mark.parametrize("metric", BY_NAME)
def test_custom_calls_without_a_known_name_are_an_error_for_attention(
        recorded_trace_dir, monkeypatch, metric):
    """A step that holds Mosaic custom calls of which none carries a name
    the yardstick knows does not read as "no attention": the reader raises,
    whether the reading has the cell's configuration or none."""
    for r in (reading(recorded_trace_dir, monkeypatch),
              recorded_cells_reading(recorded_trace_dir, monkeypatch)):
        assert all(d.custom_call_ns > 0 and not d.kernel_ns
                   for d in r.reds.values())
        with pytest.raises(LookupError, match="none carries a kernel name"):
            getattr(device, metric)(r)


@pytest.mark.parametrize("gone", flops.FLASH_KERNELS)
def test_a_counted_kernel_that_did_not_run_is_an_error_not_a_smaller_number(
        scoped_trace_dir, monkeypatch, gone):
    """The program renames or fuses one of the kernels the architecture
    counts for every step: attention's readers, and that kernel's share,
    raise instead of reading the rest as the whole."""
    whole = recorded_cells_reading(scoped_trace_dir, monkeypatch)
    r = whole._replace(reds={chip: d._replace(kernel_ns={
        (k + "_v2" if k == gone else k): ns for k, ns in d.kernel_ns.items()})
        for chip, d in whole.reds.items()})
    share = {k: m for k, m in zip(flops.FLASH_KERNELS, SHARES)}
    for metric in ("attn_kernel_ms_per_step", "attn_kernel_roofline",
                   share[gone]):
        with pytest.raises(LookupError, match=f"counts .*{gone}"):
            getattr(device, metric)(r)
    for kernel, metric in share.items():
        if kernel != gone:
            assert 0 < getattr(device, metric)(r) < 100
    # without an architecture's count nothing says the kernel had to run
    assert 0 < device.attn_kernel_ms_per_step(r._replace(config={})) < (
        device.attn_kernel_ms_per_step(whole))


@pytest.mark.parametrize("metric", SCOPE_READERS + BY_NAME)
def test_no_trace_at_all_reads_as_nothing(tmp_path, monkeypatch, metric):
    monkeypatch.setattr(harness, "SCRATCH", tmp_path)
    r = harness.Reading({"name": CELL}, {}, {}, {}, {}, {})
    module = device if metric in BY_NAME else scopes
    assert getattr(module, metric)(r) is None


def test_a_step_without_a_custom_call_has_no_attention_kernel_to_read(
        scoped_trace_dir, monkeypatch):
    """Attention left to XLA: no Mosaic custom call in the step, so the
    by-name readers find nothing and say so with ``None``."""
    r = recorded_cells_reading(scoped_trace_dir, monkeypatch)
    r = r._replace(reds={chip: d._replace(kernel_ns={})
                         for chip, d in r.reds.items()})
    assert all(d.custom_call_ns == 0 for d in r.reds.values())
    assert [getattr(device, m)(r) for m in BY_NAME] == [None] * len(BY_NAME)


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
        session.record_span(names.INIT, now - 1.20, 0.002)
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
        assert program.runtime_init_s(r) == pytest.approx(0.002, abs=1e-9)
        assert program.step_trace_lower_s(r) == pytest.approx(0.037, abs=1e-5)
    finally:
        telemetry.finish(write_report=False)
