"""``trace_reduce`` on hand-built event lists (the interval arithmetic) and
on the trace recorded from the first traced four-chip run (fixed numbers)."""

import json
from pathlib import Path

import pytest

from cellbench import trace_reduce as tr
from cellbench.trace_reduce import Event

DATA = Path(__file__).resolve().parent / "data"


def test_union_subtract_and_gaps():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 2), (1, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [
        (0, 2), (3, 5), (7, 9)]
    assert tr.idle_gaps([(1, 2), (4, 6)], 0, 8) == [(0, 1), (2, 4), (6, 8)]
    assert tr.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]


MATMUL = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kOutput, calls=%c"
FLASH = ('%block_0.3 = (bf16[4,8,8]{2,1,0}) custom-call(bf16[4,8,8]{2,1,0} %q), '
         'custom_call_target="tpu_custom_call", frontend_attributes='
         '{kernel_metadata={"kernel":"' + tr.FLASH_KERNELS[0] + '"}}')
#: a Mosaic custom call of another kernel than attention's, and one unnamed
EXPERTS = ('%experts.7 = (bf16[8,8]{1,0}) custom-call(bf16[8,8]{1,0} %x), '
           'custom_call_target="tpu_custom_call", frontend_attributes='
           '{kernel_metadata={"kernel":"grouped_matmul"}}')
UNNAMED = ('%shard_map.4 = (bf16[8,8]{1,0}) custom-call(bf16[8,8]{1,0} %x), '
           'custom_call_target="tpu_custom_call", frontend_attributes='
           '{kernel_metadata={}}')
GATHER = "%all-gather-start.3 = (f32[2,8]{1,0}, f32[8,8]{1,0}) all-gather-start(f32[2,8]{1,0} %w)"
GATHER_DONE = "%all-gather-done.3 = f32[8,8]{1,0} all-gather-done((f32[2,8]{1,0}, f32[8,8]{1,0}) %all-gather-start.3)"
ADAM = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %state_opt_state_0__mu__x), kind=kLoop, calls=%d"
LOGITS = "%fusion.8 = f32[4,7,512]{2,1,0} fusion(bf16[4,7,512]{2,1,0} %l), kind=kLoop, calls=%e"


def synthetic_lines():
    """Three runs of one program, 100 ns apart; in each period a matmul
    0-40, a flash call 40-55, an all-gather in flight 30-70 whose ``-done``
    the core waits in 55-70 (so 30-55 is hidden under compute and 55-70
    exposed), an optimizer op 70-75, a logits op 75-80, idle 80-100."""
    modules, ops, in_flight = [], [], []
    for k in range(3):
        t = 1000 + 100 * k
        modules.append(Event("jit_step(1)", t, t + 80))
        ops += [Event(MATMUL, t, t + 40), Event(FLASH, t + 40, t + 55),
                Event(GATHER_DONE, t + 55, t + 70),
                Event(ADAM, t + 70, t + 75), Event(LOGITS, t + 75, t + 80)]
        in_flight.append(Event(GATHER, t + 30, t + 70))
    modules.append(Event("jit_other", 990, 995))
    return {tr.MODULES_LINE: modules, tr.OPS_LINE: ops,
            tr.ASYNC_LINE: in_flight}


def test_whole_steps_busy_idle_and_exposed_collectives():
    red = tr.reduce_device(synthetic_lines(), vocab=512)
    assert (red.window_ns, red.steps) == (200, 2)   # first start -> last start
    assert red.busy_ns == 160 and red.gaps[0] == (1080, 1100)
    assert len(red.gaps) == 2
    assert red.collective_ns == 80 and red.collective_exposed_ns == 30
    assert red.custom_call_ns == 30
    assert red.kernel_ns == {tr.FLASH_KERNELS[0]: 30}
    assert red.group_ns == {"collectives": 30, "flash custom calls": 30,
                            "other custom calls": 0, "matmul fusions": 80,
                            "optimizer update": 10, "loss/logits": 10,
                            "other": 0}
    assert red.op_ns["fusion.1"] == 80 and red.op_ns["block_0.3"] == 30
    assert tr.group_of(Event(LOGITS, 0, 1)) == "other"   # no vocab given


def test_a_custom_call_is_attentions_only_by_its_name():
    """A kernel that is not attention's goes under its own name and one
    without a name under ``other custom calls``; neither is counted as a
    flash kernel, both are custom calls."""
    lines = synthetic_lines()
    lines[tr.OPS_LINE] = [
        Event(EXPERTS if e.name == MATMUL else
              UNNAMED if e.name == LOGITS else e.name, e.start, e.end)
        for e in lines[tr.OPS_LINE]]
    red = tr.reduce_device(lines, vocab=512)
    assert red.kernel_ns == {tr.FLASH_KERNELS[0]: 30, "grouped_matmul": 80}
    assert red.custom_call_ns == 30 + 80 + 10
    assert red.group_ns["flash custom calls"] == 30
    assert red.group_ns["grouped_matmul"] == 80
    assert red.group_ns["other custom calls"] == 10
    assert red.group_ns["matmul fusions"] == red.group_ns["loss/logits"] == 0
    assert [tr.kernel_of(Event(n, 0, 1))
            for n in (FLASH, EXPERTS, UNNAMED, MATMUL)] == [
                tr.FLASH_KERNELS[0], "grouped_matmul", None, None]
    rows = dict(tr.breakdown({0: red}, tr.Trace({}, {}), ())["device_ops"])
    assert rows["[grouped_matmul]"] == 80e-9
    assert rows["[flash custom calls]"] == 30e-9


def test_a_single_run_of_the_program_gives_no_whole_step():
    lines = synthetic_lines()
    lines[tr.MODULES_LINE] = lines[tr.MODULES_LINE][:1]
    assert tr.reduce_device(lines) is None


def test_gaps_are_named_by_the_host_span_that_covers_them():
    host = {"python": [Event("data_wait", 1078, 1090),
                       Event("dispatch", 1090, 1097),
                       Event("loss_fetch", 1100, 1180),
                       Event("not_ours", 1000, 1300)]}
    named = tr.name_gaps([(1080, 1100), (1180, 1200)], host,
                         ("data_wait", "dispatch", "loss_fetch"))
    assert named[0] == ("data_wait", 20e-9)
    assert named[1] == ("(no span)", 20e-9)


@pytest.fixture(scope="module")
def recorded(recorded_trace_dir):
    return tr.load(tr.find_xplane(recorded_trace_dir))


def test_recorded_chip_trace_gives_fixed_numbers(recorded):
    want = json.loads((DATA / "trace_fsdp4" / "expected.json").read_text())
    reds = tr.reduce_trace(recorded, vocab=50257)
    assert sorted(reds) == want["chips"]
    for chip, red in reds.items():
        w = want["by_chip"][str(chip)]
        assert red.steps == w["steps"]
        assert red.window_ns == pytest.approx(w["window_ns"], rel=1e-9)
        assert red.busy_ns == pytest.approx(w["busy_ns"], rel=1e-9)
        assert red.collective_ns == pytest.approx(w["collective_ns"], rel=1e-9)
        assert red.collective_exposed_ns == pytest.approx(
            w["collective_exposed_ns"], rel=1e-9)
        assert red.custom_call_ns == pytest.approx(w["custom_call_ns"],
                                                   rel=1e-9)
        assert red.kernel_ns == w["kernel_ns"]   # from before the names
        assert 0 < red.busy_ns <= red.window_ns
        assert red.collective_exposed_ns <= red.collective_ns
        for group, ns in w["group_ns"].items():
            assert red.group_ns[group] == pytest.approx(ns, rel=1e-9)
    b = tr.breakdown(reds, recorded, ("data_wait", "dispatch", "loss_fetch"))
    assert [g[0] for g in b["idle_gaps"]] == want["gap_names"]
