"""From a profiler trace (``.xplane.pb``) to numbers: the one reduction
every per-layer reader and the ``device`` block of a traced run go through.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  A TPU
trace holds one plane per chip (``/device:TPU:<n>``) whose lines include
``XLA Modules`` (one event per run of a compiled program), ``XLA Ops`` (one
event per operation on the core, named by its HLO text) and, on some chips,
``Async XLA Ops`` (operations in flight), and a ``/host:CPU`` plane whose
lines are host threads carrying the runner's ``TraceAnnotation`` spans.
All times are nanoseconds on one clock.

The arithmetic below works on plain ``Event`` lists, so the tests check it
on hand-built lists as well as on a recorded trace.
"""

from __future__ import annotations

import collections
import re
from pathlib import Path
from typing import Iterable, NamedTuple, Optional, Sequence

from cellbench.flops import FLASH_KERNELS

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
#: the lines of a chip's plane that are read: one event per run of a compiled
#: program; one per operation on the core (its name is the HLO instruction's
#: text); one per asynchronous operation in flight (copies, collectives)
MODULES_LINE, OPS_LINE, ASYNC_LINE = "XLA Modules", "XLA Ops", "Async XLA Ops"

#: op groups of the breakdown, in the order an operation is tried against
#: them; a Mosaic custom call that carries another kernel's name than
#: attention's makes a group of that name
GROUPS = ("collectives", "flash custom calls", "other custom calls",
          "matmul fusions", "optimizer update", "loss/logits", "other")
_COLL = (r"(all-gather|all-reduce|reduce-scatter|collective-permute|"
         r"all-to-all|async-collective)(-start|-done)?")
#: by the instruction's own name, or by its opcode where XLA renamed it
COLLECTIVE_NAME = re.compile(rf"^{_COLL}(\.[0-9]+)?$")
COLLECTIVE_OPCODE = re.compile(rf" {_COLL}\(")
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
#: a Mosaic custom call's event text carries the name its ``pallas_call`` was
#: given (``frontend_attributes={kernel_metadata={"kernel":"<name>"}}``)
#: whatever a ``shard_map`` round the call does to the instruction's name
KERNEL_NAME = re.compile(r'kernel_metadata=\{\s*"kernel"\s*:\s*"([^"]+)"')
#: the breakdown group of a Mosaic custom call that carries no name
UNNAMED_CALLS = "other custom calls"


class Event(NamedTuple):
    name: str      # on a chip's op lines: the HLO instruction's text
    start: float   # ns
    end: float     # ns

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def short(self) -> str:
        """The instruction's own name: ``%fusion.22 = ...`` -> ``fusion.22``."""
        return self.name.split(" = ")[0].lstrip("%")[:80]


class Trace(NamedTuple):
    devices: dict   # chip id -> {line name: [Event]}
    host: dict      # thread name -> [Event]


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m and plane.name != HOST_PLANE:
            continue
        lines = {}
        for line in plane.lines:
            events = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            events.sort(key=lambda e: e.start)
            lines.setdefault(line.name, []).extend(events)
        if m:
            devices[int(m.group(1))] = lines
        else:
            host = lines
    return Trace(devices, host)


# ---------------------------------------------------------------------------
# interval arithmetic


def merge(intervals: Iterable) -> list:
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(events: Sequence[Event], lo: float, hi: float) -> list:
    """Events cut to ``[lo, hi]``; what lies outside is dropped."""
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def spans(events: Sequence[Event]) -> list:
    return [(e.start, e.end) for e in events]


def subtract(a: Iterable, b: Iterable) -> list:
    """The part of union(a) that union(b) does not cover."""
    out, b = [], merge(b)
    for s, e in merge(a):
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def idle_gaps(busy: Iterable, lo: float, hi: float) -> list:
    """Gaps of ``[lo, hi]`` that no busy interval covers."""
    return subtract([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# what a training trace is made of


def is_collective(e: Event) -> bool:
    """all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute,
    synchronous or as ``-start``/``-done`` halves, and the compiler's
    ``async-collective-start/-done`` fusions."""
    return bool(COLLECTIVE_NAME.match(e.short)
                or COLLECTIVE_OPCODE.search(e.name))


def is_custom_call(e: Event) -> bool:
    return CUSTOM_CALL in e.name


def kernel_of(e: Event) -> Optional[str]:
    """The program's name of a Mosaic custom call; None for a call that
    carries none and for anything else."""
    if not is_custom_call(e):
        return None
    m = KERNEL_NAME.search(e.name)
    return m.group(1) if m else None


def group_of(e: Event, vocab: Optional[int] = None) -> str:
    """The breakdown group of one device operation, from its HLO text.

    A Mosaic custom call goes by the name the program gave its kernel:
    attention's under ``flash custom calls``, another kernel under its own
    name, one without a name under ``other custom calls``.
    ``matmul fusions`` are the output fusions (``kind=kOutput``: a
    convolution at the root, on this compiler often with the Adam update of
    the weight it differentiates fused behind it) and bare dots;
    ``optimizer update`` is whatever else reads the optimizer's state;
    ``loss/logits`` is whatever else produces a ``vocab``-wide result."""
    name = e.name
    if is_collective(e):
        return "collectives"
    if is_custom_call(e):
        kernel = kernel_of(e)
        if kernel in FLASH_KERNELS:
            return "flash custom calls"
        return kernel or UNNAMED_CALLS
    if "kind=kOutput" in name or " convolution(" in name or " dot(" in name:
        return "matmul fusions"
    if "opt_state" in name:
        return "optimizer update"
    if vocab:
        result = name.split(" fusion(")[0]
        if re.search(rf"[\[,]{vocab}[\],]", result):
            return "loss/logits"
    return "other"


def step_window(modules: Sequence[Event]) -> Optional[tuple]:
    """``(lo, hi, steps)``: from the start of the first run of the
    busiest compiled program in the capture to the start of its last run,
    which is ``steps`` whole periods.  None with fewer than two runs."""
    by_name = collections.defaultdict(list)
    for e in modules:
        by_name[e.name].append(e)
    if not by_name:
        return None
    runs = max(by_name.values(), key=lambda es: sum(e.dur for e in es))
    if len(runs) < 2:
        return None
    return runs[0].start, runs[-1].start, len(runs) - 1


class DeviceReduction(NamedTuple):
    window_ns: float
    steps: int
    busy_ns: float
    gaps: list           # [(start, end)] idle, longest first
    group_ns: dict       # breakdown group -> summed op durations
    op_ns: dict          # op name -> summed durations
    collective_ns: float        # time with a collective running or in flight
    collective_exposed_ns: float   # ... and no other operation on the core
    kernel_ns: dict             # kernel name -> its custom calls' durations

    @property
    def custom_call_ns(self) -> float:
        """Every Mosaic custom call, named or not."""
        return sum(self.kernel_ns.values()) + self.group_ns[UNNAMED_CALLS]


def reduce_device(lines: dict,
                  vocab: Optional[int] = None) -> Optional[DeviceReduction]:
    """Everything the readers need from one chip's plane, over the whole
    steps of the capture.  A collective counts wherever it shows: on the op
    line (synchronous, or the ``-start``/``-done`` halves the core waits in)
    and in flight on the async line; it is exposed while no other operation
    runs on the core."""
    win = step_window(lines.get(MODULES_LINE, []))
    if win is None:
        return None
    lo, hi, steps = win
    ops = clip(lines.get(OPS_LINE, []), lo, hi)
    busy = merge(spans(ops))
    gaps = sorted(idle_gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
    group_ns = dict.fromkeys(GROUPS, 0.0)
    op_ns: dict = collections.defaultdict(float)
    kernel_ns: dict = collections.defaultdict(float)
    for e in ops:
        group = group_of(e, vocab)
        group_ns[group] = group_ns.get(group, 0.0) + e.dur
        op_ns[e.short] += e.dur
        kernel = kernel_of(e)
        if kernel:
            kernel_ns[kernel] += e.dur
    in_flight = [e for e in clip(lines.get(ASYNC_LINE, []), lo, hi)
                 if is_collective(e)]
    coll = [e for e in ops if is_collective(e)]
    compute = [e for e in ops if not is_collective(e)]
    return DeviceReduction(
        window_ns=hi - lo, steps=steps, busy_ns=total(busy), gaps=gaps,
        group_ns=group_ns, op_ns=dict(op_ns),
        collective_ns=total(spans(coll) + spans(in_flight)),
        collective_exposed_ns=total(subtract(
            spans(coll) + spans(in_flight), spans(compute))),
        kernel_ns=dict(kernel_ns))


def reduce_trace(trace: Trace, vocab: Optional[int] = None) -> dict:
    """chip id -> :class:`DeviceReduction`, chips with no whole step left out."""
    out = {}
    for chip, lines in sorted(trace.devices.items()):
        red = reduce_device(lines, vocab)
        if red is not None:
            out[chip] = red
    return out


def name_gaps(gaps: Sequence, host: dict, names: Sequence[str]) -> list:
    """``[(host span name, seconds)]`` for each idle gap: the runner's span
    (one of ``names``) that overlaps the gap longest, ``"(no span)"`` if none
    does."""
    marks = [e for events in host.values() for e in events
             if e.name in names]
    out = []
    for s, e in gaps:
        best, best_ns = "(no span)", 0.0
        for m in marks:
            overlap = min(e, m.end) - max(s, m.start)
            if overlap > best_ns:
                best, best_ns = m.name, overlap
        out.append((best, (e - s) / 1e9))
    return out


def breakdown(reds: dict, trace: Trace, names: Sequence[str],
              top: int = 10) -> dict:
    """The result line's ``breakdown``: device operations by group and by
    name that took most time (seconds, mean over chips), and the longest
    idle gaps of the worst chip by what the host was doing."""
    n = len(reds)
    groups = collections.defaultdict(float)
    ops = collections.defaultdict(float)
    for red in reds.values():
        for k, v in red.group_ns.items():
            groups[k] += v / n / 1e9
        for k, v in red.op_ns.items():
            ops[k] += v / n / 1e9
    rows = [[f"[{k}]", v] for k, v in groups.items() if v > 0]
    rows += [[k, v] for k, v in ops.items()]
    rows.sort(key=lambda r: -r[1])
    worst = max(reds.values(), key=lambda r: 1 - r.busy_ns / r.window_ns)
    return {"device_ops": rows[:top],
            "idle_gaps": [list(g) for g in name_gaps(
                worst.gaps[:top], trace.host, names)]}
