"""Per-layer readers over the names the PROGRAM writes onto its device work:
kernel names (``pallas_call(name=, metadata=)``; attention's are looked for
by the yardstick's copy, ``flops.FLASH_KERNELS``) and ``jax.named_scope``
scopes (``tpudist/telemetry/names.py``, imported, not spelled again here).

``Reading.reds`` keeps instruction short names only and ``trace_reduce.load``
drops event stats, so this module reads the traced run's ``.xplane.pb``
itself (once, memoised for all its readers) and reuses
``trace_reduce.step_window`` / ``clip`` so that it counts the same whole
steps as the other readers.

Where the names are read from (decided on the first traced chip run of
PR 25, which printed the stat keys of the live TPU trace):

- a kernel's name: the custom call's own event text, which carries
  ``frontend_attributes={kernel_metadata={"kernel":"<name>"}}`` verbatim
  whatever a ``shard_map`` round the call does to the instruction's name
  (``trace_reduce.kernel_of``);
- an operation's scope: its ``tf_op`` stat, the ``op_name`` the compiler
  kept for the instruction (``jit(step)/transpose(jvp(TransformerLM))/
  block_3/attn/qkv/dot_general:``; for a fusion, the one XLA gave the
  fusion).  The profiler stores it once per instruction, on the event's
  METADATA, and ``jax.profiler.ProfileData`` shows an event's own stats
  only (offset, duration), so :func:`op_scopes` takes it from the file's
  protobuf with a small wire-format reader (no dependency beyond Python).
  The HLO module in the ``/host:metadata`` plane is not needed.

Every reader returns ``None`` and never raises when what it reads is
absent: a program without the vocabulary, a trace without the stat, a run
whose operations carry none of the names.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path
from typing import NamedTuple, Optional

from cellbench import flops, trace_reduce
from cellbench.trace_reduce import Event

try:
    from tpudist.telemetry import names
except ImportError:   # a program without the vocabulary: nothing to read
    names = None

#: the stat of an operation's event metadata that carries its scope
SCOPE_STAT = "tf_op"


class Op(NamedTuple):
    event: Event             # clipped to the whole steps of the capture
    scope: str               # "" where the trace gives none
    kernel: Optional[str]    # the program's name of a Mosaic custom call
    phase: Optional[str]     # fwd / bwd / optimizer; None: carries no name


class ChipOps(NamedTuple):
    steps: int
    busy_ns: float
    ops: list


# ---------------------------------------------------------------------------
# the scope stat, from the file's protobuf (tsl/profiler/protobuf/xplane.proto:
# XSpace.planes=1; XPlane.name=2 .event_metadata=4 .stat_metadata=5, both maps
# of key=1 -> value=2; XEventMetadata.name=2 .stats=5; XStatMetadata.name=2;
# XStat.metadata_id=1 .str_value=5 .ref_value=7, a stat_metadata id whose name
# is the string)


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for anything length-delimited or fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), None)


def op_scopes(path: str) -> dict:
    """chip id -> ``{event name: scope}`` for the operations whose event
    metadata carries a ``SCOPE_STAT``; ``{}`` for a file that is no XSpace."""
    out = {}
    try:
        data = memoryview(Path(path).read_bytes())
        for field, plane in _fields(data):
            if field != 1:
                continue
            name, stat_names, events = "", {}, []
            for f, v in _fields(plane):
                if f == 2:
                    name = _text(v)
                elif f == 4:
                    events.append(v)
                elif f == 5:
                    key = next(x for g, x in _fields(v) if g == 1)
                    stat_names[key] = next(
                        (_text(x) for g, x in _fields(_map_value(v))
                         if g == 2), "")
            m = trace_reduce.DEVICE_PLANE.match(name)
            if not m:
                continue
            wanted = {k for k, n in stat_names.items() if n == SCOPE_STAT}
            scopes = {}
            for entry in events:
                event_name, scope = "", None
                for f, v in _fields(_map_value(entry)):
                    if f == 2:
                        event_name = _text(v)
                    elif f == 5:
                        stat = dict(_fields(v))
                        if stat.get(1) in wanted:
                            scope = (_text(stat[5]) if 5 in stat
                                     else stat_names.get(stat.get(7), ""))
                if scope:
                    scopes[event_name] = scope
            out[int(m.group(1))] = scopes
    except (OSError, ValueError, IndexError, StopIteration, TypeError):
        return {}
    return out


@functools.lru_cache(maxsize=None)
def _under(*scope_names: str):
    """Matches an ``op_name`` with one of ``scope_names`` among its
    components, bare or wrapped (``attn``, ``jvp(loss)``)."""
    alternatives = "|".join(re.escape(s) for s in scope_names)
    return re.compile(rf"(^|[/(])({alternatives})([/)]|$)")


def phase_of(scope: str, kernel: Optional[str]) -> Optional[str]:
    """``fwd`` / ``bwd`` / ``optimizer`` for an operation that carries one of
    the program's scopes or a kernel name, None for one that carries none."""
    if kernel is None and not _under(*names.SCOPES).search(scope):
        return None
    if names.BACKWARD_MARK in scope:
        return "bwd"
    if _under(names.OPTIMIZER).search(scope):
        return "optimizer"
    return "fwd"


def _op(e: Event, scope: str) -> Op:
    kernel = trace_reduce.kernel_of(e)
    return Op(e, scope, kernel, phase_of(scope, kernel))


@functools.lru_cache(maxsize=4)
def load(path: str) -> dict:
    """chip id -> :class:`ChipOps` for the whole steps of the capture."""
    scopes = op_scopes(path)
    trace = trace_reduce.load(Path(path))
    out = {}
    for chip, lines in trace.devices.items():
        win = trace_reduce.step_window(lines.get(trace_reduce.MODULES_LINE, []))
        if win is None:
            continue
        lo, hi, steps = win
        events = trace_reduce.clip(lines.get(trace_reduce.OPS_LINE, []), lo, hi)
        by_name = scopes.get(chip, {})
        out[chip] = ChipOps(
            steps, trace_reduce.total(trace_reduce.spans(events)),
            [_op(e, by_name.get(e.name, "")) for e in events])
    return out


def _chips(r) -> dict:
    """The traced run's chips, or ``{}`` where there is nothing to read."""
    if names is None:
        return {}
    try:
        from cellbench import run

        path = trace_reduce.find_xplane(
            Path(run.SCRATCH) / "trace" / r.cell["name"])
        return load(str(path))
    except (OSError, KeyError, ValueError):
        return {}


def _ms_per_step(r, pick) -> Optional[float]:
    """Mean over chips of the summed durations of the operations ``pick``
    keeps, per step; None where it keeps none on any chip."""
    per_chip = []
    for chip in _chips(r).values():
        ns = sum(op.event.dur for op in chip.ops if pick(op))
        if ns > 0:
            per_chip.append(ns / chip.steps / 1e6)
    return sum(per_chip) / len(per_chip) if per_chip else None


def _kernel_ms(r, kernel: str):
    return _ms_per_step(r, lambda op: op.kernel == kernel)


def flash_fwd_ms_per_step(r):
    return _kernel_ms(r, flops.FLASH_FWD)


def flash_bwd_dq_ms_per_step(r):
    return _kernel_ms(r, flops.FLASH_BWD_DQ)


def flash_bwd_dkv_ms_per_step(r):
    return _kernel_ms(r, flops.FLASH_BWD_DKV)


def fwd_ms_per_step(r):
    return _ms_per_step(r, lambda op: op.phase == "fwd")


def bwd_ms_per_step(r):
    return _ms_per_step(r, lambda op: op.phase == "bwd")


def optimizer_ms_per_step(r):
    return _ms_per_step(r, lambda op: op.phase == "optimizer")


def unscoped_ms_per_step(r):
    """What ``fwd`` + ``bwd`` + ``optimizer`` leave of the device's busy
    time (no metric of its own: 100 - ``scoped_device_pct`` says it)."""
    return _ms_per_step(r, lambda op: op.phase is None)


def scoped_device_pct(r):
    """Share of the device's busy time spent in operations that carry one
    of the program's scopes or a kernel name, least-covered chip."""
    shares = []
    for chip in _chips(r).values():
        scoped = sum(op.event.dur for op in chip.ops if op.phase is not None)
        if scoped > 0 and chip.busy_ns > 0:
            shares.append(100.0 * scoped / chip.busy_ns)
    return min(shares) if shares else None


def attn_glue_ms_per_step(r):
    """Operations of the attention sublayer (``attn``: pre-LN through the
    residual add, forward and backward) that are neither a flash kernel nor
    a matmul fusion: the transposes, copies, pads and norms round them."""
    if names is None:
        return None
    attn = _under(names.ATTN)
    return _ms_per_step(r, lambda op: (
        attn.search(op.scope) is not None and op.kernel is None
        and trace_reduce.group_of(op.event) != "matmul fusions"))
