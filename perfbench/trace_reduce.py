"""From a profiler trace to intervals, and the interval arithmetic the
per-layer readers share.  Reads ``.xplane.pb`` with ``jax.profiler.ProfileData``
only (no tensorflow, no xprof).

What a TPU trace looks like (read by hand on the v5e, PR 23; print one with
perfbench/tools/trace_dump.py): one plane ``/device:TPU:<n>`` per chip.  Its
line ``XLA Modules`` has one event per executed program
(``jit_per_shard(<fingerprint>)``).  Its line ``XLA Ops`` has one event per
executed HLO instruction, serial on the chip, and the event's name is the
WHOLE instruction as the optimized HLO prints it::

    %attn.96 = (bf16[128,1024,64]{...}, f32[128,8,1024]{...}) custom-call(bf16[...] %bitcast.5609, ...), custom_call_target="tpu_custom_call", ...
    %fusion.36 = (f32[31261696]{0:T(1024)}, ...) fusion(...), kind=kLoop, calls=%fused_computation...

so the instruction's name, its opcode, a fusion's kind and a custom call's
target can all be read from the trace alone: a Pallas (Mosaic) kernel is a
``custom-call`` whose target is ``tpu_custom_call``.  The line
``Async XLA Ops`` has one event per asynchronous operation, lasting from its
``-start`` to its ``-done`` (on the v5e: ``copy-start``, ``slice-start``).
Host threads are lines of the plane ``/host:CPU``; the benchmark's own
``jax.profiler.TraceAnnotation`` spans (``bench/...``) are events of the
main thread's line, on the same clock.

A collective's interval is the instruction's own event where it is
synchronous (the v5e step has plain ``all-reduce``), and where it is
asynchronous its event on the async line, or else the span from the start of
``<op>-start`` to the end of its ``<op>-done``; the sum of the two halves'
durations would not be wire time.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import statistics
from typing import Callable, Iterable

Interval = tuple[float, float]  # nanoseconds, start <= end

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"

COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "ragged-all-to-all", "collective-broadcast",
)
#: instructions whose event spans the events of a called computation: they
#: are neither compute nor communication themselves
CONTAINER_OPCODES = ("while", "conditional", "call")
MOSAIC_TARGET = "tpu_custom_call"
_SUFFIX = re.compile(r"\.\d+$")
# ``%name = TYPE opcode(``: TYPE is one token or one parenthesised tuple
_INSTRUCTION = re.compile(r"%?([\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\(")
_FUSION_KIND = re.compile(r"\bkind=(\w+)")
_CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> list[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: list[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def length(intervals: Iterable[Interval]) -> float:
    """Total length of the union of ``intervals``."""
    return sum(end - start for start, end in merge(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> list[Interval]:
    """The part of the union of ``a`` that the union of ``b`` does not cover."""
    cover = merge(b)
    out: list[Interval] = []
    j = 0
    for start, end in merge(a):
        at = start
        while j < len(cover) and cover[j][1] <= at:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > at:
                out.append((at, cover[k][0]))
            at = max(at, cover[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


# ---------------------------------------------------------------------------
# the trace, reduced to what the readers use
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Op:
    name: str       # the instruction's name: ``fusion.36``
    start: float
    end: float
    opcode: str     # ``fusion``, ``all-reduce``, ``custom-call`` ...
    #: what a breakdown sums this under: the opcode, with a fusion's kind
    #: or a custom call's target
    label: str

    @property
    def interval(self) -> Interval:
        return (self.start, self.end)


def parse_op(text: str, start: float, end: float) -> Op:
    """An event of an op line.  ``text`` is the whole instruction on a TPU;
    a bare instruction name (``all-reduce-start.3``) is read as well, its
    opcode being the name without its numeric suffix."""
    m = _INSTRUCTION.match(text)
    if not m:
        name = text.lstrip("%")
        opcode = _SUFFIX.sub("", name)
        return Op(name, start, end, opcode, opcode)
    name, opcode = m.groups()
    label = opcode
    if opcode == "fusion":
        kind = _FUSION_KIND.search(text)
        label = f"fusion {kind.group(1)}" if kind else opcode
    elif opcode == "custom-call":
        target = _CALL_TARGET.search(text)
        label = f"custom-call {target.group(1)}" if target else opcode
    return Op(name, start, end, opcode, label)


@dataclasses.dataclass
class Chip:
    ops: list[Op]           # sorted by start
    modules: list[Op]       # executed programs, sorted by start
    #: asynchronous operations, each from its start to its done
    async_ops: list[Op] = dataclasses.field(default_factory=list)

    def steps(self) -> list[Interval]:
        """One interval per execution of the step program: the module that
        took most of the chip's time."""
        totals: dict[str, float] = {}
        for m in self.modules:
            totals[m.name] = totals.get(m.name, 0.0) + (m.end - m.start)
        if not totals:
            return []
        step_name = max(totals, key=totals.get)
        return [m.interval for m in self.modules if m.name == step_name]

    def window(self) -> Interval | None:
        """From the first step's start to the last step's end: the edges of
        a trace hold the profiler's own start and stop, not the workload."""
        steps = self.steps()
        return (steps[0][0], steps[-1][1]) if steps else None


@dataclasses.dataclass
class Trace:
    chips: dict[int, Chip]
    host: list[Op]          # the benchmark's annotations, sorted by start


def load(path: str, annotation_prefix: str = "bench/") -> Trace | None:
    """Reduce an ``.xplane.pb`` file; None where it holds no TPU plane (a CPU
    run: there is no device timeline to read, and none is made up)."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, annotation_prefix)


def reduce_planes(planes, annotation_prefix: str = "bench/") -> Trace | None:
    chips: dict[int, Chip] = {}
    host: list[Op] = []
    for plane in planes:
        device = DEVICE_PLANE.search(plane.name)
        if device:
            lines = {OPS_LINE: [], ASYNC_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                into = lines.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    into.append(parse_op(ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
                into.sort(key=lambda o: o.start)
            if lines[OPS_LINE]:
                chips[int(device.group(1))] = Chip(
                    lines[OPS_LINE], lines[MODULES_LINE], lines[ASYNC_LINE])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(annotation_prefix):
                        host.append(parse_op(ev.name, ev.start_ns,
                                             ev.start_ns + ev.duration_ns))
    if not chips:
        return None
    host.sort(key=lambda o: o.start)
    return Trace(chips, host)


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def is_collective(op: Op) -> bool:
    return op.opcode.startswith(COLLECTIVE_PREFIXES)


def is_container(op: Op) -> bool:
    return op.opcode in CONTAINER_OPCODES


def is_mosaic(op: Op) -> bool:
    """A Pallas kernel: the custom call Mosaic compiles."""
    return op.label == f"custom-call {MOSAIC_TARGET}"


def collective_intervals(ops: Iterable[Op],
                         async_ops: Iterable[Op] = ()) -> list[Interval]:
    """Intervals during which a collective is in flight on the chip.  An
    async pair on the op line is matched first-in first-out per kind; where
    two pairs of a kind interleave, either matching gives the same union."""
    out = [op.interval for op in async_ops if is_collective(op)]
    open_starts: dict[str, list[float]] = {}
    for op in ops:
        if not is_collective(op):
            continue
        if op.opcode.endswith("-start"):
            open_starts.setdefault(op.opcode[:-len("-start")],
                                   []).append(op.start)
        elif op.opcode.endswith("-done"):
            pending = open_starts.get(op.opcode[:-len("-done")])
            out.append((pending.pop(0) if pending else op.start, op.end))
        else:
            out.append(op.interval)
    return out


def compute_intervals(ops: Iterable[Op]) -> list[Interval]:
    """Intervals of every instruction that is neither a collective nor a
    control-flow container."""
    return [op.interval for op in ops
            if not is_collective(op) and not is_container(op)]


def busy_intervals(ops: Iterable[Op]) -> list[Interval]:
    return merge(op.interval for op in ops)


# ---------------------------------------------------------------------------
# reductions shared by the readers
# ---------------------------------------------------------------------------


def per_step_ms(trace: Trace,
                step_ns: Callable[[Chip, float, float], float]) -> float | None:
    """``step_ns(the step's part of the chip, start, end)`` per step; the
    median over a chip's steps, then the worst chip, in milliseconds."""
    worst = None
    for chip in trace.chips.values():
        starts = [o.start for o in chip.ops]
        async_starts = [o.start for o in chip.async_ops]
        values = []
        for lo, hi in chip.steps():
            # an instruction runs inside the program that holds it, so the
            # step's events are those that start in [lo, hi)
            part = Chip(
                chip.ops[bisect.bisect_left(starts, lo):
                         bisect.bisect_left(starts, hi)], [],
                chip.async_ops[bisect.bisect_left(async_starts, lo):
                               bisect.bisect_left(async_starts, hi)])
            values.append(step_ns(part, lo, hi))
        if values:
            median = statistics.median(values)
            worst = median if worst is None else max(worst, median)
    return None if worst is None else worst / 1e6


def busy_and_window(trace: Trace) -> list[tuple[float, float]]:
    """Per chip: (seconds in which an instruction ran, seconds of window)."""
    out = []
    for chip in trace.chips.values():
        window = chip.window()
        if window is None:
            continue
        busy = length(clip(busy_intervals(chip.ops), *window))
        out.append((busy / 1e9, (window[1] - window[0]) / 1e9))
    return out


def top_device_ops(trace: Trace, limit: int = 10) -> list[list]:
    """The instructions that took most device time inside the window, summed
    by opcode as the trace prints it, with a fusion's kind and a custom
    call's target (``fusion kOutput``, ``custom-call tpu_custom_call``),
    averaged over chips: [[label, seconds]]."""
    totals: dict[str, float] = {}
    for chip in trace.chips.values():
        window = chip.window()
        if window is None:
            continue
        for op in chip.ops:
            if is_container(op):
                continue
            part = clip([op.interval], *window)
            if part:
                totals[op.label] = (totals.get(op.label, 0.0)
                                    + part[0][1] - part[0][0])
    n = max(len(trace.chips), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / n / 1e9] for name, ns in ranked]


def idle_gaps_by_host_span(trace: Trace, limit: int = 10) -> list[list]:
    """Idle time of the idlest chip inside its window, summed by what the
    host was doing: each gap goes to the benchmark span that overlaps it
    most, or to ``(no benchmark span)``.  [[name, seconds]], longest first."""
    idlest = None
    for chip in trace.chips.values():
        window = chip.window()
        if window is None:
            continue
        gaps = subtract([window], busy_intervals(chip.ops))
        if idlest is None or length(gaps) > length(idlest):
            idlest = gaps
    totals: dict[str, float] = {}
    for lo, hi in idlest or []:
        best, best_overlap = "(no benchmark span)", 0.0
        for span in trace.host:
            if span.start >= hi:
                break
            overlap = min(span.end, hi) - max(span.start, lo)
            if overlap > best_overlap:
                best, best_overlap = span.name, overlap
        totals[best] = totals.get(best, 0.0) + (hi - lo)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / 1e9] for name, ns in ranked]
