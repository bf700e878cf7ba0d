"""The program's own phase scopes, read from outside it: which phase of the
training step each compiled instruction belongs to, and the per-step device
time of each phase.

What the program provides (``bagua_tpu``, from PR 24 on; a program without
them reads as ``unattributed`` everywhere and the span/gauge readers return
None): ``jax.named_scope``s around the phases of the compiled step, which
JAX writes into every instruction's ``metadata={op_name="..."}`` as a path

    jit(bagua_step)/jvp(bagua.loss)/TransformerLM/block_3/attn/dot_general
    jit(bagua_step)/transpose(jvp(bagua.loss))/.../checkpoint/rematted_computation/block_3/attn/flash_fwd/pallas_call
    jit(bagua_step)/shard_map/bagua.comm/bucket_17/psum
    jit(bagua_step)/bagua.optimizer/mul

The transform wrappers are JAX's own: ``jvp(bagua.loss)`` with no
``transpose(`` is the forward pass, ``transpose(jvp(bagua.loss))`` anywhere
in the path is the backward pass, and ``rematted_computation`` is a
``jax.checkpoint`` replay of forward work inside the backward pass.  The
innermost ``bagua.*`` component names the phase.

How it is joined to time: the device trace names every executed instruction
(``Op.name``, e.g. ``fusion.36``); the optimized HLO text the driver holds
(``ctx.hlo_text``) is of the same executable and maps that name to its
``op_name``.  Two limits, both XLA's: a fusion carries ONE ``op_name`` (its
root's), so a phase time is an attribution by fusion root; and a combined
all-reduce carries one constituent bucket's ``bucket_<i>``.  What the
compiler made itself has no ``op_name``; ``instruction_scopes`` says whose it
takes.

The program's spans and gauges are read in-process from its own singletons
(``ReaderContext`` holds benchmark spans only): ``program_spans`` /
``program_gauge`` below.  Every reader returns None rather than raise where
the program has nothing to read.
"""

from __future__ import annotations

import collections
import functools
import re
import statistics

from perfbench import hlo_bytes
from perfbench import trace_reduce as tr

FORWARD, BACKWARD, REPLAY = "forward", "backward", "remat_replay"
OPTIMIZER, LAYOUT, GUARD = "optimizer", "layout", "guard"
UNATTRIBUTED = "unattributed"
PHASES = (FORWARD, BACKWARD, REPLAY, OPTIMIZER, LAYOUT, GUARD, UNATTRIBUTED)

_SCOPE = re.compile(r"bagua\.(\w+)")
_REPLAY_MARK = "rematted_computation"
_NAME = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|body)=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"\s*(?:ENTRY )?%?([\w.\-]+) [^=]*\{\s*$")
# an operand: ``%name`` not directly after ``=`` (``calls=%computation``)
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")


def phase_of(op_name: str | None) -> str:
    """The phase an ``op_name`` path belongs to; the innermost ``bagua.*``
    component decides, ``bagua.layout`` and ``bagua.comm`` both read as
    ``layout`` (what the bucket plan costs besides the wire)."""
    if not op_name:
        return UNATTRIBUTED
    parts = op_name.split("/")
    for part in reversed(parts):
        scope = _SCOPE.search(part)
        if scope is None:
            continue
        kind = scope.group(1)
        if kind == "loss":
            if _REPLAY_MARK in parts:
                return REPLAY
            backward = any(p.startswith("transpose(") and "bagua.loss" in p
                           for p in parts)
            return BACKWARD if backward else FORWARD
        if kind in ("layout", "comm"):
            return LAYOUT
        if kind in (OPTIMIZER, GUARD):
            return kind
        return UNATTRIBUTED
    return UNATTRIBUTED


@functools.lru_cache(maxsize=2)
def instruction_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name path}`` for every instruction of the
    optimized HLO text that has one.  An instruction the compiler made
    itself carries none (on the v5e: the ``copy-start``/``slice-start``
    prefetches between memory spaces with their ``-done`` halves,
    ``ConcatBitcast``, layout ``copy``s, a fusion of unnamed root); it takes,
    in this order, the path most of the computation it calls agrees on, its
    first consumer's, or its first operand's — data movement is put down to
    the phase it was made for.  Cached: callers do not change the result."""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    operands: dict[str, list[str]] = {}
    users: dict[str, list[str]] = collections.defaultdict(list)
    members: dict[str, list[str]] = collections.defaultdict(list)
    computation = None
    for line in hlo_text.splitlines():
        opened = _COMPUTATION.match(line)
        if opened:
            computation = opened.group(1)
            continue
        named = _NAME.match(line)
        if not named:
            continue
        name = named.group(1)
        refs = _OPERAND.findall(line, named.end())
        for ref in refs:
            users[ref].append(name)
        op_name = _OP_NAME.search(line)
        if op_name and op_name.group(1):
            own[name] = op_name.group(1)
            members[computation].append(op_name.group(1))
            continue
        operands[name] = refs
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    for name, called in calls.items():
        inside = members.get(called)
        if inside:
            votes = collections.Counter(phase_of(p) for p in inside)
            phase = votes.most_common(1)[0][0]
            own[name] = next(p for p in inside if phase_of(p) == phase)
    # consumers first (a prefetch belongs to what waits for it), then
    # producers (an output copy belongs to what made the value); each
    # round resolves the instructions one step further from a named one
    for neighbours in (users, operands):
        pending = [n for n in operands if n not in own]
        while pending:
            found = {}
            for name in pending:
                path = next((own[o] for o in neighbours.get(name, ())
                             if o in own), None)
                if path is not None:
                    found[name] = path
            if not found:
                break
            own.update(found)
            pending = [n for n in pending if n not in found]
    return own


@functools.lru_cache(maxsize=2)
def instruction_phases(hlo_text: str) -> dict[str, str]:
    """``{instruction name: phase}``; a name that is missing is
    ``UNATTRIBUTED``."""
    return {name: phase_of(path)
            for name, path in instruction_scopes(hlo_text).items()}


def kernel_of(op_name: str | None) -> str | None:
    """The ``name=`` of the ``pallas_call`` an ``op_name`` path ends in:
    ``.../attn/flash_fwd/pallas_call`` -> ``flash_fwd``."""
    if not op_name:
        return None
    parts = op_name.split("/")
    if len(parts) >= 2 and parts[-1].startswith("pallas_call"):
        return parts[-2]
    return None


# ---------------------------------------------------------------------------
# device time by phase / by kernel
# ---------------------------------------------------------------------------


def phase_ms(ctx, phase: str) -> float | None:
    """Device time per step of the non-collective instructions of ``phase``
    (the reduction of ``compute_ms``: union inside the step, median over
    steps, worst chip).  Over ``PHASES`` the values partition
    ``compute_ms``'s instructions."""
    if ctx.trace is None or ctx.hlo_text is None:
        return None
    phases = instruction_phases(ctx.hlo_text)

    def step_ns(chip, lo, hi):
        return tr.length(tr.clip(
            [op.interval for op in chip.ops
             if not tr.is_collective(op) and not tr.is_container(op)
             and phases.get(op.name, UNATTRIBUTED) == phase], lo, hi))
    return tr.per_step_ms(ctx.trace, step_ns)


def kernel_events(chip, scopes: dict[str, str], kernel: str) -> list:
    return [op for op in chip.ops
            if tr.is_mosaic(op) and kernel_of(scopes.get(op.name)) == kernel]


def kernel_ms(ctx, kernel: str) -> float | None:
    """Summed device time per step of the Mosaic custom calls named
    ``kernel`` (replays included); 0 where the step has none.  None where
    the step has Mosaic calls but none carries a kernel name (a program
    without ``name=`` on its ``pallas_call``s)."""
    if ctx.trace is None or ctx.hlo_text is None:
        return None
    scopes = instruction_scopes(ctx.hlo_text)
    mosaic = [op for chip in ctx.trace.chips.values() for op in chip.ops
              if tr.is_mosaic(op)]
    if mosaic and not any(kernel_of(scopes.get(op.name)) for op in mosaic):
        return None

    def step_ns(chip, lo, hi):
        return sum(min(op.end, hi) - max(op.start, lo)
                   for op in kernel_events(chip, scopes, kernel))
    return tr.per_step_ms(ctx.trace, step_ns)


_RESULT_SHAPE = re.compile(r"= \(?(\w+)\[(\d+),(\d+),(\d+)\]")


@functools.lru_cache(maxsize=2)
def kernel_call_shapes(hlo_text: str) -> dict[str, tuple[int, int, int, int]]:
    """``{instruction: (batch x heads, seq, head_dim, itemsize)}`` of every
    Mosaic call whose first result is a rank-3 array (the flash kernels'
    ``o`` / ``dq`` / ``dk``)."""
    shapes = {}
    for line in hlo_text.splitlines():
        if tr.MOSAIC_TARGET not in line:
            continue
        named, shape = _NAME.match(line), _RESULT_SHAPE.search(line)
        if named and shape:
            dtype, bh, seq, head_dim = shape.groups()
            shapes[named.group(1)] = (int(bh), int(seq), int(head_dim),
                                      hlo_bytes._DTYPE_BYTES.get(dtype, 2))
    return shapes


def kernel_roofline(ctx, kernel: str, cost) -> float | None:
    """Share (%) of its roofline a flash kernel reaches: FLOP its block
    loops compute per step, over its summed device time per step, over
    min(peak FLOP/s, FLOP/byte x HBM bytes/s).  ``cost(bh, seq, head_dim,
    itemsize) -> (flop, hbm_bytes)`` of ONE call (perfbench/kernel_costs.py);
    the shape is read from the call's first result in the HLO text, and the
    calls of a step are counted in the trace.  None where the step has no
    such kernel."""
    ms = kernel_ms(ctx, kernel)
    if not ms or ctx.peak is None:
        return None
    scopes = instruction_scopes(ctx.hlo_text)
    shapes = kernel_call_shapes(ctx.hlo_text)

    def per_step(part: int) -> float:
        def step_total(chip, lo, hi):
            return sum(cost(*shapes[op.name])[part]
                       for op in kernel_events(chip, scopes, kernel)
                       if op.name in shapes)
        # per_step_ms is the shared median-over-steps, worst-chip
        # reduction; it divides by 1e6 for its unit, undone here
        return 1e6 * tr.per_step_ms(ctx.trace, step_total)

    flop, moved = per_step(0), per_step(1)
    if not flop or not moved:
        return None
    attainable = min(ctx.peak["bf16_flops_per_s"],
                     flop / moved * ctx.peak["hbm_bytes_per_s"])
    return 100.0 * flop / (ms * 1e-3) / attainable


# ---------------------------------------------------------------------------
# the program's host spans and gauges, read in-process
# ---------------------------------------------------------------------------


def program_spans() -> list[dict]:
    """The program's span ring (``bagua_tpu.obs.spans``), oldest first."""
    from bagua_tpu.obs.spans import span_ring

    return span_ring.snapshot()


def span_median_ms(name: str) -> float | None:
    """Median duration of the program's spans called ``name`` in the ring."""
    durations = [s["dur_s"] for s in program_spans() if s["name"] == name]
    return 1e3 * statistics.median(durations) if durations else None


def span_minus_child_median_ms(parent: str, child: str) -> float | None:
    """Per step, the duration of span ``parent`` less that of its child
    span ``child`` (joined by step number and thread; ``parent`` recorded
    as the child's enclosing span); the median over the steps in the ring.
    None where the program records no such pair."""
    parents, children = {}, {}
    for span in program_spans():
        key = (span.get("thread"), span.get("step"))
        if span["name"] == parent:
            parents[key] = span["dur_s"]
        elif span["name"] == child and span.get("parent") == parent:
            children[key] = span["dur_s"]
    rest = [parents[key] - children[key] for key in parents if key in children]
    return 1e3 * statistics.median(rest) if rest else None


def program_gauge(name: str) -> float | None:
    """A gauge of the program's ``telemetry.counters``; None where it was
    never set (a program that predates it)."""
    from bagua_tpu.telemetry import counters

    return counters.snapshot().get(name)
