"""A step's device time by area of the model: embed / attention / MLP / the
four parts of an expert layer / head / accumulation, beside the three
phases that are no part of the model (optimizer, bucket layout, guard) and
what is left (``other``).  Together they partition ``compute_ms``'s
instructions.

Which names mean which area is the PROGRAM's contract, not this file's:
``bagua_tpu.obs.spans.area_of`` (PR 37) maps an ``op_name`` path to an area
from the flax module names that ``models/transformer.py`` fixes, the
``bagua.moe/<part>`` scopes and the plain scopes ``loss_tail`` /
``grad_accum`` / ``pos_embed``.  It is imported in-process, as
``scopes.program_spans`` imports the span ring; a program without it (the
parent of PR 37) has no areas and every reader here returns None.

The join to time is ``scopes.instruction_scopes``'s (instruction name ->
path, the compiler's own instructions taking their computation's,
consumer's or operand's).  Precedence, so that the parts partition: what
``scopes.phase_of`` gives to ``optimizer``, ``layout`` or ``guard`` stays
there; otherwise the area the path names; otherwise ``other``.  A fusion
has one ``op_name``: an area's time is an attribution by fusion root, as a
phase's is.

All keys are reduced in ONE pass over the trace, kept on the context: the
eleven readers of a traced run share it.
"""

from __future__ import annotations

import functools
import statistics

from perfbench import scopes
from perfbench import trace_reduce as tr

OTHER = "other"
#: the phases that are not the model's: an instruction of theirs keeps its
#: phase whatever module names its path holds
PHASE_KEYS = (scopes.OPTIMIZER, scopes.LAYOUT, scopes.GUARD)
#: area ``attn`` again, but only the instructions the trace labels with one
#: of these bare opcodes (no fusion, no Mosaic call, no dot): re-layouts
ATTN_RELAYOUT = "attn_relayout"
RELAYOUT_LABELS = frozenset(
    ("copy", "reshape", "convert", "transpose", "slice", "concatenate",
     "bitcast"))

_CACHE_ATTR = "_area_table"


def program_area_of():
    """The program's ``area_of``; None where the program has none."""
    try:
        from bagua_tpu.obs.spans import area_of
    except ImportError:
        return None
    return area_of


def key_of(path: str | None, area_of) -> str:
    """The one key of the partition an ``op_name`` path belongs to."""
    phase = scopes.phase_of(path)
    if phase in PHASE_KEYS:
        return phase
    return area_of(path) or OTHER


@functools.lru_cache(maxsize=2)
def _instruction_keys(hlo_text: str, area_of) -> dict[str, str]:
    return {name: key_of(path, area_of)
            for name, path in scopes.instruction_scopes(hlo_text).items()}


def instruction_keys(ctx) -> dict[str, str] | None:
    """``{instruction name: key}`` of the compiled step; a name that is
    missing is ``OTHER``.  None where there is nothing to join."""
    area_of = program_area_of()
    if area_of is None or ctx.hlo_text is None:
        return None
    return _instruction_keys(ctx.hlo_text, area_of)


def _chip_rows(index: int, chip: tr.Chip,
               keys: dict[str, str]) -> list[dict[str, float]]:
    """``[{key: nanoseconds} per step]`` of one chip."""
    rows: list[dict[str, float]] = []

    def step_ns(part, lo, hi):
        by_key: dict[str, list[tr.Interval]] = {}
        for op in part.ops:
            if tr.is_collective(op) or tr.is_container(op):
                continue
            key = keys.get(op.name, OTHER)
            by_key.setdefault(key, []).append(op.interval)
            if key == "attn" and op.label in RELAYOUT_LABELS:
                by_key.setdefault(ATTN_RELAYOUT, []).append(op.interval)
        rows.append({key: tr.length(tr.clip(intervals, lo, hi))
                     for key, intervals in by_key.items()})
        return 0.0

    # per_step_ms is the shared cut of a chip's timeline into steps
    tr.per_step_ms(tr.Trace({index: chip}, []), step_ns)
    return rows


def table(ctx) -> dict[int, list[dict[str, float]]] | None:
    """``{chip: [{key: nanoseconds} per step]}``: inside each step of each
    chip, the union of the intervals of every non-collective, non-container
    instruction of each key (``compute_ms``'s instructions and its
    reduction inside a step).  One pass, kept on ``ctx``."""
    cached = getattr(ctx, _CACHE_ATTR, None)
    if cached is not None:
        return cached
    if ctx.trace is None:
        return None
    keys = instruction_keys(ctx)
    if keys is None:
        return None
    out = {index: _chip_rows(index, chip, keys)
           for index, chip in ctx.trace.chips.items()}
    setattr(ctx, _CACHE_ATTR, out)
    return out


def area_ms(ctx, key: str) -> float | None:
    """Device time per step of the instructions under ``key``: median over
    steps, worst chip, in milliseconds; 0.0 where the program has the
    contract and the cell no such instruction; None where the program has
    no ``area_of`` or there is no trace."""
    rows_by_chip = table(ctx)
    if rows_by_chip is None:
        return None
    medians = [statistics.median(row.get(key, 0.0) for row in rows)
               for rows in rows_by_chip.values() if rows]
    return max(medians) / 1e6 if medians else None
