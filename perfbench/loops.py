"""A looped model's passes in the device trace: the instructions whose
``op_name`` path lies inside the plain scope the program puts around the
blocks of a pass (``TransformerLM`` under ``TransformerConfig.n_passes >
1``, PR 39).  The passes are ONE scanned body: the compiled step holds the
body's instructions once (forward in one ``while``, replay and backward in
another) and the trace holds an event for each of them in every pass, so
what is read here is all passes together; the gauge ``loop/passes`` says
how many there were.

Which component names the body is the PROGRAM's contract:
``bagua_tpu.obs.spans.in_loop`` says whether a path lies inside a pass,
forward, backward and replay alike.  It is imported in-process, as
``areas.py`` imports ``area_of``; a program without it has no passes and
every reader here returns None.  The join to time is
``scopes.instruction_scopes``'s, the reduction ``compute_ms``'s (union
inside the step, median over steps, worst chip), the precedence
``areas.py``'s: what ``scopes.phase_of`` gives to ``optimizer``, ``layout``
or ``guard`` stays there, so the passes' time lies inside the areas'
(``attn`` + ``mlp`` + what a bare ``block_<i>`` or the ``remat`` boundary
leaves under ``other``).
"""

from __future__ import annotations

import functools

from perfbench import areas, scopes
from perfbench import trace_reduce as tr


def program_in_loop():
    """The program's ``in_loop``; None where the program has none."""
    try:
        from bagua_tpu.obs.spans import in_loop
    except ImportError:
        return None
    return in_loop


@functools.lru_cache(maxsize=2)
def _instructions_in_loop(hlo_text: str, in_loop) -> frozenset:
    """Names of the instructions inside a pass."""
    return frozenset(
        name for name, path in scopes.instruction_scopes(hlo_text).items()
        if in_loop(path) and scopes.phase_of(path) not in areas.PHASE_KEYS)


#: JAX's name for the sum of two cotangents of one value
_COTANGENT_SUM = "add_any"


def is_grad_sum(path: str, in_loop) -> bool:
    """Whether ``path`` is a sum of cotangents AT THE BODY'S OWN LEVEL
    (``.../loop_body/add_any``, no module between): the cotangent of a
    value that enters the body from outside it and is read in every pass,
    which is a shared weight's gradient summed over the passes (inside a
    block the same primitive adds the residual stream's two cotangents,
    under the block's name)."""
    parts = path.split("/")
    return (in_loop(path) and parts[-1] == _COTANGENT_SUM
            and in_loop(parts[-2]))


def trunk_ms(ctx, only=None) -> float | None:
    """Device time per step of the non-collective instructions inside the
    passes (``only(path, in_loop)``: of those it keeps).  None where the
    program names no pass, the step has no such instruction, or there is
    no trace."""
    in_loop = program_in_loop()
    if in_loop is None or ctx.trace is None or ctx.hlo_text is None:
        return None
    inside = _instructions_in_loop(ctx.hlo_text, in_loop)
    if only is not None:
        paths = scopes.instruction_scopes(ctx.hlo_text)
        inside = frozenset(n for n in inside if only(paths[n], in_loop))
    if not inside:
        return None

    def step_ns(chip, lo, hi):
        return tr.length(tr.clip(
            [op.interval for op in chip.ops
             if not tr.is_collective(op) and not tr.is_container(op)
             and op.name in inside], lo, hi))
    return tr.per_step_ms(ctx.trace, step_ns)
