"""The program's rare spans, read for the measured untraced window: the
interpreter's pauses (``host/gc``, ``host/blocked``) and the stall records
(``step/stall``) of ``bagua_tpu/obs/pauses.py`` and ``obs/anomaly.py``, kept
beside the program's span ring where a window's 50 to 440 steps of chatter do
not push them out, and read in-process like ``perfbench/scopes.py``'s spans.

Which steps are the window's.  The program stamps every span with the step
number of the ``train_step`` call that was last begun.  The trainer's last
step is the newest root span in the ring; before it came the profiled steps
(as many as the step program ran in the trace) and before those the window's
``len(ctx.spans["bench/train_step"])`` calls.  Where there is no device
trace (the CPU rehearsal) the profiled steps cannot be counted, and the steps
read are the window's shifted later by that many: the last profiled steps in
place of the window's first.

What is left out.  Set-up, whose compile fills the heap and whose harvest
thread holds the interpreter by design; the profiled steps; and the window's
last step: a step's spans run until the next step begins, and the last one's
run until the first profiled step does — through the drain of the steps in
flight and ``jax.profiler.start_trace``, which takes hundreds of
milliseconds and is no stall of the run's.

Every reader returns None, and never raises, on a program that has no such
spans (one that predates ``bagua_tpu.obs.pauses``); 0.0 or 0 where the
program has them and the window none.
"""

from __future__ import annotations

from perfbench import scopes


def window_spans(ctx, name: str) -> list[dict] | None:
    """The program's spans called ``name`` that fell into the measured
    untraced window (module docstring); None where the program keeps none."""
    try:
        import bagua_tpu.obs.pauses  # noqa: F401 - the capability probe
    except ImportError:
        return None
    calls = ctx.spans.get("bench/train_step")
    spans = scopes.program_spans()
    roots = [s["step"] for s in spans
             if s["name"] == "step/train_step" and s.get("step") is not None]
    if not calls or not roots:
        return None
    profiled = 0
    if ctx.trace is not None:
        profiled = max(len(chip.steps()) for chip in ctx.trace.chips.values())
    last = max(roots) - profiled        # the window's last step: left out
    steps = range(last - len(calls) + 1, last)
    return [s for s in spans if s["name"] == name and s.get("step") in steps]


def longest_ms(ctx, name: str) -> float | None:
    """Duration of the longest span ``name`` of the window, 0.0 where the
    window has none."""
    spans = window_spans(ctx, name)
    if spans is None:
        return None
    return 1e3 * max((s["dur_s"] for s in spans), default=0.0)
