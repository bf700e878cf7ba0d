"""Profiler integration — the TPU-native tracing subsystem.

SURVEY.md §5.1: the reference's OTel span pipeline exists to recover the
tensor execution order for the autotuner (covered here by
:mod:`bagua_tpu.telemetry`); its *profiling* role — seeing where step time
goes — maps to ``jax.profiler`` traces, which capture XLA op timelines,
collective costs on ICI, and host callstacks viewable in TensorBoard /
Perfetto.

Two entry points:

- :func:`trace`: context manager around any region.
- trainer auto-capture: set ``BAGUA_PROFILE_DIR=/path`` (and optionally
  ``BAGUA_PROFILE_STEPS=start:stop``, default ``2:5`` — skip compile
  steps, keep the trace small).  ``BaguaTrainer.train_step`` starts/stops
  the trace at those step numbers; no code changes in the training script.
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Optional, Tuple

logger = logging.getLogger(__name__)


def profile_dir() -> Optional[str]:
    from . import env

    return env.get_profile_dir()


def profile_steps() -> Tuple[int, int]:
    """[start, stop) step window for trainer auto-capture."""
    from . import env

    raw = env.get_profile_steps_raw()
    try:
        start, stop = raw.split(":")
        return int(start), int(stop)
    except ValueError:
        logger.warning("BAGUA_PROFILE_STEPS=%r is not start:stop; using 2:5",
                       raw)
        return 2, 5


# jax allows only one profile at a time; track the owner (a StepProfiler or
# the trace() context manager) so the other entry point skips its turn
# instead of crashing
_TRACE_OWNER: Optional[object] = None


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a ``jax.profiler`` trace of the enclosed region.

    If a trace is already running (e.g. trainer auto-capture via
    ``BAGUA_PROFILE_DIR`` has its step window open), the region runs
    untraced with a warning — jax allows only one profile at a time."""
    global _TRACE_OWNER
    import jax

    if _TRACE_OWNER is not None:
        logger.warning(
            "profiling.trace(%s): another trace is active; running untraced",
            log_dir,
        )
        yield
        return
    token = object()
    jax.profiler.start_trace(log_dir)
    _TRACE_OWNER = token  # only own it once start_trace succeeded
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        finally:
            if _TRACE_OWNER is token:
                _TRACE_OWNER = None


class StepProfiler:
    """Start/stop a trace across a step-number window (trainer hook).

    Registered with ``atexit`` so a run that ends before the stop step
    still flushes its trace instead of silently losing it.
    """

    def __init__(self, log_dir: str, start: int, stop: int):
        self.log_dir = log_dir
        self.start = start
        self.stop = stop
        self._active = False
        self._done = False

    @classmethod
    def from_env(cls) -> Optional["StepProfiler"]:
        d = profile_dir()
        if not d:
            return None
        start, stop = profile_steps()
        prof = cls(d, start, stop)
        import atexit

        atexit.register(prof.close)
        return prof

    def on_step(self, step: int) -> None:
        """Call once per train step BEFORE dispatching it."""
        global _TRACE_OWNER
        import jax

        if self._done:
            return
        if not self._active and step >= self.start:
            if _TRACE_OWNER is not None:
                # another trainer's window is still open — skip rather
                # than crash on jax's one-profile-at-a-time limit
                return
            jax.profiler.start_trace(self.log_dir)
            _TRACE_OWNER = self
            self._active = True
            logger.info("profiler: tracing steps [%d, %d) -> %s",
                        self.start, self.stop, self.log_dir)
        elif self._active and step >= self.stop:
            self.close()

    def close(self) -> None:
        global _TRACE_OWNER
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            if _TRACE_OWNER is self:
                _TRACE_OWNER = None
            logger.info("profiler: trace written to %s", self.log_dir)
