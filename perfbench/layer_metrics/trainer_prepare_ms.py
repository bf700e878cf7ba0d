"""Host time of one ``BaguaTrainer.train_step`` between its hooks and the
jitted call: the program's span ``step/prepare`` (the reset check, the
autotune and migration branches, the step-cache key, the MFU preparation, the
static footprint, the fault accounting), which with ``step/hooks``,
``step/dispatch``, ``step/watchdog_handoff`` and ``step/end`` tiles the root
span; the median over the program's span ring, read in-process
(perfbench/scopes.py).  The largest part of ``trainer_overhead_ms``."""

from perfbench import scopes

LAYER = "trainer"
UNIT = "ms"
MOVES = "step_ms_p90"
SOURCE = "host_clock"


def reduce(ctx):
    return scopes.span_median_ms("step/prepare")
