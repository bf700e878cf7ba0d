"""Host time of one ``BaguaTrainer.train_step`` besides the jitted call:
per step, the program's root span ``step/train_step`` less its child
``step/dispatch`` (``check_abort``, the cadence / ledger / anomaly hooks,
``host_pre_step``, the step-cache key, the watchdog hand-off, the beacon);
the median over the steps in the program's span ring, read in-process
(perfbench/scopes.py).  ``dispatch_ms`` is the same call timed from outside,
so ``dispatch_ms`` less this is about the jit dispatch itself."""

from perfbench import scopes

LAYER = "trainer"
UNIT = "ms"
MOVES = "step_ms_p90"
SOURCE = "host_clock"


def reduce(ctx):
    return scopes.span_minus_child_median_ms("step/train_step",
                                             "step/dispatch")
