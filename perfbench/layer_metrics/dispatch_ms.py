"""Host time of one ``BaguaTrainer.train_step`` call: the benchmark's span
around the call, which only enqueues the step (obs hooks, watchdog hand-off
and jit dispatch included), median over the untraced window.  It bounds the
step only where the device waits for the host (``device_idle_share``)."""

import statistics

LAYER = "trainer"
UNIT = "ms"
MOVES = "step_ms_p90"
SOURCE = "host_clock"


def reduce(ctx):
    calls = ctx.spans.get("bench/train_step")
    return 1e3 * statistics.median(calls) if calls else None
