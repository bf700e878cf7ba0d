"""Time a step waits for its input: the benchmark's span around
``next(batches)`` (``prefetch_to_device`` hands over a batch already on the
device and starts the next transfer), median per step over the untraced
window.  Prediction: about 0."""

import statistics

LAYER = "input"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "host_clock"


def reduce(ctx):
    waits = ctx.spans.get("bench/next_batch")
    return 1e3 * statistics.median(waits) if waits else None
