"""Device time of the model step itself: per step, the union of the
intervals of every instruction that is not a collective (forward, backward
and optimizer update as XLA compiled them; Pallas kernels included); median
over steps, worst chip."""

from perfbench import trace_reduce as tr

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def compute_ns(chip, lo, hi):
    return tr.length(tr.clip(tr.compute_intervals(chip.ops), lo, hi))


def reduce(ctx):
    if ctx.trace is None:
        return None
    return tr.per_step_ms(ctx.trace, compute_ns)
