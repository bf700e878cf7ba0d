"""Communication the step does not hide: per step, the union of the
intervals in which a collective is in flight on a chip, minus the union of
that chip's compute instructions; median over steps, worst chip."""

from perfbench import trace_reduce as tr

LAYER = "communication"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def exposed_ns(chip, lo, hi):
    in_flight = tr.clip(tr.collective_intervals(chip.ops, chip.async_ops),
                        lo, hi)
    return tr.length(tr.subtract(in_flight, tr.compute_intervals(chip.ops)))


def reduce(ctx):
    if ctx.trace is None:
        return None
    return tr.per_step_ms(ctx.trace, exposed_ns)
