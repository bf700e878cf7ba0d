"""Device time per step of gradient accumulation itself
(``BaguaTrainer(accum_steps > 1)``): the plain scope ``grad_accum`` around
the micro-batch reshape and slices, the zeros of the scan's carry, the
adds of loss and gradients and the final division; not the micro-steps'
loss, which reads by phase and by area like any other.  0 where
``accum_steps`` is 1.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "trainer"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "accum")
