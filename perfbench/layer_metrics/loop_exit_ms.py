"""Device time per step of a looped model's exits, forward and backward:
the module ``exit_gate`` (a ``Dense(1)`` on each pass's normed state) and
the plain scope ``exit_dist`` (the exit distribution over the passes, its
entropy, the weighting of the passes' per-token cross-entropies and the
mean): the program's area ``exit``.  The heads themselves (``final_norm``,
``lm_head``, the per-token cross-entropy) read under ``head_ms``.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py), 0.0 where the step has no such instruction."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "exit")
