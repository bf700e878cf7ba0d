"""Device time per step under ``bagua.moe/dispatch``: the sort by expert,
the padded layout and its index maps, the gather of the tokens' rows into
it; backward: the sum over a token's ``k`` rows.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "moe/dispatch")
