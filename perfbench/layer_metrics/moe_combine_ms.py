"""Device time per step under ``bagua.moe/combine``: the weighted gather
out of the padded layout; backward: the gather of the output's cotangent
into the layout and the gates' gradient.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "moe/combine")
