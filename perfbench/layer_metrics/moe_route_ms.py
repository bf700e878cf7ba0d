"""Device time per step under ``bagua.moe/route``, forward and backward:
the router's projection, softmax, top-k and the balance loss.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "moe/route")
