"""Device time per step under ``bagua.moe/shared``, forward, backward and
replay: the shared expert beside the routed ones (its three matrices on
every token, the activation, the float32 sigmoid gate), which every
expert-parallel rank computes alike.

Median over steps, worst chip; None where the program has no ``area_of``,
0.0 where it knows no such part (perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "moe/shared")
