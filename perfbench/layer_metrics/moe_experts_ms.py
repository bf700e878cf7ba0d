"""Device time per step under ``bagua.moe/experts``: the grouped-matmul
calls (``gmm_fwd``, ``gmm_bwd_drhs``), the cast of the expert matrices,
the gate and the hidden rows rebuilt in the backward pass.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "moe/experts")
