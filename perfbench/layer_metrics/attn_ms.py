"""Device time per step of attention, forward, backward and replay, the
flash kernels included: the instructions whose ``op_name`` path names
``block_<i>/attn_norm`` or ``block_<i>/attn`` (the norm, the q / k / v / o
projections, ``q_norm`` / ``k_norm``, RoPE, the Mosaic calls or the
fallback attention, and the re-layouts between them).

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "attn")
