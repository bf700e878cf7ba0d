"""Device time per step of linear attention in the Olmo-Hybrid cell, forward,
backward and replay, the ``gdn_*`` kernels and row passes at 96-lane keys
under 192-lane values included: the instructions whose ``op_name`` path names
``block_<i>/linear_attn`` or ``block_<i>/linear_attn_post_norm`` (the fused
in-projections, the passes around the rule, the Mosaic calls, the running
sums of the log decay, the out-projection and the output norm behind it).
``linattn_ms`` under this cell's name: that entry lists Qwen3-Next's cell.

Median over steps, worst chip; None where the program has no ``area_of``,
0.0 where it knows no such area (perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "linattn")
