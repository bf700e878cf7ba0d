"""Device time per step of linear attention, forward, backward and replay,
the ``gdn_*`` kernels included: the instructions whose ``op_name`` path
names ``block_<i>/linear_attn_norm`` or ``block_<i>/linear_attn`` (the norm,
the fused in-projections, the causal convolution and SiLU, the L2 norms and
the two gates, the Mosaic calls or the ``jax.numpy`` chunks, the running
sums of the log decay around them, the gated norm, the out-projection).

Median over steps, worst chip; None where the program has no ``area_of``,
0.0 where it knows no such area (perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "linattn")
