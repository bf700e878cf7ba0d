"""Device time per step of the state-space layers, forward, backward and
replay, the ``ssd_*`` kernels included: the instructions whose ``op_name``
path names ``block_<i>/ssm_norm`` or ``block_<i>/ssm`` (the norm, the fused
in-projection, the causal convolution with its bias and SiLU, softplus and
the running sums of the log decay, the Mosaic calls or the ``jax.numpy``
chunks, the gate and the grouped norm, the out-projection).

Median over steps, worst chip; None where the program has no ``area_of``,
0.0 where it knows no such area (perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "ssm")
