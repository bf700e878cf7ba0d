"""Share of its roofline that the windowed flash-attention dK/dV kernel
reaches (perfbench/kernel_costs_window.py: the band's pairs, four matmuls
each; dK / dV written once per key / value head), over
``flash_win_dkv_ms``."""

from perfbench import kernel_costs_window

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_window.roofline(ctx, "flash_win_bwd_dkv")
