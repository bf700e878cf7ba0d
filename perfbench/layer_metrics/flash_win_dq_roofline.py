"""Share of its roofline that the windowed flash-attention dQ kernel reaches
(perfbench/kernel_costs_window.py: the band's pairs, three matmuls each;
K / V once per key / value head), over ``flash_win_dq_ms``."""

from perfbench import kernel_costs_window

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_window.roofline(ctx, "flash_win_bwd_dq")
