"""Share of its roofline that the windowed flash-attention forward kernel
reaches: the FLOP of the (query, key) pairs inside the band and the least
HBM bytes a call can move, K / V once per key / value head
(perfbench/kernel_costs_window.py), over ``flash_win_fwd_ms``, over
min(peak bf16 FLOP/s, FLOP/byte x HBM bytes/s) of perfbench/peaks.json."""

from perfbench import kernel_costs_window

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_window.roofline(ctx, "flash_win_fwd")
