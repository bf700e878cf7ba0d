"""Device time per step inside the WINDOWED flash-attention dQ kernel
(``flash_win_bwd_dq``: K / V of the band's blocks under a query block);
summed durations of its Mosaic calls, median over steps, worst chip."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "flash_win_bwd_dq")
