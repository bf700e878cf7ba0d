"""Device time per step inside the WINDOWED flash-attention dK/dV kernel
(``flash_win_bwd_dkv``: a key / value head's k block under the band's
query blocks, summed over the group's query heads in float32); summed
durations of its Mosaic calls, median over steps, worst chip."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "flash_win_bwd_dkv")
