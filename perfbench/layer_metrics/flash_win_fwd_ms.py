"""Device time per step inside the WINDOWED flash-attention forward kernel
(``flash_win_fwd``: the layers whose attention is a causal window; in the
SmallThinker cell three layers of four, and once more each where the
backward pass replays it): summed durations of the Mosaic custom calls
whose ``op_name`` ends in ``flash_win_fwd/pallas_call``
(perfbench/scopes.py); median over steps, worst chip."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "flash_win_fwd")
