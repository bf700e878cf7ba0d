"""Device time per step inside the flash-attention forward kernel (24 calls a step in gpt2-medium, and 24 more where
the backward pass replays it):
summed durations of the Mosaic custom calls whose ``op_name`` ends in
``flash_fwd/pallas_call`` — the ``name=`` the program gives its
``pallas_call`` (perfbench/scopes.py); median over steps, worst chip.  With
its two siblings it divides ``pallas_ms``."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "flash_fwd")
