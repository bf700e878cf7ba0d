"""Device time per step inside the flash-attention dQ kernel of the backward pass:
summed durations of the Mosaic custom calls whose ``op_name`` ends in
``flash_bwd_dq/pallas_call`` — the ``name=`` the program gives its
``pallas_call`` (perfbench/scopes.py); median over steps, worst chip.  With
its two siblings it divides ``pallas_ms``."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "flash_bwd_dq")
