"""Device time per step inside the BLOCK-DIFFUSION flash-attention dQ
kernel (``flash_bd_bwd_dq``: every layer of a model whose rows are ``[x ;
x~]`` under the block-diffusion mask; in the SDAR cell six layers): summed
durations of the Mosaic custom calls whose ``op_name`` ends in
``flash_bd_bwd_dq/pallas_call`` (perfbench/scopes.py); median over steps,
worst chip."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "flash_bd_bwd_dq")
