"""Device time per step inside the grouped outer-product kernel that makes
the expert matrices' gradients: summed durations of the Mosaic custom calls
whose ``op_name`` ends in ``gmm_bwd_drhs/pallas_call`` (three calls a layer
in OLMoE's step); median over steps, worst chip."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "gmm_bwd_drhs")
