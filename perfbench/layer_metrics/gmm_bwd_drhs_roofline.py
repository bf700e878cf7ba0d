"""Share of its roofline that the grouped outer-product kernel reaches:
2 x padded rows x d x f FLOP a call and the least HBM bytes it can move
(perfbench/kernel_costs_gmm.py), over ``gmm_bwd_drhs_ms``, over min(peak
bf16 FLOP/s, FLOP/byte x HBM bytes/s) of perfbench/peaks.json."""

from perfbench import kernel_costs_gmm

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_gmm.roofline(ctx, "gmm_bwd_drhs")
