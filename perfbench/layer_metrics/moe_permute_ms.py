"""``moe_ms`` without its Mosaic calls: what the mixture-of-experts layers
spend outside the grouped-matmul kernels per step — routing, the sort and
gather by expert, the scatter into and the gather out of the kernels'
padded layout, the expert gate, the weighted scatter-add back to tokens,
and their transposes in the backward pass.  Memory- and latency-bound work
beside the kernels' compute-bound one."""

from perfbench import kernel_costs_gmm

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_gmm.moe_ms(ctx, include_kernels=False)
