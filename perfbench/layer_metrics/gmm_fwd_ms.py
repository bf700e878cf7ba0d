"""Device time per step inside the grouped-matmul forward kernel: summed
durations of the Mosaic custom calls whose ``op_name`` ends in
``gmm_fwd/pallas_call`` (perfbench/scopes.py) — in OLMoE's step six calls a
layer: gate, up and down in the forward pass, and the same kernel with the
matrices transposed for their three d_lhs in the backward pass; median over
steps, worst chip."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "gmm_fwd")
