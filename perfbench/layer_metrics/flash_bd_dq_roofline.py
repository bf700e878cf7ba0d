"""Share of its roofline that the block-diffusion flash-attention dQ
kernel reaches: the FLOP of the VISIBLE (query, key) pairs — ``L (L + B)`` a
head of the ``(2 L)^2`` — and the least HBM bytes a call can move, K / V
once per key / value head (perfbench/kernel_costs_blockdiff.py), over
``flash_bd_dq_ms``, over min(peak bf16 FLOP/s, FLOP/byte x HBM bytes/s) of
perfbench/peaks.json."""

from perfbench import kernel_costs_blockdiff

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_blockdiff.roofline(ctx, "flash_bd_bwd_dq")
