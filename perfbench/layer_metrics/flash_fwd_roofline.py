"""Share of its roofline that the flash-attention forward kernel reaches:
the FLOP its block loops really compute (causal by block) and the least HBM
bytes it can move (perfbench/kernel_costs.py), over ``flash_fwd_ms``, over
min(peak bf16 FLOP/s, FLOP/byte x HBM bytes/s) of perfbench/peaks.json."""

from perfbench import kernel_costs, scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_roofline(ctx, "flash_fwd", kernel_costs.flash_fwd)
