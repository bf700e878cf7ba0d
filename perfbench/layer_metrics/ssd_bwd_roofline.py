"""Share of its roofline that the state-space-scan kernel ``ssd_bwd`` reaches:
twice the forward's FLOP of the RECURRENT form — 10 P N a position and head
— and the least HBM bytes a call can move (perfbench/kernel_costs_ssd.py),
over ``ssd_bwd_ms``, over min(peak bf16 FLOP/s, FLOP/byte x HBM bytes/s) of
perfbench/peaks.json."""

from perfbench import kernel_costs_ssd

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_ssd.roofline(ctx, "ssd_bwd")
