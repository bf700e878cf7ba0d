"""Share of its roofline that the gated-delta-rule kernel ``gdn_fwd``
reaches: the FLOP of the RECURRENT form of the rule — 6 d_k d_v a position
and value head, which the chunked kernel's own work is about twice — and the
least HBM bytes a call can move (perfbench/kernel_costs_gdn.py), over
``gdn_fwd_ms``, over min(peak bf16 FLOP/s, FLOP/byte x HBM bytes/s) of
perfbench/peaks.json."""

from perfbench import kernel_costs_gdn

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_gdn.roofline(ctx, "gdn_fwd")
