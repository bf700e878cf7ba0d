"""Share of its roofline that the gated-delta-rule kernel ``gdn_fwd``
reaches at 96-lane keys under 192-lane values (Olmo-Hybrid's cell): the FLOP
of the RECURRENT form of the rule — 6 d_k d_v a position and value head
forward, twice that backward, whatever the chunked kernel does — and the
least HBM bytes a call can move (perfbench/kernel_costs_gdn.py, whose counts
are functions of d_k, d_v and the program's head gauges), over
``gdn96_fwd_ms``, over min(peak bf16 FLOP/s, FLOP/byte x HBM bytes/s) of
perfbench/peaks.json.  None where the program sets no ``linattn/*`` gauges
or the step has no such kernel."""

from perfbench import kernel_costs_gdn

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_gdn.roofline(ctx, "gdn_fwd")
