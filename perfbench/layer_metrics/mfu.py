"""Model FLOP/s utilisation: the FLOP the forward and backward passes
require per unit of work (perfbench/flops.py: analytic, no recomputation,
attention at the full s x s) times the units per second per chip measured in
this run's untraced window, over the chip's published bf16 peak
(perfbench/peaks.json).  Per cell it is ``tokens_per_s_per_chip`` times a
constant, which is why it is not an end-to-end metric."""

LAYER = "model"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "host_clock"


def reduce(ctx):
    if ctx.peak is None or ctx.rate_per_chip is None:
        return None
    return 100.0 * ctx.flops_per_unit * ctx.rate_per_chip / ctx.peak["bf16_flops_per_s"]
