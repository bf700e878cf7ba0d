"""Device time per step inside the two grouped-matmul kernels (``gmm_fwd``
+ ``gmm_bwd_drhs``) where the chip holds one expert-parallel rank's share:
the layout's row count is static at its worst case (every routed pair held
here: 51,200 rows a layer in the SmallThinker cell) while about a quarter of
it holds routed rows; the kernels multiply all of it, the zero rows like
the others, so this time does NOT follow the live rows (PERF.md §6, PR 34:
skipping the dead row blocks cut it to a fifth and made the step's time
follow the routing, which moves from step to step).  None where the step
has no kernel names to read."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    parts = [scopes.kernel_ms(ctx, name)
             for name in ("gmm_fwd", "gmm_bwd_drhs")]
    return None if None in parts else sum(parts)
