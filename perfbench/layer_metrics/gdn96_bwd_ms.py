"""Device time per step inside the gated-delta-rule kernel ``gdn_bwd`` at
heads that are no whole lane tile (Olmo-Hybrid: 30 heads of 96-lane keys
under 192-lane values, four heads a grid step, the last block ragged):
summed durations of the Mosaic custom calls whose ``op_name`` ends in
``gdn_bwd/pallas_call`` (perfbench/scopes.py); median over steps, worst
chip.  ``gdn_bwd_ms`` under this cell's name: that entry lists
Qwen3-Next's cell."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "gdn_bwd")
