"""Device time per step inside the gated-delta-rule kernel ``gdn_fwd``
(``bagua_tpu/ops/gated_delta.py``: the chunked scan of a linear-attention
layer, forward, and once more each where the backward pass replays it):
summed durations of the Mosaic custom calls whose ``op_name`` ends in
``gdn_fwd/pallas_call`` (perfbench/scopes.py); median over steps, worst
chip."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "gdn_fwd")
