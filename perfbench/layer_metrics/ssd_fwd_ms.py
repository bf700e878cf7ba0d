"""Device time per step inside the state-space-scan kernel ``ssd_fwd``
(``bagua_tpu/ops/ssd.py``: the chunked scan of a Mamba-2 layer, forward, and
once more each where the backward pass replays it): summed durations of the
Mosaic custom calls whose ``op_name`` ends in ``ssd_fwd/pallas_call``
(perfbench/scopes.py); median over steps, worst chip."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.kernel_ms(ctx, "ssd_fwd")
