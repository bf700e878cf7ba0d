"""What the bucket plan and the flat-resident layout cost on the device
besides the wire, per step: the NON-collective instructions under the
program's ``bagua.layout`` scope (the leaf view of flat parameters and, under
autodiff, the scatter of gradients back into flats; flatten, pad, unflatten)
and under ``bagua.comm`` (casts, scaling, codecs around a bucket's
collective) (perfbench/scopes.py); median over steps, worst chip."""

from perfbench import scopes

LAYER = "communication"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.phase_ms(ctx, scopes.LAYOUT)
