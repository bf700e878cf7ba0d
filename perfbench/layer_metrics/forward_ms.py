"""Device time of the forward pass per step: the non-collective
instructions whose ``op_name`` sits under the program's ``bagua.loss`` scope
as ``jvp(bagua.loss)`` and under no ``transpose(`` (perfbench/scopes.py);
union inside the step, median over steps, worst chip — ``compute_ms``'s
reduction, of which this is one part."""

from perfbench import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.phase_ms(ctx, scopes.FORWARD)
