"""Device time of the backward pass per step, without the replay of
rematerialised forward work: non-collective instructions under
``transpose(jvp(bagua.loss))`` with no ``rematted_computation`` in the path
(perfbench/scopes.py); median over steps, worst chip."""

from perfbench import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.phase_ms(ctx, scopes.BACKWARD)
