"""Device time per step of the non-collective instructions that resolve
to no ``bagua.*`` scope, neither by their own ``op_name`` nor through the
computation they call, their consumer or their operand
(perfbench/scopes.py); median over steps, worst chip.  It is the whole of
``compute_ms`` for a program without phase scopes, and what a reader of the
other phase metrics must hold against them."""

from perfbench import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.phase_ms(ctx, scopes.UNATTRIBUTED)
