"""Device time of the optimizer update per step: non-collective
instructions under the program's ``bagua.optimizer`` scope (the optax update,
``apply_updates``, the algorithm's pre- and post-step stages), with the
prefetches and copies the compiler made for them (perfbench/scopes.py);
median over steps, worst chip."""

from perfbench import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.phase_ms(ctx, scopes.OPTIMIZER)
