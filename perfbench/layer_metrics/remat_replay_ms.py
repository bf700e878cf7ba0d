"""Device time per step spent recomputing forward work inside the
backward pass (``jax.checkpoint``): non-collective instructions under
``bagua.loss`` with ``rematted_computation`` in their ``op_name``, replayed
Pallas kernels included (perfbench/scopes.py); median over steps, worst
chip.  0 where the model does not rematerialise."""

from perfbench import scopes

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return scopes.phase_ms(ctx, scopes.REPLAY)
