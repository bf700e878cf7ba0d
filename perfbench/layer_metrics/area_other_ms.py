"""Device time per step of ``compute_ms``'s instructions that no area
takes and that are not ``optimizer_ms``, ``bucket_layout_ms`` or the
guard's: paths under a bare ``block_<i>`` (residual adds XLA did not fuse
into a neighbour), under the loss function but no module, the scan's own
plumbing, and what carries no ``op_name`` and borrows none.  With the
areas and those three it adds up to ``compute_ms``.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, areas.OTHER)
