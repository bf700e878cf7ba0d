"""Device time per step of the token table, forward and backward: the
instructions whose ``op_name`` path names the module ``embed`` (the
gather, the cast of the table, the scatter-add of its gradient) or the
plain scope ``pos_embed`` (slice and add of the learned position table of
the bert and gpt2 configurations).  AdamW's update of the table is
``optimizer_ms``'s unless the compiler fused it into the gradient's
fusion and that fusion's root carries this path.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "embed")
