"""Device time per step of the head, forward and backward: ``final_norm``,
``lm_head`` (the logits and both gradient matmuls) and the plain scope
``loss_tail`` (cross-entropy and mean).  Where the one-chip compiler
fuses AdamW's update of the head matrix into the output fusion of the
matmul that makes its gradient, that fusion reads here if its root
carries the head's path and under ``optimizer_ms`` if it carries
``bagua.optimizer``: a fusion has one ``op_name``.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "head")
