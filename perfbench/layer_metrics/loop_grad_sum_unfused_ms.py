"""Device time per step of those sums of a looped model's shared weights'
gradients over the passes that the compiled step runs as instructions of
their own (the sums it fuses into the matmuls that make the gradients are
not seen apart, hence the name): the backward of the ONE scanned body carries every
weight's float32 gradient sum from pass to pass, and the instructions named
``.../loop_body/add_any`` (a sum of cotangents at the body's own level) add
a pass's part to it.  Compiled for a v5e (PR 39) those are the attention
matrices' and the norm scales' sums; the FFN matrices' sums are fused into
the output of the matmuls that make their gradients and read under
``mlp_ms``.  Part of ``loop_trunk_ms`` and, naming no module, of
``area_other_ms``.

Median over steps, worst chip; None where the program names no pass or the
step has no such instruction (perfbench/loops.py)."""

from perfbench import loops

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return loops.trunk_ms(ctx, only=loops.is_grad_sum)
