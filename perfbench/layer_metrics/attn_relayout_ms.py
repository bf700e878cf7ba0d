"""The part of ``attn_ms`` that computes nothing: the instructions of the
attention area that the trace labels with a bare ``copy``, ``reshape``,
``convert``, ``transpose``, ``slice``, ``concatenate`` or ``bitcast``
(``trace_reduce``'s ``op.label``): no fusion, no Mosaic call, no dot.
What q, k, v, o and their gradients cost to bring into the layout their
consumer reads.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, areas.ATTN_RELAYOUT)
