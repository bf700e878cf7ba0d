"""Device time per step of the feed-forward area, forward, backward and
replay: ``block_<i>/mlp_norm`` and the dense ``MLPBlock``
``block_<i>/mlp``.  In a model whose ``mlp`` is an expert layer this is
the norm in front of the experts and what ``MoEMLP`` runs outside its
``bagua.moe`` scopes (the reshapes between ``[b, s, d]`` and rows); the
expert layer itself is ``moe_route_ms`` ... ``moe_combine_ms``.

Median over steps, worst chip; None where the program has no ``area_of``
(perfbench/areas.py)."""

from perfbench import areas

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return areas.area_ms(ctx, "mlp")
