"""Device time per step inside the passes of a looped model: the
instructions whose ``op_name`` path has the ``loop_body`` component (the
plain scope around the blocks of a pass), forward, backward and replay, all
passes together.  It is the trunk: every block of every pass, and nothing of
the heads, the exit gate or the update; about ``attn_ms`` + ``mlp_ms``, plus
what a bare ``block_<i>`` (residual adds left unfused) and the ``remat``
boundary's copies put under ``area_other_ms``.

Median over steps, worst chip; None where the program names no pass
(perfbench/loops.py)."""

from perfbench import loops

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return loops.trunk_ms(ctx)
