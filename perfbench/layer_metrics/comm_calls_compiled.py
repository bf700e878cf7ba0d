"""Collective instructions of the compiled step's optimized HLO (an async
``-start``/``-done`` pair counts once), found with the pattern of
perfbench/hlo_bytes.py: what XLA's combiner made of the
``buckets_per_step`` collectives the program asked for.  0 on one chip."""

from perfbench import hlo_bytes

LAYER = "communication"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def reduce(ctx):
    if ctx.hlo_text is None:
        return None
    return len(hlo_bytes.collectives(ctx.hlo_text, ctx.chips))
