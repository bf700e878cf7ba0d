"""Bytes one chip puts on the interconnect per step: the collectives of the
compiled step's optimized HLO under the ring model (perfbench/hlo_bytes.py).
A count; 0 on one chip, where the communication layer returns the bucket
untouched."""

from perfbench import hlo_bytes

LAYER = "communication"
UNIT = "bytes"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def reduce(ctx):
    if ctx.hlo_text is None:
        return None
    return hlo_bytes.wire_bytes(ctx.hlo_text, ctx.chips)
