"""Buckets of the plan that the compiled step exchanges: the program's
gauge ``comm/buckets_per_step``, set when it builds a step program (0 where
the communication world is one chip), read in-process (perfbench/scopes.py).
What XLA's combiner makes of them is ``comm_calls_compiled``."""

from perfbench import scopes

LAYER = "communication"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def reduce(ctx):
    return scopes.program_gauge("comm/buckets_per_step")
