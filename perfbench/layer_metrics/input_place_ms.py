"""The program's own half of a step's wait for input: the span
``input/place`` that ``prefetch_to_device`` opens around
``trainer.shard_batch`` (host-to-device placement of the next batch, on the
consumer's thread); the median over the program's span ring, read in-process
(perfbench/scopes.py).  ``input_wait_ms`` less this is the user's iterator
(``input/source``)."""

from perfbench import scopes

LAYER = "input"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "host_clock"


def reduce(ctx):
    return scopes.span_median_ms("input/place")
