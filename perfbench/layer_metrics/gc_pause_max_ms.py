"""The longest collection of Python's cyclic collector inside the measured
untraced window: the program's span ``host/gc`` (timed between the two phases
of a ``gc.callbacks`` hook; a collection of generation 2, or any of 1 ms or
more), which the interpreter spends on whichever thread tripped it while no
other Python thread runs.  0.0 where none reached 1 ms.  A full collection's
time grows with the tracked heap: the ledger's medians show whether it creeps
up.  Which steps are the window's: perfbench/host_pauses.py."""

from perfbench import host_pauses

LAYER = "trainer"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "host_clock"


def reduce(ctx):
    return host_pauses.longest_ms(ctx, "host/gc")
