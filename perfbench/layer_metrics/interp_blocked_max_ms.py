"""The longest interval of the measured untraced window in which no Python
thread could run: the program's span ``host/blocked``, from the time its
heartbeat thread (a 20 ms sleep) was due to the time it woke, where that was
50 ms or more late — a C call that kept the interpreter lock, a collection
(then a ``host/gc`` span overlaps it), the process not running.  0.0 where
the heartbeat was never that late.  Which steps are the window's:
perfbench/host_pauses.py."""

from perfbench import host_pauses

LAYER = "trainer"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "host_clock"


def reduce(ctx):
    return host_pauses.longest_ms(ctx, "host/blocked")
