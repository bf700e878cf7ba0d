"""Step windows of the measured untraced window that the program's anomaly
detector flagged: the number of its ``step/stall`` records there (0 in a
steady run; ``stall_max_ms`` is the largest).  A count, so it prints under
``--rehearse`` too — where the profiled steps cannot be told from the
window's and the steps read are shifted by them (perfbench/host_pauses.py)."""

from perfbench import host_pauses

LAYER = "trainer"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def reduce(ctx):
    stalls = host_pauses.window_spans(ctx, "step/stall")
    return None if stalls is None else len(stalls)
