"""The longest stall of the measured untraced window, by what it cost: the
program's record ``step/stall`` (a step window that its anomaly detector
flagged: longer than the rolling median by 30 % and by 5 robust deviations)
less the detector's median at the time, so a run that stalled once for 3 s
reads 3000 whatever its step.  0.0 where no window was flagged.  The record's
attributes say of what kind it was (its phases' seconds, ``explained_s``, the
threads' stacks sampled while it lasted); ``stalls_in_window`` counts them.
Which steps are the window's: perfbench/host_pauses.py."""

from perfbench import host_pauses

LAYER = "trainer"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "host_clock"


def reduce(ctx):
    stalls = host_pauses.window_spans(ctx, "step/stall")
    if stalls is None:
        return None
    return 1e3 * max((s["dur_s"] - s["attrs"]["baseline_p50"] for s in stalls),
                     default=0.0)
