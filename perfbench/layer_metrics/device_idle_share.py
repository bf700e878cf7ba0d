"""Share of the traced window in which no instruction ran on the chip:
1 - union(busy) / window, the idlest chip.  The window runs from the first
traced step's start to the last one's end."""

from perfbench import trace_reduce as tr

LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    if ctx.trace is None:
        return None
    shares = [1.0 - busy / window
              for busy, window in tr.busy_and_window(ctx.trace) if window > 0]
    return 100.0 * max(shares) if shares else None
