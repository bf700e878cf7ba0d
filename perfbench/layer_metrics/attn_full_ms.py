"""Device time per step inside the three flash-attention kernels of the
FULL-attention layers (``flash_fwd`` + ``flash_bwd_dq`` +
``flash_bwd_dkv``; in the SmallThinker cell one layer of four, 28 query
heads over 4 key / value heads, every causal block visited): beside the
three ``flash_win_*_ms`` it says what a window layer costs against a full
one.  None where the step has no kernel names to read."""

from perfbench import scopes

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    parts = [scopes.kernel_ms(ctx, name)
             for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")]
    return None if None in parts else sum(parts)
