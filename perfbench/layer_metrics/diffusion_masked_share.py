"""Share of a step's clean positions that are masked in the noised copy and
so carry loss: ``diffusion/masked_tokens_per_step`` /
``diffusion/tokens_per_step``, the program's gauges — the first set by
``block_diffusion_noise`` for the batch it drew last, the second when a
block-diffusion step is traced.  About the mean noise level, one half; the
other positions run through the trunk and the head and weigh nothing.  None
where the program sets no such gauge (another model, a program that
predates them)."""

from perfbench import scopes

LAYER = "model"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def reduce(ctx):
    masked = scopes.program_gauge("diffusion/masked_tokens_per_step")
    tokens = scopes.program_gauge("diffusion/tokens_per_step")
    if masked is None or not tokens:
        return None
    return 100.0 * masked / tokens
