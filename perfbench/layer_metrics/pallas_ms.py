"""Device time inside Pallas (Mosaic) kernels per step: summed durations of
the ``custom-call`` instructions whose target is ``tpu_custom_call`` (the
trace prints the whole instruction, so no ``name=`` on the ``pallas_call`` is
needed to find them); median over steps, worst chip.  0 where the step has
no kernel: ``flash_attention`` falls back to XLA below ``MIN_FLASH_SEQ`` =
1024."""

from perfbench import trace_reduce as tr

LAYER = "kernels"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def kernel_ns(chip, lo, hi):
    return sum(min(o.end, hi) - max(o.start, lo)
               for o in chip.ops if tr.is_mosaic(o))


def reduce(ctx):
    if ctx.trace is None:
        return None
    return tr.per_step_ms(ctx.trace, kernel_ns)
