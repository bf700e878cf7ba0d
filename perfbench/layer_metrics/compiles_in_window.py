"""Backend compilations requested inside the measured window
(``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``
events): the trainer's step cache must serve every step from the program
warmed up during set-up, so this is 0 or the run is not ``correct``."""

LAYER = "trainer"
UNIT = "count"
MOVES = "step_ms_p90"
SOURCE = "program_counter"


def reduce(ctx):
    return ctx.counters.get("compiles_in_window")
