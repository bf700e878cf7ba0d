"""Share of the rows the grouped-matmul kernels multiply that are padding:
1 - ``moe/rows_per_step`` / ``moe/padded_rows_per_step``, the program's
gauges, set when a step with a dropless expert layer is traced (tokens x
experts per token, against the static row count of the kernels' layout in
which every expert's rows are rounded up to the 128-row block at the worst
case).  None where the program sets no such gauge (a dense model, a program
that predates them)."""

from perfbench import scopes

LAYER = "model"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def reduce(ctx):
    rows = scopes.program_gauge("moe/rows_per_step")
    padded = scopes.program_gauge("moe/padded_rows_per_step")
    if not rows or not padded:
        return None
    return 100.0 * (1.0 - rows / padded)
