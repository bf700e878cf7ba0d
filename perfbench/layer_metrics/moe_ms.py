"""Device time per step inside the mixture-of-experts layers, forward and
backward: the non-collective instructions whose ``op_name`` path has a
``bagua.moe`` component — the program's scopes ``bagua.moe/route``
(router, softmax, top-k, balance loss), ``/dispatch`` (sort, gather),
``/experts`` (the grouped matmuls with their padded layout, the gate) and
``/combine`` (weight, scatter-add); union inside the step, median over
steps, worst chip (``compute_ms``'s reduction).  ``perfbench/scopes.py``
takes the innermost ``bagua.*`` component for the phase and knows no
``moe``, so these instructions are ``unattributed_ms`` in the phase split
(PERF.md §3): this is that part by its name."""

from perfbench import kernel_costs_gmm

LAYER = "model"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def reduce(ctx):
    return kernel_costs_gmm.moe_ms(ctx, include_kernels=True)
