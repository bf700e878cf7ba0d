"""FLOP and HBM bytes of ONE call of each state-space-scan kernel
(``bagua_tpu/ops/ssd.py``: ``ssd_fwd`` / ``ssd_bwd``), for the
``ssd_*_roofline`` metrics.

FLOP are those of the RECURRENT form of the scan, per position and head of
width ``P`` over a state of ``N``: the decay of the state ``P N``
multiplies, the rank-one update ``delta x B^T`` and the read ``S C`` ``2 P
N`` each, and twice that backward:

    ssd_fwd    5 P N   a position and head
    ssd_bwd   10 P N

The kernels compute the CHUNKED form, which does more (inside a chunk of
``Q`` positions ``C B^T`` a group and ``M x`` a head — ``2 Q (N / heads a
group + P)`` FLOP a position and head, 20,480 at the published sizes —
beside the read and the update against the carried state at ``2 P N`` each,
32,768: 53,248 against the count's 40,960; and the backward pass makes the
forward's values again), so a
share cannot pass 100 % unless the time leaves work out: no choice of chunk
can raise the count.

HBM bytes are the least a call can move: ``x`` and ``y`` once a head, ``B``
and ``C`` once a GROUP, the step sizes in float32; the backward call reads
those and ``dy`` and writes the four cotangents.  The per-chunk states the
forward call keeps for the backward one, and the running sum of the log
decay that rides beside the step sizes, are not counted: they are the
implementation's, not the scan's.

What the HLO does not say — heads, groups — is read from the program's
gauges ``ssm/heads`` / ``ssm/groups``, set when the step is traced; a
call's shapes are read from its operands in the compiled step (x ``[b, T,
heads x P]``, B ``[b, T, groups x N]``, C the same).
"""

from __future__ import annotations

import functools

from perfbench import hlo_bytes, kernel_costs_window, scopes
from perfbench import trace_reduce as tr


def _cost(passes: int, head_tensors: int, group_tensors: int,
          scalar_tensors: int):
    """``passes`` times the forward's FLOP; the tensors a call moves once,
    counted by kind: ``[b, T, heads, P]`` (x, y and their cotangents), ``[b,
    T, groups, N]`` (B, C and theirs) and the float32 ``[b, T, heads]``
    step sizes (and theirs)."""
    def cost(batch: int, seq: int, heads: int, groups: int, p: int, n: int,
             itemsize: int):
        flop = passes * batch * seq * heads * 5 * p * n
        moved = batch * seq * (head_tensors * heads * p * itemsize
                               + group_tensors * groups * n * itemsize
                               + scalar_tensors * heads * 4)
        return flop, moved
    return cost


COSTS = {
    #: reads x, B C, dt; writes y
    "ssd_fwd": _cost(1, 2, 2, 1),
    #: reads x dy, B C, dt; writes dx, dB dC, d dt
    "ssd_bwd": _cost(2, 3, 4, 2),
}


@functools.lru_cache(maxsize=2)
def call_shapes(hlo_text: str) -> dict[str, tuple[int, int, int, int, int]]:
    """``{instruction: (batch, seq, heads x P, groups x N, itemsize)}`` of
    every Mosaic call whose first three operands are rank-3 ``x``, ``B`` and
    ``C`` (B and C equal shapes) over the same rows, and whose fourth is the
    rank-4 scalars ``[b, heads, chunks, Q]``."""
    found = {}
    for line in hlo_text.splitlines():
        if tr.MOSAIC_TARGET not in line:
            continue
        named = kernel_costs_window._NAME.match(line)
        operands = kernel_costs_window._OPERANDS.search(line)
        if not named or not operands:
            continue
        shapes = [(dtype, [int(n) for n in dims.split(",") if n])
                  for dtype, dims in kernel_costs_window._SHAPE.findall(
                      operands.group(1))]
        if len(shapes) < 6 or [len(d) for _, d in shapes[:4]] != [3, 3, 3, 4]:
            continue
        (dtype, x), (_, b), (_, c) = shapes[:3]
        if b == c and x[:2] == b[:2]:
            found[named.group(1)] = (x[0], x[1], x[2], b[2],
                                     hlo_bytes._DTYPE_BYTES.get(dtype, 2))
    return found


def roofline(ctx, kernel: str) -> float | None:
    """Share (%) of its roofline that ``kernel`` reaches: the recurrent
    form's FLOP per step over its summed device time per step, over min(peak
    FLOP/s, FLOP/byte x HBM bytes/s).  None where the step has no such
    kernel or the program sets no ``ssm/*`` gauges."""
    ms = scopes.kernel_ms(ctx, kernel)
    heads = scopes.program_gauge("ssm/heads")
    groups = scopes.program_gauge("ssm/groups")
    if not ms or ctx.peak is None or not heads or not groups:
        return None
    names = scopes.instruction_scopes(ctx.hlo_text)
    shapes = call_shapes(ctx.hlo_text)

    def one(op_name: str):
        batch, seq, width, maps, itemsize = shapes[op_name]
        return COSTS[kernel](batch, seq, int(heads), int(groups),
                             width // int(heads), maps // int(groups),
                             itemsize)

    def per_step(part: int) -> float:
        def step_total(chip, lo, hi):
            return sum(one(op.name)[part]
                       for op in scopes.kernel_events(chip, names, kernel)
                       if op.name in shapes)
        return 1e6 * tr.per_step_ms(ctx.trace, step_total)

    flop, moved = per_step(0), per_step(1)
    if not flop or not moved:
        return None
    attainable = min(ctx.peak["bf16_flops_per_s"],
                     flop / moved * ctx.peak["hbm_bytes_per_s"])
    return 100.0 * flop / (ms * 1e-3) / attainable
