"""FLOP and HBM bytes of ONE call of each grouped-matmul kernel
(``bagua_tpu/ops/gmm.py``), for the ``gmm_*_roofline`` metrics, and the
reduction that reads the calls' shapes from the compiled step.

The kernels work on the block-aligned PADDED layout: every group's rows are
rounded up to the row block, so that a row block belongs to one group and a
grid step is one dense product.  What a call computes is therefore fixed by
the padded row count ``R`` (a static shape; the zero rows are multiplied
like the others), whatever the routing:

    gmm_fwd       [R, d] x [G, d, f] -> [R, f]        2 R d f FLOP
                  (the forward product, and d_lhs with ``rhs`` transposed)
    gmm_bwd_drhs  [R, d]^T [R, f] by group -> [G, d, f] f32    2 R d f FLOP

HBM bytes are the least the call can move: the rows in once, every group's
matrix once, the result out once.  The kernels move more (``gmm_fwd``
fetches a group's ``[d, block_f]`` slab again for every row block of the
group; ``gmm_bwd_drhs`` reads the rows once per block of the other
dimension), which is why the share is of a roofline and not of the kernel's
own traffic.  With more FLOP per byte than the chip's ridge (240 on a v5e)
both are compute-bound at these sizes, and the share cannot pass 100 %
unless FLOP are counted that the kernel does not compute.
"""

from __future__ import annotations

import functools
import re

from perfbench import hlo_bytes, scopes
from perfbench import trace_reduce as tr


def gmm_fwd(rows: int, d: int, f: int, groups: int, itemsize: int):
    """(FLOP, least HBM bytes) of one ``gmm_fwd`` call on ``rows`` padded
    rows."""
    return (2 * rows * d * f,
            itemsize * (rows * d + groups * d * f + rows * f))


def gmm_bwd_drhs(rows: int, d: int, f: int, groups: int, itemsize: int):
    """(FLOP, least HBM bytes) of one ``gmm_bwd_drhs`` call: two row
    operands in ``itemsize``, the per-group result in float32."""
    return (2 * rows * d * f,
            itemsize * rows * (d + f) + 4 * groups * d * f)


COSTS = {"gmm_fwd": gmm_fwd, "gmm_bwd_drhs": gmm_bwd_drhs}

_NAME = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPERANDS = re.compile(
    r"operand_layout_constraints=\{([^{}]*(?:\{[^{}]*\}[^{}]*)*)\}")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


@functools.lru_cache(maxsize=2)
def call_shapes(hlo_text: str) -> dict[str, tuple[int, int, int, int, int]]:
    """``{instruction: (padded rows, d, f, groups, itemsize)}`` of every
    Mosaic call whose operands are those of a grouped matmul: the block ->
    group table (s32, rank 1), then either rows [R, d] and matrices
    [G, d, f] (``gmm_fwd``) or two row operands [R, d], [R, f]
    (``gmm_bwd_drhs``, whose group count is its result's leading size)."""
    found = {}
    for line in hlo_text.splitlines():
        if tr.MOSAIC_TARGET not in line:
            continue
        named, operands = _NAME.match(line), _OPERANDS.search(line)
        if not named or not operands:
            continue
        shapes = [(dtype, [int(n) for n in dims.split(",") if n])
                  for dtype, dims in _SHAPE.findall(operands.group(1))]
        if len(shapes) != 3 or shapes[0][0] != "s32" or len(shapes[0][1]) != 1:
            continue
        (dtype, lhs), (_, rhs) = shapes[1], shapes[2]
        itemsize = hlo_bytes._DTYPE_BYTES.get(dtype, 2)
        if len(lhs) == 2 and len(rhs) == 3 and lhs[1] == rhs[1]:
            found[named.group(1)] = (lhs[0], lhs[1], rhs[2], rhs[0], itemsize)
        elif len(lhs) == 2 and len(rhs) == 2 and lhs[0] == rhs[0]:
            result = _SHAPE.search(line, named.end())
            groups = int(result.group(2).split(",")[0]) if result else 0
            found[named.group(1)] = (lhs[0], lhs[1], rhs[1], groups, itemsize)
    return found


def roofline(ctx, kernel: str) -> float | None:
    """Share (%) of its roofline that ``kernel`` reaches: its FLOP per step
    over its summed device time per step, over min(peak FLOP/s, FLOP/byte x
    HBM bytes/s); shapes from the compiled step's text, calls counted in
    the trace (``scopes.kernel_roofline``'s reduction, for these operands).
    None where the step has no such kernel."""
    ms = scopes.kernel_ms(ctx, kernel)
    if not ms or ctx.peak is None:
        return None
    names = scopes.instruction_scopes(ctx.hlo_text)
    shapes = call_shapes(ctx.hlo_text)

    def per_step(part: int) -> float:
        def step_total(chip, lo, hi):
            return sum(COSTS[kernel](*shapes[op.name])[part]
                       for op in scopes.kernel_events(chip, names, kernel)
                       if op.name in shapes)
        return 1e6 * tr.per_step_ms(ctx.trace, step_total)

    flop, moved = per_step(0), per_step(1)
    if not flop or not moved:
        return None
    attainable = min(ctx.peak["bf16_flops_per_s"],
                     flop / moved * ctx.peak["hbm_bytes_per_s"])
    return 100.0 * flop / (ms * 1e-3) / attainable


def moe_ns(ctx, include_kernels: bool):
    """``step_ns`` for ``tr.per_step_ms``: device time inside a step of the
    non-collective instructions whose scope path has a ``bagua.moe``
    component, forward and backward; without the Mosaic calls where
    ``include_kernels`` is false.  None where the compiled step has no such
    scope (a program without the MoE scopes, or a dense model)."""
    paths = scopes.instruction_scopes(ctx.hlo_text)
    inside = {name for name, path in paths.items()
              if "bagua.moe" in path.split("/")}
    if not inside:
        return None

    def step_ns(chip, lo, hi):
        return tr.length(tr.clip(
            [op.interval for op in chip.ops
             if op.name in inside and not tr.is_collective(op)
             and not tr.is_container(op)
             and (include_kernels or not tr.is_mosaic(op))], lo, hi))
    return step_ns


def moe_ms(ctx, include_kernels: bool) -> float | None:
    if ctx.trace is None or ctx.hlo_text is None:
        return None
    step_ns = moe_ns(ctx, include_kernels)
    return None if step_ns is None else tr.per_step_ms(ctx.trace, step_ns)
