"""FLOP and HBM bytes of ONE call of each gated-delta-rule kernel
(``bagua_tpu/ops/gated_delta.py``: ``gdn_fwd`` / ``gdn_bwd``), for the
``gdn_*_roofline`` metrics.

FLOP are those of the RECURRENT form of the rule, per position and value
head ``3 d_k d_v`` multiply-accumulates forward (decay-and-read ``S^T k``,
the rank-one update ``k u^T``, the output read ``S^T q``) at 2 FLOP each,
and twice that backward:

    gdn_fwd    6 d_k d_v   a position and value head
    gdn_bwd   12 d_k d_v

The kernels compute the CHUNKED form, which does about twice that (inside a
chunk of ``C`` positions the products ``K K^T``, ``Q K^T``, two with the
solve's inverse and ``P U``, ``5 C (d_k + d_v) / 2`` more
multiply-accumulates a position, and the backward pass makes the forward's
values again), so a share cannot pass 100 % unless the time leaves work out:
no choice of chunk can raise the count.

HBM bytes are the least a call can move: q and k once per KEY head, v and o
once per VALUE head, the two per-position scalars (log decay, write
strength) in float32; the backward call reads those and ``dO`` and writes
the five cotangents.  The per-chunk states the forward call keeps for the
backward one are not counted: they are the implementation's, not the
rule's.

What the HLO does not say — the number of key and value heads — is read
from the program's gauges ``linattn/key_heads`` / ``linattn/value_heads``,
set when the step is traced; a call's shapes are read from its operands in
the compiled step (q ``[b, T, key_heads x d_k]``, k the same, v ``[b, T,
value_heads x d_v]``).
"""

from __future__ import annotations

import functools

from perfbench import hlo_bytes, kernel_costs_window, scopes
from perfbench import trace_reduce as tr


def _cost(passes: int, key_tensors: int, value_tensors: int,
          scalar_tensors: int):
    """``passes`` times the forward's FLOP; the tensors a call moves once,
    counted by kind: ``[b, T, key_heads, d_k]`` (q, k and their cotangents),
    ``[b, T, value_heads, d_v]`` (v, o, dO, dv) and the float32 ``[b, T,
    value_heads]`` scalars (log decay, write strength and theirs)."""
    def cost(batch: int, seq: int, key_heads: int, value_heads: int,
             d_k: int, d_v: int, itemsize: int):
        flop = passes * batch * seq * value_heads * 6 * d_k * d_v
        moved = batch * seq * (key_tensors * key_heads * d_k * itemsize
                               + value_tensors * value_heads * d_v * itemsize
                               + scalar_tensors * value_heads * 4)
        return flop, moved
    return cost


COSTS = {
    #: reads q k, v, g beta; writes o
    "gdn_fwd": _cost(1, 2, 2, 2),
    #: reads q k, v dO, g beta; writes dq dk, dv, dg dbeta
    "gdn_bwd": _cost(2, 4, 3, 4),
}


@functools.lru_cache(maxsize=2)
def call_shapes(hlo_text: str) -> dict[str, tuple[int, int, int, int, int]]:
    """``{instruction: (batch, seq, key_heads x d_k, value_heads x d_v,
    itemsize)}`` of every Mosaic call whose first three operands are rank-3
    ``q``, ``k`` (equal shapes) and ``v`` over the same rows, and whose
    fourth is the rank-4 scalars ``[b, value_heads, chunks, C]``."""
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
        if len(shapes) < 5 or [len(d) for _, d in shapes[:4]] != [3, 3, 3, 4]:
            continue
        (dtype, q), (_, k), (_, v) = shapes[:3]
        if q == k and q[:2] == v[:2]:
            found[named.group(1)] = (q[0], q[1], q[2], v[2],
                                     hlo_bytes._DTYPE_BYTES.get(dtype, 2))
    return found


def roofline(ctx, kernel: str) -> float | None:
    """Share (%) of its roofline that ``kernel`` reaches: the recurrent
    form's FLOP per step over its summed device time per step, over min(peak
    FLOP/s, FLOP/byte x HBM bytes/s).  None where the step has no such
    kernel or the program sets no ``linattn/*`` gauges."""
    ms = scopes.kernel_ms(ctx, kernel)
    key_heads = scopes.program_gauge("linattn/key_heads")
    value_heads = scopes.program_gauge("linattn/value_heads")
    if not ms or ctx.peak is None or not key_heads or not value_heads:
        return None
    names = scopes.instruction_scopes(ctx.hlo_text)
    shapes = call_shapes(ctx.hlo_text)

    def one(op_name: str):
        batch, seq, key_width, value_width, itemsize = shapes[op_name]
        return COSTS[kernel](batch, seq, int(key_heads), int(value_heads),
                             key_width // int(key_heads),
                             value_width // int(value_heads), itemsize)

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
