"""FLOP and HBM bytes of ONE call of each WINDOWED flash-attention kernel
with grouped key / value heads (``bagua_tpu/ops/flash_attention.py``:
``flash_win_fwd`` / ``flash_win_bwd_dq`` / ``flash_win_bwd_dkv``), for the
``flash_win_*_roofline`` metrics, and the reduction that reads the calls'
shapes from the compiled step.

FLOP are those of the (query, key) pairs INSIDE the band only — ``j <= i``
and ``i - j < window``, per query head — at 2 FLOP a multiply-accumulate
(softmax arithmetic left out, as in ``flops.py``):

    flash_win_fwd      2 matmuls   q k^T, p v
    flash_win_bwd_dq   3 matmuls   q k^T, dO v^T, dS k
    flash_win_bwd_dkv  4 matmuls   q k^T, p^T dO, dO v^T, dS^T q

The kernels compute whole blocks (the diagonal block and the band's far
edge are masked in registers, not skipped), so they do MORE than is counted
here and a share cannot pass 100 % unless the time leaves work out.

HBM bytes are the least a call can move: q, o, dO and dq once per QUERY
head; k, v, dk, dv once per KEY / VALUE head (the kernels fetch a kv head's
K / V once for its group of query heads, and write its dK / dV once); the
forward's ``[b h, 8, seq]`` float32 stripe of ``lse``; ``lse`` and
``delta`` read back as one float32 row a head.  The dK/dV kernel really
re-reads Q and dO once per k block (its group axis is innermost): that is
the kernel's traffic, not the least, which is why the share is of a
roofline.

What the HLO does not say — the window and the number of key / value heads
(the head width follows from it) — is read from the program's gauges
``attn/window`` and ``attn/kv_heads``, set when the step is traced.
"""

from __future__ import annotations

import functools
import re

from perfbench import hlo_bytes, scopes
from perfbench import trace_reduce as tr


def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one head with ``j <= i`` and ``i - j <
    window``."""
    window = min(window, seq)
    return window * (window + 1) // 2 + (seq - window) * window


def _cost(matmuls: int, q_tensors: int, kv_tensors: int, stat_bytes):
    def cost(batch: int, seq: int, heads: int, kv_heads: int, head_dim: int,
             window: int, itemsize: int):
        flop = (batch * heads * band_pairs(seq, window) * matmuls * 2
                * head_dim)
        row = batch * seq * head_dim * itemsize
        moved = (q_tensors * heads * row + kv_tensors * kv_heads * row
                 + stat_bytes(batch * heads, seq))
        return flop, moved
    return cost


COSTS = {
    #: reads q (per query head), k v (per kv head); writes o and the stripe
    "flash_win_fwd": _cost(2, 2, 2, lambda bh, seq: bh * 8 * seq * 4),
    #: reads q dO, k v, lse, delta; writes dq
    "flash_win_bwd_dq": _cost(3, 3, 2, lambda bh, seq: 2 * bh * seq * 4),
    #: reads q dO, k v, lse, delta; writes dk dv
    "flash_win_bwd_dkv": _cost(4, 2, 4, lambda bh, seq: 2 * bh * seq * 4),
}

_NAME = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPERANDS = re.compile(
    r"operand_layout_constraints=\{([^{}]*(?:\{[^{}]*\}[^{}]*)*)\}")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


@functools.lru_cache(maxsize=2)
def call_shapes(hlo_text: str) -> dict[str, tuple[int, int, int, int, int]]:
    """``{instruction: (batch, seq, heads x head_dim, kv_heads x head_dim,
    itemsize)}`` of every Mosaic call whose first two operands are the
    flash kernels' ``q`` [b, s, h d] and ``k`` [b, s, kv d]."""
    found = {}
    for line in hlo_text.splitlines():
        if tr.MOSAIC_TARGET not in line:
            continue
        named, operands = _NAME.match(line), _OPERANDS.search(line)
        if not named or not operands:
            continue
        shapes = [(dtype, [int(n) for n in dims.split(",") if n])
                  for dtype, dims in _SHAPE.findall(operands.group(1))]
        if len(shapes) < 3 or any(len(dims) != 3 for _, dims in shapes[:3]):
            continue
        (dtype, q), (_, k) = shapes[0], shapes[1]
        if q[:2] == k[:2]:
            found[named.group(1)] = (q[0], q[1], q[2], k[2],
                                     hlo_bytes._DTYPE_BYTES.get(dtype, 2))
    return found


def roofline(ctx, kernel: str) -> float | None:
    """Share (%) of its roofline that the windowed ``kernel`` reaches: the
    band's FLOP per step over its summed device time per step, over
    min(peak FLOP/s, FLOP/byte x HBM bytes/s) (``scopes.kernel_roofline``'s
    reduction, for these operands).  None where the step has no such
    kernel or the program sets no ``attn/*`` gauges."""
    ms = scopes.kernel_ms(ctx, kernel)
    window = scopes.program_gauge("attn/window")
    kv_heads = scopes.program_gauge("attn/kv_heads")
    if not ms or ctx.peak is None or not window or not kv_heads:
        return None
    names = scopes.instruction_scopes(ctx.hlo_text)
    shapes = call_shapes(ctx.hlo_text)

    def one(op_name: str):
        batch, seq, q_width, kv_width, itemsize = shapes[op_name]
        head_dim = kv_width // int(kv_heads)
        return COSTS[kernel](batch, seq, q_width // head_dim, int(kv_heads),
                             head_dim, int(window), itemsize)

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
