"""FLOP and HBM bytes of ONE call of each BLOCK-DIFFUSION flash-attention
kernel (``bagua_tpu/ops/flash_attention.py``: ``flash_bd_fwd`` /
``flash_bd_bwd_dq`` / ``flash_bd_bwd_dkv``), for the ``flash_bd_*_roofline``
metrics.

A call's rows are ``[x ; x~]``: a clean sequence of ``L`` positions and then
its noised copy, diffusion blocks of ``B`` positions.  FLOP are those of the
VISIBLE (query, key) pairs only, per query head — clean to clean ``L (L +
B) / 2``, noised to clean ``L (L - B) / 2``, noised to noised ``L B``: ``L
(L + B)`` of the ``(2 L)^2`` — at 2 FLOP a multiply-accumulate (softmax
arithmetic left out, as in ``flops.py``):

    flash_bd_fwd      2 matmuls   q k^T, p v
    flash_bd_bwd_dq   3 matmuls   q k^T, dO v^T, dS k
    flash_bd_bwd_dkv  4 matmuls   q k^T, p^T dO, dO v^T, dS^T q

The kernels compute whole kernel blocks (those at the mask's edges are
masked in registers, not skipped; with 512-wide blocks at ``L`` 4,096, 80
block pairs = 21.0 M pairs for the 16.8 M visible), so they do MORE than is
counted here and a share cannot pass 100 % unless the time leaves work out
or the kernel skips pairs it should visit.

HBM bytes are the least a call can move over its ``2 L`` rows: q, o, dO and
dq once per QUERY head; k, v, dk, dv once per KEY / VALUE head; the
forward's ``[b h, 8, 2 L]`` float32 stripe of ``lse``; ``lse`` and ``delta``
read back as one float32 row a head.

What the HLO does not say — the diffusion block and the number of key /
value heads — is read from the program's gauges ``attn/diffusion_block`` and
``attn/kv_heads``, set when the step is traced; the calls' shapes are read
from the compiled step as the windowed kernels' are
(``kernel_costs_window.call_shapes``).
"""

from __future__ import annotations

from perfbench import kernel_costs_window, scopes
from perfbench import trace_reduce as tr


def visible_pairs(length: int, block: int) -> int:
    """Visible (query, key) pairs of one head over the rows ``[x ; x~]`` of
    a sequence of ``length`` positions in diffusion blocks of ``block``."""
    return length * (length + block)


def _cost(matmuls: int, q_tensors: int, kv_tensors: int, stat_bytes):
    def cost(batch: int, rows: int, heads: int, kv_heads: int, head_dim: int,
             block: int, itemsize: int):
        flop = (batch * heads * visible_pairs(rows // 2, block) * matmuls * 2
                * head_dim)
        row = batch * rows * head_dim * itemsize
        moved = (q_tensors * heads * row + kv_tensors * kv_heads * row
                 + stat_bytes(batch * heads, rows))
        return flop, moved
    return cost


COSTS = {
    #: reads q (per query head), k v (per kv head); writes o and the stripe
    "flash_bd_fwd": _cost(2, 2, 2, lambda bh, rows: bh * 8 * rows * 4),
    #: reads q dO, k v, lse, delta; writes dq
    "flash_bd_bwd_dq": _cost(3, 3, 2, lambda bh, rows: 2 * bh * rows * 4),
    #: reads q dO, k v, lse, delta; writes dk dv
    "flash_bd_bwd_dkv": _cost(4, 2, 4, lambda bh, rows: 2 * bh * rows * 4),
}


def roofline(ctx, kernel: str) -> float | None:
    """Share (%) of its roofline that the block-diffusion ``kernel``
    reaches: the visible pairs' FLOP per step over its summed device time
    per step, over min(peak FLOP/s, FLOP/byte x HBM bytes/s).  None where
    the step has no such kernel or the program sets no such gauges."""
    ms = scopes.kernel_ms(ctx, kernel)
    block = scopes.program_gauge("attn/diffusion_block")
    kv_heads = scopes.program_gauge("attn/kv_heads")
    if not ms or ctx.peak is None or not block or not kv_heads:
        return None
    names = scopes.instruction_scopes(ctx.hlo_text)
    shapes = kernel_costs_window.call_shapes(ctx.hlo_text)

    def one(op_name: str):
        batch, rows, q_width, kv_width, itemsize = shapes[op_name]
        head_dim = kv_width // int(kv_heads)
        return COSTS[kernel](batch, rows, q_width // head_dim, int(kv_heads),
                             head_dim, int(block), itemsize)

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
