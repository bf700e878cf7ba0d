"""FLOP and HBM bytes of ONE call of each flash-attention kernel
(``bagua_tpu/ops/flash_attention.py``), for the ``<kernel>_roofline``
metrics.  ``flops.py`` counts what the mathematics of a training step needs
(attention at the full ``s x s``); these count what the kernels' block loops
really compute, so that a kernel's share of its roofline is about the kernel
and not about the convention.

Causal masking is by BLOCK: a q block ``j`` visits the k blocks that start
at or before its last row, a k block ``kb`` the q blocks that end after its
first column; the diagonal blocks are computed whole and masked in
registers.  At ``seq`` 1024 with 512-wide blocks that is 3 of 4 block pairs,
not half.  Per visited pair of ``block_q x block_k`` (2 FLOP per
multiply-accumulate; softmax arithmetic on the VPU is left out, as in
``flops.py``):

    flash_fwd      2 matmuls   q k^T, p v                    4 bq bk d
    flash_bwd_dq   3 matmuls   q k^T, dO v^T, dS k           6 bq bk d
    flash_bwd_dkv  4 matmuls   q k^T, p^T dO, dO v^T, dS^T q  8 bq bk d

HBM bytes are the least the kernel can move: every operand read once and
every result written once per call (the whole-sequence operands keep their
block index over the inner grid axis, so Pallas fetches them once per
``batch x head``).  ``lse`` is written as an 8-sublane f32 stripe and read
back, like ``delta``, as one f32 row.
"""

from __future__ import annotations


def flash_blocks(seq: int) -> tuple[int, int]:
    """(block_q, block_k) the program picks at ``seq``: its own choice
    (``flash_attention`` calls ``pick_block`` for both), asked of it."""
    from bagua_tpu.ops.tiles import pick_block

    return pick_block(seq), pick_block(seq)


def causal_block_pairs(seq: int, block_q: int, block_k: int) -> int:
    """Block pairs the causal loops visit per ``batch x head``: for q block
    ``j`` the k blocks ``0 .. ceil((j+1) block_q / block_k) - 1``.  The
    dK/dV kernel's loop (q blocks from ``kb block_k // block_q`` on) visits
    the same pairs from the other side."""
    n_k = seq // block_k
    return sum(min(-(-((j + 1) * block_q) // block_k), n_k)
               for j in range(seq // block_q))


def _flash(matmuls: int, reads: int, writes: int, extra_bytes):
    def cost(bh: int, seq: int, head_dim: int, itemsize: int,
             blocks: tuple[int, int] | None = None):
        block_q, block_k = blocks or flash_blocks(seq)
        pairs = causal_block_pairs(seq, block_q, block_k)
        flop = bh * pairs * matmuls * 2 * block_q * block_k * head_dim
        tensor = bh * seq * head_dim * itemsize
        return flop, (reads + writes) * tensor + extra_bytes(bh, seq)
    return cost


#: reads q k v; writes o and the [bh, 8, seq] f32 stripe of lse
flash_fwd = _flash(2, 3, 1, lambda bh, seq: bh * 8 * seq * 4)
#: reads q k v dO and the f32 rows lse, delta; writes dq
flash_bwd_dq = _flash(3, 4, 1, lambda bh, seq: 2 * bh * seq * 4)
#: reads q k v dO, lse, delta; writes dk dv
flash_bwd_dkv = _flash(4, 4, 2, lambda bh, seq: 2 * bh * seq * 4)
