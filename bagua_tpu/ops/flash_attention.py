"""Fused flash attention — Pallas TPU kernels for the transformer hot path.

The reference framework has no attention code at all (SURVEY.md §5.7): its
BERT workload runs stock torch attention and Bagua only accelerates the
gradient communication around it.  Here the model family is first-class, so
its hottest op gets the TPU treatment the reference reserved for its CUDA
codec kernels (bagua_kernels.cu): a blockwise online-softmax attention that
never materializes the [seq, seq] score matrix in HBM.

Design (FlashAttention-2 style, TPU-first):

- layout: the kernels read q / k / v / dO and write o / dq / dk / dv as
  ``[batch, seq, heads * head_dim]`` — a free reshape of the model's
  ``[batch, seq, heads, head_dim]``, which is what the projections make and
  consume, so no transposed copy stands between a projection and a kernel.
  A ``BlockSpec`` picks a head's lanes of the last axis by the head index:
  one head a grid step where ``head_dim`` is a multiple of 128, and
  ``128 // head_dim`` heads side by side in one 128-lane block below it
  (two at 64).  Heads that share a block share its loads; a head's matmuls
  contract over (or write) all 128 lanes with the other heads' lanes
  masked to zero (or dropped by a select), which costs the MXU what a
  64-wide operand padded to its 128 lanes costs.  The per-row statistics
  (``lse``, ``delta``) are head-major f32 rows ``[batch * heads / g, g,
  seq]``, ``g`` the heads of a block.
- forward: grid over (batch * heads / g, q_blocks); K/V of the block's heads
  for the whole sequence are resident in VMEM per grid step while each q
  block streams through, carrying (o, m, l) through a ``fori_loop`` over k
  blocks.  Causal blocks above the diagonal are never visited (loop bound
  ``j+1``), the diagonal block is masked in-register.
- backward: saves only the per-row logsumexp (``m + log l``) and recomputes
  probabilities blockwise — two kernels, one accumulating dK/dV over q
  blocks at/after the diagonal (Q/dO resident), one accumulating dQ over k
  blocks at/before it (K/V resident).  ``delta = rowsum(dO * O)`` is a
  cheap XLA-fused precompute.
- all matmuls hit the MXU via ``dot_general(..., preferred_element_type=
  f32)``; softmax math is f32 on the VPU; inputs/outputs stay in the model
  dtype (bf16).

- grouped key / value heads (``k`` / ``v`` with fewer heads than ``q``, one
  head a 128-lane block): the k / v ``BlockSpec``s pick the kv head
  ``head // group``, whose block index does not change over the group's
  consecutive grid rows, so K / V are fetched once a kv head and never
  repeated in HBM; the dK/dV kernel walks the group on an innermost grid
  axis and sums its query heads' contributions in float32 scratch.
- a causal window (``window``: query ``i`` sees keys ``i - window < j <=
  i``): the three loops start and stop at the band's blocks, those outside
  it are never visited, and the band's two edges are masked in registers.
  Windowed calls are named ``flash_win_fwd`` / ``flash_win_bwd_dq`` /
  ``flash_win_bwd_dkv``.

- a block-diffusion mask (``diffusion_block``: the rows are a clean
  sequence and its noised copy, ``[x ; x~]``, under the four-quadrant mask
  of block-diffusion training, :func:`block_diffusion_mask`): kernels of
  their own at the end of this file, ``flash_bd_fwd`` / ``flash_bd_bwd_dq``
  / ``flash_bd_bwd_dkv``, whose loops visit the blocks that hold a visible
  pair and no other.

Falls back to the plain jnp implementation off-TPU, for tiny/ragged
sequence lengths, for heads the 128-lane blocks cannot take (``heads *
head_dim`` no multiple of 128, an odd head count at 64), and under
``BAGUA_FLASH_ATTENTION=0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANE = 128


def block_diffusion_mask(seq: int, block: int):
    """The ``[seq, seq]`` boolean mask of block-diffusion training over the
    rows ``[x ; x~]``: a clean sequence of ``seq // 2`` positions and then
    its noised copy, position ``i`` of either in diffusion block ``i //
    block``.  A clean query sees the clean keys of its own block and the
    blocks before it; a noised query the clean keys of the blocks before
    its own and the noised keys of its own block; no clean query sees a
    noised key.  Dense: the fallback's and the tests' form; the kernels
    build it a visited block pair at a time, in registers."""
    half = seq // 2
    row = jnp.arange(seq)
    noised = row >= half
    blk = (row - half * noised) // block
    q_n, k_n = noised[:, None], noised[None, :]
    q_b, k_b = blk[:, None], blk[None, :]
    return jnp.where(k_n, q_n & (k_b == q_b),
                     jnp.where(q_n, k_b < q_b, k_b <= q_b))


def reference_attention(q, k, v, dtype, causal: bool = True,
                        window: int | None = None,
                        diffusion_block: int | None = None):
    """Plain (materializing) attention; the fallback and the test golden.
    ``q``: [batch, seq, heads, head_dim]; ``k/v`` the same, or with fewer
    (key / value) heads, each shared by ``heads // kv_heads`` consecutive
    query heads.  ``window`` (causal only): query ``i`` sees the keys
    ``i - window < j <= i``.  ``diffusion_block``: the rows are ``[x ;
    x~]`` under :func:`block_diffusion_mask` in place of the causal one."""
    b, s, h, d = q.shape
    if k.shape[2] != h:
        k, v = (jnp.repeat(t, h // t.shape[2], axis=2) for t in (k, v))
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if diffusion_block is not None:
        mask = block_diffusion_mask(s, diffusion_block)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    elif causal:
        mask = jnp.tril(jnp.ones((s, s), jnp.bool_))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s, s), jnp.bool_), -window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# heads of one 128-lane block
# ---------------------------------------------------------------------------


def heads_per_block(heads: int, head_dim: int) -> int:
    """How many heads the kernels take a grid step: 1 where ``head_dim``
    fills whole 128-lane blocks, ``128 // head_dim`` side by side in one
    block below that; 0 where the blocks cannot take the heads at all."""
    if head_dim % _LANE == 0:
        return 1
    g = _LANE // head_dim
    if _LANE % head_dim == 0 and heads % g == 0:
        return g
    return 0


def _only_head(x, gi, heads):
    """``x`` [rows, heads * d] with every lane outside head ``gi`` zeroed: a
    contraction over all the lanes is then head ``gi``'s own."""
    if heads == 1:
        return x
    d = x.shape[1] // heads
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    keep = (lane >= gi * d) & (lane < (gi + 1) * d)
    return jnp.where(keep, x, jnp.zeros_like(x))


def _slots(parts, shape, axis):
    """One value of ``shape`` that holds ``parts[k]`` in slot ``k`` of
    ``axis`` — slots ``shape[axis] // len(parts)`` wide along the lanes
    (axis 1), one sublane each along axis 0, the last part filling what is
    left.  A part is of ``shape`` or broadcasts to it."""
    out = parts[-1]
    if len(parts) > 1:
        width = shape[1] // len(parts) if axis else 1
        at = lax.broadcasted_iota(jnp.int32, shape, axis)
        for k in range(len(parts) - 2, -1, -1):
            out = jnp.where(at < (k + 1) * width, parts[k], out)
    return out


def _by_head(parts, shape):
    """One [rows, heads * d] value that holds, in head ``gi``'s lanes, those
    lanes of ``parts[gi]`` ([rows, heads * d], or [rows, 1] broadcast)."""
    return _slots(parts, shape, 1)


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


# ---------------------------------------------------------------------------
# the band a block visits
# ---------------------------------------------------------------------------


def _keep(q_pos, k_pos, window):
    """The causal mask of a visited block pair, cut to the window's band."""
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    return keep


def _k_blocks(j, block_q, block_k, n_kb_total, causal, window):
    """``(first, end)`` of the k blocks q block ``j`` visits: to the
    diagonal where causal, and from the block that holds the first row's
    oldest key, ``j * block_q - (window - 1)``, where windowed."""
    if not causal:
        return 0, n_kb_total
    # last k block overlapping [0, (j+1)*block_q)
    end = lax.min((((j + 1) * block_q + block_k - 1) // block_k), n_kb_total)
    if window is None:
        return 0, end
    return lax.max(j * block_q - (window - 1), 0) // block_k, end


def _q_blocks(kb, block_q, block_k, n_qb_total, causal, window):
    """``(first, end)`` of the q blocks k block ``kb`` is visited by: from
    the diagonal where causal, and to the block that holds the last query
    of its last key, ``(kb + 1) * block_k - 1 + window - 1``."""
    if not causal:
        return 0, n_qb_total
    first = (kb * block_k) // block_q
    if window is None:
        return first, n_qb_total
    return first, lax.min(((kb + 1) * block_k + window - 2) // block_q + 1,
                          n_qb_total)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k,
                scale, heads, window):
    block_q, lanes = q_ref.shape[1], q_ref.shape[2]
    j = pl.program_id(1)
    # keep model dtype: the MXU runs bf16 inputs at full rate
    qs = [_only_head(q_ref[0], gi, heads) for gi in range(heads)]
    kb_first, n_kb = _k_blocks(j, block_q, block_k,
                               k_ref.shape[1] // block_k, causal, window)
    q_pos = j * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(kb, carry):
        o, ms, ls = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        if causal:
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
        ms_new, ls_new, corrs, pvs = [], [], [], []
        for q, m, l in zip(qs, ms, ls):
            logits = scale * _dot(q, k_blk, _NT)
            if causal:
                # a row whose keys of this block are all outside the band
                # accumulates exp(0) here; the first block that holds one
                # of its keys (the diagonal at the latest) rescales that
                # by exp(NEG_INF - m) = 0
                logits = jnp.where(_keep(q_pos, k_pos, window), logits,
                                   NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new)
            ms_new.append(m_new)
            ls_new.append(l * corr + p.sum(axis=-1, keepdims=True))
            corrs.append(corr)
            # all the block's lanes of p v; the head's own are kept below
            pvs.append(_dot(p.astype(v_blk.dtype), v_blk, _NN))
        o = o * _by_head(corrs, o.shape) + _by_head(pvs, o.shape)
        return o, tuple(ms_new), tuple(ls_new)

    o0 = jnp.zeros((block_q, lanes), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o, ms, ls = lax.fori_loop(kb_first, n_kb, body,
                              (o0, (m0,) * heads, (l0,) * heads))
    ls = [jnp.maximum(l, 1e-30) for l in ls]
    o_ref[0] = (o / _by_head(ls, o.shape)).astype(o_ref.dtype)
    # lse written as an 8-sublane stripe: (heads, block_q) output blocks
    # violate the TPU (8, 128) tile floor, so head gi's row is sublane gi
    # and the last head's row is repeated over the rest
    rows = [jnp.broadcast_to((m + jnp.log(l)).reshape(1, block_q),
                             (8, block_q)) for m, l in zip(ms, ls)]
    lse_ref[0] = _slots(rows, (8, block_q), 0)


def _specs(s, heads, d, kv_heads=None):
    """``(g, tensor, kv_tensor, stat_rows)``: the heads of a block, and the
    ``BlockSpec`` makers over ``[b, s, heads * d]`` tensors and the ``[b *
    heads / g, g, s]`` rows — ``tensor(rows)`` a ``rows``-long block j of
    the sequence (the whole of it where ``rows == s``) of grid row i's ``g``
    heads, ``kv_tensor(rows)`` the same of the key / value head those heads
    read (``tensor`` itself without grouping: with it, consecutive grid rows
    of a group name one block and Pallas fetches it once), ``stat_rows``
    those heads' whole f32 rows."""
    g = heads_per_block(heads, d)
    hp = heads // g
    group = heads // (kv_heads or heads)

    def maker(head_of):
        def tensor(rows):
            if rows == s:
                index = lambda i, j: (i // hp, 0, head_of(i % hp))
            else:
                index = lambda i, j: (i // hp, j, head_of(i % hp))
            return pl.BlockSpec((1, rows, g * d), index,
                                memory_space=pltpu.VMEM)
        return tensor

    tensor = maker(lambda head: head)
    kv_tensor = tensor if group == 1 else maker(lambda head: head // group)
    stat_rows = pl.BlockSpec((1, g, s), lambda i, j: (i, 0, 0),
                             memory_space=pltpu.VMEM)
    return g, tensor, kv_tensor, stat_rows


# jitted, like ``_bwd``: every layer of a model calls these with the same
# shapes, and Pallas traces a kernel body anew at each ``pallas_call``; under
# ``jit`` the layers share one trace.  gpt2-medium's 72 calls cost a warm
# start 15 s of tracing on the chip's host otherwise (PERF.md §6, PR 32)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _fwd(q, k, v, heads, causal, block_q, block_k, interpret, window=None):
    """q: [b, s, heads * d], k/v: [b, s, kv_heads * d] -> (o like q, lse
    [b * heads / g, g, s] f32: the head rows of the 8-sublane stripe the
    kernel writes)."""
    b, s, hd = q.shape
    d = hd // heads
    g, tensor, kv_tensor, _ = _specs(s, heads, d, k.shape[2] // d)
    o, stripe = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, block_k=block_k,
            scale=1.0 / (d ** 0.5), heads=g, window=window,
        ),
        grid=(b * heads // g, s // block_q),
        in_specs=[tensor(block_q), kv_tensor(s), kv_tensor(s)],
        out_specs=[
            tensor(block_q),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, hd), q.dtype),
            jax.ShapeDtypeStruct((b * heads // g, 8, s), jnp.float32),
        ],
        interpret=interpret,
        # a windowed call carries a name of its own: a reader of the trace
        # tells a window layer's calls from a full layer's
        name="flash_fwd" if window is None else "flash_win_fwd",
    )(q, k, v)
    return o, stripe[:, :g, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _stat(ref, gi, start, rows):
    """Head ``gi``'s ``rows`` statistics from ``start`` as a column."""
    return ref[0, gi, pl.ds(start, rows)].reshape(rows, 1)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *sums, causal, block_q, scale, heads,
                    window, group):
    """One k block of one (query) head.  With grouped key / value heads
    (``group > 1``) the grid's innermost axis walks the group's query
    heads: ``sums`` are the k block's float32 dK and dV, begun at the
    group's first head and written out, rounded once, at its last."""
    block_k, lanes = k_ref.shape[1], k_ref.shape[2]
    kb = pl.program_id(1)
    ks = [_only_head(k_ref[0], gi, heads) for gi in range(heads)]
    vs = [_only_head(v_ref[0], gi, heads) for gi in range(heads)]
    qb_start, qb_end = _q_blocks(kb, block_q, block_k,
                                 q_ref.shape[1] // block_q, causal, window)
    k_pos = kb * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :]
        if causal:
            q_pos = qb * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
        dks, dvs = [], []
        for gi, (k_h, v_h) in enumerate(zip(ks, vs)):
            lse = _stat(lse_ref, gi, qb * block_q, block_q)
            delta = _stat(delta_ref, gi, qb * block_q, block_q)
            s_ij = scale * _dot(q_blk, k_h, _NT)
            if causal:
                s_ij = jnp.where(_keep(q_pos, k_pos, window), s_ij, NEG_INF)
            p = jnp.exp(s_ij - lse).astype(k_h.dtype)
            # dV += P^T dO
            dvs.append(_dot(p, do_blk, _TN))
            dp = _dot(do_blk, v_h, _NT)
            ds = (p.astype(jnp.float32) * (dp - delta)).astype(k_h.dtype)
            # dK += scale * dS^T Q
            dks.append(_dot(ds, q_blk, _TN))
        return (dk + scale * _by_head(dks, dk.shape),
                dv + _by_head(dvs, dv.shape))

    zeros = jnp.zeros((block_k, lanes), jnp.float32)
    dk, dv = lax.fori_loop(qb_start, qb_end, body, (zeros, zeros))
    if group == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return
    dk_sum, dv_sum = sums
    member = pl.program_id(2)

    @pl.when(member == 0)
    def _():
        dk_sum[...] = dk
        dv_sum[...] = dv

    @pl.when(member > 0)
    def _():
        dk_sum[...] += dk
        dv_sum[...] += dv

    @pl.when(member == group - 1)
    def _():
        dk_ref[0] = dk_sum[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sum[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, causal, block_k, scale, heads, window):
    block_q, lanes = q_ref.shape[1], q_ref.shape[2]
    j = pl.program_id(1)
    qs = [_only_head(q_ref[0], gi, heads) for gi in range(heads)]
    dos = [_only_head(do_ref[0], gi, heads) for gi in range(heads)]
    lses = [_stat(lse_ref, gi, j * block_q, block_q) for gi in range(heads)]
    deltas = [_stat(delta_ref, gi, j * block_q, block_q)
              for gi in range(heads)]
    kb_first, n_kb = _k_blocks(j, block_q, block_k,
                               k_ref.shape[1] // block_k, causal, window)
    q_pos = j * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        if causal:
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
        dqs = []
        for q_h, do_h, lse, delta in zip(qs, dos, lses, deltas):
            s_ij = scale * _dot(q_h, k_blk, _NT)
            if causal:
                s_ij = jnp.where(_keep(q_pos, k_pos, window), s_ij, NEG_INF)
            p = jnp.exp(s_ij - lse)
            dp = _dot(do_h, v_blk, _NT)
            ds = (p * (dp - delta)).astype(k_blk.dtype)
            dqs.append(_dot(ds, k_blk, _NN))
        return dq + scale * _by_head(dqs, dq.shape)

    dq = lax.fori_loop(kb_first, n_kb, body,
                       jnp.zeros((block_q, lanes), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_call(b, s, heads, kv_heads, d, block_k, tensor, stat_rows):
    """``(grid, in_specs, out_specs, scratch)`` of the dK/dV call over
    ``heads`` query and ``kv_heads`` key / value blocks of ``d`` lanes.
    Without grouping: a (query = key) head a grid row, k blocks inner.  With
    it: a KEY / VALUE head a grid row, k blocks, and innermost the group's
    query heads, over which the k block's dK / dV block stays resident
    while each member's whole-sequence Q / dO and statistics come in."""
    group = heads // kv_heads
    if group == 1:
        in_specs = [tensor(s), tensor(block_k), tensor(block_k), tensor(s),
                    stat_rows, stat_rows]
        return ((b * heads, s // block_k), in_specs,
                [tensor(block_k), tensor(block_k)], [])
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    whole = vmem((1, s, d), lambda i, j, m: (i // kv_heads, 0,
                                             (i % kv_heads) * group + m))
    block = vmem((1, block_k, d), lambda i, j, m: (i // kv_heads, j,
                                                   i % kv_heads))
    rows = vmem((1, 1, s), lambda i, j, m: (i * group + m, 0, 0))
    return ((b * kv_heads, s // block_k, group),
            [whole, block, block, whole, rows, rows], [block, block],
            [pltpu.VMEM((block_k, d), jnp.float32)] * 2)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 12))
def _bwd(q, k, v, o, lse, do, heads, causal, block_q, block_k, interpret,
         dlse=None, window=None):
    """``lse``: [b * heads / g, g, s] f32 (the head rows of the forward's
    stripe).

    ``dlse``, like it: cotangent of the logsumexp output (only when the
    caller consumed lse, e.g. ring-attention merging).  It enters the
    standard backward as ``ds_ij += p_ij * dlse_i``, i.e. an effective
    ``delta_i - dlse_i`` — no kernel change needed.
    """
    b, s, hd = q.shape
    d = hd // heads
    kv_heads = k.shape[2] // d
    g, tensor, kv_tensor, stat_rows = _specs(s, heads, d, kv_heads)
    delta = (
        (do.astype(jnp.float32) * o.astype(jnp.float32))
        .reshape(b, s, heads, d)
        .sum(axis=-1)
        .transpose(0, 2, 1)
        .reshape(lse.shape)
    )
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    scale = 1.0 / (d ** 0.5)
    grid, in_specs, out_specs, scratch = _dkv_call(
        b, s, heads // g, kv_heads // g, g * d, block_k, tensor, stat_rows)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, block_q=block_q, scale=scale,
            heads=g, window=window, group=heads // kv_heads,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_bwd_dkv" if window is None else "flash_win_bwd_dkv",
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, block_k=block_k, scale=scale,
            heads=g, window=window,
        ),
        grid=(b * heads // g, s // block_q),
        in_specs=[tensor(block_q), kv_tensor(s), kv_tensor(s),
                  tensor(block_q), stat_rows, stat_rows],
        out_specs=tensor(block_q),
        out_shape=jax.ShapeDtypeStruct((b, s, hd), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq" if window is None else "flash_win_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, heads, causal, block_q, block_k, interpret,
               window=None):
    """q [b, s, heads * d], k/v [b, s, kv_heads * d] -> (o like q, lse [b *
    heads / g, g, s] f32: row-major that is [b, heads, s])."""
    return _fwd(q, k, v, heads, causal, block_q, block_k, interpret, window)


#: ``checkpoint_name`` tags on what the forward kernel made.  A remat policy
#: that keeps matmul outputs keeps these too (``utils.remat_wrap``): to
#: ``jax.checkpoint`` the kernel is an opaque ``pallas_call``, and without
#: the tags the backward pass runs ``flash_fwd`` again to get them back.
KEPT_O = "flash_o"
KEPT_LSE = "flash_lse"


def _flash_lse_fwd(q, k, v, heads, causal, block_q, block_k, interpret,
                   window):
    o, lse = _fwd(q, k, v, heads, causal, block_q, block_k, interpret,
                  window)
    # tagged HERE so the value returned and the residual are one variable
    # (a tag on the caller's side names a copy and the kernel is replayed);
    # the head rows, not the 8-sublane stripe the kernel writes
    o = checkpoint_name(o, KEPT_O)
    lse = checkpoint_name(lse, KEPT_LSE)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(heads, causal, block_q, block_k, interpret, window, res,
                   cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _bwd(q, k, v, o, lse, do, heads, causal, block_q, block_k,
                interpret, dlse, window)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def kv_grouping_supported(heads: int, kv_heads: int, head_dim: int) -> bool:
    """Whether the kernels take ``kv_heads`` key / value heads under
    ``heads`` query heads: as many (no grouping), or a divisor of them with
    a head a 128-lane block — heads that share a block would need their kv
    heads side by side in one too."""
    if kv_heads == heads:
        return True
    return heads % kv_heads == 0 and heads_per_block(heads, head_dim) == 1


def _in_model_layout(q, k, v, causal, block_q, block_k, interpret,
                     window=None):
    """The kernels over the model's ``[b, s, h, d]`` q and ``[b, s, kv_h,
    d]`` k / v: the heads merge into the last axis and split out of it
    again by reshape, no element moves.  -> (o [b, s, h, d], lse [b, h, s]
    f32)."""
    b, s, h, d = q.shape
    merged = lambda x: x.reshape(b, s, x.shape[2] * d)
    o, lse = _flash_lse(merged(q), merged(k), merged(v), h, causal, block_q,
                        block_k, interpret, window)
    return o.reshape(b, s, h, d), lse.reshape(b, h, s)


def _band(window, causal: bool, seq: int):
    """``window`` as the kernels take it: None where it cuts nothing (no
    window, or one as long as the sequence: plain causal attention, under
    the plain kernels' names)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(f"a window ({window}) is a causal band of at least "
                         "the query's own position")
    return None if window >= seq else int(window)


def flash_attention_with_lse(q, k, v, *, causal: bool, block_q: int = 0,
                             block_k: int = 0, interpret: bool = False,
                             window: int | None = None):
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ([batch, heads, seq] f32) — the merge statistic for combining partial
    attentions over K/V blocks (ring attention).  No fallback: the caller
    gates on :func:`flash_supported`.  Output ``o`` is f32 (merging
    precision)."""
    from .tiles import pick_block

    b, s, h, d = q.shape
    # the kernels size K/V buffers from q's length — equal chunks only
    assert k.shape[1] == s and v.shape[1] == s, (q.shape, k.shape, v.shape)
    block_q = block_q or pick_block(s)
    block_k = block_k or pick_block(s)
    if (s % block_q or s % block_k or not heads_per_block(h, d)
            or not kv_grouping_supported(h, k.shape[2], d)):
        # no silent fallback here (the caller gates on flash_supported):
        # a non-divisible grid would TRUNCATE the sequence
        raise ValueError(
            f"seq {s} is not a multiple of block sizes "
            f"({block_q}, {block_k}), or {h} heads of {d} (over "
            f"{k.shape[2]} key / value heads) do not fill "
            "128-lane blocks; flash_attention_with_lse has no reference "
            "fallback"
        )
    o, lse = _in_model_layout(q, k, v, causal, block_q, block_k, interpret,
                              _band(window, causal, s))
    return o.astype(jnp.float32), lse


def _enabled() -> bool:
    from .. import env

    return env.is_flash_attention_enabled()


# below this the plain XLA attention is taken.  Set in round 5 from a sweep
# of the pre-chip yardstick (best-of-two walls, record deleted in PR 46),
# never measured through perfbench: ROADMAP Queue 3 item 3 owes it a cell.
MIN_FLASH_SEQ = 1024


def flash_supported(seq: int, heads: int, head_dim: int,
                    block: int = _LANE, kv_heads: int | None = None) -> bool:
    """Whether the fused kernel pays: on-TPU, sequence long enough that the
    [seq, seq] HBM materialization hurts (measured crossover ~1k on v5p),
    block-aligned, heads that fill 128-lane blocks of the model's layout
    (:func:`heads_per_block`; grouped key / value heads:
    :func:`kv_grouping_supported`), and K/V + Q/dO fitting the per-step
    VMEM budget."""
    if not _enabled():
        return False
    if jax.default_backend() != "tpu":
        return False
    if seq < MIN_FLASH_SEQ or seq % block:
        return False
    if not heads_per_block(heads, head_dim):
        return False
    if not kv_grouping_supported(heads, kv_heads or heads, head_dim):
        return False
    # each kernel keeps 2 full-sequence operands resident (K+V fwd and dQ,
    # Q+dO in the dK/dV pass), a block's heads wide — 128 lanes, or
    # head_dim above that — and double-buffered by the pipeline: 4 bf16
    # seq×lane buffers must stay under the ~16 MB VMEM budget with headroom
    return 4 * seq * max(head_dim, _LANE) * 2 <= 12 * 1024 * 1024


def flash_attention(q, k, v, dtype=None, *, causal: bool = True,
                    block_q: int = 0, block_k: int = 0,
                    interpret: bool = False, force: bool = False,
                    window: int | None = None):
    """Drop-in for :func:`reference_attention`: ``q`` is [batch, seq, heads,
    head_dim], ``k/v`` the same or with fewer (key / value) heads, returns
    [batch, seq, heads, head_dim] in ``dtype`` (default: q.dtype).
    ``window``: query ``i`` sees keys ``i - window < j <= i`` only.

    ``force`` skips the platform and sequence-length checks (tests run the
    kernel in interpret mode on CPU); a shape no grid covers — a sequence
    the blocks do not divide, heads that do not fill 128-lane blocks —
    still takes the reference.
    """
    from .tiles import pick_block

    b, s, h, d = q.shape
    kv_h = k.shape[2]
    dtype = dtype or q.dtype
    block_q = block_q or pick_block(s)
    block_k = block_k or pick_block(s)
    if not force and not flash_supported(s, h, d, max(block_q, block_k),
                                         kv_h):
        return reference_attention(q, k, v, dtype, causal=causal,
                                   window=window)
    if (s % block_q or s % block_k or not heads_per_block(h, d)
            or not kv_grouping_supported(h, kv_h, d)):
        return reference_attention(q, k, v, dtype, causal=causal,
                                   window=window)
    # lse is discarded; its zero cotangent enters the backward as a no-op
    o, _ = _in_model_layout(q, k, v, causal, block_q, block_k, interpret,
                            _band(window, causal, s))
    return o.astype(dtype)


# ---------------------------------------------------------------------------
# block diffusion: the rows [x ; x~] under the four-quadrant mask
# ---------------------------------------------------------------------------
#
# ``half`` positions of a clean sequence and then of its noised copy, diffusion
# blocks of ``block`` positions (:func:`block_diffusion_mask`).  Of the (2
# half)^2 pairs ``half * (half + block)`` are visible.  Each kernel works out,
# from scalars, which blocks of the other side hold a visible pair of its own
# block, and which of those are visible WHOLE: two loops, one over the whole
# blocks with no mask and one over the edges with the mask built in registers
# from per-row (per-column) bounds.  Rows are taken by position alone, so a
# kernel block may straddle the two halves (``half`` no multiple of it).
# One head a 128-lane block (``head_dim`` a multiple of 128), grouped key /
# value heads as in the causal kernels.


def _cdiv(a, b):
    return (a + b - 1) // b


def _bd_k_segments(r0, block_q, block_k, half, block):
    """``(whole, edges)``: the k blocks the q block of rows ``r0 .. r0 +
    block_q - 1`` visits, as lists of ``(first, end)`` in ascending order —
    ``whole`` those every row of it sees every key of, ``edges`` the others
    that hold a visible pair (the clean blocks at its diagonal, then its
    own noised block).  ``r0`` may be traced."""
    r1 = r0 + block_q
    has_clean, has_noised = r0 < half, r1 > half
    i0, i1 = jnp.maximum(r0, half) - half, r1 - half   # its noised positions
    # clean keys [0, seen) some row sees, [0, every) every row does
    seen = jnp.maximum(
        jnp.where(has_clean,
                  ((jnp.minimum(r1, half) - 1) // block + 1) * block, 0),
        jnp.where(has_noised, (i1 - 1) // block * block, 0))
    every = jnp.minimum(
        jnp.where(has_clean, (r0 // block + 1) * block, half),
        jnp.where(has_noised, i0 // block * block, half))
    clean_end = _cdiv(seen, block_k)
    # noised keys of the diffusion blocks its noised rows lie in
    first = jnp.maximum((half + i0 // block * block) // block_k, clean_end)
    end = jnp.where(has_noised,
                    _cdiv(half + ((i1 - 1) // block + 1) * block, block_k),
                    first)
    return ([(0, every // block_k)],
            [(every // block_k, clean_end), (first, end)])


def _bd_q_segments(c0, block_q, block_k, half, block):
    """``(whole, edges)`` of the q blocks that visit the k block of columns
    ``c0 .. c0 + block_k - 1``: :func:`_bd_k_segments` from the other side.
    A clean key is seen by the clean rows from its diffusion block on and by
    the noised rows behind it, a noised key by the noised rows of its own
    block."""
    c1 = c0 + block_k
    n_qb = 2 * half // block_q
    has_clean, has_noised = c0 < half, c1 > half
    first_blk = c0 // block                       # of its first clean key
    last_blk = (jnp.minimum(c1, half) - 1) // block       # of its last
    j0, j1 = jnp.maximum(c0, half) - half, c1 - half  # its noised positions
    half_end = _cdiv(half, block_q)
    # the noised rows of its noised keys' diffusion blocks
    own_lo = (half + j0 // block * block) // block_q
    own_end = _cdiv(half + ((j1 - 1) // block + 1) * block, block_q)
    clean_only = jnp.logical_and(has_clean, jnp.logical_not(has_noised))
    e1_lo = jnp.where(has_clean, first_blk * block // block_q, own_lo)
    w1_hi = jnp.where(has_clean,
                      jnp.where(has_noised, half_end, half // block_q),
                      own_lo)
    w1_lo = jnp.where(clean_only,
                      jnp.minimum(_cdiv(last_blk * block, block_q), w1_hi),
                      w1_hi)
    e2_end = jnp.where(has_noised, jnp.maximum(own_end, w1_hi), half_end)
    e3_lo = jnp.where(
        has_clean,
        jnp.maximum((half + (first_blk + 1) * block) // block_q, e2_end),
        n_qb)
    w2_lo = jnp.where(clean_only,
                      _cdiv(half + (last_blk + 1) * block, block_q), n_qb)
    return ([(w1_lo, w1_hi), (w2_lo, n_qb)],
            [(e1_lo, w1_lo), (w1_hi, e2_end), (e3_lo, w2_lo)])


def _walk(segments, body, carry):
    """``fori_loop`` of ``body(block index, carry)`` over the blocks of
    ``segments`` in order: one loop, its counter mapped onto the segments by
    scalar selects."""
    starts, total = [], 0
    for lo, hi in segments:
        starts.append(total)
        total = total + (hi - lo)

    def at(t):
        index = segments[-1][0] + t - starts[-1]
        for k in range(len(segments) - 2, -1, -1):
            index = jnp.where(t < starts[k + 1],
                              segments[k][0] + t - starts[k], index)
        return index

    return lax.fori_loop(0, total, lambda t, c: body(at(t), c), carry)


def _bd_row_bounds(rows, half, block):
    """Per query row ``(clean_end, own_lo)``: it sees the clean keys ``c <
    clean_end`` and the noised keys ``own_lo <= c < own_lo + block`` (none:
    ``own_lo`` past every key, for a clean row)."""
    noised = rows >= half
    first = (rows - jnp.where(noised, half, 0)) // block * block
    return (jnp.where(noised, first, first + block),
            jnp.where(noised, half + first, 2 * half))


def _bd_keep_rows(bounds, k_pos, block):
    clean_end, own_lo = bounds
    return (k_pos < clean_end) | ((k_pos >= own_lo) & (k_pos < own_lo + block))


def _bd_col_bounds(cols, half, block):
    """Per key column ``(lo, hi, behind)``: the rows ``lo <= r < hi`` and
    ``r >= behind`` see it — a clean key its own and the later clean rows
    and the noised rows behind its block, a noised key its block's noised
    rows."""
    noised = cols >= half
    first = (cols - jnp.where(noised, half, 0)) // block * block
    return (jnp.where(noised, half + first, first),
            jnp.where(noised, half + first + block, half),
            jnp.where(noised, 2 * half, half + first + block))


def _bd_keep_cols(bounds, q_pos):
    lo, hi, behind = bounds
    return ((q_pos >= lo) & (q_pos < hi)) | (q_pos >= behind)


def _bd_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, scale,
                   half, block):
    block_q, lanes = q_ref.shape[1], q_ref.shape[2]
    r0 = pl.program_id(1) * block_q
    q = q_ref[0]
    whole, edges = _bd_k_segments(r0, block_q, block_k, half, block)
    bounds = _bd_row_bounds(
        r0 + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0), half, block)

    def visit(masked):
        def body(kb, carry):
            o, m, l = carry
            k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
            logits = scale * _dot(q, k_blk, _NT)
            if masked:
                # a row none of whose keys this block holds accumulates
                # exp(0) here; its own noised (or clean) key, in the last
                # block it visits at the latest, rescales that to 0
                k_pos = kb * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                logits = jnp.where(_bd_keep_rows(bounds, k_pos, block),
                                   logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            o = o * corr + _dot(p.astype(v_blk.dtype), v_blk, _NN)
            return o, m_new, l
        return body

    carry = (jnp.zeros((block_q, lanes), jnp.float32),
             jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32))
    carry = _walk(whole, visit(False), carry)
    o, m, l = _walk(edges, visit(True), carry)
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to((m + jnp.log(l)).reshape(1, block_q),
                                  (8, block_q))


def _bd_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, *, block_k, scale, half, block):
    block_q, lanes = q_ref.shape[1], q_ref.shape[2]
    r0 = pl.program_id(1) * block_q
    q, do = q_ref[0], do_ref[0]
    lse = _stat(lse_ref, 0, r0, block_q)
    delta = _stat(delta_ref, 0, r0, block_q)
    whole, edges = _bd_k_segments(r0, block_q, block_k, half, block)
    bounds = _bd_row_bounds(
        r0 + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0), half, block)

    def visit(masked):
        def body(kb, dq):
            k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s_ij = scale * _dot(q, k_blk, _NT)
            if masked:
                k_pos = kb * block_k + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s_ij = jnp.where(_bd_keep_rows(bounds, k_pos, block), s_ij,
                                 NEG_INF)
            p = jnp.exp(s_ij - lse)
            dp = _dot(do, v_blk, _NT)
            ds = (p * (dp - delta)).astype(k_blk.dtype)
            return dq + _dot(ds, k_blk, _NN)
        return body

    dq = _walk(whole, visit(False), jnp.zeros((block_q, lanes), jnp.float32))
    dq = _walk(edges, visit(True), dq)
    dq_ref[0] = (scale * dq).astype(dq_ref.dtype)


def _bd_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, *sums, block_q, scale, half, block,
                       group):
    """One k block of one query head; the group's query heads on the
    innermost grid axis, summed in ``sums`` as in ``_bwd_dkv_kernel``."""
    block_k, lanes = k_ref.shape[1], k_ref.shape[2]
    c0 = pl.program_id(1) * block_k
    k, v = k_ref[0], v_ref[0]
    whole, edges = _bd_q_segments(c0, block_q, block_k, half, block)
    bounds = _bd_col_bounds(
        c0 + lax.broadcasted_iota(jnp.int32, (1, block_k), 1), half, block)

    def visit(masked):
        def body(qb, carry):
            dk, dv = carry
            q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]
            do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :]
            lse = _stat(lse_ref, 0, qb * block_q, block_q)
            delta = _stat(delta_ref, 0, qb * block_q, block_q)
            s_ij = scale * _dot(q_blk, k, _NT)
            if masked:
                q_pos = qb * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                s_ij = jnp.where(_bd_keep_cols(bounds, q_pos), s_ij, NEG_INF)
            p = jnp.exp(s_ij - lse).astype(k.dtype)
            dv = dv + _dot(p, do_blk, _TN)
            dp = _dot(do_blk, v, _NT)
            ds = (p.astype(jnp.float32) * (dp - delta)).astype(k.dtype)
            return dk + _dot(ds, q_blk, _TN), dv
        return body

    zeros = jnp.zeros((block_k, lanes), jnp.float32)
    carry = _walk(whole, visit(False), (zeros, zeros))
    dk, dv = _walk(edges, visit(True), carry)
    dk = scale * dk
    if group == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return
    dk_sum, dv_sum = sums
    member = pl.program_id(2)

    @pl.when(member == 0)
    def _():
        dk_sum[...] = dk
        dv_sum[...] = dv

    @pl.when(member > 0)
    def _():
        dk_sum[...] += dk
        dv_sum[...] += dv

    @pl.when(member == group - 1)
    def _():
        dk_ref[0] = dk_sum[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sum[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _bd_fwd(q, k, v, heads, block, block_q, block_k, interpret):
    """q: [b, 2 half, heads * d], k/v: [b, 2 half, kv_heads * d] -> (o like
    q, lse [b * heads, 1, 2 half] f32)."""
    b, s, hd = q.shape
    d = hd // heads
    _, tensor, kv_tensor, _ = _specs(s, heads, d, k.shape[2] // d)
    o, stripe = pl.pallas_call(
        functools.partial(_bd_fwd_kernel, block_k=block_k,
                          scale=1.0 / (d ** 0.5), half=s // 2, block=block),
        grid=(b * heads, s // block_q),
        in_specs=[tensor(block_q), kv_tensor(s), kv_tensor(s)],
        out_specs=[
            tensor(block_q),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, hd), q.dtype),
            jax.ShapeDtypeStruct((b * heads, 8, s), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bd_fwd",
    )(q, k, v)
    return o, stripe[:, :1, :]


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _bd_bwd(q, k, v, o, lse, do, heads, block, block_q, block_k, interpret):
    b, s, hd = q.shape
    d = hd // heads
    kv_heads = k.shape[2] // d
    _, tensor, kv_tensor, stat_rows = _specs(s, heads, d, kv_heads)
    delta = (
        (do.astype(jnp.float32) * o.astype(jnp.float32))
        .reshape(b, s, heads, d)
        .sum(axis=-1)
        .transpose(0, 2, 1)
        .reshape(lse.shape)
    )
    kind = dict(scale=1.0 / (d ** 0.5), half=s // 2, block=block)
    grid, in_specs, out_specs, scratch = _dkv_call(
        b, s, heads, kv_heads, d, block_k, tensor, stat_rows)
    dk, dv = pl.pallas_call(
        functools.partial(_bd_bwd_dkv_kernel, block_q=block_q,
                          group=heads // kv_heads, **kind),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_bd_bwd_dkv",
    )(q, k, v, do, lse, delta)
    dq = pl.pallas_call(
        functools.partial(_bd_bwd_dq_kernel, block_k=block_k, **kind),
        grid=(b * heads, s // block_q),
        in_specs=[tensor(block_q), kv_tensor(s), kv_tensor(s),
                  tensor(block_q), stat_rows, stat_rows],
        out_specs=tensor(block_q),
        out_shape=jax.ShapeDtypeStruct((b, s, hd), q.dtype),
        interpret=interpret,
        name="flash_bd_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bd(q, k, v, heads, block, block_q, block_k, interpret):
    return _bd_fwd(q, k, v, heads, block, block_q, block_k, interpret)[0]


def _flash_bd_fwd(q, k, v, heads, block, block_q, block_k, interpret):
    o, lse = _bd_fwd(q, k, v, heads, block, block_q, block_k, interpret)
    # the causal kernels' tags: a remat policy that keeps theirs keeps these
    o = checkpoint_name(o, KEPT_O)
    lse = checkpoint_name(lse, KEPT_LSE)
    return o, (q, k, v, o, lse)


def _flash_bd_bwd(heads, block, block_q, block_k, interpret, res, do):
    return _bd_bwd(*res, do, heads, block, block_q, block_k, interpret)


_flash_bd.defvjp(_flash_bd_fwd, _flash_bd_bwd)


def block_diffusion_supported(seq: int, heads: int, head_dim: int,
                              block: int = _LANE,
                              kv_heads: int | None = None) -> bool:
    """:func:`flash_supported` for the ``flash_bd_*`` kernels over ``seq``
    rows (both halves): the same gates, and one head a 128-lane block."""
    return (head_dim % _LANE == 0
            and flash_supported(seq, heads, head_dim, block, kv_heads))


def block_diffusion_attention(q, k, v, dtype=None, *, diffusion_block: int,
                              block_q: int = 0, block_k: int = 0,
                              interpret: bool = False, force: bool = False):
    """Attention of block-diffusion training: ``q`` is [batch, 2 half,
    heads, head_dim], the rows a clean sequence and then its noised copy;
    ``k/v`` the same or with fewer (key / value) heads; every row sees the
    keys :func:`block_diffusion_mask` gives it, under one softmax over both
    halves.  Returns [batch, 2 half, heads, head_dim] in ``dtype``.  The
    kernels where :func:`block_diffusion_supported` (``force``: wherever a
    grid covers the shape, for the interpret-mode tests), elsewhere
    :func:`reference_attention` under the same mask."""
    from .tiles import pick_block

    b, s, h, d = q.shape
    kv_h = k.shape[2]
    dtype = dtype or q.dtype
    if s % 2 or (s // 2) % diffusion_block:
        raise ValueError(
            f"{s} rows are no clean and noised copy of a sequence of whole "
            f"diffusion blocks of {diffusion_block}")
    block_q = block_q or pick_block(s)
    block_k = block_k or pick_block(s)
    covered = (s % block_q == 0 and s % block_k == 0 and d % _LANE == 0
               and kv_grouping_supported(h, kv_h, d))
    if not covered or not (force or block_diffusion_supported(
            s, h, d, max(block_q, block_k), kv_h)):
        return reference_attention(q, k, v, dtype,
                                   diffusion_block=diffusion_block)
    merged = lambda x: x.reshape(b, s, x.shape[2] * d)
    o = _flash_bd(merged(q), merged(k), merged(v), h, int(diffusion_block),
                  block_q, block_k, interpret)
    return o.reshape(b, s, h, d).astype(dtype)
