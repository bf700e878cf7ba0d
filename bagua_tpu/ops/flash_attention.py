"""Fused flash attention — Pallas TPU kernels for the transformer hot path.

The reference framework has no attention code at all (SURVEY.md §5.7): its
BERT workload runs stock torch attention and Bagua only accelerates the
gradient communication around it.  Here the model family is first-class, so
its hottest op gets the TPU treatment the reference reserved for its CUDA
codec kernels (bagua_kernels.cu): a blockwise online-softmax attention that
never materializes the [seq, seq] score matrix in HBM.

Design (FlashAttention-2 style, TPU-first):

- forward: grid over (batch*heads, q_blocks); K/V for the whole sequence are
  resident in VMEM per grid step while each q block streams through, carrying
  (o, m, l) in registers through a ``fori_loop`` over k blocks.  Causal
  blocks above the diagonal are never visited (loop bound ``j+1``), the
  diagonal block is masked in-register.
- backward: saves only the per-row logsumexp (``m + log l``) and recomputes
  probabilities blockwise — two kernels, one accumulating dK/dV over q
  blocks at/after the diagonal, one accumulating dQ over k blocks at/before
  it.  ``delta = rowsum(dO * O)`` is a cheap XLA-fused precompute.
- all matmuls hit the MXU via ``dot_general(..., preferred_element_type=
  f32)``; softmax math is f32 on the VPU; inputs/outputs stay in the model
  dtype (bf16).

Falls back to the plain jnp implementation off-TPU, for tiny/ragged
sequence lengths, and under ``BAGUA_FLASH_ATTENTION=0``.
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


def reference_attention(q, k, v, dtype, causal: bool = True):
    """Plain (materializing) attention; the fallback and the test golden.
    ``q/k/v``: [batch, seq, heads, head_dim]."""
    b, s, h, d = q.shape
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), jnp.bool_))
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k,
                scale):
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    j = pl.program_id(1)
    q = q_ref[0]  # keep model dtype: the MXU runs bf16 inputs at full rate
    n_kb_total = k_ref.shape[1] // block_k
    if causal:
        # last k block overlapping [0, (j+1)*block_q)
        n_kb = lax.min(
            (((j + 1) * block_q + block_k - 1) // block_k), n_kb_total
        )
    else:
        n_kb = n_kb_total
    q_pos = j * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(kb, carry):
        o, m, l = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        logits = scale * lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return o * corr + pv, m_new, l_new

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o, m, l = lax.fori_loop(0, n_kb, body, (o0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    # lse written as an 8-sublane stripe: (1, block_q) output blocks violate
    # the TPU (8, 128) tile floor, so the row is broadcast over 8 sublanes
    lse = (m + jnp.log(l)).reshape(1, block_q)
    lse_ref[0] = jnp.broadcast_to(lse, (8, block_q))


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    """q/k/v: [bh, s, d] -> (o [bh, s, d], lse [bh, s] f32)."""
    bh, s, d = q.shape
    grid = (bh, s // block_q)
    kv_spec = pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0),
                           memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, block_k=block_k,
            scale=float(1.0 / (d ** 0.5)),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, s), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, causal, block_q, scale):
    block_k, d = k_ref.shape[1], k_ref.shape[2]
    kb = pl.program_id(1)
    k_blk = k_ref[0]
    v_blk = v_ref[0]
    n_qb_total = q_ref.shape[1] // block_q
    qb_start = (kb * block_k) // block_q if causal else 0
    k_pos = kb * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)].reshape(block_q, 1)
        delta = (
            delta_ref[0, 0, pl.ds(qb * block_q, block_q)].reshape(block_q, 1)
        )
        s_ij = scale * lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            q_pos = qb * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s_ij = jnp.where(q_pos >= k_pos, s_ij, NEG_INF)
        p = jnp.exp(s_ij - lse).astype(k_blk.dtype)
        # dV += P^T dO
        dv = dv + lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p.astype(jnp.float32) * (dp - delta)).astype(k_blk.dtype)
        # dK += scale * dS^T Q
        dk = dk + scale * lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = lax.fori_loop(qb_start, n_qb_total, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, causal, block_k, scale):
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    j = pl.program_id(1)
    q_blk = q_ref[0]
    do_blk = do_ref[0]
    lse = lse_ref[0, 0, pl.ds(j * block_q, block_q)].reshape(block_q, 1)
    delta = delta_ref[0, 0, pl.ds(j * block_q, block_q)].reshape(block_q, 1)
    n_kb_total = k_ref.shape[1] // block_k
    if causal:
        n_kb = lax.min(
            (((j + 1) * block_q + block_k - 1) // block_k), n_kb_total
        )
    else:
        n_kb = n_kb_total
    q_pos = j * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s_ij = scale * lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s_ij = jnp.where(q_pos >= k_pos, s_ij, NEG_INF)
        p = jnp.exp(s_ij - lse)
        dp = lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta)).astype(k_blk.dtype)
        return dq + scale * lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = lax.fori_loop(0, n_kb, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret,
         dlse=None):
    """``lse``: [bh, 1, s] f32 (one sublane of the forward's stripe).

    ``dlse`` [bh, s]: cotangent of the logsumexp output (only when the
    caller consumed lse, e.g. ring-attention merging).  It enters the
    standard backward as ``ds_ij += p_ij * dlse_i``, i.e. an effective
    ``delta_i - dlse_i`` — no kernel change needed.
    """
    bh, s, d = q.shape
    delta = (
        (do.astype(jnp.float32) * o.astype(jnp.float32))
        .sum(axis=-1)
        .reshape(bh, 1, s)
    )
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).reshape(bh, 1, s)

    seq_spec = pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    kb_spec = pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0),
                           memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, block_q=block_q,
            scale=float(1.0 / (d ** 0.5)),
        ),
        grid=(bh, s // block_k),
        in_specs=[seq_spec, kb_spec, kb_spec, seq_spec, row_spec, row_spec],
        out_specs=[kb_spec, kb_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    qb_spec = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0),
                           memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, block_k=block_k,
            scale=float(1.0 / (d ** 0.5)),
        ),
        grid=(bh, s // block_q),
        in_specs=[qb_spec, seq_spec, seq_spec, qb_spec, row_spec, row_spec],
        out_specs=qb_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, causal, block_q, block_k, interpret)
    return o, lse[:, 0, :]


#: ``checkpoint_name`` tags on what the forward kernel made.  A remat policy
#: that keeps matmul outputs keeps these too (``utils.remat_wrap``): to
#: ``jax.checkpoint`` the kernel is an opaque ``pallas_call``, and without
#: the tags the backward pass runs ``flash_fwd`` again to get them back.
KEPT_O = "flash_o"
KEPT_LSE = "flash_lse"


def _flash_lse_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, causal, block_q, block_k, interpret)
    # tagged HERE so the value returned and the residual are one variable
    # (a tag on the caller's side names a copy and the kernel is replayed);
    # the [bh, 1, s] row, not the 8-sublane stripe the kernel writes
    o = checkpoint_name(o, KEPT_O)
    lse = checkpoint_name(lse[:, :1, :], KEPT_LSE)
    return (o, lse[:, 0, :]), (q, k, v, o, lse)


def _flash_lse_bwd(causal, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret,
                dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(q, k, v, *, causal: bool, block_q: int = 0,
                             block_k: int = 0, interpret: bool = False):
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
    if s % block_q or s % block_k:
        # no silent fallback here (the caller gates on flash_supported):
        # a non-divisible grid would TRUNCATE the sequence
        raise ValueError(
            f"seq {s} is not a multiple of block sizes "
            f"({block_q}, {block_k}); flash_attention_with_lse has no "
            "reference fallback"
        )

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    o, lse = _flash_lse(fold(q), fold(k), fold(v), causal, block_q, block_k,
                        interpret)
    o = o.reshape(b, h, s, d).transpose(0, 2, 1, 3).astype(jnp.float32)
    return o, lse.reshape(b, h, s)


def _enabled() -> bool:
    from .. import env

    return env.is_flash_attention_enabled()


# below this XLA's fused attention is already faster — re-validated r5 at
# BERT-Large's seq 384: plain 104.7 vs forced-flash 99.2 seq/s at batch 8
# (BENCH_BERT_SWEEP.json); the kernel pays from ~1k tokens (3.0x at 4096)
MIN_FLASH_SEQ = 1024


def flash_supported(seq: int, head_dim: int, block: int = _LANE) -> bool:
    """Whether the fused kernel pays: on-TPU, sequence long enough that the
    [seq, seq] HBM materialization hurts (measured crossover ~1k on v5p),
    block-aligned, and K/V + Q/dO fitting the per-step VMEM budget."""
    if not _enabled():
        return False
    if jax.default_backend() != "tpu":
        return False
    if seq < MIN_FLASH_SEQ or seq % block:
        return False
    # each kernel keeps 2 full-sequence operands resident (K+V fwd, Q+dO in
    # the dK/dV pass), double-buffered by the pipeline: 4 bf16 seq×lane
    # buffers must stay under the ~16 MB VMEM budget with headroom
    return 4 * seq * max(head_dim, _LANE) * 2 <= 12 * 1024 * 1024


def flash_attention(q, k, v, dtype=None, *, causal: bool = True,
                    block_q: int = 0, block_k: int = 0,
                    interpret: bool = False, force: bool = False):
    """Drop-in for :func:`reference_attention`: ``q/k/v`` are
    [batch, seq, heads, head_dim], returns [batch, seq, heads, head_dim] in
    ``dtype`` (default: q.dtype).

    ``force`` skips the platform check (tests run the kernel in interpret
    mode on CPU).
    """
    from .tiles import pick_block

    b, s, h, d = q.shape
    dtype = dtype or q.dtype
    block_q = block_q or pick_block(s)
    block_k = block_k or pick_block(s)
    if not force and not flash_supported(s, d, max(block_q, block_k)):
        return reference_attention(q, k, v, dtype, causal=causal)
    if s % block_q or s % block_k:
        return reference_attention(q, k, v, dtype, causal=causal)

    def fold(x):  # [b, s, h, d] -> [b*h, s, d]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    # lse is discarded; its zero cotangent enters the backward as a no-op
    o, _ = _flash_lse(fold(q), fold(k), fold(v), causal, block_q, block_k,
                      interpret)
    return (
        o.reshape(b, h, s, d).transpose(0, 2, 1, 3).astype(dtype)
    )
