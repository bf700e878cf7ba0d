"""Ring attention: causal flash-style attention over a sequence-parallel axis.

Absent from the reference (SURVEY.md §5.7 — its closest primitives are the
MoE all-to-all and ``alltoall_v``); first-class here because long-context is a
framework requirement.  Design is the TPU-native ring form (Liu et al.,
arXiv 2310.01889): the sequence is sharded over the ``'sp'`` mesh axis, each
step combines the resident K/V block with a numerically-stable online-softmax
update while ``lax.ppermute`` rotates K/V one hop around the ring — the
rotation rides ICI concurrently with the block matmuls, which is exactly the
compute/comm overlap the reference's Rust scheduler provided for DP, applied
to attention.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def make_ring_attention(sp_size: int, axis_name: str = "sp",
                        use_flash: str = "auto", interpret: bool = False):
    """Build an ``attn_fn(q, k, v, dtype)`` for ``TransformerLM`` that runs
    causal attention over a sequence sharded on ``axis_name``.

    Inputs per shard: [batch, seq_local, heads, head_dim] where shard i holds
    global positions [i*seq_local, (i+1)*seq_local).  Must run inside
    shard_map over a mesh containing ``axis_name`` (of size ``sp_size``).

    ``use_flash``: ``"auto"`` (Pallas flash kernel per ring step when
    :func:`bagua_tpu.ops.flash_attention.flash_supported` says it pays),
    ``"always"`` (force the kernel path), or ``"never"``.  ``interpret``
    runs the kernels in the Pallas interpreter (CPU tests).  The flash form
    computes each resident K/V block with the fused kernel and combines
    blocks with the standard (o, logsumexp) merge — identical math to the
    inline online-softmax loop, but the [s_local, s_local] scores never
    touch HBM.
    """
    if use_flash not in ("auto", "always", "never"):
        raise ValueError(
            f"use_flash={use_flash!r}: expected 'auto', 'always', or 'never'"
        )

    def attn_fn(q, k, v, dtype):
        b, s, h, d = q.shape
        if k.shape[2] != h:
            raise NotImplementedError(
                f"ring attention takes one key / value head a query head: "
                f"got {k.shape[2]} for {h} (n_kv_heads)")
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
        from .mesh import axis_bound

        if not axis_bound(axis_name):
            # outside shard_map (e.g. model.init): plain local attention —
            # shapes and params are identical, only used for tracing
            from ..models.transformer import causal_attention

            return causal_attention(q, k, v, dtype)

        from ..ops.flash_attention import flash_supported

        if use_flash == "always" or (
            use_flash == "auto" and flash_supported(s, h, d)
        ):
            return _ring_flash(q, k, v, dtype, sp_size, axis_name,
                               interpret=interpret)
        my = lax.axis_index(axis_name)
        q32 = q.astype(jnp.float32)
        q_pos = my * s + jnp.arange(s)

        # ring neighbor: receive from the previous rank so that after t hops
        # we hold the K/V block originated by shard (my - t) mod sp
        perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]

        def body(t, carry):
            o, m, l, k_blk, v_blk = carry
            src = (my - t) % sp_size
            k_pos = src * s + jnp.arange(s)
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)
            ) * scale
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, NEG_INF)

            m_new = jnp.maximum(m, logits.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            # fully-masked blocks contribute nothing (exp(NEG_INF - m) == 0)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
            o_new = o * corr[..., None] + pv

            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            return o_new, m_new, l_new, k_blk, v_blk

        o0 = jnp.zeros((b, h, s, d), jnp.float32)
        m0 = jnp.full((b, h, s), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, s), jnp.float32)
        o, m, l, _, _ = lax.fori_loop(0, sp_size, body, (o0, m0, l0, k, v))
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(dtype)  # [b, s, h, d]

    return attn_fn


def _merge_partials(o1, lse1, o2, lse2):
    """Combine two normalized partial attentions over disjoint K/V sets.
    ``o``: [b, s, h, d] f32, ``lse``: [b, h, s] f32."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    wsum = w1 + w2
    wt = lambda w: (w / wsum).transpose(0, 2, 1)[..., None]  # [b, s, h, 1]
    return wt(w1) * o1 + wt(w2) * o2, m + jnp.log(wsum)


def _ring_flash(q, k, v, dtype, sp_size, axis_name, interpret=False):
    """Ring attention with the fused flash kernel per resident block.

    Step 0 is the causal diagonal block; later steps are full
    (non-causal) cross-attention against earlier shards' K/V, merged with
    the (o, lse) statistics.  Blocks originating AFTER this shard are
    masked out by forcing their lse to -inf (zero merge weight, zero
    gradient) — same wasted bubble compute as the inline loop, but every
    matmul runs in the MXU-blocked kernel.
    """
    from ..ops.flash_attention import flash_attention_with_lse

    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]

    o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                      interpret=interpret)
    k_blk, v_blk = k, v
    for t in range(1, sp_size):
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = (my - t) % sp_size
        o_t, lse_t = flash_attention_with_lse(q, k_blk, v_blk, causal=False,
                                              interpret=interpret)
        lse_t = jnp.where(src < my, lse_t, NEG_INF)
        o, lse = _merge_partials(o, lse, o_t, lse_t)
    return o.astype(dtype)
