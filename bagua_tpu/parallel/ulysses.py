"""Ulysses-style sequence parallelism: all-to-all head/sequence reshard.

Absent from the reference; SURVEY.md §5.7 notes its ``alltoall_v`` + MoE
all-to-all machinery are exactly the primitives Ulysses (DeepSpeed-Ulysses,
arXiv 2309.14509) needs.  Here it is two ``lax.all_to_all`` calls over the
``'sp'`` axis: heads are scattered so each shard sees the FULL sequence for
its subset of heads, runs an unmodified local attention, and reshards back.
Complements ring attention: Ulysses keeps attention math local (better for
short-ish sequences / many heads), the ring streams K/V (better for very
long sequences).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp
from jax import lax


def make_ulysses_attention(
    sp_size: int,
    axis_name: str = "sp",
    inner_attn: Optional[Callable] = None,
):
    """Build an ``attn_fn(q, k, v, dtype)`` for ``TransformerLM``.

    Per-shard inputs [batch, seq_local, heads, head_dim]; ``heads`` must be
    divisible by ``sp_size``.  ``inner_attn`` is the local full-sequence
    attention (default: the model's standard causal attention).
    """

    def attn_fn(q, k, v, dtype):
        from ..models.transformer import causal_attention

        inner = inner_attn or causal_attention
        from .mesh import axis_bound

        if k.shape[2] != q.shape[2]:
            raise NotImplementedError(
                f"ulysses attention takes one key / value head a query "
                f"head: got {k.shape[2]} for {q.shape[2]} (n_kv_heads)")

        if not axis_bound(axis_name):
            # outside shard_map (e.g. model.init): plain local attention
            return inner(q, k, v, dtype)
        if q.shape[2] % sp_size:
            raise ValueError(
                f"heads {q.shape[2]} not divisible by sp_size {sp_size}"
            )

        # [b, s_loc, h, d] -> [b, s_global, h/sp, d]
        def to_seq(x):
            return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

        # [b, s_global, h/sp, d] -> [b, s_loc, h, d]
        def to_heads(x):
            return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

        o = inner(to_seq(q), to_seq(k), to_seq(v), dtype)
        return to_heads(o)

    return attn_fn
