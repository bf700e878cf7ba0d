"""GShard top-1 / top-2 gating.

Counterpart of /root/reference/bagua/torch_api/model_parallel/moe/sharded_moe.py
(``top1gating`` :93, ``top2gating`` :168, capacity + load-balancing auxiliary
loss).  Re-derived from the GShard formulation (arXiv 2006.16668) rather than
ported: everything is dense one-hot einsum math — no sorting, no scatter —
so XLA lowers it to MXU-friendly matmuls with static shapes.

Shapes: ``logits`` is [tokens, n_experts]; returned ``dispatch`` is
[tokens, n_experts, capacity] (0/1), ``combine`` the same shape weighted by
the gate probability, and ``l_aux`` a scalar.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _positions_in_expert(mask: jax.Array) -> jax.Array:
    """For each (token, expert) with mask==1: how many earlier tokens chose
    this expert (its slot index in the expert's capacity buffer)."""
    return (jnp.cumsum(mask, axis=0) - 1) * mask


def _load_balancing_loss(probs: jax.Array, mask: jax.Array) -> jax.Array:
    """GShard aux loss: n_experts * Σ_e mean_t(probs_te) * mean_t(mask_te)."""
    n_experts = probs.shape[-1]
    density = mask.astype(jnp.float32).mean(axis=0)
    density_proxy = probs.mean(axis=0)
    return jnp.sum(density * density_proxy) * n_experts


def top1_gating(
    logits: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Switch-style top-1 routing with capacity dropping."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    n_experts = probs.shape[-1]
    index = jnp.argmax(probs, axis=-1)
    mask = jax.nn.one_hot(index, n_experts, dtype=jnp.float32)
    l_aux = _load_balancing_loss(probs, mask)

    pos = _positions_in_expert(mask)
    keep = mask * (pos < capacity)
    gate = (probs * keep).sum(axis=-1)  # chosen prob; 0 for dropped tokens
    dispatch = keep[:, :, None] * jax.nn.one_hot(
        pos.astype(jnp.int32), capacity, dtype=jnp.float32
    )
    combine = gate[:, None, None] * dispatch
    return dispatch, combine, l_aux


def topk_routing(
    logits: jax.Array, k: int, *, renormalize: bool = True,
    balance_over_topk: bool = False, score: str = "softmax",
    choice_bias=None, scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless top-k routing for any ``k``: no capacity, no dispatch tensor.

    Returns (expert_idx [tokens, k], gate_weights [tokens, k], l_aux).
    Consumed by the sort + grouped-matmul (``bagua_tpu.ops.gmm``) dropless
    MoE path.

    Defaults are the capacity gates' conventions (GShard): top-1 keeps the
    raw chosen probability, ``k > 1`` renormalizes over the winners, and the
    aux loss is over the top-1 assignment (GShard eq. 4).
    ``renormalize=False`` keeps the raw softmax probabilities of the winners
    (HF ``norm_topk_prob: false``, OLMoE).  ``balance_over_topk=True`` takes
    the balance loss over all ``k`` assignments of a token, as HF's
    ``load_balancing_loss_func`` does: ``n_experts * sum_e (assignments on
    e / tokens) * mean_t probs[t, e]``.

    ``score="sigmoid"``: the scores are ``sigmoid(logits)``, each expert's by
    itself and not normalised over the experts (the DeepSeek-V3 / Nemotron-H
    routers); the winners' are renormalised as ``s / (sum s + 1e-20)``.
    ``choice_bias`` [n_experts]: added to the scores for the CHOICE of the
    ``k`` winners only — the weights are the unbiased scores at the winners,
    so the bias has no gradient (it enters only the indices; the families
    move it by a load-balance rule outside the gradient).  ``scale``
    multiplies the final weights (``routed_scaling_factor``).
    """
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"router score {score!r}: 'softmax' or 'sigmoid'")
    logits = logits.astype(jnp.float32)
    probs = (jax.nn.sigmoid(logits) if score == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    n_experts = probs.shape[-1]
    if choice_bias is None:
        gates, eidx = jax.lax.top_k(probs, k)
    else:
        _, eidx = jax.lax.top_k(
            probs + choice_bias.astype(jnp.float32), k)
        gates = jnp.take_along_axis(probs, eidx, axis=-1)
    if balance_over_topk:
        mask = jax.nn.one_hot(eidx, n_experts, dtype=jnp.float32).sum(axis=1)
    else:
        mask = jax.nn.one_hot(eidx[:, 0], n_experts, dtype=jnp.float32)
    l_aux = _load_balancing_loss(probs, mask)
    if k > 1 and renormalize:
        total = gates.sum(-1, keepdims=True)
        gates = gates / (total + 1e-20 if score == "sigmoid"
                         else jnp.maximum(total, 1e-9))
    if scale != 1.0:
        gates = gates * scale
    return eidx.astype(jnp.int32), gates, l_aux


def top2_gating(
    logits: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """GShard top-2 routing: second expert chosen from the masked
    distribution, gates renormalized over the two winners."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    n_experts = probs.shape[-1]

    index1 = jnp.argmax(probs, axis=-1)
    mask1 = jax.nn.one_hot(index1, n_experts, dtype=jnp.float32)
    probs_wo_1 = probs * (1.0 - mask1)
    index2 = jnp.argmax(probs_wo_1, axis=-1)
    mask2 = jax.nn.one_hot(index2, n_experts, dtype=jnp.float32)

    # aux loss over the top-1 assignment only (GShard eq. 4)
    l_aux = _load_balancing_loss(probs, mask1)

    # capacity: first-choice tokens fill slots before second-choice tokens
    pos1 = _positions_in_expert(mask1)
    count1 = mask1.sum(axis=0, keepdims=True)
    pos2 = _positions_in_expert(mask2) + count1 * mask2
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    g1 = (probs * keep1).sum(axis=-1)
    g2 = (probs * keep2).sum(axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    dispatch1 = keep1[:, :, None] * jax.nn.one_hot(
        pos1.astype(jnp.int32), capacity, dtype=jnp.float32
    )
    dispatch2 = keep2[:, :, None] * jax.nn.one_hot(
        pos2.astype(jnp.int32), capacity, dtype=jnp.float32
    )
    dispatch = jnp.maximum(dispatch1, dispatch2)
    combine = g1[:, None, None] * dispatch1 + g2[:, None, None] * dispatch2
    return dispatch, combine, l_aux
