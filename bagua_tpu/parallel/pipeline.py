"""Pipeline parallelism (GPipe-style) over a ``'pp'`` mesh axis.

Absent from the reference (SURVEY.md §2.3: PP "not present"); additive here.
SPMD formulation: transformer blocks are stacked along a leading layer dim
(``nn.scan``), that dim is sharded over ``'pp'`` so stage ``s`` holds layers
``[s*L/pp, (s+1)*L/pp)``, and one jitted step runs the classic microbatch
schedule as a ``lax.scan`` over ``n_micro + pp - 1`` ticks: every tick each
stage applies its blocks to the activation it holds, then ``lax.ppermute``
hands activations one hop down the pipeline (no wraparound — stages beyond
the end discard, stages before the start receive zeros, which is exactly
the warm-up/drain bubble).  The last stage accumulates the loss; a ``psum``
over ``'pp'`` replicates it.

Embedding / positional / final-norm / head parameters are replicated across
stages (SPMD: every stage traces the same program), so their gradients are
*partial* per stage — the trainer's ``pp_axis`` mode scales them by
``pp_size`` and lets the bucket allreduce span ``pp`` to sum them (see
``BaguaTrainer``).  Stage (block) leaves are sharded and averaged over data
axes only.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..models.transformer import (
    Block, RMSNorm, TokenEmbed, TransformerConfig, refuse_not_built,
)
from .mesh import axis_bound as _axis_bound


class _ScanBlock(nn.Module):
    """Block adapter with scan signature (carry, _) -> (carry, None).  One
    traced block stands for every layer of the stack: the layers of the
    plan are all one (``NOT_BUILT``'s ``mixed_layers`` under ``pipeline``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, _):
        return Block(self.cfg, name="block")(x), None


class PipelinedTransformerLM(nn.Module):
    """Causal LM computing its LOSS inside the pipeline schedule.

    ``__call__(tokens [batch, seq+1]) -> scalar`` per-shard loss (replicated
    over pp).  ``cfg.n_layers`` must be divisible by ``pp_size``; the module
    creates the LOCAL stack of ``n_layers // pp_size`` blocks, so ``init``
    outside the mesh yields local-shape leaves — expand with
    :func:`globalize_pp_params` before handing them to the trainer.

    Outside ``shard_map`` (e.g. ``model.init``) the schedule degenerates to
    a plain sequential forward over the local blocks with a full-batch loss
    — shapes (and therefore params) are identical.
    """

    cfg: TransformerConfig
    pp_size: int
    n_microbatches: int = 1
    pp_axis: str = "pp"

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        refuse_not_built(cfg, consumers=("pipeline",))
        assert cfg.n_layers % self.pp_size == 0, (cfg.n_layers, self.pp_size)
        n_local = cfg.n_layers // self.pp_size

        embed = TokenEmbed(cfg.vocab_size, cfg.d_model, name="embed",
                           dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (cfg.max_seq_len, cfg.d_model), cfg.param_dtype)
        block_cls = nn.remat(_ScanBlock) if cfg.remat else _ScanBlock
        blocks = nn.scan(
            block_cls,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            length=n_local,
        )(cfg, name="blocks")
        final_norm = RMSNorm(cfg.dtype, cfg.param_dtype, name="final_norm")
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head")

        def embed_fn(toks):
            s = toks.shape[1]
            return embed(toks) + pos[:s][None].astype(cfg.dtype)

        def loss_of(y, targets):
            import optax

            logits = head(final_norm(y)).astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            ).mean()

        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        if not _axis_bound(self.pp_axis) or self.pp_size == 1:
            # degenerate path (init trace, or pp=1): plain sequential run
            y, _ = blocks(embed_fn(inputs), None)
            return loss_of(y, targets)

        pp, n_micro = self.pp_size, self.n_microbatches
        b = inputs.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        mb_in = inputs.reshape(n_micro, b // n_micro, -1)
        mb_tgt = targets.reshape(n_micro, b // n_micro, -1)
        stage = lax.axis_index(self.pp_axis)
        perm = [(i, i + 1) for i in range(pp - 1)]

        def tick(carry, t):
            recv, acc = carry
            feed = jnp.clip(t, 0, n_micro - 1)
            x0 = embed_fn(mb_in[feed])
            x_in = jnp.where(stage == 0, x0, recv)
            y, _ = blocks(x_in, None)
            out_idx = t - (pp - 1)
            ls = loss_of(y, mb_tgt[jnp.clip(out_idx, 0, n_micro - 1)])
            take = jnp.logical_and(stage == pp - 1,
                                   jnp.logical_and(out_idx >= 0,
                                                   out_idx < n_micro))
            acc = acc + jnp.where(take, ls, 0.0)
            recv = lax.ppermute(y, self.pp_axis, perm)
            return (recv, acc), None

        recv0 = jnp.zeros((b // n_micro, inputs.shape[1], cfg.d_model),
                          cfg.dtype)
        (_, acc), _ = lax.scan(
            tick, (recv0, jnp.zeros((), jnp.float32)),
            jnp.arange(n_micro + pp - 1),
        )
        # only the last stage accumulated; replicate the mean loss.
        # tp_reduce (psum fwd, identity bwd), NOT a raw psum: under
        # unchecked shard_map psum transposes to psum, which would scale
        # every gradient by pp
        from .tensor_parallel import tp_reduce

        return tp_reduce(acc, self.pp_axis) / n_micro


def pp_param_dim(name: str) -> Optional[int]:
    """Stage-stacked leaves (everything under the ``blocks`` scan scope)
    are sharded along their leading layer dim.  Matching is by exact path
    SEGMENT — a user param like ``resblocks.conv.kernel`` is not captured
    (the substring hazard ``expert_keyword`` was deprecated for)."""
    return 0 if "blocks" in name.split(".") else None


def pp_lm_loss_fn(model: PipelinedTransformerLM):
    def loss_fn(params, batch):
        return model.apply({"params": params}, batch["tokens"])

    return loss_fn


def globalize_pp_params(params, rng, pp_size: int, tp_size: int = 1,
                        tp_param_dim=None):
    """Expand LOCAL stage stacks ``[L/pp, ...]`` to GLOBAL ``[L, ...]``.

    Norm scales are re-expanded as ones; kernels are re-drawn lecun-normal
    over their per-layer contracting dims (layer dim 0 excluded).  With
    ``tp_size > 1`` (3-D parallelism: the blocks also carry tensor-parallel
    kernels) each tp leaf's sharded dim — reported by ``tp_param_dim`` in
    per-layer coordinates, shifted past the stage dim — is expanded to its
    global width as well, and the redraw uses the GLOBAL fan-in.
    """
    from ..models.transformer import tp_param_fan_in_dims
    from ..tensor import _name_of_path
    from .tensor_parallel import redraw_lecun

    if tp_param_dim is None and tp_size > 1:
        from ..models.transformer import tp_param_dim as _default_tp_dim

        tp_param_dim = _default_tp_dim

    def fix(path, leaf):
        name = _name_of_path(path)
        if pp_param_dim(name) is None or (pp_size == 1 and tp_size == 1):
            return leaf
        shape = [leaf.shape[0] * pp_size, *leaf.shape[1:]]
        if name.endswith(".scale"):  # norm scales: ones
            return jnp.ones(tuple(shape), leaf.dtype)
        tpd = tp_param_dim(name) if tp_size > 1 else None
        if tpd is not None:
            shape[tpd + 1] = shape[tpd + 1] * tp_size
        nonlocal rng
        rng, sub = jax.random.split(rng)
        # per-layer kernels: contracting dims from the tp table, shifted
        # past the leading layer dim; default: all but first and last
        inner = tp_param_fan_in_dims(name)
        contracting = (
            tuple(ax + 1 for ax in inner) if inner is not None
            else tuple(range(1, len(shape) - 1))
        )
        return redraw_lecun(sub, tuple(shape), contracting, leaf.dtype)

    return jax.tree_util.tree_map_with_path(fix, params)
