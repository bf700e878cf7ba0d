"""ResNet family for the synthetic throughput benchmark.

The reference's CI benchmark trains ResNet50 on synthetic ImageNet-shaped
batches and gates on img/s per device
(/root/reference/.buildkite/scripts/benchmark_master.sh:83-98,
examples/benchmark/synthetic_benchmark.py).  This is the TPU-first
equivalent: bfloat16 convs (MXU), f32 params and batch-norm statistics,
NHWC layout (TPU-native), static shapes.

Batch-norm *applies* in bfloat16 by default (``norm_dtype``): the training
step is HBM-bandwidth-bound on TPU, and an f32 norm forces every activation
tensor through an f32 round-trip between bf16 convs — measured 25% of
ResNet50 step time on v5e.  Flax's ``BatchNorm`` still computes the batch
statistics in f32 internally (``_compute_stats`` promotes), and running
stats live in ``param_dtype`` f32, so only the normalize/scale/shift
arithmetic drops to bf16.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, name="proj_conv"
            )(residual)
            residual = self.norm(name="proj_norm")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    norm_dtype: Any = jnp.bfloat16  # f32 restores the conservative pre-norm cast
    norm_cls: Any = None  # override with SyncBatchNorm for cross-chip stats
    #: rematerialize each bottleneck block in the backward pass.  Round 5
    #: read it on v5e (pre-chip yardstick; no cell runs it) as a LOSS
    #: — conv recompute re-reads activations/weights, ADDING HBM traffic
    #: (28.1 -> 33.0 GB/step at batch 128) for -18% img/s — so it stays
    #: off by default; use it only when activation memory, not speed, is
    #: the binding constraint (it admits batch 512 on one chip).
    remat: bool = False
    #: ``None`` recomputes everything inside a block; ``"dots"`` keeps
    #: dot/conv results (jax.checkpoint_policies.dots_saveable does not
    #: cover conv_general, so on this conv trunk it approximates full
    #: recompute — kept for API symmetry with TransformerConfig).
    remat_policy: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32)
        norm_base = self.norm_cls or nn.BatchNorm
        norm = partial(
            norm_base, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.norm_dtype, param_dtype=jnp.float32,
        )
        block_cls = BottleneckBlock
        if self.remat:
            from ..utils import remat_wrap

            block_cls = remat_wrap(BottleneckBlock, self.remat_policy)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                 name="stem_conv")(x)
        x = norm(name="stem_norm")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block_cls(
                    self.num_filters * 2 ** i, strides, conv, norm,
                    name=f"stage{i}_block{j}",
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x


ResNet50 = partial(ResNet, stage_sizes=(3, 4, 6, 3))
ResNet101 = partial(ResNet, stage_sizes=(3, 4, 23, 3))


def classification_loss_fn(model, batch_stats=None):
    """Softmax cross-entropy over integer labels.

    Only the ``params`` collection is trainable/communicated; batch-norm
    running statistics are closed over as a frozen constant (train-mode BN
    normalizes with per-batch statistics, so they never affect the loss —
    matching the reference's synthetic benchmark, which never evals).  Carrying
    live running stats across steps is the SyncBatchNorm contrib path.
    """
    import optax

    def loss_fn(params, batch):
        variables = {"params": params}
        if batch_stats is not None:
            variables["batch_stats"] = batch_stats
            logits, _ = model.apply(
                variables, batch["images"], train=True, mutable=["batch_stats"]
            )
        else:
            logits = model.apply(variables, batch["images"], train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]
        ).mean()

    return loss_fn
