"""QAdam: quantized-momentum Adam (1-bit-Adam family).

Counterpart of /root/reference/bagua/torch_api/algorithms/q_adam.py:13-203.
Two phases, switched by ``need_reset`` at the warmup boundary (:118-125):

- warmup (``step < warmup_steps``): gradients are full-precision averaged,
  both Adam moments update from the averaged gradient (:88-92), parameters
  step by the Adam rule (:94-100).
- compressed: the *momentum* (``exp_avg``) updates locally from the raw
  gradient (the reference's in-pipeline python op :178-189), is then
  8-bit-compressed scatter-gather averaged (:190-195), and the second moment
  is frozen (:88 guard).

The algorithm owns its optimizer (the reference requires the dedicated
``QAdamOptimizer``), so the trainer's optax path is bypassed.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..communication import LINK_DCN, LINK_ICI, ReduceOp
from ..compression import compressed_scatter_gather_allreduce
from ..obs.spans import phase_scope
from .base import Algorithm, AlgorithmContext


class QAdamOptState(NamedTuple):
    exp_avg: object
    exp_avg_sq: object


class QAdamAlgorithm(Algorithm):
    name = "qadam"
    owns_optimizer = True
    #: the momenta are elementwise maps of the gradient, so they live as
    #: bucket flats under the resident layout and the compressed momentum
    #: pipeline consumes them with zero repacking
    supports_flat_resident = True
    #: non-hierarchical compressed-phase wire format (byte accounting)
    wire_codec_flat = "minmax_uint8"

    def __init__(
        self,
        warmup_steps: int = 100,
        lr: float = 1e-3,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        hierarchical: bool = True,
        codec: str = "minmax_uint8",
    ):
        """
        Args:
            warmup_steps: Steps of full-precision gradient allreduce before
                switching to compressed momentum communication.
            lr / betas / eps / weight_decay: Adam hyperparameters (reference
                QAdamOptimizer q_adam.py:13-46).
            hierarchical: Enable hierarchical communication in the
                compressed phase.
            codec: Wire codec of the compressed DCN ring hops in the
                hierarchical compressed phase (overridable by
                ``BAGUA_COMPRESS_INTER``).
        """
        from ..compression.codecs import get_codec

        get_codec(codec)  # fail fast on a typo'd codec name
        self.warmup_steps = warmup_steps
        self.lr = lr
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.hierarchical = hierarchical
        self.codec = codec
        self._compressed = False

    @property
    def wire_codec_dcn(self):
        return self.codec

    def need_reset(self, step: int) -> bool:
        if step == self.warmup_steps and not self._compressed:
            self._compressed = True
            return True
        return False

    def compile_key(self) -> tuple:
        # the traced step branches on _compressed at trace time; an autotune
        # switch back to qadam resets it to False mid-training, which must
        # NOT reuse the compressed-phase compile
        return (self._compressed,)

    def tensors_to_buckets(self, decl_buckets, named_params, world_size):
        from ..bucket import BucketPlan

        # world-size alignment for the compressed scatter-gather
        # (reference q_adam.py:158-166 aligns buckets to get_world_size())
        return BucketPlan.from_declaration_buckets(
            decl_buckets, named_params, alignment=world_size
        )

    # ---- phase 1: warmup grad allreduce ---------------------------------

    def process_grads(self, ctx: AlgorithmContext, grads, params, algo_state, step):
        if self._compressed:
            return grads, algo_state
        flats = ctx.bucket_flats(grads)
        flats = [ctx.hierarchical_allreduce(f, ReduceOp.AVG, False) for f in flats]
        return ctx.from_bucket_flats(flats, grads), algo_state

    # ---- optimizer -------------------------------------------------------

    def init_optimizer_state(self, params):
        zeros = jax.tree.map(jnp.zeros_like, params)
        return QAdamOptState(exp_avg=zeros, exp_avg_sq=jax.tree.map(jnp.zeros_like, params))

    def _communicate_momentum(self, ctx: AlgorithmContext, exp_avg):
        flats = ctx.bucket_flats(exp_avg)
        # the true two-level decomposition where the mesh supports it:
        # full-precision slice-local reduce-scatter (ICI is cheap), the
        # COMPRESSED RING allreduce of the 1/intra momentum shard across
        # slices (quantized ppermute hops, fp32 accumulation — the 1-bit
        # Adam relaxation applied ON the slow link's hops), slice-local
        # allgather.  Buckets are world-aligned (tensors_to_buckets), so
        # both tiers divide evenly.
        use_two_level = (
            self.hierarchical
            and ctx.two_tier()
            and ctx.internode.nranks() > 1
        )
        # legacy Leader form for hierarchical meshes the two-level gate
        # refuses (an extra comm axis folded in): full-precision intra
        # average, compressed scatter-gather across slices
        use_hier = (
            not use_two_level
            and self.hierarchical
            and ctx.internode is not None
            and ctx.intranode is not None
            and ctx.internode.nranks() > 1
            and ctx.intranode.nranks() > 1
        )
        out = []
        for i, f in enumerate(flats):
            # inside the trainer's bagua.optimizer scope: the momentum
            # exchange names itself bagua.comm (innermost bagua.* wins)
            with phase_scope(f"bagua.comm/bucket_{i}"):
                f = self._communicate_bucket(ctx, f, use_two_level, use_hier)
            out.append(f)
        return ctx.from_bucket_flats(out, exp_avg)

    def _communicate_bucket(self, ctx: AlgorithmContext, f, use_two_level,
                            use_hier):
        if jnp.ndim(f) != 1:
            # a shaped bucket (bucket.py): the tiers and the compressed
            # scatter-gather cut per-rank chunks of a 1-D run, so ravel
            # here, at the point of use
            return self._communicate_bucket(
                ctx, f.reshape(-1), use_two_level, use_hier).reshape(f.shape)
        if use_two_level:
            f = ctx.tier_reduce_scatter(f, ReduceOp.AVG)
            f = ctx.tier_allreduce(f, ReduceOp.AVG, codec=self.codec)
            f = ctx.tier_allgather(f)
        elif use_hier:
            f = ctx.intranode.allreduce(f, ReduceOp.AVG)
            # the knob's `off` escape hatch holds on the legacy leg
            # too: full-precision inter average (tier_allreduce, so
            # the DCN chunk knob's ring schedule survives) instead
            # of the codec
            if ctx.codec_for(LINK_DCN, self.codec) is None:
                f = ctx.tier_allreduce(f, ReduceOp.AVG)
            else:
                f = compressed_scatter_gather_allreduce(
                    ctx.internode, f, average=True)
        elif ctx.comm.nranks() > 1:
            if ctx.codec_for(LINK_ICI, self.codec) is None:
                # bucket_allreduce keeps the chunk knobs' ring
                # schedule on the full-precision escape hatch
                f = ctx.bucket_allreduce(f, ReduceOp.AVG, False)
            else:
                f = compressed_scatter_gather_allreduce(
                    ctx.comm, f, average=True)
        return f

    def optimizer_update(self, ctx, params, grads, opt_state: QAdamOptState, algo_state, step):
        beta1, beta2 = self.betas
        # reference QAdamOptimizer.step increments step_id first (:77), so the
        # bias corrections use step_id = step + 1
        step_id = (step + 1).astype(jnp.float32)

        exp_avg = jax.tree.map(
            lambda m, g: m * beta1 + g * (1.0 - beta1), opt_state.exp_avg, grads
        )
        if self._compressed:
            # second moment frozen (q_adam.py:88 guard); momentum averaged
            # via the compressed pipeline
            exp_avg = self._communicate_momentum(ctx, exp_avg)
            exp_avg_sq = opt_state.exp_avg_sq
        else:
            exp_avg_sq = jax.tree.map(
                lambda v, g: v * beta2 + (g * g) * (1.0 - beta2),
                opt_state.exp_avg_sq,
                grads,
            )

        bias1 = 1.0 - beta1 ** step_id
        bias2 = 1.0 - beta2 ** step_id

        def upd(p, m, v):
            denom = jnp.sqrt(v) / jnp.sqrt(bias2) + self.eps
            new_p = p - (self.lr / bias1) * (m / denom)
            if self.weight_decay:
                new_p = new_p - self.lr * self.weight_decay * p
            return new_p

        new_params = jax.tree.map(upd, params, exp_avg, exp_avg_sq)
        return new_params, QAdamOptState(exp_avg, exp_avg_sq), algo_state
