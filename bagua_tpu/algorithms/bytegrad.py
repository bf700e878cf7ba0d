"""ByteGrad: 8-bit compressed gradient allreduce.

Counterpart of /root/reference/bagua/torch_api/algorithms/bytegrad.py (buckets
aligned to the world size :38-43, centralized op with
``scattergather=True, compression="MinMaxUInt8"`` :50-56) backed by
comm_ops/centralized_low_precision_synchronous.rs.

Hierarchical mode follows the reference's Leader pattern
(communicators/mod.rs:264-297): reduce full-precision inside the slice (ICI
is cheap), compress across slices.  Since ISSUE 15 the cross-slice stage is
the fused compressed ring (``tier_allreduce(codec=)``): each DCN ``ppermute``
hop carries the quantized partial sum + sidecar and accumulates in fp32 —
compressed bytes ARE the wire bytes, where the previous form ran the codec
as a discrete scatter-gather stage between full-precision tier collectives.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..communication import LINK_ICI, ReduceOp
from ..compression import compressed_scatter_gather_allreduce
from .base import Algorithm, AlgorithmContext


class ByteGradAlgorithm(Algorithm):
    name = "bytegrad"
    supports_overlap = True
    #: the codec pipeline already runs on flat buckets, so the resident
    #: layout feeds it with zero repacking
    supports_flat_resident = True
    #: set from a cpu-sim record (8-dev mesh; deleted in PR 46), never
    #: measured on the chip: the overlap restructure was never clearly
    #: faster for the codec pipeline there (0.69-0.95x, noise-bound under
    #: interleaved A/B), so ``auto`` keeps bytegrad serialized; opt in
    #: with ``overlap="on"``.  Owed a cell on a real multi-chip mesh, where
    #: the quantize sits on the critical comm path: ROADMAP Queue 3 item 3
    overlap_auto = False
    #: non-hierarchical path wire format (the compressed scatter-gather):
    #: the byte-accounting default for ``bucket_tier_bytes``
    wire_codec_flat = "minmax_uint8"
    #: the hierarchical DCN stage can carry an error-feedback residual when
    #: ``BAGUA_COMPRESS_INTER`` escalates the ring to a stateful codec
    #: (onebit_ef / topk); the flat scatter-gather pipeline never does —
    #: it has one wire format (minmax_uint8)
    supports_ef_state = True

    def __init__(self, hierarchical: bool = True, average: bool = True,
                 codec: str = "minmax_uint8"):
        """
        Args:
            hierarchical: Enable hierarchical communication (slice-local
                full-precision reduce, compressed cross-slice ring).
            average: If True average the reduced gradients, else sum.
            codec: Wire codec of the compressed DCN ring hops
                (``minmax_uint8`` — the reference format — or ``int8`` /
                ``fp8_e4m3`` / ``fp8_e5m2``).  The per-tier policy knobs
                (``BAGUA_COMPRESS_INTER``) override it.
        """
        from ..compression.codecs import get_codec

        get_codec(codec)  # fail fast on a typo'd codec name
        self.hierarchical = hierarchical
        self.average = average
        self.codec = codec

    @property
    def wire_codec_dcn(self):
        """The DCN tier's family-default codec (byte accounting + the
        ``auto`` policy resolution ride this)."""
        return self.codec

    def tensors_to_buckets(self, decl_buckets, named_params, world_size):
        from ..bucket import BucketPlan

        # align bucket length to the world size so each rank owns an equal
        # chunk in the scatter-gather (reference bytegrad.py:38-43)
        return BucketPlan.from_declaration_buckets(
            decl_buckets, named_params, alignment=world_size
        )

    def reduce_bucket_grad(self, ctx: AlgorithmContext, index: int, flat):
        if jnp.ndim(flat) != 1 and ctx.comm.nranks() > 1:
            # a shaped bucket (bucket.py): the pipeline cuts per-rank
            # chunks of a 1-D run, so ravel here, at the point of use
            return self.reduce_bucket_grad(
                ctx, index, flat.reshape(-1)).reshape(flat.shape)
        # the whole codec (compress → alltoall → decompress → chunk-reduce →
        # compress → allgather → decompress) runs per bucket, so under the
        # overlap scheduler it sits inside the overlap window: bucket i's
        # quantize + scatter-gather can proceed while bucket i+1's gradient
        # is still being produced by the backward
        use_hier = (
            self.hierarchical
            and ctx.two_tier()
            and ctx.internode.nranks() > 1
        )
        if use_hier:
            # two-level form, codec on the DCN stage ONLY — compress where
            # bytes are expensive: full-precision slice-local
            # reduce-scatter (ICI is cheap), then the COMPRESSED RING
            # allreduce of the 1/intra shard across slices — every DCN
            # ppermute hop carries the codec payload (quantize-on-send,
            # fp32 accumulate on receive; the shard is re-quantized once
            # for the ring's broadcast phase), so compressed bytes are
            # what actually cross the slow link — then a full-precision
            # slice-local allgather re-replicates.  The shard divides the
            # inter world because buckets are padded to the full world
            # size (tensors_to_buckets above).  The policy knob
            # (BAGUA_COMPRESS_INTER) can override the codec or force the
            # DCN stage back to full precision.
            op = ReduceOp.AVG if self.average else ReduceOp.SUM
            chunk = ctx.tier_reduce_scatter(flat, op)
            chunk = ctx.tier_allreduce(chunk, op, codec=self.codec)
            return ctx.tier_allgather(chunk)
        if ctx.comm.nranks() > 1:
            if ctx.codec_for(LINK_ICI, self.codec) is None:
                # the policy knob forced `off`: full precision even on
                # the family's own flat pipeline — the documented
                # debug-a-divergence escape hatch.  (A forced codec NAME
                # keeps the minmax scatter-gather: that pipeline has one
                # wire format; the ring tiers honor forced names.)
                # bucket_allreduce, not a bare fused psum: the chunk
                # knobs' ring schedule must survive the escape hatch.
                op = ReduceOp.AVG if self.average else ReduceOp.SUM
                return ctx.bucket_allreduce(flat, op, False)
            return compressed_scatter_gather_allreduce(
                ctx.comm, flat, average=self.average
            )
        return flat

    process_grads = Algorithm.process_grads_bucketed
