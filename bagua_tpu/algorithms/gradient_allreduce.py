"""Centralized synchronous full-precision data parallelism.

Counterpart of /root/reference/bagua/torch_api/algorithms/gradient_allreduce.py:8-38
plus its backing comm op
(comm_ops/centralized_full_precision_synchronous.rs:16-56).  One fused
``psum``/``pmean`` per bucket, issued where the bucket's gradient becomes
ready.  That placement is all XLA gives on a TPU today: the all-reduces of
the compiled step are *synchronous* — the compiler combines the per-bucket
collectives (99 buckets -> 16 calls on four v5e chips) and none of them
overlaps backward compute, so the whole exchange is exposed (32.5 ms of a
120 ms BERT-Large step; PERF.md, "the dp4 exchange, read off the chip").
The overlap the reference's Rust scheduler + dedicated CUDA stream bought is
NOT had for free here; ROADMAP.md Queue 1 item 1 holds what was tried.

A bucket that is one tensor keeps the tensor's own shape (bucket.py): the
fused ``psum`` takes it as it takes a 1-D flat.
"""

from __future__ import annotations

from typing import Optional

from ..communication import ReduceOp
from .base import Algorithm, AlgorithmContext


class GradientAllReduceAlgorithm(Algorithm):
    name = "gradient_allreduce"
    supports_overlap = True
    #: the per-bucket allreduce consumes resident bucket buffers directly
    #: (zero repacking; shaped or 1-D alike); ``auto`` takes it on the
    #: word of a cpu-sim record, though all seven cells of the benchmark
    #: run this layout and none the leaf one (ROADMAP Queue 3 item 3)
    supports_flat_resident = True
    #: reduced buckets are replicated (plain psum/ring sum — a NaN/Inf
    #: contribution from any rank survives into every rank's copy), so the
    #: gradient-health sentinel rides them with no extra collective
    grad_health_replicated = True
    #: the per-bucket flat reduction can carry an error-feedback residual
    #: when the codec policy forces a stateful codec (onebit_ef / topk)
    #: onto its rings
    supports_ef_state = True

    def __init__(
        self,
        hierarchical: bool = False,
        average: bool = True,
        comm_dtype: Optional[object] = None,
    ):
        """
        Args:
            hierarchical: Enable hierarchical (intra-node then inter-node)
                communication.
            average: If True average gradients over ranks, else sum.
            comm_dtype: Optional on-the-wire dtype for the allreduce (e.g.
                ``jnp.bfloat16`` halves the bytes on ICI/DCN; gradients are
                cast back afterwards, so params and optimizer state stay in
                full precision).  TPU-idiomatic middle ground between
                full-precision allreduce and ByteGrad's uint8 pipeline —
                bf16 keeps f32's exponent range, so no scale factor is
                needed.  The reduction itself accumulates in f32 (XLA
                upcasts psum accumulators on TPU).
        """
        self.hierarchical = hierarchical
        self.average = average
        self.comm_dtype = comm_dtype

    def reduce_bucket_grad(self, ctx: AlgorithmContext, index: int, flat):
        op = ReduceOp.AVG if self.average else ReduceOp.SUM
        if self.comm_dtype is None:
            return ctx.bucket_allreduce(flat, op, self.hierarchical)
        orig = flat.dtype
        flat = ctx.bucket_allreduce(
            flat.astype(self.comm_dtype), op, self.hierarchical
        )
        return flat.astype(orig)

    process_grads = Algorithm.process_grads_bucketed
