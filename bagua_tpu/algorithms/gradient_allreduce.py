"""Centralized synchronous full-precision data parallelism.

Counterpart of /root/reference/bagua/torch_api/algorithms/gradient_allreduce.py:8-38
plus its backing comm op
(comm_ops/centralized_full_precision_synchronous.rs:16-56).  A bucket's
gradient is exchanged where it becomes ready, in one of two forms:

- **all-reduce, then every rank updates everything** — one fused
  ``psum``/``pmean`` a bucket.  One chip's world, ``hierarchical=True``,
  model-parallel meshes, a transform that is not elementwise, an
  error-feedback codec on the wire, a ``comm_dtype`` narrower than the
  parameters.
- **all-gather of the resident chunks -> loss -> reduce-scatter -> update
  of the owned chunk** — wherever the trainer can take it
  (``BaguaTrainer._mark_sharded_update``: more than one rank on a pure-dp
  mesh, bucket-flat state, an elementwise optimizer, a wire as wide as the
  parameters; no option selects it).  An all-reduce IS that pair of
  collectives; behind the reduce-scatter a rank holds the reduced gradient
  of its own chunk alone, steps that chunk's parameters and moments, and
  stores no other rank's (arXiv:2004.13336, the weight-update sharding XLA
  does for replicated training, in ZeRO-3's order).  **Between steps
  ``state.params`` holds each such bucket as a global array of unchanged
  shape, cut over the comm axes along its leading axis like its moments**
  (``BaguaTrainer._resident_specs``); the step gathers the buffers once, at
  its top (``AlgorithmContext.gather_resident``), and returns the updated
  chunks.  The gather's result is a temporary, never the donated parameter
  buffer: a gather at the END of the step cost two whole-parameter copies
  (11 ms of bert-large's 107 ms step on four chips), and in front of the
  forward the compiler moves the cast the matrices are read through before
  it, so the wire carries bfloat16 (PERF.md §6, PR 57).  A chunk is **rows
  of the bucket**: the leading axis of a shaped bucket's tensor, a run of a
  1-D flat (``AlgorithmContext.update_sharded``) — never a ravel of a
  matrix, which on a TPU is a re-tiling copy (bucket.py).  The
  reduce-scatter carries ``comm_dtype`` where one is set; the all-gather is
  written in the parameters' own dtype, which is why a narrower wire still
  keeps the all-reduce: with a float32 gather and the copies the sharded
  form read 7.9 % slower there (bert-large on four v5e chips: 31,633
  against 34,328 tokens/s/chip, PERF.md §6, PR 49; §7 has the arithmetic
  with the bfloat16 gather).

Either way the collectives of the compiled step are *synchronous* on a TPU
today: the compiler combines the per-bucket calls into a few large ones and
none of them overlaps backward compute, so the exchange is exposed (PERF.md
§5 and §6, "the dp4 exchange, read off the chip").  The overlap the
reference's Rust scheduler + dedicated CUDA stream bought is NOT had for
free here; ROADMAP.md Queue 1 item 2 holds what was tried.  What the second
form saves is the update the exchange forces into the open — a pass over a
1/world of the state instead of all of it — and (world − 1)/world of the
memory of parameters and moments between steps (bert-large, four chips:
27,048 -> 28,755 tokens/s/chip, 10.31 -> 7.77 GB with the moments, PR 49;
PERF.md §5 has the parameters', PR 57).
"""

from __future__ import annotations

from typing import Optional

from ..communication import ReduceOp
from .base import Algorithm, AlgorithmContext


class GradientAllReduceAlgorithm(Algorithm):
    name = "gradient_allreduce"
    supports_overlap = True
    #: the per-bucket exchange consumes resident bucket buffers directly
    #: (zero repacking; shaped or 1-D alike); ``auto`` takes it on the
    #: word of a cpu-sim record, though all nine cells of the benchmark
    #: run this layout and none the leaf one (ROADMAP Queue 3 item 3)
    supports_flat_resident = True
    #: reduced buckets are replicated (plain psum/ring sum — a NaN/Inf
    #: contribution from any rank survives into every rank's copy), so the
    #: gradient-health sentinel rides them with no extra collective — except
    #: under the sharded update, where a rank holds its own chunk of the sum
    #: alone and the trainer reads the verdict off the updated chunks (one
    #: tiny ``pmin`` makes it the same on every rank)
    grad_health_replicated = True
    #: the per-bucket flat reduction can carry an error-feedback residual
    #: when the codec policy forces a stateful codec (onebit_ef / topk)
    #: onto its rings
    supports_ef_state = True
    #: the trainer may shard this family's update over the comm world
    #: (module docstring; ``hierarchical=True`` keeps the all-reduce)
    supports_sharded_update = True

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
        if ctx.update_sharded(index):
            # this rank's chunk of the reduced bucket: the same values the
            # all-reduce hands those rows, cast back from the wire dtype
            def reduce(f):
                return ctx.bucket_reduce_scatter(f, op)
        else:
            def reduce(f):
                return ctx.bucket_allreduce(f, op, self.hierarchical)
        if self.comm_dtype is None:
            return reduce(flat)
        return reduce(flat.astype(self.comm_dtype)).astype(flat.dtype)

    process_grads = Algorithm.process_grads_bucketed
