"""Algorithm base class — the pluggable "what/when to communicate" contract.

Counterpart of /root/reference/bagua/torch_api/algorithms/base.py:8-156.  The
reference's 7 hooks are driven by autograd events (grad-ready marks, post
backward, post optimizer step); under XLA the whole train step is one traced
program, so the hooks become *functional stages* of the step:

  reference hook                        bagua_tpu stage
  ------------------------------------  ----------------------------------
  init_tensors / tensors_to_buckets     init_tensors / tensors_to_buckets (same)
  init_forward_pre_hook (mark ready)    (implicit: XLA schedules collectives)
  init_backward_hook (per-grad mark)    process_grads (bucketed comm on grads)
  init_post_backward_hook (wait ops)    process_pre_step (weight comm lands here)
  init_post_optimizer_step_hook         process_post_step
  init_operations                       the body of the stages above
  need_reset                            need_reset (host-side, triggers rebuild)

All stages except ``need_reset``/``init_tensors``/``tensors_to_buckets`` are
traced inside ``shard_map`` over the data-parallel mesh axes and may call
collectives through ``ctx``.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

#: comm-axes tuples a dropped-codec warning was already logged for (the
#: warning fires at trace time, once per topology, not once per bucket)
_CODEC_DROP_WARNED: set = set()

#: (family, codec, reason) triples a stateless-EF-codec warning was already
#: logged for — an error-feedback codec riding the wire WITHOUT its residual
#: is a deliberate honesty control (BAGUA_EF_RESIDUAL=off) or an unsupported
#: family, and either way the run should say so exactly once
_EF_STATELESS_WARNED: set = set()

from ..bucket import BucketPlan
from ..obs.spans import phase_scope
from ..communication import BaguaCommunicator, ReduceOp
from ..define import TensorDeclaration
from ..tensor import NamedParam


def chunk_form(buf, n: int):
    """The form in which a bucket buffer is cut into ``n`` per-rank chunks:
    the buffer itself where its leading axis divides — rows of a shaped
    bucket's tensor, runs of a 1-D flat; no ravel, which on a TPU is a
    re-tiling copy of a matrix (bucket.py) — else its 1-D run (ZeRO alone
    cuts such a bucket: its plan pads every bucket's numel to ``n``).  A
    chunk of rows raveled IS the run chunk of the same rank, so the stages
    that take 1-D runs (the rings, the codecs) ravel a chunk at their point
    of use and lose nothing."""
    return buf if buf.shape[0] % n == 0 else buf.reshape(-1)


@dataclass
class AlgorithmContext:
    """Static per-compile context handed to traced algorithm stages."""

    comm: BaguaCommunicator              # spans all dp axes ("global")
    internode: Optional[BaguaCommunicator]
    intranode: Optional[BaguaCommunicator]
    plan: BucketPlan
    world_size: int
    #: overlap scheduler active for this compiled step (the trainer streams
    #: per-bucket collectives via :meth:`Algorithm.reduce_bucket_grad`)
    overlap: bool = False
    #: target per-rank bytes of one independent ring sub-collective; None
    #: keeps the fused psum/psum_scatter primitives (no chunking).  The
    #: link-agnostic fallback for the per-tier knobs below.
    overlap_chunk_bytes: Optional[int] = None
    #: per-tier chunk targets: the ICI tiers (slice-local ``intra`` axis,
    #: and the single-axis flat path) and the DCN tier (cross-slice
    #: ``inter`` axis) size their ring chunks against DIFFERENT bytes —
    #: a chunk that amortizes an ICI hop is far too small for a DCN hop.
    #: None falls back to :attr:`overlap_chunk_bytes`.
    intra_chunk_bytes: Optional[int] = None
    inter_chunk_bytes: Optional[int] = None
    #: flat-resident layout active: params/grads/opt state trees handed to
    #: the algorithm stages are ``{"flats": (...), "local": {...}}`` bucket
    #: containers, NOT leaf pytrees — reach their flat buffers through
    #: :meth:`bucket_flats` / :meth:`from_bucket_flats` so one stage
    #: implementation serves both layouts
    flat_resident: bool = False
    #: per-link-class codec policy (docs/compression.md): what the ring
    #: hops of each bandwidth tier carry on the wire.  Values are the
    #: ``BAGUA_COMPRESS_{INTRA,INTER}`` knob values — ``auto`` (default)
    #: defers to the algorithm family's own wire codec (ByteGrad/QAdam
    #: compress the DCN tier natively; everything else stays full
    #: precision), ``off`` FORCES full precision on the tier, and a codec
    #: name forces that codec for every family riding the tier.
    intra_codec: Optional[str] = None
    inter_codec: Optional[str] = None
    #: error-feedback residual machinery allowed on this mesh/trainer:
    #: the trainer clears it on meshes whose state layout cannot carry
    #: the per-bucket residual (expert/sharded axes, stacked families) and
    #: when ``BAGUA_EF_RESIDUAL=off`` (the stateless honesty control).
    #: :meth:`Algorithm.ef_codec` gates on it.
    ef_enabled: bool = False
    #: the exact family's exchange is all-gather of the resident chunks ->
    #: loss -> reduce-scatter -> update of the owned chunk
    #: (``BaguaTrainer._mark_sharded_update`` says where): parameters and
    #: optimizer state arrive as each bucket's owned chunk, and
    #: :meth:`update_sharded` says bucket by bucket whether it is taken
    sharded_update: bool = False

    def codec_for(self, link_class: str, family_default=None):
        """Resolve the wire codec for one link class: the tier's policy
        knob where it names a codec or forces ``off``, else the algorithm
        family's default (``None`` = full precision).  ``LINK_DCN``
        compressed / ``LINK_ICI`` full-precision is the default posture —
        only the compression families carry a DCN family default, and
        ``auto`` never compresses ICI."""
        from ..communication import LINK_DCN

        knob = (self.inter_codec if link_class == LINK_DCN
                else self.intra_codec)
        if knob in (None, "", "auto"):
            return family_default
        if knob == "off":
            return None
        return knob

    def flat_ring_codec(self, warn: bool = True):
        """The knob-resolved codec for the FLAT (whole-comm-world) ring —
        or None when this comm world cannot ride a ring (multiple mesh
        axes, or a single rank).  The ring is the only compressed carrier
        on the flat path, so a knob-forced codec there must either engage
        the ring or be LOUDLY dropped — and the byte accounting uses the
        same resolution, so it can never claim a wire reduction the
        collective did not deliver."""
        from ..communication import LINK_ICI

        codec = self.codec_for(LINK_ICI, None)
        if codec is None:
            return None
        if len(self.comm.axes) == 1 and self.comm.nranks() > 1:
            return codec
        if warn and self.comm.nranks() > 1 \
                and self.comm.axes not in _CODEC_DROP_WARNED:
            _CODEC_DROP_WARNED.add(self.comm.axes)
            logger.warning(
                "compress_intra=%r ignored: the flat comm world spans "
                "mesh axes %s and the compressed ring permutes over "
                "exactly one — this collective stays full precision "
                "(use hierarchical=True with compress_inter to compress "
                "the cross-slice tier)", codec, self.comm.axes,
            )
        return None

    def bucket_flats(self, tree) -> List:
        """The per-bucket flat gradient/param/state buffers of ``tree``
        under the active layout: the resident flats themselves (already
        bucket-flat — zero repacking), or the traced flatten of a leaf
        pytree.  The ONE accessor algorithm stages use, so the resident
        layout cannot silently re-pay the per-step flatten it removed."""
        if self.flat_resident:
            return list(tree["flats"])
        return self.plan.flatten_tree(tree)

    def from_bucket_flats(self, flats, like):
        """Inverse of :meth:`bucket_flats`: rebuild ``like``'s layout from
        per-bucket flat buffers — a no-copy container under the resident
        layout, the traced unflatten for leaf pytrees."""
        if self.flat_resident:
            return {"flats": tuple(flats), "local": like["local"]}
        return self.plan.unflatten_tree(flats, like)

    # ---- bandwidth tiers (hierarchical two-level decomposition) ----------
    #
    # A hierarchical (multi-slice) mesh has two link classes: the ``intra``
    # axis rides slice-local ICI, the ``inter`` axis rides cross-slice DCN
    # with orders of magnitude less bandwidth.  The reference's
    # Leader/Worker hierarchical communicator (communicators/mod.rs:243-336)
    # exists to keep the slow link's bytes minimal; the TPU rendering is a
    # true two-level decomposition
    #
    #     slice-local reduce-scatter  ->  cross-slice allreduce on the
    #     1/intra_size shard          ->  slice-local allgather
    #
    # so DCN carries ``1/intra_size`` of each bucket's bytes instead of the
    # full bucket the old nested-psum form moved.  Each stage is available
    # fused (psum_scatter/psum/all_gather) or as the chunked
    # double-buffered rings with PER-TIER chunk sizing.

    def two_tier(self) -> bool:
        """Whether the two-level decomposition is available: both tier
        communicators exist and together tile the comm world exactly (an
        extra comm axis — e.g. ``sp`` folded in for partial-grad summation
        — would be skipped by the tiered stages, so it forces the flat
        path; same guard as ZeRO's staged layout)."""
        return (
            self.internode is not None
            and self.intranode is not None
            and self.internode is not self.intranode
            and self.intranode.nranks() > 1
            and self.world_size
            == self.internode.nranks() * self.intranode.nranks()
        )

    def chunk_bytes_for(self, link_class: str) -> Optional[int]:
        """The ring chunk target for one link class: the per-tier knob
        where set, else the link-agnostic :attr:`overlap_chunk_bytes`."""
        from ..communication import LINK_DCN

        tier = (self.inter_chunk_bytes if link_class == LINK_DCN
                else self.intra_chunk_bytes)
        return tier if tier else self.overlap_chunk_bytes

    def _comm_chunks(self, comm: BaguaCommunicator, numel: int,
                     itemsize: int, link_class: str) -> int:
        """Sub-collective count for one tier's collective over ``comm``
        (1 = keep the fused XLA primitive).  The ONE gate for every bucket
        collective — flat and tiered — so the ring can never apply to one
        half of a scatter/gather pair and not the other."""
        from ..communication import ring_chunks_for

        target = self.chunk_bytes_for(link_class)
        if not target:
            return 1
        if len(comm.axes) != 1 or comm.nranks() <= 1:
            return 1  # ring permutes over exactly one mesh axis
        return ring_chunks_for(numel, itemsize, comm.nranks(), target,
                               link_class)

    def _ring_chunks(self, numel: int, itemsize: int) -> int:
        """Chunk gate for the FLAT (whole comm world) path."""
        from ..communication import LINK_ICI

        return self._comm_chunks(self.comm, numel, itemsize, LINK_ICI)

    # -- per-tier stage helpers (shared by allreduce/bytegrad/zero) --------

    def _reduce_scatter_over(self, comm: BaguaCommunicator, link_class: str,
                             buf, op: ReduceOp, codec):
        """``buf`` reduced over ``comm``; returns this rank's chunk, rank r
        owning the r-th chunk of :func:`chunk_form` (rows where the leading
        axis divides).  Fused ``psum_scatter`` over the leading axis unless
        the overlap scheduler set a ring chunk target for the link class or
        ``codec`` names a wire codec: the rings take the 1-D run, and the
        chunk has its rows back behind them."""
        x = chunk_form(buf, comm.nranks())
        k = self._comm_chunks(comm, x.size, x.dtype.itemsize, link_class)
        if codec is None and k == 1:
            return comm.reduce_scatter(x, op)
        chunk = comm.ring_reduce_scatter(x.reshape(-1), op, num_chunks=k,
                                         codec=codec)
        return chunk.reshape((-1,) + x.shape[1:])

    def _allgather_over(self, comm: BaguaCommunicator, link_class: str,
                        chunk, codec):
        """Inverse of :meth:`_reduce_scatter_over` (rank chunks in rank
        order along the leading axis) under the same chunk gate, sized on
        the whole buffer the chunk tiles, so the pair stays
        layout-symmetric."""
        k = self._comm_chunks(comm, chunk.size * comm.nranks(),
                              chunk.dtype.itemsize, link_class)
        if codec is None and k == 1:
            return comm.allgather(chunk, axis=0, tiled=True)
        full = comm.ring_allgather(chunk.reshape(-1), num_chunks=k,
                                   codec=codec)
        return full.reshape((-1,) + chunk.shape[1:])

    def tier_reduce_scatter(self, flat, op: ReduceOp, codec=None):
        """Slice-local (ICI) reduce-scatter of ``flat`` — this rank's
        1/intra chunk, ring-chunked against the ICI target.  The ICI codec
        policy resolves against ``codec`` as the family default (full
        precision unless the knob names a codec — ICI bytes are cheap)."""
        from ..communication import LINK_ICI

        return self._reduce_scatter_over(
            self.intranode, LINK_ICI, flat, op,
            self.codec_for(LINK_ICI, codec))

    def tier_allreduce(self, chunk, op: ReduceOp, codec=None):
        """Cross-slice (DCN) allreduce of this rank's shard, ring-chunked
        against the DCN target — the only stage whose bytes cross the slow
        link, and therefore the stage the codec policy compresses: with a
        resolved codec the shard rides the compressed ring (quantized
        ppermute hops, fp32 accumulation), so compressed bytes are what
        actually cross DCN."""
        from ..communication import LINK_DCN

        codec = self.codec_for(LINK_DCN, codec)
        k = self._comm_chunks(self.internode, chunk.size,
                              chunk.dtype.itemsize, LINK_DCN)
        if codec is None and k == 1:
            return self.internode.allreduce(chunk, op)
        return self.internode.ring_allreduce(
            chunk.reshape(-1), op, num_chunks=k, codec=codec
        ).reshape(chunk.shape)

    def tier_allgather(self, chunk, codec=None):
        """Slice-local (ICI) allgather of this rank's chunk back to the
        whole buffer — the inverse of :meth:`tier_reduce_scatter`."""
        from ..communication import LINK_ICI

        return self._allgather_over(self.intranode, LINK_ICI, chunk,
                                    self.codec_for(LINK_ICI, codec))

    def two_level_allreduce(self, flat, op: ReduceOp, dcn_codec=None):
        """The two-level hierarchical allreduce of one flat buffer:
        reduce-scatter over ``intra``, allreduce the 1/intra shard over
        ``inter``, allgather over ``intra``.  Buffers the intra world does
        not divide are zero-padded internally (sound for SUM/AVG) and
        sliced back.  AVG divides ONCE by the comm world after the summing
        stages — the same single division the flat ``pmean`` applies, so
        the only difference from the flat path is sum association order.
        ``dcn_codec`` is the family default for the DCN stage (the codec
        policy's ``auto`` resolution); with a codec the DCN ring's
        broadcast phase quantizes the UNDIVIDED inter-sum and the world
        division scales the dequantized fp32 afterwards — quantization is
        scale-invariant, so this equals dividing first."""
        assert op in (ReduceOp.SUM, ReduceOp.AVG), op
        n_intra = self.intranode.nranks()
        size = flat.shape[0]
        from ..communication import LINK_ICI

        ki = self._comm_chunks(self.intranode, size, flat.dtype.itemsize,
                               LINK_ICI)
        pad = (-size) % (n_intra * ki)
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)]
            )
        chunk = self.tier_reduce_scatter(flat, ReduceOp.SUM)
        chunk = self.tier_allreduce(chunk, ReduceOp.SUM, codec=dcn_codec)
        if op == ReduceOp.AVG:
            chunk = chunk / self.world_size
        full = self.tier_allgather(chunk)
        return full[:size] if pad else full

    def hierarchical_allreduce(self, flat, op: ReduceOp, hierarchical: bool,
                               dcn_codec=None):
        """Hierarchical = the two-level decomposition above (DCN carries the
        1/intra shard); non-hierarchical = one fused collective over the
        whole comm world.  Ops beyond SUM/AVG (and non-flat operands) keep
        the legacy nested form — correct, just not shard-reduced."""
        if not (hierarchical and self.two_tier()):
            return self.comm.allreduce(flat, op)
        if op not in (ReduceOp.SUM, ReduceOp.AVG) or jnp.ndim(flat) != 1:
            flat = self.intranode.allreduce(flat, op)
            return self.internode.allreduce(flat, op)
        return self.two_level_allreduce(flat, op, dcn_codec)

    def bucket_allreduce(self, flat, op: ReduceOp, hierarchical: bool,
                         dcn_codec=None):
        """One bucket's gradient allreduce under the active comm config:
        the two-level decomposition on hierarchical two-tier meshes
        (per-tier ring chunking when the overlap scheduler set targets,
        compressed DCN hops when the codec policy resolves one), the
        chunked double-buffered ring when a chunk size OR flat codec is
        set on a single-axis comm world, else the fused psum path.  The
        serialized non-hierarchical construction (``overlap=off``, codec
        knobs at default) always takes the fused psum path."""
        # the stages that cut a bucket into per-rank chunks take its 1-D
        # run: a shaped bucket (bucket.py) ravels here, at the point of use,
        # and has its shape back behind them; the fused psum takes the
        # tensor as it is (both reshapes are no-ops on a 1-D flat)
        def raveled(reduce):
            return reduce(flat.reshape(-1)).reshape(flat.shape)

        if hierarchical and self.two_tier():
            return raveled(lambda f: self.hierarchical_allreduce(
                f, op, True, dcn_codec))
        flat_codec = self.flat_ring_codec()
        k = self._ring_chunks(flat.size, flat.dtype.itemsize)
        if flat_codec is not None:
            # a forced flat codec rides the ring for hierarchical
            # families too: past the branch above the hierarchical flag
            # is inert (two_tier() failed — hierarchical_allreduce would
            # lower the same fused psum), and the byte accounting
            # resolves through the identical flat_ring_codec gate, so
            # honoring the knob here is what keeps the spans truthful
            return raveled(lambda f: self.comm.ring_allreduce(
                f, op, num_chunks=k, codec=flat_codec))
        if k > 1 and not hierarchical:
            return raveled(lambda f: self.comm.ring_allreduce(
                f, op, num_chunks=k))
        return self.hierarchical_allreduce(flat, op, hierarchical)

    # -- this rank's chunk of a bucket (ZeRO slices it out of a whole buffer;
    # under the exact family's sharded update it is what rests) -------------

    def owned_chunk(self, buf, comm: Optional[BaguaCommunicator] = None):
        """This rank's chunk of a bucket buffer over ``comm`` (default: the
        comm world): a slice of the leading axis of :func:`chunk_form` —
        the chunk :meth:`bucket_reduce_scatter` hands this rank and
        :meth:`bucket_allgather` puts back."""
        comm = self.comm if comm is None else comm
        x = chunk_form(buf, comm.nranks())
        size = x.shape[0] // comm.nranks()
        return jax.lax.dynamic_slice_in_dim(x, comm.rank() * size, size)

    def bucket_reduce_scatter(self, flat, op: ReduceOp):
        """One bucket's reduce-scatter over the comm world under the active
        comm config (ZeRO's grad half, and the exact family's under the
        sharded update); the chunk layout is identical between the ring and
        ``psum_scatter`` paths.  A knob-forced flat codec compresses these
        rings too — every family riding the flat tier honors the forced
        policy, so the byte accounting's claim stays true for the
        scatter/gather dance."""
        from ..communication import LINK_ICI

        return self._reduce_scatter_over(self.comm, LINK_ICI, flat, op,
                                         self.flat_ring_codec())

    def bucket_allgather(self, chunk):
        """Re-replication half of the dance (this rank's chunk -> the whole
        buffer), the inverse of :meth:`bucket_reduce_scatter`."""
        from ..communication import LINK_ICI

        return self._allgather_over(self.comm, LINK_ICI, chunk,
                                    self.flat_ring_codec())

    def update_sharded(self, index: int) -> bool:
        """Whether bucket ``index``'s update is taken by the rank that owns
        its chunk (:attr:`sharded_update`): every bucket whose leading axis
        the comm world divides — a shaped bucket by rows, a 1-D flat by
        runs.  A bucket that does not divide keeps the all-reduce and a
        replicated update: no plan is padded to the world for it, so that
        the exact family's plans, and its checkpoints, stay the same at
        every world size (an elastic resume restores them directly)."""
        return (self.sharded_update
                and self.plan.buckets[index].buffer_shape[0]
                % self.comm.nranks() == 0)

    def gather_resident(self, params):
        """The whole parameter buffers of a flat-resident container whose
        buckets rest as they are updated: a bucket :meth:`update_sharded`
        takes is this rank's chunk of it (the reduce-scatter's chunk; the
        moments rest so too) and is gathered, every other passes through.
        The trainer calls it once at the top of a step, before anything
        reads whole parameters — the gather's result is a temporary, never
        the next step's resident buffer, so the compiler copies no parameter
        around it (PERF.md §6, PR 57) — and the evaluation step likewise."""
        flats = list(params["flats"])
        for i in range(len(flats)):
            if self.update_sharded(i):
                with phase_scope(f"bagua.comm/bucket_{i}"):
                    flats[i] = self.bucket_allgather(flats[i])
        return {"flats": tuple(flats), "local": params["local"]}

    # -- bandwidth-tier-aware launch schedule ------------------------------

    def _wire_bytes(self, numel: int, itemsize: int, codec_name) -> int:
        """Host-side wire bytes of one ``numel``-element operand under a
        resolved codec name (None = full precision)."""
        if codec_name is None:
            return int(numel) * int(itemsize)
        from ..compression.codecs import get_codec

        return get_codec(codec_name).wire_bytes(int(numel))

    def bucket_tier_bytes(self, index: int, hierarchical: bool = True,
                          dcn_codec=None, flat_codec=None) -> dict:
        """Host-side per-tier bytes-on-wire estimate for one bucket's
        gradient collective under the ACTIVE config (ring model: a tier's
        allreduce moves ``2(n-1)/n`` of its operand, a scatter/gather half
        moves ``(n-1)/n``).  ``dcn_bytes`` is what crosses the slow link —
        the number the two-level decomposition exists to shrink, and the
        key the tier-aware overlap scheduler orders launches by.  On a
        tier-less mesh there is no slow link at all — ``dcn_bytes`` is 0.
        On a two-tier mesh with ``hierarchical=False``, ``dcn_bytes``
        reports the slow-link bytes the flat collective DOES pay there
        (its full operand crosses the slice boundary) — the comparison
        number the two-level decomposition is judged against.

        ``dcn_codec``/``flat_codec`` are the algorithm family's wire-codec
        defaults (``Algorithm.wire_codec_dcn``/``wire_codec_flat``); the
        tier knobs override them through :meth:`codec_for`, and the
        estimate then reports COMPRESSED wire bytes — so the DCN-first
        launch order and the codecs' byte accounting describe what
        actually crosses the wire, not the fp32 operand the codec
        replaced."""
        import numpy as np

        b = self.plan.buckets[index]
        from ..communication import LINK_DCN, LINK_ICI

        itemsize = int(np.dtype(b.dtype).itemsize)
        numel = int(b.padded_numel)
        nbytes = numel * itemsize
        # the flat wire codec resolved exactly as the COLLECTIVES resolve
        # it — the accounting must never report compressed bytes the wire
        # did not carry.  A scatter-gather family (flat_codec set)
        # compresses on any comm world with its own pipeline unless the
        # knob forces `off` (a forced codec NAME keeps the family's
        # minmax pipeline — one wire format there); an exact family
        # compresses only when the knob names a codec AND the flat ring
        # can carry it (flat_ring_codec's validity gate).
        if flat_codec is not None:
            resolved_flat = (
                flat_codec
                if self.codec_for(LINK_ICI, flat_codec) is not None
                else None
            )
        else:
            resolved_flat = self.flat_ring_codec(warn=False)
        if not self.two_tier():
            wire = self._wire_bytes(numel, itemsize, resolved_flat)
            return {"tier": "flat", "bytes": nbytes,
                    "ici_bytes": wire, "dcn_bytes": 0,
                    "dcn_codec": None,
                    "flat_codec": resolved_flat}
        if not hierarchical:
            ne = self.internode.nranks()
            wire = self._wire_bytes(numel, itemsize, resolved_flat)
            return {"tier": "flat", "bytes": nbytes,
                    "ici_bytes": wire,
                    "dcn_bytes": int(2 * wire * (ne - 1) // ne),
                    "dcn_codec": resolved_flat,
                    "flat_codec": resolved_flat}
        ni = self.intranode.nranks()
        ne = self.internode.nranks()
        resolved_dcn = self.codec_for(LINK_DCN, dcn_codec)
        # the intra tier is single-axis with >1 ranks by two_tier(), so a
        # knob-forced ICI codec always engages its rings
        ici_codec = self.codec_for(LINK_ICI, None)
        ici_wire = self._wire_bytes(numel, itemsize, ici_codec)
        # full precision keeps the byte-granularity shard estimate the
        # launch-order pin certifies; a codec's payload is per-ELEMENT, so
        # its estimate rides the element-granularity shard
        dcn_wire = (
            -(-numel * itemsize // ni) if resolved_dcn is None
            else self._wire_bytes(-(-numel // ni), itemsize, resolved_dcn)
        )
        return {
            "tier": "two_level",
            "bytes": nbytes,
            # rs + ag halves over intra: 2 * (ni-1)/ni of the flat
            "ici_bytes": int(2 * ici_wire * (ni - 1) // ni),
            # the inter allreduce moves 2(ne-1)/ne of the 1/ni shard —
            # compressed where the codec policy resolves one
            "dcn_bytes": int(2 * dcn_wire * (ne - 1) // ne) if ne > 1 else 0,
            "dcn_codec": resolved_dcn if ne > 1 else None,
            "flat_codec": None,
        }

    def bucket_launch_order(self, hierarchical: bool,
                            dcn_codec=None) -> List[int]:
        """Launch order for the overlap scheduler's per-bucket collectives.
        On a two-tier mesh with the hierarchical path active, buckets whose
        DCN stage dominates are streamed FIRST (descending cross-slice
        bytes — COMPRESSED wire bytes where a codec rides the tier, stable)
        so the slow link is busy for the whole backward window; everywhere
        else the plan's (readiness) order stands.  Results are still
        assembled in plan order — only the traced issue order changes, so
        overlap-vs-serialized numerics are untouched."""
        n = len(self.plan.buckets)
        if not (self.overlap and hierarchical and self.two_tier()):
            return list(range(n))
        dcn = [self.bucket_tier_bytes(i, hierarchical,
                                      dcn_codec=dcn_codec)["dcn_bytes"]
               for i in range(n)]
        return sorted(range(n), key=lambda i: -dcn[i])


class Algorithm:
    """Base algorithm: plain distributed data parallelism hooks.

    Subclasses override stages; the default implementation is a no-op pass
    (gradients unchanged), matching the reference's ``Algorithm`` which only
    wires default bucketing/marking (base.py:24-125).
    """

    #: False for gossip-style algorithms whose weights differ across ranks;
    #: the trainer then keeps params/opt/algo state stacked per rank.
    replicated_params: bool = True
    #: True when the algorithm provides its own optimizer update (QAdam).
    owns_optimizer: bool = False
    #: True when the optimizer state is sharded over the comm axes (ZeRO-1):
    #: params stay replicated but opt_state is built per rank inside
    #: shard_map via ``init_optimizer_state_sharded(ctx, params)``.
    sharded_opt_state: bool = False
    #: Alignment for bucket padding (compressed ops need world_size).
    bucket_alignment: int = 1
    #: Hierarchical (intra-node then inter-node) communication.
    hierarchical: bool = False
    #: Overlap contract: when True the trainer's overlap scheduler may call
    #: :meth:`reduce_bucket_grad` once per bucket — in gradient-readiness
    #: order, as each bucket's accumulated gradient finalizes — instead of
    #: the whole-tree :meth:`process_grads`, then hand the per-bucket
    #: results to :meth:`grads_from_reduced`.  Families whose gradient comm
    #: is not a per-bucket map (gossip weight exchanges, QAdam's momentum
    #: pipeline) keep False and always run serialized.
    supports_overlap: bool = False
    #: Whether ``overlap="auto"`` may pick the overlap path for this family
    #: (explicit ``overlap="on"`` always wins).  Set from a cpu-sim
    #: record (8-device mesh, toy widths; deleted in PR 46), never
    #: measured on the chip: ROADMAP Queue 3 item 3.
    overlap_auto: bool = True
    #: Flat-resident contract: when True the trainer may keep params /
    #: grads / optimizer state as bucket-flat buffers across steps
    #: (``{"flats", "local"}`` containers) and every traced stage must go
    #: through :meth:`AlgorithmContext.bucket_flats` /
    #: :meth:`AlgorithmContext.from_bucket_flats` instead of touching leaf
    #: pytrees.  Families whose stages inspect leaf shapes stay False and
    #: always run the leaf layout.
    supports_flat_resident: bool = False
    #: Whether ``flat_resident="auto"`` may pick the resident layout for
    #: this family (explicit ``flat_resident="on"`` always wins) — set
    #: from a cpu-sim record like :attr:`overlap_auto` (Queue 3 item 3).
    flat_resident_auto: bool = True
    #: Straggler coupling: True when every train step synchronizes with
    #: every rank (a per-step gradient collective), so a slow peer gates
    #: the step — the ``step.straggle`` fault point then dilates each step.
    #: Asynchronous families whose steps run on stale local weights set
    #: False: a straggler binds them only at their own negotiated
    #: boundaries (they call :func:`bagua_tpu.faults.inject.maybe_straggle`
    #: there themselves).
    straggler_gates_step: bool = True
    #: Wire-codec defaults for the byte accounting AND the codec policy's
    #: ``auto`` resolution (docs/compression.md): ``wire_codec_dcn`` names
    #: the codec the family's hierarchical path rides on the cross-slice
    #: DCN stage (ByteGrad/QAdam compress it natively), ``wire_codec_flat``
    #: the codec its non-hierarchical bucket collective carries (ByteGrad's
    #: compressed scatter-gather).  None = full precision.
    wire_codec_dcn: Optional[str] = None
    wire_codec_flat: Optional[str] = None
    #: Error-feedback state contract: True when the family's gradient comm
    #: is the per-bucket flat reduction of :meth:`process_grads_bucketed` /
    #: :meth:`reduce_bucket_grad`, so a per-bucket fp32 residual flat can
    #: ride ``algo_state`` and :meth:`compensate_flats` can fold it into the
    #: buckets before they hit the wire.  Families whose comm is not a
    #: bucket map (gossip exchanges, QAdam's momentum pipeline, ZeRO's
    #: scatter/gather ownership) keep False — an error-feedback codec forced
    #: onto them rides STATELESS with a loud once-per-run warning.
    supports_ef_state: bool = False
    #: Gradient-health sentinel contract: True when the family's POST-comm
    #: gradient representation is bitwise-identical on every rank (a plain
    #: summed/averaged bucket reduce), so the per-bucket ``isfinite``
    #: verdict computed on it is already globally consistent — the guard
    #: then piggybacks on the existing bucket collectives with no extra
    #: launch (non-finite contributions survive the sum).  Families whose
    #: gradients stay rank-local or sharded after comm (gossip exchanges,
    #: ZeRO chunks, QAdam's compressed-momentum pipeline) keep False and
    #: the trainer fuses their local verdicts with one tiny ``pmin``.
    grad_health_replicated: bool = False
    #: Sharded-update contract: True when the family's gradient comm is an
    #: exact per-bucket sum or average that :meth:`reduce_bucket_grad` can
    #: hand back as this rank's chunk alone
    #: (:meth:`AlgorithmContext.update_sharded`), so that the trainer's
    #: optimizer steps the owned chunk, which is what rests between steps
    #: (:meth:`AlgorithmContext.gather_resident`).  Such a family leaves the
    #: weights alone in :meth:`process_pre_step` / :meth:`process_post_step`:
    #: the second is handed the updated chunks, not whole buffers.
    supports_sharded_update: bool = False

    def need_reset(self, step: int) -> bool:
        """Host-side: return True to rebuild buckets/recompile (reference
        base.py:15-22, used by QAdam's warmup boundary)."""
        return False

    def compile_key(self) -> tuple:
        """Host-side state that changes the TRACED program (beyond the
        phase counter).  Part of the trainer's compiled-step cache key —
        without it, flipping such state (e.g. QAdam's ``_compressed`` after
        an autotune switch re-anchors its warmup) would silently reuse a
        stale compile."""
        return ()

    def init_tensors(self, named_params: Sequence[NamedParam]) -> List[NamedParam]:
        """Which tensors to communicate, in registration order (reference
        base.py:24-49 registers grads in reversed module order — the caller
        already passes reversed order)."""
        return list(named_params)

    def tensors_to_buckets(
        self,
        decl_buckets: Sequence[Sequence[TensorDeclaration]],
        named_params: Sequence[NamedParam],
        world_size: int,
    ) -> BucketPlan:
        """Declarations -> concrete plan (reference base.py:51-70)."""
        return BucketPlan.from_declaration_buckets(
            decl_buckets, named_params, alignment=self.bucket_alignment
        )

    # ---- traced stages --------------------------------------------------

    def init_state(self, ctx: AlgorithmContext, params) -> Any:
        """Create algorithm state (peer-weight replicas, momenta, ...).
        The base state is the error-feedback residual container when an EF
        codec is active under this context, else None."""
        return self.ef_init_state(ctx, None)

    # ---- error-feedback residual (stateful codecs) -----------------------
    #
    # The 1-bit and top-k codecs are BIASED quantizers: their per-step error
    # does not average out, so SGD on their raw output diverges.  Error
    # feedback (EF-SignSGD, arXiv:1901.09847; 1-bit Adam, arXiv:2102.02888)
    # restores convergence by carrying the quantization error forward: each
    # step compresses ``grad + residual`` and keeps the part the wire lost.
    # The residual lives in ``algo_state["ef"]["buckets"]`` as one fp32 buffer
    # per bucket ([1, *buffer_shape] per shard, stacked [world, *buffer_shape]
    # globally: ``[1, padded_numel]`` for a 1-D bucket) so it rides the
    # existing state machinery: grad-guard skips rewind it with the step,
    # rebuckets migrate it through
    # ``relayout_flats``, and checkpoints carry it with a layout sidecar.
    #
    # One local encode/decode roundtrip per bucket models the wire error.
    # The ring's per-hop re-quantization of PARTIAL sums is not captured —
    # the residual compensates the dominant (input quantization) error term,
    # which is the published algorithms' formulation too; the hop error
    # shrinks with chunk count and accumulates in fp32.

    def ef_codec(self, ctx: AlgorithmContext):
        """The error-feedback codec whose residual this family accumulates
        under the ACTIVE config, or None.  Resolution mirrors what the
        wire actually carries: the DCN then ICI tier codecs on the
        hierarchical two-tier path, the flat ring codec otherwise (skipped
        for scatter-gather families with their own flat pipeline — a
        forced codec NAME never engages there, so neither may EF).  An EF
        codec that resolves on an unsupported family, or with the residual
        disabled (``BAGUA_EF_RESIDUAL=off`` — the honesty control), rides
        STATELESS with a once-per-run warning."""
        from ..communication import LINK_DCN, LINK_ICI
        from ..compression.codecs import get_codec

        names: List = []
        if getattr(self, "hierarchical", False) and ctx.two_tier():
            names.append(ctx.codec_for(LINK_DCN, self.wire_codec_dcn))
            names.append(ctx.codec_for(LINK_ICI, None))
        elif self.wire_codec_flat is None:
            names.append(ctx.flat_ring_codec(warn=False))
        codec = None
        for name in names:
            if name is None:
                continue
            c = get_codec(name)
            if getattr(c, "error_feedback", False):
                codec = c
                break
        if codec is None:
            return None
        if self.supports_ef_state and ctx.ef_enabled:
            return codec
        reason = ("unsupported_family" if not self.supports_ef_state
                  else "residual_disabled")
        key = (type(self).__name__, codec.name, reason)
        if key not in _EF_STATELESS_WARNED:
            _EF_STATELESS_WARNED.add(key)
            logger.warning(
                "codec %r is an error-feedback codec but its residual is "
                "OFF (%s) for %s: the wire carries raw %s output, whose "
                "quantization bias is known to stall/diverge SGD — only "
                "use this as a convergence control",
                codec.name, reason, type(self).__name__, codec.name,
            )
        return None

    def ef_init_state(self, ctx: AlgorithmContext, state: Any) -> Any:
        """Merge the error-feedback residual container into ``state``
        (traced, per shard): one zero fp32 buffer per bucket — this shard's
        ``[1, *buffer_shape]`` row of the stacked ``[world, *buffer_shape]``
        global.  Identity when no EF codec is active, so families that
        build their own state just wrap it through here."""
        if self.ef_codec(ctx) is None:
            return state
        ef = {"buckets": tuple(
            jnp.zeros((1,) + b.buffer_shape, jnp.float32)
            for b in ctx.plan.buckets
        )}
        if state is None:
            return {"ef": ef}
        assert isinstance(state, dict) and "ef" not in state, state
        return {**state, "ef": ef}

    def algo_state_specs(self, ctx: AlgorithmContext, default, stacked):
        """shard_map partition specs (pytree prefixes) for this family's
        algo state: ``default`` is the trainer's replicated spec,
        ``stacked`` its per-rank stacked-leading-axis spec — which is what
        the EF residual's ``[world, *buffer_shape]`` buckets ride."""
        if self.ef_codec(ctx) is None:
            return default
        return {"ef": stacked}

    def compensate_flats(self, ctx: AlgorithmContext, flats, algo_state):
        """Fold the per-bucket error-feedback residual into the bucket
        flats about to hit the wire and accumulate the new quantization
        error: ``c = grad + r``; the wire carries ``encode(c)``; ``r' =
        c - decode(encode(c))``.  Identity (no traced ops at all) when no
        EF codec is active — the compiled step with compression off is
        byte-identical to one without this hook."""
        codec = self.ef_codec(ctx)
        if codec is None:
            return flats, algo_state
        ef = algo_state.get("ef") if isinstance(algo_state, dict) else None
        if ef is None:
            # state predates the codec flip; the trainer's knob-sync
            # migration adds the container before the next compiled step
            return flats, algo_state
        out, residuals = [], []
        for flat, res in zip(flats, ef["buckets"]):
            c = flat.astype(jnp.float32) + res[0]
            # the codec quantizes a 1-D run (a shaped bucket ravels here)
            c1 = c.reshape(-1)
            dec = codec.decode(codec.encode(c1[None, :]), c1.shape[0])[0]
            residuals.append((c - dec.reshape(c.shape))[None])
            out.append(c.astype(flat.dtype))
        new_state = dict(algo_state)
        new_state["ef"] = {"buckets": tuple(residuals)}
        return out, new_state

    def process_grads(self, ctx: AlgorithmContext, grads, params, algo_state, step):
        """Gradient communication stage (runs where the reference's backward
        hooks + wait_pending_comm_ops ran)."""
        return grads, algo_state

    # ---- overlap scheduler stages (supports_overlap families) -----------

    def reduce_bucket_grad(self, ctx: AlgorithmContext, index: int, flat):
        """Communicate ONE bucket's final flat gradient (traced).  The
        trainer's overlap scheduler calls this per bucket so each
        collective's operands are exactly that bucket's finalized gradient —
        open dataflow XLA's latency-hiding scheduler can overlap with the
        backward compute still producing later buckets.  Returns the
        communicated buffer: the full reduced flat for dense families, this
        rank's owned chunk for sharded-opt-state families."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the overlap contract"
        )

    def grads_from_reduced(self, ctx: AlgorithmContext, reduced, grads,
                           algo_state, step):
        """Assemble the post-communication gradient representation from the
        per-bucket :meth:`reduce_bucket_grad` results (the overlap path's
        replacement for :meth:`process_grads`).  Default: rebuild the
        gradient layout from the reduced buckets — the resident flat
        container under flat residency, the leaf unflatten otherwise."""
        return ctx.from_bucket_flats(reduced, grads), algo_state

    def process_grads_bucketed(self, ctx: AlgorithmContext, grads, params,
                               algo_state, step):
        """The serialized comm stage for ``supports_overlap`` families:
        the same per-bucket reduction the overlap scheduler streams, issued
        after the full backward — one implementation, so the two paths
        cannot drift numerically.  Dense families alias ``process_grads``
        to this.  Under the flat-resident layout the grads already ARE the
        bucket flats, so this stage communicates them with zero repacking.
        Launch order rides :meth:`AlgorithmContext.bucket_launch_order`
        (DCN-dominant buckets first on hierarchical two-tier meshes under
        the overlap scheduler); results assemble in plan order."""
        flats = ctx.bucket_flats(grads)
        with phase_scope("bagua.layout"):
            flats, algo_state = self.compensate_flats(ctx, flats, algo_state)
        order = ctx.bucket_launch_order(getattr(self, "hierarchical", False),
                                        dcn_codec=self.wire_codec_dcn)
        reduced: List = [None] * len(flats)
        for i in order:
            # a child of the trainer's bagua.comm scope: which declared
            # bucket a compiled collective came from (XLA's combiner keeps
            # ONE constituent's name on a combined collective)
            with phase_scope(f"bucket_{i}"):
                reduced[i] = self.reduce_bucket_grad(ctx, i, flats[i])
        with phase_scope("bagua.layout"):
            return self.grads_from_reduced(ctx, reduced, grads, algo_state,
                                           step)

    # ---- flat-resident layout hooks (supports_flat_resident families) ----

    def relayout_algo_state(self, old_plan, new_plan, algo_state):
        """Migrate plan-keyed algorithm state when the trainer re-buckets
        resident flat state (autotune / overlap-readiness re-bucketing,
        cross-plan checkpoint restore).  Families whose state holds flat
        bucket buffers (gossip peer replicas) override with a
        :func:`bagua_tpu.bucket.relayout_flats` pass; param-shaped or empty
        state needs no migration."""
        if algo_state is None:
            return None
        if isinstance(algo_state, dict) and set(algo_state) == {"ef"}:
            from ..bucket import relayout_flats

            flats = relayout_flats(old_plan, new_plan,
                                   list(algo_state["ef"]["buckets"]))
            # the residual is fp32 regardless of the bucket dtype the
            # relayout cast its segments through (exact for fp32 plans;
            # sub-fp32 plans round the carried error once per rebucket)
            return {"ef": {"buckets": tuple(
                f.astype(jnp.float32) for f in flats
            )}}
        raise NotImplementedError(
            f"{type(self).__name__} carries algorithm state but does not "
            "implement relayout_algo_state; re-bucketing its flat-resident "
            "state would corrupt plan-keyed buffers"
        )

    def process_pre_step(self, ctx: AlgorithmContext, params, algo_state, step):
        """Weight transformation after backward, before the optimizer update
        (the reference's post-backward copy-back for decentralized ops)."""
        return params, algo_state

    def process_post_step(self, ctx: AlgorithmContext, params, algo_state, step):
        """Weight transformation after the optimizer update (the reference's
        post-optimizer-step hook, used by low-precision decentralized)."""
        return params, algo_state

    def optimizer_update(self, ctx, params, grads, opt_state, algo_state, step):
        raise NotImplementedError("only algorithms with owns_optimizer=True")

    def init_optimizer_state(self, params):
        raise NotImplementedError("only algorithms with owns_optimizer=True")

    # ---- host-side hook --------------------------------------------------

    def host_pre_step(self, trainer, state):
        """Host-side (untraced) hook run at the top of every
        ``BaguaTrainer.train_step`` — the between-steps boundary where
        asynchronous algorithms swap weights (reference async
        init_forward_pre_hook's lock, async_model_average.py:156-168)."""
        return state

    def on_restore(self, trainer) -> None:
        """Host-side hook run after ``BaguaTrainer.restore_checkpoint``
        materialized a state for this trainer (elastic restarts included).
        Algorithms carrying host-side schedule state tied to the PREVIOUS
        run (async model averaging's in-flight round, launch anchor, agreed
        period) reset it here so the resumed run starts from a clean
        window instead of consuming stale cross-resize state."""
        return None
