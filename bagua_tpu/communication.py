"""Communicators and collective primitives — the TPU-native comm backend.

Replaces the reference's whole native comm stack: NCCL unique-id rendezvous +
``BaguaSingleCommunicator`` / ``BaguaHierarchicalCommunicator`` (Rust + Aluminum,
/root/reference/rust/bagua-core/bagua-core-internal/src/communicators/mod.rs)
and the 22 Python collective wrappers
(/root/reference/bagua/torch_api/communication.py:230-852).

Design: a :class:`BaguaCommunicator` names one or more mesh axes.  Its methods
come in one flavor only — *traced* — and must run inside ``shard_map`` over the
mesh; they lower straight to XLA collectives (``psum``/``all_gather``/
``all_to_all``/``ppermute``) that ride ICI.  The module-level functions
(:func:`allreduce`, :func:`allgather`, ...) are the eager, user-facing
primitives with reference semantics: input carries a leading *rank* axis and
the collective runs across it on the global mesh.  There is no NCCL-id
rendezvous: device bring-up is ``jax.distributed.initialize`` + mesh building
(:func:`init_process_group`).
"""

from __future__ import annotations

import logging
import os
import threading
from enum import IntEnum
from functools import lru_cache, partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from . import env
from .parallel.mesh import build_mesh, get_global_mesh, hierarchical_mesh, mesh_axis_size, set_global_mesh

logger = logging.getLogger(__name__)


# Numbering matches the reference (communication.py:25-36), which itself must
# match Aluminum's ReductionOperator — kept for wire/API compatibility.
class ReduceOp(IntEnum):
    """Available reduction operations: ``SUM``, ``PRODUCT``, ``MIN``, ``MAX``,
    ``BAND``, ``BOR``, ``BXOR`` and ``AVG``."""

    SUM = 0
    PRODUCT = 1
    MIN = 2
    MAX = 3
    BOR = 7
    BAND = 8
    BXOR = 9
    AVG = 10


def _tree_map(f, tree):
    return jax.tree.map(f, tree)


# ---- abort flag (reference communicators/mod.rs:74-80, 456-471) -----------
#
# The reference exposes ``abort()``/``check_abort`` so a wedged collective
# can be cancelled cooperatively and tested
# (tests/comm/test_communicator.py:40-60).  XLA cannot cancel a compiled
# program mid-flight, so the TPU rendering is a process-wide flag: new work
# fails fast (the trainer checks it before every dispatch), background
# control loops (async model average) stop launching rounds, and the
# watchdog raises it before terminating a wedged process so cooperating
# threads wind down first.

_ABORT_EVENT = threading.Event()
_ABORT_REASON: Optional[str] = None


class BaguaAborted(RuntimeError):
    """Raised by :func:`check_abort` after :func:`abort` was called."""


def abort(reason: str = "user abort") -> None:
    """Flag every communicator as aborted; in-flight XLA programs finish
    (they cannot be cancelled) but no new communication is dispatched."""
    global _ABORT_REASON
    # lock-free by design: the Event is the sync point (reason is written
    # before set(), so a reader that saw the event sees the reason), the
    # store is a single GIL-atomic ref assignment, and check_abort
    # tolerates a torn read with its `or "aborted"` fallback
    _ABORT_REASON = reason  # bagua: lint-ignore[unguarded-shared-write] -- Event-published; GIL-atomic store; stale read falls back to "aborted"
    _ABORT_EVENT.set()
    from .telemetry import counters

    counters.incr("comm/aborts")
    logger.error("bagua_tpu: communication aborted: %s", reason)


def is_aborted() -> bool:
    return _ABORT_EVENT.is_set()


def check_abort() -> None:
    """Raise :class:`BaguaAborted` if :func:`abort` has been called
    (reference ``check_abort``, communicators/mod.rs:74-80)."""
    if _ABORT_EVENT.is_set():
        raise BaguaAborted(_ABORT_REASON or "aborted")


def reset_abort() -> None:
    """Clear the abort flag (recovery path after the cause was handled —
    the reference re-creates communicators after an abort)."""
    global _ABORT_REASON
    was_aborted = _ABORT_EVENT.is_set()
    _ABORT_REASON = None
    _ABORT_EVENT.clear()
    if was_aborted:
        from .faults import inject as _inject
        from .telemetry import counters

        counters.incr("comm/abort_resets")
        # an injected collective hang that reached abort and was then
        # reset is a completed recovery (chaos-drill accounting)
        _inject.record_recovery("collective.hang")


def collapse_trivial_axes(mesh: Mesh, axes) -> Tuple[str, ...]:
    """Drop size-1 axes (keeping at least one) so single-axis collectives
    (alltoall/ppermute) work whenever the topology is effectively 1-D."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    nontrivial = tuple(a for a in axes if mesh.shape[a] > 1)
    return nontrivial if nontrivial else axes[-1:]


class BaguaCommunicator:
    """A communicator spanning one or more mesh axes.

    Counterpart of ``BaguaSingleCommunicator`` (communicators/mod.rs:20-60);
    hierarchical execution is expressed by holding *two* of these (one over
    ``intra``, one over ``inter``) instead of Leader/Worker role objects.

    All methods must be called inside ``shard_map`` over a mesh containing
    ``axes``.
    """

    def __init__(self, axes, mesh: Optional[Mesh] = None):
        self.axes: Tuple[str, ...] = (axes,) if isinstance(axes, str) else tuple(axes)
        self._mesh = mesh

    @property
    def mesh(self) -> Mesh:
        return self._mesh if self._mesh is not None else get_global_mesh()

    @property
    def axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def nranks(self) -> int:
        return mesh_axis_size(self.mesh, self.axes)

    # -- traced ops (inside shard_map) ------------------------------------

    def rank(self):
        return lax.axis_index(self.axes)

    def allreduce(self, x, op: ReduceOp = ReduceOp.AVG):
        ax = self.axes
        if not ax:
            # zero-axis communicator (e.g. a tp-only mesh has no data axes):
            # every reduction is an identity over a single member
            return x
        if op == ReduceOp.SUM:
            return lax.psum(x, ax)
        if op == ReduceOp.AVG:
            return lax.pmean(x, ax)
        if op == ReduceOp.MAX:
            return lax.pmax(x, ax)
        if op == ReduceOp.MIN:
            return lax.pmin(x, ax)
        # rare ops: gather then reduce locally (still a single XLA all-gather)
        gathered = lax.all_gather(x, ax, axis=0)  # [nranks, ...]
        if op == ReduceOp.PRODUCT:
            return jnp.prod(gathered, axis=0)
        if op == ReduceOp.BOR:
            return jax.lax.reduce(gathered, jnp.zeros((), gathered.dtype), lax.bitwise_or, (0,))
        if op == ReduceOp.BAND:
            return jax.lax.reduce(gathered, ~jnp.zeros((), gathered.dtype), lax.bitwise_and, (0,))
        if op == ReduceOp.BXOR:
            return jax.lax.reduce(gathered, jnp.zeros((), gathered.dtype), lax.bitwise_xor, (0,))
        raise ValueError(f"unsupported ReduceOp {op}")

    def allgather(self, x, axis: int = 0, tiled: bool = True):
        return lax.all_gather(x, self.axes, axis=axis, tiled=tiled)

    def reduce_scatter(self, x, op: ReduceOp = ReduceOp.SUM, axis: int = 0):
        if op == ReduceOp.AVG:
            return lax.psum_scatter(x, self.axes, scatter_dimension=axis, tiled=True) / self.nranks()
        if op == ReduceOp.SUM:
            return lax.psum_scatter(x, self.axes, scatter_dimension=axis, tiled=True)
        raise ValueError(f"reduce_scatter supports SUM/AVG, got {op}")

    def alltoall(self, x, split_axis: int = 0, concat_axis: int = 0):
        # multiple axes are treated as one flattened axis (XLA supports
        # axis-name sequences), e.g. the ('dp','pp') bucket communicator
        ax = self.axes[0] if len(self.axes) == 1 else tuple(self.axes)
        return lax.all_to_all(x, ax, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=False)

    def alltoall_tiled(self, x, split_axis: int = 0, concat_axis: int = 0):
        ax = self.axes[0] if len(self.axes) == 1 else tuple(self.axes)
        return lax.all_to_all(x, ax, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    def alltoall_v(
        self, x, output, input_offsets, send_sizes, output_offsets, recv_sizes
    ):
        """Ragged all-to-all (reference ``alltoall_v``,
        communicators/mod.rs:632-676): rank r sends
        ``x[input_offsets[i] : input_offsets[i]+send_sizes[i]]`` to each rank
        i, which lands at ``output_offsets`` in that rank's ``output`` buffer
        (which supplies capacity, dtype, and the values of untouched slots).
        Lowers to XLA's native ragged-all-to-all over ICI.
        """
        if len(self.axes) != 1:
            raise ValueError("alltoall_v needs a single mesh axis")
        return lax.ragged_all_to_all(
            x, output, input_offsets, send_sizes, output_offsets, recv_sizes,
            axis_name=self.axes[0],
        )

    def ppermute(self, x, perm: Sequence[Tuple[int, int]]):
        if len(self.axes) != 1:
            raise ValueError("ppermute needs a single mesh axis")
        return lax.ppermute(x, self.axes[0], perm=list(perm))

    # -- chunked ring collectives (overlap scheduler, ISSUE 2) -------------
    #
    # ``psum``/``psum_scatter`` hand XLA ONE monolithic collective per
    # bucket: the latency-hiding scheduler can overlap it with unrelated
    # compute, but cannot start reducing a bucket's early bytes while its
    # late bytes are still being produced, nor interleave two phases of the
    # same bucket.  The ring forms below decompose a bucket into
    # ``num_chunks`` INDEPENDENT sub-collectives built from ``ppermute``
    # hops + local adds — double-buffered in the sense that chunk ``c+1``'s
    # local adds are free to run while chunk ``c``'s hop is on the wire.
    # Chunk layout matches the tiled ``psum_scatter``/``all_gather`` pair
    # exactly (rank r owns the r-th CONTIGUOUS slice), so ZeRO's
    # reduce-scatter → update → all-gather dance can swap primitives
    # without relayouting its optimizer-state chunks.
    #
    # ``codec=`` (ISSUE 15) fuses a compression codec INTO the hops: every
    # reduce-scatter ``ppermute`` carries the quantized partial sum
    # (payload + the codec's f32 sidecar), the receiver dequantizes and
    # adds its own block in fp32 (the accumulation-dtype contract —
    # quantization error enters per hop, never through the accumulator),
    # and the allgather phase quantizes each rank's finished chunk exactly
    # ONCE, forwarding the payload unchanged hop to hop.  Compressed bytes
    # are what cross the wire — a 4x payload reduction for the u8/int8/fp8
    # codecs minus the sidecar.  ``codec=None`` is byte-for-byte the
    # pre-codec construction (HLO-pinned).

    def _ring_valid(self) -> bool:
        """Ring forms need a single nontrivial mesh axis to permute over."""
        return len(self.axes) == 1 and self.nranks() > 1

    def _ring_blocks(self, x, n):
        """[n*m, ...] -> per-rank-block view [n, m, ...] plus a traced
        block selector (dynamic_slice: block index depends on the rank)."""
        assert x.shape[0] % n == 0, (x.shape, n)
        blocks = x.reshape((n, x.shape[0] // n) + x.shape[1:])

        def block(i):
            return jnp.squeeze(
                lax.dynamic_slice_in_dim(blocks, i % n, 1, axis=0), 0
            )

        return blocks, block

    def _ring_reduce_scatter_1(self, x, op: ReduceOp, codec=None):
        """One ring: rank r ends with the reduction of every rank's r-th
        block.  The partial sum for block b starts at rank ``(b+1) % n`` and
        travels +1 per hop, each rank adding its own contribution — n-1
        ``ppermute`` hops, each moving 1/n of the bytes (bandwidth-optimal,
        like NCCL's ring).  With ``codec``: quantize-on-send (every hop
        carries the codec payload + sidecar), dequantize and accumulate in
        fp32 on receive — the compressed output stays f32."""
        n = self.nranks()
        if op not in (ReduceOp.SUM, ReduceOp.AVG):
            raise ValueError(f"ring reduce_scatter supports SUM/AVG, got {op}")
        r = self.rank()
        _, block = self._ring_blocks(x, n)
        perm = [(i, (i + 1) % n) for i in range(n)]
        if codec is None:
            buf = block(r - 1)
            # unrolled: every hop is its own ppermute instruction, so the
            # scheduler may pipeline hop s+1's local add under hop s's wire
            # time
            for s in range(n - 1):
                buf = self.ppermute(buf, perm)
                buf = buf + block(r - 2 - s)
            if op == ReduceOp.AVG:
                buf = buf / n
            return buf
        buf = block(r - 1).astype(jnp.float32)
        m = buf.shape[0]
        for s in range(n - 1):
            parts = codec.encode(buf[None])
            parts = tuple(self.ppermute(p, perm) for p in parts)
            # m is explicit: the bit-packed/variable-payload codecs cannot
            # invert payload shape -> element count
            buf = codec.decode(parts, m)[0] \
                + block(r - 2 - s).astype(jnp.float32)
        if op == ReduceOp.AVG:
            buf = buf / n
        return buf

    def _ring_allgather_1(self, x, codec=None):
        """One ring: input is this rank's block, output is all blocks in
        rank order (``[n * m, ...]``) — the inverse of
        :meth:`_ring_reduce_scatter_1`'s ownership layout.  With ``codec``:
        this rank's block is quantized exactly ONCE; the hops forward the
        payload unchanged (no re-quantization in the broadcast phase), and
        the stacked parts decode in one chunked pass at the end."""
        n = self.nranks()
        r = self.rank()
        perm = [(i, (i + 1) % n) for i in range(n)]
        if codec is None:
            out = jnp.zeros((n,) + x.shape, x.dtype)
            out = lax.dynamic_update_slice_in_dim(out, x[None], r % n, axis=0)
            buf = x
            for s in range(n - 1):
                buf = self.ppermute(buf, perm)
                out = lax.dynamic_update_slice_in_dim(
                    out, buf[None], (r - 1 - s) % n, axis=0
                )
            return out.reshape((n * x.shape[0],) + x.shape[1:])
        cur = [p[0] for p in codec.encode(x[None])]
        stacked = [jnp.zeros((n,) + c.shape, c.dtype) for c in cur]
        stacked = [
            lax.dynamic_update_slice_in_dim(o, c[None], r % n, axis=0)
            for o, c in zip(stacked, cur)
        ]
        for s in range(n - 1):
            cur = [self.ppermute(c, perm) for c in cur]
            stacked = [
                lax.dynamic_update_slice_in_dim(o, c[None], (r - 1 - s) % n,
                                                axis=0)
                for o, c in zip(stacked, cur)
            ]
        return codec.decode(tuple(stacked), x.shape[0]).reshape(-1)

    def _ring_chunk_views(self, x, num_chunks: int, n: int):
        """Split flat ``x`` into ``num_chunks`` independent sub-buffers such
        that concatenating each rank's sub-results reproduces the CONTIGUOUS
        per-rank chunk layout: sub-chunk j is the concatenation over ranks of
        each rank-block's j-th slice (``x.reshape(n, k, -1)[:, j]``)."""
        m = x.shape[0] // n
        assert m % num_chunks == 0, (m, num_chunks)
        view = x.reshape(n, num_chunks, m // num_chunks)
        return [view[:, j].reshape(-1) for j in range(num_chunks)]

    @staticmethod
    def _resolve_codec(codec):
        """Lazy registry resolution (``compression`` imports this module,
        so the codec registry cannot be a module-level import here)."""
        if codec is None:
            return None
        from .compression.codecs import resolve_codec

        return resolve_codec(codec)

    def ring_reduce_scatter(self, x, op: ReduceOp = ReduceOp.SUM,
                            num_chunks: int = 1, codec=None):
        """Chunked ring reduce-scatter of flat ``x`` (``size % nranks == 0``;
        ``num_chunks`` must divide the per-rank block).  Returns this rank's
        contiguous slice — same layout as ``reduce_scatter(..., tiled)``.
        ``codec`` (a name or :class:`~bagua_tpu.compression.codecs.RingCodec`)
        compresses every hop; the output is the fp32 accumulation cast back
        to ``x.dtype``.  Ring-invalid communicators fall back to the fused
        full-precision primitive (a 1-rank tier has no wire to compress)."""
        codec = self._resolve_codec(codec)
        if not self._ring_valid():
            return self.reduce_scatter(x, op)
        n = self.nranks()
        if num_chunks <= 1:
            parts = [x]
        else:
            parts = self._ring_chunk_views(x, num_chunks, n)
        outs = [self._ring_reduce_scatter_1(p, op, codec) for p in parts]
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        return out.astype(x.dtype) if codec is not None else out

    def ring_allgather(self, x, num_chunks: int = 1, codec=None):
        """Chunked ring all-gather of this rank's flat chunk; inverse of
        :meth:`ring_reduce_scatter` (``[m] -> [nranks * m]`` in rank
        order).  ``codec`` quantizes this rank's chunk once and moves only
        the payload+sidecar per hop (every receiver decodes the same
        payload, so all ranks still agree bitwise on the result)."""
        codec = self._resolve_codec(codec)
        if not self._ring_valid():
            return self.allgather(x, axis=0, tiled=True)
        n = self.nranks()
        if num_chunks <= 1:
            out = self._ring_allgather_1(x, codec)
            return out.astype(x.dtype) if codec is not None else out
        mk = x.shape[0] // num_chunks
        subs = x.reshape(num_chunks, mk)
        gathered = [
            self._ring_allgather_1(subs[j], codec) for j in range(num_chunks)
        ]
        out = jnp.stack([g.reshape(n, mk) for g in gathered], axis=1)
        out = out.reshape(n * x.shape[0])
        return out.astype(x.dtype) if codec is not None else out

    def ring_allreduce(self, x, op: ReduceOp = ReduceOp.AVG,
                       num_chunks: int = 1, codec=None):
        """Chunked double-buffered ring allreduce: reduce-scatter ring then
        all-gather ring per chunk.  Wire bytes equal the monolithic
        allreduce's ring model (``2(n-1)/n`` of the buffer); what changes is
        schedulability — ``num_chunks`` independent chains the
        latency-hiding scheduler can interleave with compute and each
        other.  Buffers that don't split evenly are zero-padded internally
        (sound for SUM/AVG) and sliced back — unlike the scatter/gather
        pair, whose ownership layout forbids silent padding.

        ``codec`` makes compressed bytes what actually cross the wire: the
        reduce-scatter hops carry quantized partial sums (dequantize +
        fp32 accumulate per hop), the finished chunk — already divided for
        AVG — is re-quantized exactly once, and the allgather hops forward
        that payload unchanged.  ``codec=None`` is the exact pre-codec
        construction (HLO-pinned by tests/test_compressed_ring.py)."""
        codec = self._resolve_codec(codec)
        if not self._ring_valid():
            return self.allreduce(x, op)
        n = self.nranks()
        size = x.shape[0]
        pad = (-size) % (n * max(1, num_chunks))
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        if num_chunks <= 1:
            out = self._ring_allgather_1(
                self._ring_reduce_scatter_1(x, op, codec), codec
            )
            if codec is not None:
                out = out.astype(x.dtype)
            return out[:size] if pad else out
        parts = self._ring_chunk_views(x, num_chunks, n)
        outs = [
            self._ring_allgather_1(self._ring_reduce_scatter_1(p, op, codec),
                                   codec)
            for p in parts
        ]
        # each sub-result is [n, m/num_chunks] in rank order; re-interleave
        # back to the original flat element order
        mk = parts[0].shape[0] // n
        out = jnp.stack([o.reshape(n, mk) for o in outs], axis=1)
        out = out.reshape(x.shape)
        if codec is not None:
            out = out.astype(x.dtype)
        return out[:size] if pad else out

    def broadcast(self, x, src: int = 0):
        """Every rank gets rank ``src``'s value (reference broadcast
        communication.py:270-300)."""
        # select src's contribution via masked psum (one all-reduce; on ICI
        # XLA lowers this to an efficient broadcast tree)
        idx = self.rank()
        contrib = jnp.where(idx == src, x, jnp.zeros_like(x))
        return lax.psum(contrib, self.axes)

    #: Largest step-pairing period precompiled into one program.  shift_one's
    #: period is world/2, so this admits meshes to 256-way gossip out of the
    #: box.  Measured on XLA:CPU (tests/test_compile_scale.py): the
    #: ``lax.switch`` costs one ppermute instruction per period step — compile
    #: time 0.06/0.08/0.31 s at 32/64/256 devices (flat in practice), program
    #: text O(period × nranks).  The cap turns the far-out hazard (a pod-scale
    #: gossip axis compiling thousands of branches) into an explicit error.
    MAX_EXCHANGE_PERIOD = env.get_max_exchange_period()

    def exchange_with_peer(self, x, peer_fn: Callable[[int, int, int], int], step):
        """Pairwise send/recv with a step-dependent symmetric pairing.

        ``peer_fn(rank, nranks, step) -> peer`` must be an involution for each
        step (peer(peer(r)) == r), as in the reference's shift_one exchange
        (decentralized_full_precision_synchronous.rs:79-83).  ``step`` may be a
        traced integer; the pairing must be periodic in ``step`` with period
        dividing ``nranks`` (branches are precompiled with ``lax.switch``; the
        executed path is always exactly ONE ppermute — wire cost does not
        grow with mesh size, only program metadata does, bounded by
        :attr:`MAX_EXCHANGE_PERIOD`).
        """
        n = self.nranks()
        period_perms = []
        seen = {}
        # stop enumerating as soon as the cap is provably exceeded — at pod
        # scale the full table is O(n^2) tuples, pathological to even build
        limit = min(n, self.MAX_EXCHANGE_PERIOD + 1)
        for s in range(limit):
            perm = tuple((r, int(peer_fn(r, n, s))) for r in range(n))
            if perm in seen and s > 0:
                break
            seen[perm] = s
            period_perms.append(perm)
        period = len(period_perms)
        if period > self.MAX_EXCHANGE_PERIOD:
            raise ValueError(
                f"exchange_with_peer: pairing period exceeds the precompile "
                f"cap {self.MAX_EXCHANGE_PERIOD} (program size grows as "
                f"period x nranks).  Raise BAGUA_MAX_EXCHANGE_PERIOD to "
                f"accept the compile cost, or use peer_selection_mode='all' "
                f"on meshes this large."
            )
        branches = [partial(lambda p, v: self.ppermute(v, p), list(p)) for p in period_perms]
        return lax.switch(step % period, branches, x)

    def barrier(self):
        """Device-level barrier: a tiny psum over the axes (reference
        communicators/mod.rs:973-982 uses a 1-element allreduce too)."""
        return lax.psum(jnp.ones((), jnp.int32), self.axes)


#: compile-size guard for the chunked rings (see :func:`ring_chunks_for`)
MAX_RING_CHUNKS = env.get_max_ring_chunks()

#: link classes of a hierarchical mesh's tiers: the ``intra`` axis rides
#: ICI (slice-local interconnect), the ``inter`` axis rides DCN (the
#: cross-slice data-center network, orders of magnitude less bandwidth).
#: Per-tier chunk sizing targets different bytes per link class — a chunk
#: sized for ICI is far too small to amortize a DCN hop.
LINK_ICI = "ici"
LINK_DCN = "dcn"


def largest_divisor_leq(m: int, k: int) -> int:
    """Largest divisor of ``m`` that is <= ``k`` (``m >= 1``, ``k >= 1``).

    Direct O(sqrt(m)) divisor enumeration — the old ``while m % k: k -= 1``
    scan was O(m) for prime per-rank blocks (a 1e6-element prime block
    walked a million candidates on every host-side sizing call)."""
    if k >= m:
        return m
    best = 1
    i = 1
    while i * i <= m:
        if m % i == 0:
            if i <= k and i > best:
                best = i
            j = m // i
            if j <= k and j > best:
                best = j
        i += 1
    return best


def ring_chunks_for(numel: int, itemsize: int, nranks: int,
                    chunk_bytes: Optional[int],
                    link_class: str = LINK_ICI) -> int:
    """Host-side sizing for the chunked ring collectives: the number of
    independent sub-collectives such that each carries ~``chunk_bytes`` of
    this rank's payload per hop (``ring_allreduce`` zero-pads indivisible
    buffers, so the per-rank block is the padded one).  1 = monolithic.

    ``chunk_bytes`` may be an int (one target for every link) or a mapping
    ``{link_class: bytes}`` resolved by ``link_class`` — how the two tiers
    of a hierarchical collective size their chunks against different
    targets (:data:`LINK_ICI` vs :data:`LINK_DCN`).  A class absent from
    the mapping means NO chunking for that class — falling back from a
    missing tier knob to the link-agnostic target is
    :meth:`AlgorithmContext.chunk_bytes_for`'s job, which resolves to an
    int before calling here."""
    if isinstance(chunk_bytes, dict):
        chunk_bytes = chunk_bytes.get(link_class) or 0
    if not chunk_bytes or nranks <= 1:
        return 1
    m = -(-numel // nranks)  # per-rank block after the ring's padding
    k = max(1, int(round(m * itemsize / chunk_bytes)))
    # each sub-ring unrolls into 2(n-1) ppermute instructions, so k is
    # capped: a tiny chunk_bytes against a 10 MiB bucket would otherwise
    # emit thousands of collectives per bucket and stall/OOM the compiler
    k = min(k, m, MAX_RING_CHUNKS)
    # num_chunks must divide the per-rank block
    return largest_divisor_leq(m, k)


class BaguaBackend:
    """Per-process comm backend: mesh + the 3 standard communicators.

    Counterpart of ``get_backend`` (communication.py:47-72) which builds
    global / intra-node / inter-node communicators and a dedicated CUDA
    stream.  There is no comm stream to manage on TPU — XLA schedules
    collectives asynchronously — so this only owns mesh topology.
    """

    def __init__(self, mesh: Optional[Mesh] = None, intra_size: Optional[int] = None):
        if mesh is None:
            from .parallel.mesh import get_global_mesh_if_set

            mesh = get_global_mesh_if_set()
        if mesh is None:
            mesh = hierarchical_mesh(intra_size=intra_size)
        self.mesh = mesh
        names = mesh.axis_names
        if "inter" in names and "intra" in names:
            glob = collapse_trivial_axes(mesh, ("inter", "intra"))
            self.global_communicator = BaguaCommunicator(glob, mesh)
            self.internode_communicator = BaguaCommunicator("inter", mesh)
            self.intranode_communicator = BaguaCommunicator("intra", mesh)
        else:
            dp_axis = names[0]
            self.global_communicator = BaguaCommunicator(dp_axis, mesh)
            self.internode_communicator = self.global_communicator
            self.intranode_communicator = self.global_communicator


_BACKENDS = {}


def get_backend(model_name: str = "") -> BaguaBackend:
    """Per-process backend cache, keyed by model name AND validated against
    the live global mesh: after an elastic resize or ``set_global_mesh`` the
    cached backend's communicators span the DEAD topology — handing them
    back would dispatch collectives over devices that left the world.  A
    cached entry whose mesh is not the currently registered global mesh is
    rebuilt (identity check: an elastic resize always constructs a new
    ``Mesh``, and re-registering the same object is a no-op)."""
    from .parallel.mesh import get_global_mesh_if_set

    live = get_global_mesh_if_set()
    backend = _BACKENDS.get(model_name)
    if backend is not None and live is not None and backend.mesh is not live:
        backend = None
    if backend is None:
        backend = BaguaBackend()
        _BACKENDS[model_name] = backend
    return backend


_autotune_server = None


def start_autotune_server():
    """Start the autotune sidecar in a daemon process on this host
    (reference communication.py:95-104)."""
    global _autotune_server
    if _autotune_server is not None:
        return
    import multiprocessing

    from .service.autotune_service import run_autotune_server

    _autotune_server = multiprocessing.Process(
        target=run_autotune_server,
        kwargs=dict(
            port=env.get_bagua_service_port(),
            world_size=env.get_world_size(),
            autotune_level=env.get_autotune_level(),
            max_samples=env.get_autotune_max_samples(),
            sampling_confidence_time_s=env.get_autotune_sampling_confidence_time_s(),
            warmup_time_s=env.get_autotune_warmup_time_s(),
            is_output_autotune_log=env.is_output_autotune_log(),
            default_bucket_size=env.get_default_bucket_size(),
            tune_algorithm=env.is_autotune_algorithm_on(),
        ),
        daemon=True,
    )
    _autotune_server.start()


@lru_cache(maxsize=None)
def get_hyperparameters_service_client():
    from .service.autotune_service import AutotuneClient

    return AutotuneClient(env.get_master_addr(), env.get_bagua_service_port())


def init_process_group(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    mesh: Optional[Mesh] = None,
):
    """Initialize distributed state; call before other bagua_tpu APIs.

    TPU-native replacement for ``bagua.init_process_group``
    (communication.py:107-137): instead of a NCCL unique-id rendezvous through
    a c10d store, multi-host bring-up is ``jax.distributed.initialize`` (the
    JAX coordination service), after which every host sees the full device
    set and the global mesh spans all chips.
    """
    from .compile_cache import configure_compile_cache

    configure_compile_cache()
    # the sidecar is FORKED from this process: do it before
    # jax.distributed.initialize starts its service threads and before any
    # backend exists — a child forked from a process that holds the chip
    # (or a half-copied thread pool) fails or hangs
    if env.get_rank() == 0 and env.get_bagua_service_port() > 0:
        start_autotune_server()
    env_addr = env.get_coordinator_addr()
    if coordinator_address is not None or env_addr:
        addr = coordinator_address or env_addr
        # pass None through when env vars are unset so jax auto-detects;
        # do NOT call jax.process_count() here — it would initialize the
        # local backend and break distributed bring-up
        if num_processes is None and os.environ.get("WORLD_SIZE"):
            num_processes = int(os.environ["WORLD_SIZE"])
        if process_id is None and os.environ.get("RANK"):
            process_id = int(os.environ["RANK"])
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=num_processes,
            process_id=process_id,
        )
    if mesh is None:
        mesh = build_mesh()
    set_global_mesh(mesh)
    return mesh


# ---------------------------------------------------------------------------
# Eager collective primitives (reference communication.py:230-852).
#
# Semantics: the input's leading axis enumerates ranks (size == communicator
# world size).  ``allreduce(x)[r] == reduce_r' x[r']`` for every r — exactly
# what each process observes after the reference's synchronous collective.
#
# Multi-process: each process passes ITS slice of the rank axis — one row per
# communicator rank it OWNS.  Ranks are mesh positions (devices), so a process
# driving one device passes a leading axis of size 1 (the per-rank call shape
# of the reference API), while a process driving k local devices must pass all
# k of its rows.  _eager validates the local leading dim against the owned
# rank count and stitches the slices into one global array before dispatch,
# so the reference's "every rank calls with its own tensor" usage ports
# directly.
# ---------------------------------------------------------------------------


# compiled eager primitives, keyed on (mesh, axes, op signature, arg avals):
# re-tracing `jit(shard_map(...))` on every standalone-collective call would
# make the reference's synchronous primitive API pay a trace+dispatch cost
# per invocation
_EAGER_CACHE: dict = {}

# (mesh, axes) -> rank rows this process must feed; constant per mesh, and a
# Python scan over every mesh device is too slow to repeat per eager call
_OWNED_RANK_CACHE: dict = {}


def _owned_rank_count(comm: "BaguaCommunicator") -> int:
    """Number of DISTINCT rank-axis positions among this process's devices —
    the per-process row count for eager per-rank call shapes.  Not a
    proportional formula: with extra non-comm mesh axes a process's devices
    can cover several — or repeat the same — rank indices."""
    mesh = comm.mesh
    key = (mesh, comm.axes)
    cached = _OWNED_RANK_CACHE.get(key)
    if cached is not None:
        return cached
    import numpy as _np

    axis_idx = [mesh.axis_names.index(ax) for ax in comm.axes]
    me = jax.process_index()
    owned = {
        tuple(coord[i] for i in axis_idx)
        for coord, d in _np.ndenumerate(mesh.devices)
        if d.process_index == me
    }
    _OWNED_RANK_CACHE[key] = len(owned)
    return len(owned)


def _eager(comm: Optional[BaguaCommunicator], key, fn, *arrays):
    """Run ``fn`` once per rank: inputs' leading axis is the rank axis; inside
    ``fn`` each rank sees its own tensor (leading axis stripped).  ``key``
    identifies the operation (name + static params) for the compile cache."""
    check_abort()  # aborted communicators fail new dispatches fast
    comm = comm if comm is not None else get_backend("").global_communicator
    mesh = comm.mesh
    if jax.process_count() > 1:
        # per-rank call semantics: each process contributes one row per
        # communicator rank (= mesh device) it owns; host arrays are
        # stitched into one global array (already-global jax.Arrays pass
        # through untouched)
        from .parallel.mesh import make_global_array

        expected = _owned_rank_count(comm)
        for a in arrays:
            if isinstance(a, jax.Array) and not a.is_fully_addressable:
                continue
            rows = jnp.shape(a)[0] if jnp.ndim(a) else None
            if rows is not None and rows != expected:
                raise ValueError(
                    f"eager collective: this process owns {expected} of the "
                    f"{comm.nranks()} communicator ranks and must pass "
                    f"exactly that many rows along the leading rank axis, "
                    f"got {rows}"
                )
        in_spec = P(comm.axis_name if len(comm.axes) == 1 else comm.axes)
        arrays = tuple(
            a if isinstance(a, jax.Array) and not a.is_fully_addressable
            else make_global_array(mesh, in_spec, a)
            for a in arrays
        )
    else:
        arrays = tuple(jnp.asarray(a) for a in arrays)
    cache_key = (
        mesh, comm.axes, key,
        tuple((a.shape, a.dtype.name) for a in arrays),
    )
    compiled = _EAGER_CACHE.get(cache_key)
    if compiled is None:
        spec = P(comm.axis_name if len(comm.axes) == 1 else comm.axes)

        def wrapped(*blocks):
            out = fn(*[b[0] for b in blocks])
            return jax.tree.map(lambda o: jnp.expand_dims(o, 0), out)

        compiled = jax.jit(
            shard_map(
                wrapped, mesh=mesh, in_specs=tuple(spec for _ in arrays),
                out_specs=spec, check_vma=False,
            )
        )
        _EAGER_CACHE[cache_key] = compiled
    out = compiled(*arrays)
    _watch_eager(out, key)
    return out


def _watch_eager(out, key) -> None:
    """Fence standalone eager collectives with the global hang watchdog.

    The trainer's steps are watchdog-fenced via ``watch_result``; without
    this, a wedged ``allreduce()`` OUTSIDE the trainer would hang silently —
    the reference's comm monitor covers every scheduled op, not only
    training ones (bagua-core-internal/src/lib.rs:255-265)."""
    from .watchdog import get_comm_timeout_s, get_global_watchdog

    timeout = get_comm_timeout_s()
    if timeout is None:
        return
    leaves = jax.tree_util.tree_leaves(out)
    if not leaves:
        return
    leaf = leaves[0]
    try:
        # fence on ONE local shard, not the stacked global result: the
        # shard's buffer is ready exactly when the collective completed
        # locally, and the waiter's readback then transfers a single
        # rank-row instead of the whole [nranks, ...] output
        fence = leaf.addressable_shards[0].data
    except Exception:
        fence = leaf
    get_global_watchdog(timeout).watch_result(
        fence, f"eager:{key[0] if isinstance(key, tuple) else key}"
    )


def _comm_or_default(comm):
    return comm if comm is not None else get_backend("").global_communicator


def allreduce(send, op: ReduceOp = ReduceOp.AVG, comm: Optional[BaguaCommunicator] = None):
    """Reduce across the rank axis; every rank slice gets the result
    (reference communication.py:427-495)."""
    c = _comm_or_default(comm)
    return _eager(comm, ("allreduce", int(op)), lambda x: c.allreduce(x, op), send)


def allreduce_inplace(tensor, op: ReduceOp = ReduceOp.AVG, comm=None):
    return allreduce(tensor, op, comm)


def allgather(send, comm: Optional[BaguaCommunicator] = None):
    """Each rank slice becomes the concatenation of all slices
    (reference communication.py:498-560)."""
    c = _comm_or_default(comm)
    return _eager(comm, ("allgather",),
                  lambda x: c.allgather(x, axis=0, tiled=True), send)


allgather_inplace = allgather


def reduce_scatter(send, op: ReduceOp = ReduceOp.SUM, comm=None):
    c = _comm_or_default(comm)
    return _eager(comm, ("reduce_scatter", int(op)),
                  lambda x: c.reduce_scatter(x, op, axis=0), send)


reduce_scatter_inplace = reduce_scatter


def alltoall(send, comm=None):
    c = _comm_or_default(comm)
    return _eager(comm, ("alltoall",), lambda x: c.alltoall_tiled(x, 0, 0), send)


alltoall_inplace = alltoall


def alltoall_v(send, send_counts, output_size: Optional[int] = None, comm=None):
    """Ragged all-to-all (reference ``alltoall_v``,
    communicators/mod.rs:632-676).

    ``send``: ``[nranks, L, ...]`` — each rank slice packs its outgoing chunks
    consecutively (chunk for rank 0 first).  ``send_counts``: static
    ``[nranks, nranks]`` matrix (Python/numpy ints); ``send_counts[r][d]`` =
    elements rank r sends to rank d.  Returns ``[nranks, output_size, ...]``
    where each rank slice packs the chunks received from rank 0, 1, ...
    consecutively, zero-padded to ``output_size`` (default: the max total
    receive count — XLA needs one static shape across ranks).
    """
    import numpy as np

    c = _comm_or_default(comm)
    counts = np.asarray(send_counts, dtype=np.int64)
    n = c.nranks()
    if counts.shape != (n, n):
        raise ValueError(f"send_counts must be [{n},{n}], got {counts.shape}")
    recv_counts = counts.T  # recv_counts[d][s] = what d receives from s
    need = int(recv_counts.sum(axis=1).max())
    out_size = need if output_size is None else int(output_size)
    if out_size < need:
        raise ValueError(f"output_size {out_size} < max receive total {need}")
    # static per-rank offset tables, gathered inside the traced fn by rank
    input_offsets = np.concatenate(
        [np.zeros((n, 1), np.int64), np.cumsum(counts, axis=1)[:, :-1]], axis=1
    )
    recv_offsets = np.concatenate(
        [np.zeros((n, 1), np.int64), np.cumsum(recv_counts, axis=1)[:, :-1]],
        axis=1,
    )
    # output_offsets[r][d]: where rank r's chunk lands in rank d's output
    output_offsets = recv_offsets.T.copy()

    # XLA's native ragged-all-to-all exists on TPU; elsewhere (the CPU test
    # mesh) fall back to a padded dense all_to_all + masked scatter with
    # identical semantics.
    native = c.mesh.devices.flat[0].platform == "tpu"
    key = ("alltoall_v", native, counts.tobytes(), out_size)

    def fn_native(x):
        r = c.rank()
        sel = lambda table: jnp.asarray(table)[r]
        output = jnp.zeros((out_size,) + x.shape[1:], x.dtype)
        return c.alltoall_v(
            x, output, sel(input_offsets), sel(counts),
            sel(output_offsets), sel(recv_counts.copy()),
        )

    maxc = max(1, int(counts.max()))

    def fn_padded(x):
        r = c.rank()
        sel = lambda table: jnp.asarray(table)[r]
        my_counts, my_in_off = sel(counts), sel(input_offsets)
        my_recv_counts, my_recv_off = sel(recv_counts.copy()), sel(recv_offsets)
        # pack chunk for each destination into a padded [n, maxc, ...] buffer
        xp = jnp.concatenate(
            [x, jnp.zeros((maxc,) + x.shape[1:], x.dtype)], axis=0
        )
        idx = my_in_off[:, None] + jnp.arange(maxc)[None, :]        # [n, maxc]
        valid_out = jnp.arange(maxc)[None, :] < my_counts[:, None]
        padded = jnp.where(
            valid_out.reshape(n, maxc, *([1] * (x.ndim - 1))),
            xp[idx], 0,
        )
        got = c.alltoall(padded)                                    # [n, maxc, ...]
        # recompose: element j of chunk-from-s lands at recv_off[s]+j,
        # padding lands in a dump slot past the end
        valid_in = jnp.arange(maxc)[None, :] < my_recv_counts[:, None]
        tgt = jnp.where(
            valid_in, my_recv_off[:, None] + jnp.arange(maxc)[None, :], out_size
        )
        out = jnp.zeros((out_size + 1,) + x.shape[1:], x.dtype)
        out = out.at[tgt.reshape(-1)].set(
            got.reshape((n * maxc,) + x.shape[1:])
        )
        return out[:out_size]

    return _eager(comm, key, fn_native if native else fn_padded, send)


def broadcast(tensor, src: int = 0, comm=None):
    c = _comm_or_default(comm)
    return _eager(comm, ("broadcast", src), lambda x: c.broadcast(x, src), tensor)


def reduce(send, dst: int, op: ReduceOp = ReduceOp.SUM, comm=None, recv=None):
    """Only rank ``dst``'s slice holds the reduction (reference
    communication.py:331-375: the collective writes ONLY dst's recv buffer).
    Non-dst output slices reproduce ``recv`` — the functional analog of the
    reference's untouched recv tensor — or zeros when no ``recv`` is given."""
    c = _comm_or_default(comm)

    if recv is None:
        def fn(x):
            red = c.allreduce(x, op)
            return jnp.where(c.rank() == dst, red, jnp.zeros_like(red))

        return _eager(comm, ("reduce", dst, int(op), False), fn, send)

    def fn2(x, r):
        red = c.allreduce(x, op)
        return jnp.where(c.rank() == dst, red, r)

    return _eager(comm, ("reduce", dst, int(op), True), fn2, send, recv)


def gather(send, dst: int, comm=None, recv=None):
    """Rank ``dst``'s output slice holds every rank's data concatenated
    (``[nranks * rows, ...]``); non-dst slices reproduce ``recv`` — the
    reference leaves their recv buffers untouched
    (communication.py:576-614) — or zeros when no ``recv`` is given."""
    c = _comm_or_default(comm)

    if recv is None:
        def fn(x):
            g = c.allgather(x, axis=0, tiled=True)
            return jnp.where(c.rank() == dst, g, jnp.zeros_like(g))

        return _eager(comm, ("gather", dst, False), fn, send)

    def fn2(x, r):
        g = c.allgather(x, axis=0, tiled=True)
        return jnp.where(c.rank() == dst, g, r)

    return _eager(comm, ("gather", dst, True), fn2, send, recv)


def scatter(send, src: int, comm=None):
    """Rank r receives chunk r of rank ``src``'s data.  ``send``'s rank slices
    each hold the full [nranks*chunk] buffer; output slices hold one chunk."""
    c = _comm_or_default(comm)

    def fn(x):
        full = c.broadcast(x, src)
        n = c.nranks()
        chunks = full.reshape((n, -1) + full.shape[1:])
        return jnp.squeeze(lax.dynamic_slice_in_dim(chunks, c.rank(), 1, axis=0), 0)

    return _eager(comm, ("scatter", src), fn, send)


def send_recv(send, peer_perm: List[Tuple[int, int]], comm=None):
    """Point-to-point exchange expressed as a permutation (reference send/recv
    communication.py:233-267 — on TPU p2p is ``ppermute`` over ICI)."""
    c = _comm_or_default(comm)
    perm = tuple((int(a), int(b)) for a, b in peer_perm)
    return _eager(comm, ("send_recv", perm), lambda x: c.ppermute(x, perm), send)


def barrier(comm=None):
    c = _comm_or_default(comm)
    # per-rank call shape: one row per rank THIS process owns (multi-process
    # passes only its slice, like every other eager primitive)
    rows = _owned_rank_count(c) if jax.process_count() > 1 else c.nranks()
    out = _eager(comm, ("barrier",),
                 lambda x: c.barrier() * jnp.ones((1,), jnp.int32),
                 jnp.zeros((rows, 1), jnp.int32))
    jax.block_until_ready(out)
