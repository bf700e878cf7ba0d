"""Bucketing: partition named tensors into flat, aligned communication buffers.

Counterpart of the reference's ``BaguaBucket``
(/root/reference/bagua/torch_api/bucket.py:15-123: in-place flattening into a
contiguous buffer + padding tensor for alignment) and the autotuner's
``split_bucket_by_bucket_size`` (service/autotune_task_manager.py:86-119).

TPU-first rationale: the reference flattens so the Rust scheduler can issue one
NCCL call per bucket.  Under XLA we flatten for the same reason — one large
``psum``/``all_to_all`` per bucket beats many small ones on ICI — but the
flattening is *traced* (ravel, pad and concatenate inside the jitted step)
instead of aliasing storage.  Alignment padding to a multiple of the world
size is what lets the compressed scatter-gather ops split a bucket into equal
per-rank chunks (reference bytegrad.py:38-43).

The traced flatten is NOT free on a TPU.  A 1-D float32 buffer is tiled
``T(1024)`` and a matrix ``T(8,128)``: every ``reshape`` between a flat and a
tensor's own shape is a physical copy of the whole tensor, forward (the slice
out of the flat) and backward (the gradient's ravel-and-pad back into it) —
8.6 ms of ``reshape`` and 11.2 ms of ``copy`` a step in OLMoE's 142 ms step
(PERF.md, PR 33).  Buckets exist to fuse SMALL tensors into one collective, so:

- :func:`split_bucket_by_bucket_size` gives a tensor of at least
  ``min(bucket_size, LONE_TENSOR_BYTES)`` bytes (1 MiB, unless the caller's
  ``bucket_size`` is smaller) a bucket of its own — packing it with a norm
  scale buys the collective nothing, and XLA's combiner re-fuses the
  declared buckets into the all-reduces it wants whatever their number
  (bert-large on four chips: 125 declared buckets compile to 16
  all-reduces, 220 to 15, the wire's bytes equal; PERF.md, PR 45);
- a bucket of one tensor and no padding is *shaped*
  (:attr:`BucketSpec.shaped`): its buffer IS that tensor, in the tensor's own
  shape.  Parameters, gradients and optimizer state held bucket-flat keep it;
  nothing is sliced, reshaped, padded or concatenated for it.  The invariant
  every plan-keyed buffer obeys: its trailing axes are
  :attr:`BucketSpec.buffer_shape` (leading axes, if any, are per-rank stacks).

The predicate is the plan's alone — one tensor of at least one axis, and
``padding == 0`` — so nothing selects it per trainer.  A family whose
collective is elementwise over the buffer (the fused ``psum`` of
``GradientAllReduceAlgorithm(hierarchical=False)``, the gossip exchanges,
QAdam's warm-up allreduce) takes a shaped buffer as it took a 1-D one.  A
family that cuts a bucket into equal per-rank chunks declares
``alignment = world size``: its buckets are shaped only where the lone tensor
already divides evenly, and the code that cuts the chunks (ZeRO's
reduce-scatter and owned-chunk slice, the compressed scatter-gather, the
codecs' rings, the two-level reduce-scatter stage) ravels at that point of
use and restores the shape behind it — on a TPU that is the copy the
flat-resident layout paid for every tensor before, now paid only there.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .define import TensorDeclaration, TensorDtype, DTYPE_BYTES
from .obs.spans import phase_scope
from .tensor import NamedParam, leaves_by_name
from .utils import from_bagua_datatype


#: A tensor of this many bytes stands alone whatever ``bucket_size`` says.
#: Buckets exist to fuse SMALL tensors into one collective; a tensor of a
#: megabyte fills one by itself, XLA's combiner re-fuses the declared buckets
#: whatever their number, and what a flat costs such a tensor on a TPU is a
#: re-tiling copy each way, an update pass of its own and, under
#: ``accum_steps > 1``, an accumulation add of its own (module docstring).
#: :class:`TensorDeclaration` carries bytes and no shape, so the rule reads
#: bytes; a ``bucket_size`` under the floor is the threshold itself, as ever.
LONE_TENSOR_BYTES = 1 << 20


def split_bucket_by_bucket_size(
    tensor_list: List[TensorDeclaration],
    bucket_size: int,
    param_group_info: Optional[Dict[str, int]] = None,
) -> List[List[TensorDeclaration]]:
    """Greedy dtype-grouped split, mirroring the reference autotuner
    (autotune_task_manager.py:86-119): iterate dtypes in sorted order, fill a
    bucket until it reaches ``bucket_size`` bytes, then start a new one.

    One departure: a tensor of at least ``min(bucket_size,
    LONE_TENSOR_BYTES)`` bytes closes the open bucket first and stands alone.
    It already fills a collective; glued to its small neighbours it could not
    keep its own shape (module docstring)."""
    param_group_info = param_group_info or {}
    lone_bytes = min(bucket_size, LONE_TENSOR_BYTES)
    dtypes = sorted({TensorDtype(t.dtype).value for t in tensor_list})
    buckets: List[List[TensorDeclaration]] = []
    for dtype in dtypes:
        # flush at dtype boundaries: a bucket is one flat buffer of one dtype
        # (the reference's buckets are homogeneous in practice; carrying a
        # partial bucket across dtypes would silently cast gradients)
        tmp: List[TensorDeclaration] = []
        tmp_bytes = 0
        for td in [t for t in tensor_list if TensorDtype(t.dtype).value == dtype]:
            if td.nbytes >= lone_bytes:
                if tmp:
                    buckets.append(tmp)
                buckets.append([td])
                tmp, tmp_bytes = [], 0
                continue
            tmp_bytes += td.nbytes
            tmp.append(td)
            if tmp_bytes >= bucket_size:
                buckets.append(tmp)
                tmp, tmp_bytes = [], 0
        if tmp:
            buckets.append(tmp)
    for i in range(len(buckets)):
        buckets[i] = sorted(buckets[i], key=lambda p: param_group_info.get(p.name, -1))
    return buckets


@dataclass(frozen=True)
class BucketSpec:
    """One bucket: ordered named tensors + alignment padding (reference
    bucket.py:15-55)."""

    name: str
    tensors: Tuple[NamedParam, ...]
    alignment: int = 1

    @property
    def numel(self) -> int:
        return sum(t.numel for t in self.tensors)

    @property
    def padded_numel(self) -> int:
        n = self.numel
        if self.alignment > 1 and n % self.alignment:
            n += self.alignment - n % self.alignment
        return n

    @property
    def padding(self) -> int:
        return self.padded_numel - self.numel

    @property
    def dtype(self):
        return self.tensors[0].dtype

    @property
    def shaped(self) -> bool:
        """The bucket IS its one tensor: the buffer keeps the tensor's own
        shape (module docstring).  A scalar stays a 1-element flat."""
        return (len(self.tensors) == 1 and self.padding == 0
                and len(self.tensors[0].shape) > 0)

    @property
    def buffer_shape(self) -> Tuple[int, ...]:
        """Trailing axes of every buffer laid out under this bucket."""
        if self.shaped:
            return tuple(self.tensors[0].shape)
        return (self.padded_numel,)

    def offsets(self) -> List[int]:
        offs, off = [], 0
        for t in self.tensors:
            offs.append(off)
            off += t.numel
        return offs

    def signature(self) -> Tuple:
        return (
            self.name,
            self.alignment,
            tuple((t.name, t.shape, str(t.dtype)) for t in self.tensors),
        )


@dataclass(frozen=True)
class BucketPlan:
    """A full partition of the registered tensors into buckets."""

    buckets: Tuple[BucketSpec, ...]

    def signature(self) -> Tuple:
        return tuple(b.signature() for b in self.buckets)

    @property
    def tensor_names(self) -> List[str]:
        return [t.name for b in self.buckets for t in b.tensors]

    @staticmethod
    def from_declaration_buckets(
        decl_buckets: Sequence[Sequence[TensorDeclaration]],
        named_params: Sequence[NamedParam],
        alignment: int = 1,
    ) -> "BucketPlan":
        by_name = {p.name: p for p in named_params}
        specs = []
        for i, db in enumerate(decl_buckets):
            tensors = tuple(by_name[d.name] for d in db)
            specs.append(BucketSpec(name=str(i), tensors=tensors, alignment=alignment))
        plan = BucketPlan(buckets=tuple(specs))
        missing = set(by_name) - set(plan.tensor_names)
        if missing:
            raise ValueError(f"bucket plan misses tensors: {sorted(missing)}")
        return plan

    @staticmethod
    def build(
        named_params: Sequence[NamedParam],
        bucket_bytes: int,
        alignment: int = 1,
        param_group_info: Optional[Dict[str, int]] = None,
    ) -> "BucketPlan":
        decls = [p.declaration() for p in named_params]
        decl_buckets = split_bucket_by_bucket_size(decls, bucket_bytes, param_group_info)
        return BucketPlan.from_declaration_buckets(decl_buckets, named_params, alignment)

    # ---- traced flatten/unflatten ------------------------------------

    def flatten_tree(self, tree) -> List[jax.Array]:
        """tree -> list of bucket buffers (traced): a shaped bucket's tensor
        as it is, every other bucket raveled, padded and concatenated into
        its 1-D flat.  Equivalent of bucket.py:95-123 ``_flatten_``."""
        named = leaves_by_name(tree)
        flats = []
        # bagua.layout: what the bucket plan costs on the device besides
        # the wire (ravel, cast, pad, concatenate; slices on the way back)
        with phase_scope("bagua.layout"):
            for b in self.buckets:
                if b.shaped:
                    flats.append(named[b.tensors[0].name].astype(b.dtype))
                    continue
                parts = [jnp.ravel(named[t.name]).astype(b.dtype)
                         for t in b.tensors]
                if b.padding:
                    parts.append(jnp.zeros((b.padding,), dtype=b.dtype))
                flats.append(jnp.concatenate(parts) if len(parts) > 1
                             else parts[0])
        return flats

    def unflatten_to_named(self, flats: Sequence[jax.Array]) -> Dict[str, jax.Array]:
        named = {}
        with phase_scope("bagua.layout"):
            for b, flat in zip(self.buckets, flats):
                if b.shaped:
                    t = b.tensors[0]
                    # (a buffer some older layout wrote 1-D: same numel)
                    named[t.name] = jnp.reshape(flat, t.shape).astype(
                        t.dtype)
                    continue
                for t, off in zip(b.tensors, b.offsets()):
                    seg = jax.lax.slice_in_dim(flat, off, off + t.numel)
                    named[t.name] = seg.reshape(t.shape).astype(t.dtype)
        return named

    def unflatten_tree(self, flats: Sequence[jax.Array], tree_like):
        from .tensor import tree_from_named

        return tree_from_named(tree_like, self.unflatten_to_named(flats))

    # ---- layout portability ------------------------------------------

    def layout_descriptor(self) -> List[dict]:
        """JSON-serializable description of the flat layout — enough to
        rebuild an equivalent plan (:meth:`from_layout_descriptor`) on a
        process that never saw the original params.  Stored in checkpoint
        layout sidecars so a flat-resident checkpoint saved under one plan
        can be re-laid-out under another on restore."""
        return [
            {
                "alignment": int(b.alignment),
                # the buffer's own shape: sidecars older than the shaped
                # buckets lack the key and hold every buffer 1-D
                "buffer_shape": [int(d) for d in b.buffer_shape],
                "tensors": [
                    {
                        "name": t.name,
                        "shape": [int(d) for d in t.shape],
                        "dtype": np.dtype(t.dtype).name,
                    }
                    for t in b.tensors
                ],
            }
            for b in self.buckets
        ]

    @staticmethod
    def from_layout_descriptor(desc: Sequence[dict]) -> "BucketPlan":
        """Rebuild a plan from :meth:`layout_descriptor` output.  The
        reconstructed :class:`NamedParam` entries carry empty tree paths —
        sufficient for every flat-layout operation (flatten / unflatten /
        relayout key on names, shapes, and dtypes only)."""
        specs = []
        for i, b in enumerate(desc):
            tensors = tuple(
                NamedParam(
                    name=t["name"],
                    path=(),
                    shape=tuple(int(d) for d in t["shape"]),
                    dtype=np.dtype(t["dtype"]),
                )
                for t in b["tensors"]
            )
            specs.append(
                BucketSpec(name=str(i), tensors=tensors,
                           alignment=int(b["alignment"]))
            )
        return BucketPlan(buckets=tuple(specs))

    @staticmethod
    def saved_buffer_shapes(desc: Sequence[dict]) -> List[Tuple[int, ...]]:
        """The shapes the buffers a descriptor describes were WRITTEN in —
        what a restore must ask the checkpoint for.  The plan rebuilt from
        an older sidecar may call a bucket shaped whose buffer was saved
        1-D: same numel, one :func:`conform_flats` at restore time."""
        plan = BucketPlan.from_layout_descriptor(desc)
        return [
            tuple(int(d) for d in saved["buffer_shape"])
            if "buffer_shape" in saved else (b.padded_numel,)
            for saved, b in zip(desc, plan.buckets)
        ]


def _with_trailing(x, n_trailing: int, shape) -> jax.Array:
    """``x`` with its last ``n_trailing`` axes reshaped to ``shape`` (leading
    per-rank stack axes kept); ``x`` itself where it already has them."""
    lead = tuple(jnp.shape(x))[:jnp.ndim(x) - n_trailing]
    want = lead + tuple(shape)
    return x if tuple(jnp.shape(x)) == want else jnp.reshape(x, want)


def conform_flats(plan: BucketPlan, flats: Sequence[jax.Array],
                  saved_shapes: Sequence[Tuple[int, ...]]) -> List[jax.Array]:
    """Buffers written in ``saved_shapes`` (:meth:`BucketPlan.
    saved_buffer_shapes`) -> ``plan``'s own buffer shapes."""
    return [
        _with_trailing(f, len(saved), b.buffer_shape)
        for b, f, saved in zip(plan.buckets, flats, saved_shapes)
    ]


def relayout_flats(
    old_plan: BucketPlan, new_plan: BucketPlan, flats: Sequence[jax.Array]
) -> List[jax.Array]:
    """Migrate bucket buffers from ``old_plan``'s layout to ``new_plan``'s
    WITHOUT a leaf round trip: per-tensor 1-D segments are sliced out of the
    old flats and concatenated straight into the new ones (old padding
    dropped, new padding zero-filled), and a tensor that is its own bucket on
    both sides moves as it is — no slice, no reshape, no copy.  This is the
    flat->flat path autotune re-bucketing and cross-plan checkpoint restores
    use to move flat-RESIDENT training state, so the per-step round-trip the
    resident layout removed never sneaks back in at migration points.

    Segments slice along the LAST axis and a shaped buffer's own axes are its
    last ones, so stacked per-rank state (gossip families carry buffers with
    a leading rank axis) migrates with the same code path.  Both plans must
    cover the same tensor names."""
    #: name -> (buffer, number of trailing axes that are the tensor's):
    #: a 1-D segment, or the whole buffer of a shaped bucket
    pieces: Dict[str, Tuple[jax.Array, int]] = {}
    seg_numel: Dict[str, int] = {}
    for b, flat in zip(old_plan.buckets, flats):
        if b.shaped:
            pieces[b.tensors[0].name] = (flat, len(b.buffer_shape))
            seg_numel[b.tensors[0].name] = b.numel
            continue
        for t, off in zip(b.tensors, b.offsets()):
            pieces[t.name] = (jax.lax.slice_in_dim(
                flat, off, off + t.numel, axis=-1
            ), 1)
            seg_numel[t.name] = t.numel
    missing = [
        t.name for b in new_plan.buckets for t in b.tensors
        if t.name not in pieces
    ]
    if missing:
        raise ValueError(
            f"relayout_flats: old plan misses tensors {sorted(missing)}"
        )
    resized = {
        t.name: (seg_numel[t.name], t.numel)
        for b in new_plan.buckets for t in b.tensors
        if seg_numel[t.name] != t.numel
    }
    if resized:
        # a silently-shifted offset would corrupt every later tensor in
        # the bucket (worst case: equal total lengths, no error at all)
        raise ValueError(
            "relayout_flats: tensor sizes differ between plans — the "
            "flat buffers cannot be re-laid-out (model edit between "
            "save and restore?): "
            + ", ".join(f"{n}: {a} -> {b} elems"
                        for n, (a, b) in sorted(resized.items()))
        )
    out: List[jax.Array] = []
    for b in new_plan.buckets:
        if b.shaped:
            piece, own = pieces[b.tensors[0].name]
            out.append(_with_trailing(piece, own, b.buffer_shape)
                       .astype(b.dtype))
            continue
        parts = [
            _with_trailing(*pieces[t.name], (t.numel,)).astype(b.dtype)
            for t in b.tensors
        ]
        if b.padding:
            pad_shape = parts[0].shape[:-1] + (b.padding,)
            parts.append(jnp.zeros(pad_shape, dtype=b.dtype))
        out.append(
            jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]
        )
    return out
