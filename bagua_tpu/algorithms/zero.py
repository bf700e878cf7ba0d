"""ZeRO-1: optimizer-state sharding over the data-parallel axes.

Additive capability — the reference has no ZeRO/FSDP analog (SURVEY.md §2.3
lists it as absent; its closest relative is the flat-param
``contrib/fused_optimizer.py``).  On TPU this is the natural next step past
plain DP: optimizer state is the largest per-chip memory consumer for Adam
(2× params in f32), and the classic ZeRO-1 dance maps to two XLA collectives
per bucket:

    reduce_scatter(grads)  ->  shard-local optimizer update  ->  all_gather(params)

which costs exactly the same bytes on the wire as the allreduce it replaces
(an allreduce IS a reduce-scatter + all-gather — pinned by the compiled-HLO
byte audit in tests/test_hlo_comm_bytes.py), while storing only
``1/world_size`` of the optimizer state per chip.

**Since PR 49 the exact family does this dance by itself** wherever it can
(``GradientAllReduceAlgorithm`` with the trainer's own elementwise optimizer
on a pure-dp mesh: gradient_allreduce.py's header), and the two share one
implementation of "this rank's chunk of a bucket"
(``base.chunk_form`` / ``AlgorithmContext.owned_chunk`` /
``bucket_reduce_scatter`` / ``bucket_allgather``): **a chunk is rows of the
bucket's buffer** — the leading axis of a shaped bucket's tensor, a run of a
1-D flat — and the 1-D run of a ravel only for a shaped bucket whose rows
the shard count does not divide (its numel does: this family's plan pads
every bucket to the world).  A ravel of a matrix is a re-tiling copy on a
TPU (bucket.py).  What is left that only this class does:

- it **owns the optimizer** (``optimizer=``; the trainer's is ignored) and
  keeps one optax state *per bucket chunk*, stacked ``[shards, *chunk]``
  over the shard axis — keyed on bucket boundaries, so autotune rebucketing
  is off and checkpoints are plan-locked (the exact family's sharded
  moments are the replicated layout's arrays cut over the ranks, and are
  neither);
- **``clip_global_norm=``**: global-norm clipping of the *averaged*
  gradient, assembled with one extra scalar psum over the already-sharded
  chunks — the one norm-coupled transform everyone needs, which an
  elementwise-only update cannot express;
- **the staged layout** (``hierarchical=True``): state sharded over
  ``intra`` only, so the cross-slice tier carries ``1/intra`` of the bytes;
- **model-parallel compositions** (tp / pp / expert axes): leaves outside
  the bucket plan step shard-locally under the ``"local"`` state;
- the **overlap contract** over its reduce-scatter (``overlap="on"``).

On pure-dp meshes the params are FLAT-RESIDENT: ``TrainState.params`` holds
the bucket buffers across steps and the trainer differentiates the loss
w.r.t. them directly — the forward materializes leaf views by slicing (a
re-tiling copy on a TPU, except for a shaped bucket, whose buffer is the
leaf: bucket.py) and autodiff's transpose IS the gradient flatten, so the
per-step leaf->flat->leaf round trip the leaf layout paid is gone.
Measured on one v5e chip long before the shaped buckets (ResNet50, batch
128, comm a no-op, both families at the HBM roofline — 909 vs 920 GB/s):
the leaf layout trailed plain allreduce by 7.7%; flat-resident trailed by
~2%.  No benchmark cell runs this class on the chip today (ROADMAP Queue 2).
Leaf pytrees for eval/checkpoint/user code come from
``trainer.unstack_params(state)``.

The wrapped optax transformation must be *elementwise* (adam, adamw, sgd,
rmsprop, ...): the update for element ``i`` may depend only on gradient /
param / state values at ``i``, because each rank updates its own chunk
independently (:func:`is_elementwise`, probed at construction).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax

from ..communication import ReduceOp
from ..obs.spans import phase_scope
from .base import Algorithm, AlgorithmContext, chunk_form


def is_elementwise(optimizer: optax.GradientTransformation) -> bool:
    """Whether every element's update depends on the gradient, parameter and
    state at that element alone — the precondition for a rank to step its
    own chunk of a bucket by itself (ZeRO here, and the exact family's
    sharded update: ``BaguaTrainer._opt_elementwise``).  Probe: stepping a
    2-vector must equal stepping its two halves independently.  Multiple
    steps with gradients of VARYING norm are required — adam-family updates
    are invariant to a per-element-constant gradient scale (m and sqrt v
    scale together), so a single step cannot expose clipping.  Runs on the
    CPU backend (tiny arrays; keeps TPU compile out of a constructor)."""
    try:
        # must be an ADDRESSABLE device: jax.devices("cpu")[0] is
        # process 0's device, and committing the probe to it from any
        # other process crashes that process alone — a divergent-dispatch
        # hang (caught by tests/test_multiprocess_families.py[zero])
        device = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        # CPU backend excluded (e.g. JAX_PLATFORMS=tpu): probe on the
        # default device — two tiny compiles, still worth the guard
        device = jax.local_devices()[0]
    # (eager even where the caller is being traced: ``trainer.init`` under
    # ``jax.eval_shape``)
    with jax.default_device(device), jax.ensure_compile_time_eval():
        # norms 5, 0.14, 2.2: the clip factor changes per step, and
        # differs between the full vector and each half
        gs = [jnp.asarray([3.0, -4.0]), jnp.asarray([0.1, 0.1]),
              jnp.asarray([-1.0, 2.0])]
        p_full = jnp.asarray([0.5, -1.5])
        st_full = optimizer.init(p_full)
        for g in gs:
            up, st_full = optimizer.update(g, st_full, p_full)
            p_full = optax.apply_updates(p_full, up)
        halves = []
        for i in range(2):
            p = jnp.asarray([0.5, -1.5])[i:i + 1]
            st = optimizer.init(p)
            for g in gs:
                up, st = optimizer.update(g[i:i + 1], st, p)
                p = optax.apply_updates(p, up)
            halves.append(p)
        return bool(jnp.allclose(p_full, jnp.concatenate(halves),
                                 rtol=1e-5, atol=1e-7))


class ZeroOptimizerAlgorithm(Algorithm):
    """ZeRO stage-1 data parallelism: replicated params, sharded optimizer
    state, reduce-scatter gradient averaging.

    Args:
        optimizer: an elementwise optax ``GradientTransformation``
            (default ``optax.adam(1e-3)``).  Its state is built per flat
            bucket *chunk* — each rank stores only its ``1/world_size``
            slice.
        clip_global_norm: optional max global grad norm.  Computed on the
            averaged gradient (post reduce-scatter) with a scalar psum, so
            every rank applies the identical scale — the distributed analog
            of ``optax.clip_by_global_norm``.
    """

    owns_optimizer = True
    sharded_opt_state = True
    #: flat residency is ZeRO's native pure-dp layout (this is where the
    #: machinery was born — the measured ~7% leaf->flat->leaf round trip,
    #: VERDICT r3 #4); ``flat_resident="off"`` opts back into the leaf
    #: layout, which model-parallel compositions use regardless
    supports_flat_resident = True
    #: overlap contract (flat-resident layout only — the trainer gates on
    #: ``_zero_flat``): the per-bucket reduce-scatter is issued inside the
    #: overlap window and ``optimizer_update`` consumes the pre-reduced
    #: chunks instead of running its own collective
    supports_overlap = True
    #: set from a cpu-sim record (interleaved A/B on the 8-dev mesh;
    #: deleted in PR 46), never measured on the chip: one controlled run
    #: read 0.89-0.94x of serialized in every trial (splitting the
    #: reduce-scatter away from the chunk update defeats XLA:CPU's
    #: fusion), the rest were noise-bound — so ``auto`` keeps ZeRO
    #: serialized; opt in with ``overlap="on"``.  Owed a cell on real ICI,
    #: where the early reduce-scatter is the point: ROADMAP Queue 3 item 3
    overlap_auto = False

    def __init__(
        self,
        optimizer: Optional[optax.GradientTransformation] = None,
        clip_global_norm: Optional[float] = None,
        hierarchical: bool = False,
        check_elementwise: bool = True,
    ):
        """``hierarchical=True`` (r5): the STAGED layout — optimizer state is
        sharded over the *intra* axis only (replicated across *inter*), and
        the per-bucket dance becomes

            reduce_scatter(grads, intra) -> allreduce(chunk, inter)
            -> shard-local update -> all_gather(params, intra)

        so the inter tier (DCN on multi-pod meshes) carries only
        ``1/intra_size`` of the flat bytes per step — the same wire shape as
        the other families' hierarchical mode — at the cost of storing
        ``1/intra_size`` (not ``1/world``) of the optimizer state per chip.
        On a mesh without the inter/intra tiers it falls back to the flat
        path, like the other families' ``hierarchical`` flag."""
        self.optimizer = optimizer if optimizer is not None else optax.adam(1e-3)
        self.clip_global_norm = clip_global_norm
        self.hierarchical = hierarchical
        if check_elementwise:
            self._check_elementwise()

    def _check_elementwise(self) -> None:
        """Fail loudly at construction when the wrapped transform is not
        elementwise (e.g. ``optax.chain(clip_by_global_norm(...), adam(...))``):
        each rank updates only its own chunk, so a norm-coupled update would
        silently train on per-chunk norms (:func:`is_elementwise`)."""
        if not is_elementwise(self.optimizer):
            raise ValueError(
                "ZeroOptimizerAlgorithm requires an ELEMENTWISE optax "
                "transform (adam/adamw/sgd/rmsprop/...): updating a "
                "vector and updating its halves independently disagree, "
                "so the transform couples elements (global-norm "
                "clipping?).  Use the built-in clip_global_norm= for "
                "distributed clipping, or pass check_elementwise=False "
                "if the coupling is intentional."
            )

    def tensors_to_buckets(self, decl_buckets, named_params, world_size):
        from ..bucket import BucketPlan

        # world-size alignment so every bucket splits into equal rank chunks
        # (the same alignment the compressed scatter-gather ops use,
        # reference bytegrad.py:38-43)
        return BucketPlan.from_declaration_buckets(
            decl_buckets, named_params, alignment=world_size
        )

    # ---- chunk helpers ---------------------------------------------------

    def _staged(self, ctx: AlgorithmContext) -> bool:
        """Whether the hierarchical (intra-sharded) layout is active.  Must
        agree with the trainer's spec-side decision
        (``BaguaTrainer._zero_staged``).  The staged collectives span
        exactly inter × intra, so any extra comm axis (e.g. ``sp`` folded
        into the comm world for partial-grad summation) forces the flat
        path — staged rs/allreduce would skip that axis's reduction."""
        return (
            self.hierarchical
            and ctx.internode is not None
            and ctx.intranode is not None
            and ctx.internode is not ctx.intranode
            and ctx.world_size
            == ctx.internode.nranks() * ctx.intranode.nranks()
        )

    def _shard_comm(self, ctx: AlgorithmContext):
        """The axis the optimizer state shards over: intra when staged,
        the full comm world otherwise."""
        return ctx.intranode if self._staged(ctx) else ctx.comm

    def _my_chunk(self, ctx: AlgorithmContext, flat):
        """This rank's chunk of a bucket over the shard axis: rows where the
        bucket's leading axis divides, else a run of its ravel
        (``base.chunk_form`` — one implementation with the exact family's
        sharded update)."""
        return ctx.owned_chunk(flat, self._shard_comm(ctx))

    def _avg_scatter(self, ctx: AlgorithmContext, flat):
        """Average ``flat`` over the whole comm world and return this rank's
        owned chunk.  Flat: one reduce_scatter over all comm axes.  Staged:
        reduce_scatter over intra, then allreduce the owned chunk over inter
        — the global average with only ``1/intra`` of the bytes crossing the
        inter tier (avg-of-avgs is exact: intra rows are equal-sized)."""
        if not self._staged(ctx):
            # chunked ring when the overlap scheduler set a chunk size,
            # fused psum_scatter otherwise (identical chunk layout)
            return ctx.bucket_reduce_scatter(flat, ReduceOp.AVG)
        # staged: the per-tier helpers ring-chunk each stage against its
        # own link-class target (ICI for the intra scatter, DCN for the
        # inter allreduce) when the overlap scheduler set them; fused
        # psum_scatter/psum otherwise — jaxpr-identical to the pre-tier
        # construction
        chunk = ctx.tier_reduce_scatter(flat, ReduceOp.AVG)
        return ctx.tier_allreduce(chunk, ReduceOp.AVG)

    # ---- overlap scheduler stages ---------------------------------------

    def reduce_bucket_grad(self, ctx: AlgorithmContext, index: int, flat):
        """One bucket's gradient comm = the averaging reduce-scatter; the
        returned buffer is this rank's owned chunk."""
        return self._avg_scatter(ctx, flat)

    def grads_from_reduced(self, ctx: AlgorithmContext, reduced, grads,
                           algo_state, step):
        """Flat-resident layout only: the pre-reduced chunks ride to
        ``optimizer_update``, which then skips its own reduce-scatter (the
        collective was already issued inside the overlap window)."""
        return {"chunks": tuple(reduced), "local": grads["local"]}, algo_state

    # ---- optimizer contract ---------------------------------------------
    #
    # State protocol (shared with the trainer): ``{"buckets": (optax state
    # per bucket chunk, ...), "local": optax state over the name->array dict
    # of NON-plan leaves}``.  "local" covers tp/pp-sharded leaves (3-D
    # parallelism): each shard owns its slice outright and its gradient
    # arrives already dp-averaged from the trainer, so a shard-local
    # elementwise update is exact — no collective, state sharded like the
    # leaf.  With no model-parallel axes "local" is an empty dict's state.

    def _local_named(self, ctx: AlgorithmContext, tree):
        from ..tensor import leaves_by_name

        plan_names = set(ctx.plan.tensor_names)
        return {
            name: leaf for name, leaf in leaves_by_name(tree).items()
            if name not in plan_names
        }

    def init_optimizer_state_sharded(self, ctx: AlgorithmContext, params):
        """Per-rank optimizer state (runs inside ``shard_map``): one optax
        state per bucket built for that rank's flat chunk, plus the local
        state for non-plan (model-parallel) leaves."""
        flats = ctx.plan.flatten_tree(params)
        return {
            "buckets": tuple(
                self.optimizer.init(self._my_chunk(ctx, f)) for f in flats
            ),
            "local": self.init_optimizer_state_local(
                self._local_named(ctx, params)
            ),
        }

    def init_optimizer_state_local(self, local_named: dict):
        """Axis-free init for the non-plan (tp/pp-sharded) leaves — also
        used by the trainer via ``eval_shape`` to derive sharding specs."""
        return self.optimizer.init(local_named)

    def init_optimizer_state(self, params):  # pragma: no cover - guard
        raise NotImplementedError(
            "ZeroOptimizerAlgorithm state is sharded; the trainer must call "
            "init_optimizer_state_sharded inside shard_map"
        )

    def optimizer_update(self, ctx: AlgorithmContext, params, grads, opt_state,
                         algo_state, step):
        if isinstance(params, dict) and "flats" in params:
            # flat-resident layout (pure-dp meshes): the trainer already
            # differentiates w.r.t. the bucket flats, so there is no
            # leaf<->flat round trip here at all — reduce-scatter the flat
            # grads, update the owned chunk, allgather back to flat
            return self._optimizer_update_flat(
                ctx, params, grads, opt_state, algo_state, step
            )
        if self._staged(ctx):
            # backend gates this earlier with its own actionable error; the
            # guard here keeps direct algorithm users honest too
            raise NotImplementedError(
                "hierarchical ZeRO supports the flat-resident (pure-dp) "
                "layout only; drop hierarchical=True when composing with "
                "tp/pp/expert axes"
            )
        gflats = ctx.plan.flatten_tree(grads)
        pflats = ctx.plan.flatten_tree(params)
        # grad averaging and sharding in one collective per bucket
        # (collectives inside the trainer's bagua.optimizer scope name
        # themselves bagua.comm/...: the innermost bagua.* scope wins)
        gchunks = []
        for i, gf in enumerate(gflats):
            with phase_scope(f"bagua.comm/bucket_{i}"):
                gchunks.append(ctx.comm.reduce_scatter(
                    chunk_form(gf, ctx.comm.nranks()), ReduceOp.AVG))
        local_g = self._local_named(ctx, grads)

        if self.clip_global_norm is not None:
            # ||avg grad||² = psum of each rank's chunk contributions
            # (bucket padding is zeros and does not perturb the norm).
            # Local (model-parallel) leaves are excluded: their slices live
            # on tp/pp/ep axes outside this communicator, so a correct
            # global norm would need a second psum over those axes — ZeRO
            # with clipping is supported for pure-dp/sp meshes only.
            if local_g:
                raise NotImplementedError(
                    "clip_global_norm with model-parallel (tp/pp/expert) "
                    "leaves is not supported"
                )
            ssq = sum(
                jnp.sum(jnp.square(g.astype(jnp.float32))) for g in gchunks
            )
            with phase_scope("bagua.comm/clip_norm"):
                gnorm = jnp.sqrt(ctx.comm.allreduce(ssq, ReduceOp.SUM))
            scale = jnp.minimum(1.0, self.clip_global_norm / (gnorm + 1e-12))
            gchunks = [(g * scale.astype(g.dtype)) for g in gchunks]

        new_pflats, new_states = [], []
        for i, (gchunk, pf, st) in enumerate(
                zip(gchunks, pflats, opt_state["buckets"])):
            pchunk = self._my_chunk(ctx, pf)
            updates, st = self.optimizer.update(gchunk, st, pchunk)
            pchunk = optax.apply_updates(pchunk, updates)
            # re-replicate the updated params (rank chunks in rank order)
            with phase_scope(f"bagua.comm/bucket_{i}"):
                new_pflats.append(
                    ctx.comm.allgather(pchunk, tiled=True).reshape(pf.shape))
            new_states.append(st)
        named = ctx.plan.unflatten_to_named(new_pflats)

        local_state = opt_state["local"]
        if local_g:
            local_p = self._local_named(ctx, params)
            updates, local_state = self.optimizer.update(
                local_g, local_state, local_p
            )
            named.update(optax.apply_updates(local_p, updates))

        from ..tensor import tree_from_named

        new_params = tree_from_named(params, named)
        return new_params, {"buckets": tuple(new_states),
                            "local": local_state}, algo_state

    def _optimizer_update_flat(self, ctx: AlgorithmContext, params, grads,
                               opt_state, algo_state, step):
        shard = self._shard_comm(ctx)
        if "chunks" in grads:
            # overlap path: the reduce-scatter already ran per bucket
            # inside the overlap window (grads_from_reduced)
            gchunks = list(grads["chunks"])
        else:
            gchunks = []
            for i, gf in enumerate(grads["flats"]):
                with phase_scope(f"bagua.comm/bucket_{i}"):
                    gchunks.append(self._avg_scatter(ctx, gf))
        if self.clip_global_norm is not None:
            # chunks across the SHARD axis tile the whole flat exactly once
            # (staged: chunks are replicated over inter, so summing over
            # intra alone is the full norm — a comm-world psum would count
            # every element inter_size times)
            ssq = sum(
                jnp.sum(jnp.square(g.astype(jnp.float32))) for g in gchunks
            )
            with phase_scope("bagua.comm/clip_norm"):
                gnorm = jnp.sqrt(shard.allreduce(ssq, ReduceOp.SUM))
            scale = jnp.minimum(1.0, self.clip_global_norm / (gnorm + 1e-12))
            gchunks = [(g * scale.astype(g.dtype)) for g in gchunks]

        new_flats, new_states = [], []
        for i, (gchunk, pf, st) in enumerate(
                zip(gchunks, params["flats"], opt_state["buckets"])):
            pchunk = self._my_chunk(ctx, pf)
            updates, st = self.optimizer.update(gchunk, st, pchunk)
            pchunk = optax.apply_updates(pchunk, updates)
            # re-replicate (rank chunks in rank order over the shard axis;
            # staged: every inter row gathers the identical chunks, so the
            # result stays replicated across inter with no inter traffic).
            # Both gathers are chunk-aware, so the ring pair stays
            # layout-symmetric when overlap chunking is on (the staged one
            # against the ICI tier's target).
            with phase_scope(f"bagua.comm/bucket_{i}"):
                new_flats.append((
                    ctx.bucket_allgather(pchunk) if shard is ctx.comm
                    else ctx.tier_allgather(pchunk)
                ).reshape(pf.shape))
            new_states.append(st)
        new_params = {"flats": tuple(new_flats), "local": params["local"]}
        return new_params, {"buckets": tuple(new_states),
                            "local": opt_state["local"]}, algo_state
