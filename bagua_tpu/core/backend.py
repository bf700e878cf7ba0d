"""BaguaTrainer — the training-loop integration (``with_bagua`` equivalent).

Counterpart of the reference's ``BaguaModule``
(/root/reference/bagua/torch_api/distributed.py:244-508) plus the Rust
``BaguaCommBackend`` scheduler
(/root/reference/rust/bagua-core/bagua-core-internal/src/lib.rs:158-337).

The reference splits one training step across Python hooks, a Rust readiness
scheduler, and a comm worker thread so NCCL calls overlap backward compute.
On TPU the same step is ONE jitted SPMD program: ``shard_map`` over the
data-parallel mesh axes, collectives placed by the algorithm's stages, overlap
done by XLA's async collectives.  What survives of the scheduler is its
*bookkeeping*: bucket plans, re-bucketing on autotune updates, phase switches
(``need_reset``) — all host-side here, each yielding a cached compiled step.
"""

from __future__ import annotations

import logging
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import env
from ..algorithms.base import Algorithm, AlgorithmContext
from ..algorithms.zero import is_elementwise
from ..bucket import (BucketPlan, conform_flats,
                      split_bucket_by_bucket_size)
from ..communication import (
    BaguaCommunicator, ReduceOp, abort, check_abort, collapse_trivial_axes,
)
from ..faults import inject as _inject
from ..obs.spans import (
    ACCUM_SCOPE, phase_scope, trace_span, trace_step_span,
)
from ..obs.step_observer import StepObserver
from ..parallel.mesh import build_mesh, hierarchical_mesh, mesh_axis_size
from ..telemetry import counters
from ..tensor import build_params, _name_of_path

logger = logging.getLogger(__name__)


def _stack_tree(t):
    """Add a leading length-1 per-rank axis to every leaf — the stacked
    state layout the gossip/expert families shard over their rank axis
    (``shard_map`` out_specs put the mesh axis on this new dimension)."""
    return jax.tree.map(lambda x: jnp.asarray(x)[None], t)


def _find_adam_moments(opt_state):
    """Locate adam-family first/second moments inside a nested optax state
    (``ScaleByAdamState``-like: has param-shaped ``mu`` and ``nu``).  Returns
    ``(mu, nu)`` or None — feeds the QAdam switch adapter."""
    if hasattr(opt_state, "mu") and hasattr(opt_state, "nu"):
        return (opt_state.mu, opt_state.nu)
    if isinstance(opt_state, (tuple, list)):
        for item in opt_state:
            found = _find_adam_moments(item)
            if found is not None:
                return found
    return None


#: memo for the flat-safety probe, keyed by the transform itself (optax
#: transforms are NamedTuples of functions — hashable); repeated trainer
#: inits with one optimizer instance pay the probe once
_FLAT_SAFE_MEMO: Dict[Any, bool] = {}


def _optimizer_flattens_safely(optimizer) -> bool:
    """Whether the transform's update commutes with flattening — the
    precondition for running it on bucket-flat state (memoized)."""
    try:
        memo_key = optimizer if isinstance(optimizer, tuple) else None
        hash(memo_key)
    except TypeError:
        memo_key = None
    if memo_key is not None and memo_key in _FLAT_SAFE_MEMO:
        return _FLAT_SAFE_MEMO[memo_key]
    safe = _probe_flatten_safety(optimizer)
    if memo_key is not None:
        _FLAT_SAFE_MEMO[memo_key] = safe
    return safe


def _probe_flatten_safety(optimizer) -> bool:
    """Probe: two update steps on a matrix param must equal the same steps
    on its raveled vector (elementwise transforms commute exactly;
    shape-aware ones diverge on the very first update).  The matrix is
    128x130 because factored second moments (the canonical shape-aware
    family, optax.adafactor) only engage at ``min_dim_size_to_factor`` =
    128 — a tiny probe would wave them through.  Values are full-rank
    pseudo-noise: a rank-1 pattern would make the factored and full
    moments coincide.  Runs on the CPU backend (eager, the same pattern
    as ZeRO's elementwise probe).  A transform the probe cannot run
    (exotic state/dtype requirements) is reported unsafe — falling back
    to the leaf layout only costs the round-trip perf."""
    try:
        try:
            device = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            device = jax.local_devices()[0]
        with jax.default_device(device):
            n = 128 * 130
            base = jnp.sin(jnp.arange(n, dtype=jnp.float32) * 0.37)
            p2 = {"w": (base * 0.5).reshape(128, 130)}
            p1 = {"w": p2["w"].ravel()}
            gs = [
                jnp.cos(jnp.arange(n, dtype=jnp.float32) * k + k)
                .reshape(128, 130) * s
                for k, s in ((0.11, 0.1), (0.41, 1.0))
            ]
            s2, s1 = optimizer.init(p2), optimizer.init(p1)
            for g in gs:
                u2, s2 = optimizer.update({"w": g}, s2, p2)
                p2 = optax.apply_updates(p2, u2)
                u1, s1 = optimizer.update({"w": g.ravel()}, s1, p1)
                p1 = optax.apply_updates(p1, u1)
            return bool(jnp.allclose(p2["w"].ravel(), p1["w"],
                                     rtol=1e-5, atol=1e-7))
    except Exception as e:  # pragma: no cover - transform-dependent
        logger.info("flat-safety probe could not run (%s); keeping the "
                    "leaf layout", e)
        return False


class TrainState(NamedTuple):
    step: jax.Array        # int32 scalar, replicated
    params: Any
    opt_state: Any
    algo_state: Any


class BaguaTrainer:
    """Owns mesh, bucket plan, compiled step cache, and autotune check-ins.

    Args:
        loss_fn: ``loss_fn(params, batch) -> scalar`` (per-shard mean loss).
        optimizer: an optax ``GradientTransformation`` (ignored when the
            algorithm owns its optimizer, as QAdam does).
        algorithm: a :class:`bagua_tpu.algorithms.base.Algorithm`.
        mesh: optional explicit mesh.  Default: hierarchical
            ``('inter','intra')`` mesh when the algorithm asks for
            hierarchical comm, else a flat 1-D ``('dp',)`` mesh — the analog
            of the reference's three communicators (communication.py:47-72).
        dp_axes: mesh axes that carry data parallelism (default: all axes).
        bucket_bytes: bucket size in bytes (default env BAGUA_DEFAULT_BUCKET_SIZE).
    """

    def __init__(
        self,
        loss_fn: Callable,
        optimizer: Optional[optax.GradientTransformation],
        algorithm: Algorithm,
        mesh: Optional[Mesh] = None,
        dp_axes: Optional[Tuple[str, ...]] = None,
        bucket_bytes: Optional[int] = None,
        model_name: str = "bagua_module",
        autotune: Optional[bool] = None,
        donate: bool = True,
        expert_axis: Optional[str] = None,
        expert_params=None,
        expert_keyword: Optional[str] = None,
        seq_axis: Optional[str] = None,
        tp_axis: Optional[str] = None,
        tp_param_dim=None,
        pp_axis: Optional[str] = None,
        pp_param_dim=None,
        accum_steps: int = 1,
        overlap: Optional[str] = None,
        overlap_chunk_bytes: Optional[int] = None,
        overlap_chunk_bytes_intra: Optional[int] = None,
        overlap_chunk_bytes_inter: Optional[int] = None,
        compress_intra: Optional[str] = None,
        compress_inter: Optional[str] = None,
        flat_resident: Optional[str] = None,
        grad_guard: Optional[str] = None,
        grad_guard_budget: int = 3,
    ):
        """``expert_axis``: mesh axis carrying expert parallelism (MoE).
        Expert params are sharded over it and excluded from the data-parallel
        bucket plan (reference ``param.expert`` flags, moe/experts.py:26-29 +
        distributed.py:66).  Which params are experts is decided by
        ``expert_params``: a ``name -> bool`` callable or an explicit
        collection of param names; default = exact-name marking for params
        created by :class:`bagua_tpu.model_parallel.moe.MoEMLP`.
        ``expert_keyword`` (substring matching) is deprecated — it silently
        captured any param whose name contained the keyword.

        ``seq_axis``: mesh axis carrying sequence/context parallelism (ring
        attention / Ulysses).  The batch is replicated over it (each shard
        slices its own sequence chunk, see ``sp_lm_loss_fn``) while gradient
        communication spans it: each shard's grads cover only its chunk's
        contribution, so dp-style averaging over dp × sp restores the full
        gradient.

        ``tp_axis``: mesh axis carrying tensor parallelism (Megatron-style;
        see ``parallel/tensor_parallel.py``).  ``tp_param_dim`` maps a param
        name to the dimension of its GLOBAL array sharded over ``tp_axis``
        (None for replicated params); default: the transformer family's
        ``models.transformer.tp_param_dim``.  TP leaves are excluded from
        the data-parallel bucket plan (each shard owns its slice; grads need
        averaging over dp only), while dense-leaf grads are exact and
        identical across tp thanks to the model's conjugate collectives —
        so the bucket allreduce deliberately does NOT span tp.

        ``pp_axis``: mesh axis carrying pipeline parallelism (GPipe
        microbatch schedule; see ``parallel/pipeline.py``).  Stage-stacked
        leaves (``pp_param_dim(name) == 0``) are sharded and averaged over
        data axes only, like tp slices.  Replicated leaves (embedding,
        head) get PARTIAL grads — each stage contributes only its own use —
        so they are scaled by pp_size and the bucket allreduce DOES span
        pp, turning its average into the required sum.

        ``tp_axis`` and ``pp_axis`` compose (3-D parallelism over
        dp × pp × tp): stage-stacked block kernels that are also
        tensor-parallel carry both placements — ``P(pp, ..., tp, ...)`` —
        with the tp dim (reported in per-layer coordinates) shifted past
        the leading stage dim.  Bucketed (dense) grads still communicate
        over dp + pp only; tp stays out of the bucket plan entirely.

        ``accum_steps``: gradient accumulation.  The per-rank batch leading
        dimension must be ``accum_steps × microbatch``; the step scans the
        microbatches (``lax.scan``, so the backward is compiled once),
        averaging losses and gradients before any algorithm stage runs —
        communication still happens once per step, on the accumulated
        gradient, exactly as if the full batch had fit in memory — unless
        the overlap scheduler restructures the scan (below).

        ``overlap``: the overlap-aware bucket communication scheduler
        (Bagua's core thesis, arXiv 2107.01499: the wins come from WHEN you
        communicate).  ``"off"`` keeps the exact serialized step
        construction — every collective after the full backward/scan.
        ``"on"`` streams per-bucket collectives into compute: with
        ``accum_steps > 1`` the last microbatch is peeled out of the scan
        (bit-identical gradient sum order) so each bucket's collective is
        issued as soon as its accumulated gradient finalizes, overlapping
        with the remaining backward; buckets are re-ordered by observed
        gradient readiness (one-time, host-side) so the first-finalized
        bucket heads the comm sequence.  ``"auto"`` (default, or env
        ``BAGUA_OVERLAP``) resolves by ``Algorithm.overlap_auto`` (set
        from a cpu-sim record).  Supported families: gradient_allreduce,
        bytegrad, and flat-resident ZeRO; others always run serialized.

        ``overlap_chunk_bytes``: target per-rank bytes of one independent
        chunked-ring sub-collective (``communication.ring_allreduce``), so
        even the ``accum_steps == 1`` path exposes multiple independent
        collectives the latency-hiding scheduler can interleave.  Default
        0 / env ``BAGUA_OVERLAP_CHUNK_BYTES``: keep the fused XLA
        collectives.  Only applies while the overlap scheduler is active,
        on single-axis comm worlds.

        ``overlap_chunk_bytes_intra`` / ``overlap_chunk_bytes_inter``:
        per-bandwidth-tier chunk targets for the hierarchical two-level
        decomposition (docs/hierarchical.md) — the slice-local ICI stages
        (and the flat single-axis ring) size against the intra target, the
        cross-slice DCN stage against the inter one, because a chunk that
        amortizes an ICI hop is far too small for a DCN hop.  Default 0 /
        env ``BAGUA_OVERLAP_CHUNK_BYTES_INTRA`` / ``..._INTER``: fall back
        to ``overlap_chunk_bytes`` for that tier.  Setting either is, like
        the link-agnostic knob, an explicit opt-in to the ring path.

        ``compress_intra`` / ``compress_inter``: the per-link-class codec
        policy (docs/compression.md) — what the ring hops of each
        bandwidth tier carry on the wire.  ``auto`` (default, or env
        ``BAGUA_COMPRESS_INTRA`` / ``BAGUA_COMPRESS_INTER``) defers to
        the algorithm family: ByteGrad/QAdam compress the cross-slice DCN
        stage natively (quantized ppermute hops, fp32 accumulation) and
        everything else stays full precision — the Bagua relaxation
        applied only where bytes are expensive.  ``off`` forces full
        precision on the tier (even for the compression families); a
        codec name (``minmax_uint8``/``int8``/``fp8_e4m3``/``fp8_e5m2``)
        forces that codec for every family riding the tier — an explicit
        opt-in to lossy gradient communication for exact families.
        Unlike the chunk knobs these apply to the serialized path too
        (compression is a wire format, not a schedule), and both ride the
        step-cache key, ``BaguaHyperparameter``, and the autotune
        recommendation path (the autopilot's ``compress_dcn`` trend hint
        actuates ``compress_inter`` through it).

        ``flat_resident``: the flat-resident training-state layout
        (docs/flat_layout.md).  ``"on"``: params, gradients, and optimizer
        state live as the bucket plan's flat buffers ACROSS steps — the
        step differentiates the loss w.r.t. the flats directly (the
        forward materializes leaf views by fusable slicing; autodiff's
        scatter-add IS the gradient flatten), collectives consume the
        flats with zero repacking in both the serialized and overlap
        paths, and the optimizer updates the flats natively (a
        ``fuse_optimizer`` wrapper is unwrapped — bucket flats already ARE
        the fused layout).  Removes the per-step leaf->flat->leaf round
        trip every bucketed family otherwise pays (~7% measured for ZeRO,
        VERDICT r3 #4).  ``"off"``: the exact leaf pytree construction.
        ``"auto"`` (default, or env ``BAGUA_FLAT_RESIDENT``): resident
        wherever the family supports it (see
        ``Algorithm.supports_flat_resident``) on a mesh without
        model-parallel axes (tp/pp/expert keep the leaf layout — their
        sharded leaves live outside the bucket plan).  Requires an
        ELEMENTWISE optimizer, like ``fuse_optimizer`` and ZeRO (the
        update for element i may only read element i); shape-aware
        transforms (factored second moments) change meaning on flats —
        use ``flat_resident="off"`` for those.  Leaf pytrees for
        eval/checkpoint/user code come from ``unstack_params(state)``.

        ``grad_guard``: the gradient-health sentinel (docs/robustness.md).
        Every step computes a per-bucket ``isfinite`` verdict on the
        gradients — riding the already-reduced bucket buffers where the
        family replicates them (no extra collective), else one fused
        MIN-allreduce of the per-bucket scalars — surfaced as
        ``trainer.step_metrics["grad_healthy"]``.  Policy ``"off"``
        (default, or env ``BAGUA_GRAD_GUARD``) adds nothing to the traced
        program; ``"warn"`` logs unhealthy steps; ``"skip"`` REWINDS them
        (params/opt/algo state keep their pre-step values — exact in flat
        and leaf layouts and under ``accum_steps > 1``, since the verdict
        is computed on the fully-accumulated gradient) and escalates to
        abort after ``grad_guard_budget`` consecutive skips; ``"abort"``
        raises the comm abort flag on the first unhealthy step.  The
        verdict is identical on every rank, so replicated state never
        diverges.  With the guard on and healthy gradients the selects
        pass the new state through bitwise — loss trajectories are
        byte-identical to ``"off"``."""
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.algorithm = algorithm
        if mesh is None:
            from ..parallel.mesh import get_global_mesh_if_set

            mesh = get_global_mesh_if_set()
        if mesh is None:
            mesh = (
                hierarchical_mesh()
                if algorithm.hierarchical
                else build_mesh()
            )
        self.mesh = mesh
        # fail fast on typo'd axis names: silently nulling them would include
        # expert params in the dense DP plan and corrupt MoE training
        for label, ax in (("expert_axis", expert_axis), ("seq_axis", seq_axis),
                          ("tp_axis", tp_axis), ("pp_axis", pp_axis)):
            if ax is not None and ax not in mesh.axis_names:
                raise ValueError(
                    f"{label}={ax!r} is not a mesh axis "
                    f"(mesh axes: {mesh.axis_names})"
                )
        if tp_axis is not None or pp_axis is not None:
            label = "tp_axis" if tp_axis is not None else "pp_axis"
            if expert_axis is not None:
                raise NotImplementedError(
                    f"combining {label} with expert_axis is not supported yet"
                )
            if not algorithm.replicated_params:
                raise NotImplementedError(
                    f"{label} requires a replicated-params algorithm "
                    "(gossip state is per-rank)"
                )
        self.tp_axis = tp_axis
        self.pp_axis = pp_axis
        if tp_param_dim is None and tp_axis is not None:
            from ..models.transformer import tp_param_dim as _default_tp_dim

            tp_param_dim = _default_tp_dim
        if pp_param_dim is None and pp_axis is not None:
            from ..parallel.pipeline import pp_param_dim as _default_pp_dim

            pp_param_dim = _default_pp_dim
        self._tp_param_dim = tp_param_dim
        self._pp_param_dim = pp_param_dim
        self.expert_axis = expert_axis
        self._expert_filter = self._make_expert_filter(expert_params, expert_keyword)
        self.seq_axis = seq_axis
        if dp_axes is None:
            dp_axes = tuple(
                a for a in mesh.axis_names
                if a in ("dp", "inter", "intra")
                and a not in (self.expert_axis, self.seq_axis, self.tp_axis,
                              self.pp_axis)
            )
            if (
                not dp_axes
                and self.expert_axis is None
                and self.seq_axis is None
                and self.tp_axis is None
                and self.pp_axis is None
            ):
                dp_axes = (mesh.axis_names[0],)
        self.dp_axes = tuple(dp_axes)
        if (
            self.expert_axis is not None or self.seq_axis is not None
        ) and not algorithm.replicated_params:
            raise NotImplementedError(
                "expert/sequence parallelism with gossip (per-rank-weight) "
                "algorithms is not supported yet"
            )
        # the batch is sharded over dp AND ep, so dense-grad comm spans both;
        # expert grads are only averaged over dp (experts differ across ep);
        # sp shards contribute partial grads, so comm spans sp too; pp-dense
        # grads are partial per stage, so comm spans pp (after a pp_size
        # prescale that turns the average into the required sum)
        self.comm_axes = self.dp_axes + tuple(
            a for a in (self.expert_axis, self.seq_axis, self.pp_axis)
            if a is not None
        )
        self.world_size = mesh_axis_size(mesh, self.comm_axes)
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self.accum_steps = int(accum_steps)
        self.overlap = (overlap or env.get_overlap_mode()).strip().lower()
        if self.overlap not in ("auto", "on", "off"):
            raise ValueError(f"overlap must be auto|on|off, got {overlap!r}")
        self.overlap_chunk_bytes = int(
            env.get_overlap_chunk_bytes() if overlap_chunk_bytes is None
            else overlap_chunk_bytes
        )
        self.overlap_chunk_bytes_intra = int(
            env.get_overlap_chunk_bytes_intra()
            if overlap_chunk_bytes_intra is None else overlap_chunk_bytes_intra
        )
        self.overlap_chunk_bytes_inter = int(
            env.get_overlap_chunk_bytes_inter()
            if overlap_chunk_bytes_inter is None else overlap_chunk_bytes_inter
        )
        from ..compression.codecs import validate_codec_policy

        self.compress_intra = validate_codec_policy(
            env.get_compress_intra() if compress_intra is None
            else compress_intra, "compress_intra"
        )
        self.compress_inter = validate_codec_policy(
            env.get_compress_inter() if compress_inter is None
            else compress_inter, "compress_inter"
        )
        #: error-feedback residual machinery allowed here: on unless the
        #: honesty control (BAGUA_EF_RESIDUAL=off) disables it or the mesh
        #: carries model-parallel/expert axes (their stacked algo-state
        #: layouts have no spec mapping for the per-bucket residual).
        #: Whether a residual is ACTUALLY carried is then the algorithm's
        #: call (Algorithm.ef_codec: a stateful codec resolved on its wire
        #: + supports_ef_state).
        self._ef_enabled = (
            not env.is_ef_residual_disabled()
            and self._shard_axis is None
            and self.expert_axis is None
        )
        self.flat_resident = (
            flat_resident or env.get_flat_resident_mode()
        ).strip().lower()
        if self.flat_resident not in ("auto", "on", "off"):
            raise ValueError(
                f"flat_resident must be auto|on|off, got {flat_resident!r}"
            )
        if self.flat_resident == "on" and not self._flat_supported():
            # fail at construction, not first init: "on" on an unsupported
            # configuration is a user error, never a silent fallback
            raise ValueError(
                "flat_resident='on' is not supported here: "
                f"{type(algorithm).__name__} (supports_flat_resident="
                f"{algorithm.supports_flat_resident}) with "
                f"tp/pp axis={self._shard_axis!r}, "
                f"expert axis={self.expert_axis!r} — model-parallel leaves "
                "live outside the bucket plan; use flat_resident='auto' "
                "or 'off'"
            )
        self.grad_guard = (grad_guard or env.get_grad_guard_mode()).strip().lower()
        if self.grad_guard not in ("off", "warn", "skip", "abort"):
            raise ValueError(
                f"grad_guard must be off|warn|skip|abort, got {grad_guard!r}"
            )
        if grad_guard_budget < 1:
            raise ValueError(
                f"grad_guard_budget must be >= 1, got {grad_guard_budget}"
            )
        self.grad_guard_budget = int(grad_guard_budget)
        self._guard_skips = 0
        #: monotonic count of guard rewinds (never reset): async model
        #: averaging compares it across a round's flight window to veto
        #: applying the round's delta on top of a rewound state
        self._guard_rewinds_total = 0
        self._pending_health: list = []
        #: per-step observability surface (host side): after each
        #: ``train_step`` under an active grad guard, ``grad_healthy`` is
        #: the step's scalar verdict and ``grad_health_buckets`` the
        #: per-bucket vector (async jax arrays — reading them syncs)
        self.step_metrics: Dict[str, Any] = {}
        self._overlap_ordered = False
        self.bucket_bytes = bucket_bytes or env.get_default_bucket_size()
        self.model_name = model_name
        self.donate = donate

        comm = BaguaCommunicator(collapse_trivial_axes(mesh, self.comm_axes), mesh)
        inter = BaguaCommunicator("inter", mesh) if "inter" in mesh.axis_names else None
        intra = BaguaCommunicator("intra", mesh) if "intra" in mesh.axis_names else None
        self._comm, self._inter, self._intra = comm, inter, intra

        self._plan: Optional[BucketPlan] = None
        self._named_params = None
        self._step_cache: Dict[Any, Callable] = {}
        #: XLA cost/memory model results cached per step-cache key —
        #: ``step_cost_analysis`` re-lowered and re-queried on EVERY call
        #: before this cache existed, which the ledger's per-step MFU gauge
        #: would have paid every step
        self._cost_analysis_cache: Dict[Any, Dict[str, Any]] = {}
        self._memory_analysis_cache: Dict[Any, Optional[Dict[str, int]]] = {}
        #: key -> threading.Event for cost/memory analyses a background
        #: harvest thread is computing (the per-step MFU path must not pay
        #: an inline lower+compile on the dispatch hot path; a concurrent
        #: synchronous caller joins the harvest instead of re-compiling)
        self._cost_analysis_pending: Dict[Any, threading.Event] = {}
        self._current_step_key: Optional[Tuple] = None
        self._step_counter = 0
        self._phase = 0

        # configured instances by family name, so an autotune family switch
        # that returns to the user's family restores THEIR settings
        name = getattr(algorithm, "name", None)
        self._user_algorithms = {name: algorithm} if name else {}

        self.autotune = env.get_autotune_level() >= 1 if autotune is None else autotune
        if self.autotune and algorithm.sharded_opt_state:
            # a rebucket would orphan the per-bucket chunk states (they are
            # keyed on bucket boundaries, unlike the param-shaped states of
            # the other families)
            logger.warning(
                "autotune disabled: %s shards optimizer state per bucket, "
                "which autotune rebucketing would invalidate",
                type(algorithm).__name__,
            )
            self.autotune = False
        self._autotune_client = None
        self._autotune_failures = 0
        self._autotune_completed = not self.autotune
        self._telemetry_reported = False
        self._pending_state_migration = None
        self._stashed_opt_state = None
        #: flat-resident layout ACTIVE (resolved from the mode at init());
        #: generalizes the old ZeRO-only ``_zero_flat`` gate to every
        #: supports_flat_resident family
        self._flat_resident = False
        #: whether init() has resolved + built the state layout: before
        #: this, a flat_resident recommendation adjusts the MODE (init
        #: builds the layout directly); after, it queues a live
        #: flat<->leaf state migration (:meth:`_apply_flat_resident`)
        self._flat_layout_live = False
        #: the optimizer the compiled step actually runs: the user's, or a
        #: ``fuse_optimizer`` wrapper's inner transform when the resident
        #: flats already are the fused layout (resolved at init())
        self._opt = optimizer
        self._param_template = None

        from ..watchdog import get_comm_timeout_s, get_global_watchdog

        timeout = get_comm_timeout_s()
        self._watchdog = get_global_watchdog(timeout) if timeout else None
        from ..profiling import StepProfiler

        self._profiler = StepProfiler.from_env()
        #: everything that watches a step on the host (cadence, goodput
        #: ledger, anomaly detector, MFU / HBM gauges, beacon, speed
        #: tracker, exporter start-up): docs/observability.md
        self._observer = StepObserver()

    # ---- plan management -----------------------------------------------

    def _ctx(self, plan: BucketPlan, overlap: bool = False) -> AlgorithmContext:
        return self._mark_sharded_update(AlgorithmContext(
            comm=self._comm,
            internode=self._inter,
            intranode=self._intra,
            plan=plan,
            world_size=self.world_size,
            overlap=overlap,
            overlap_chunk_bytes=(
                self.overlap_chunk_bytes or None if overlap else None
            ),
            intra_chunk_bytes=(
                self.overlap_chunk_bytes_intra or None if overlap else None
            ),
            inter_chunk_bytes=(
                self.overlap_chunk_bytes_inter or None if overlap else None
            ),
            # the codec policy applies to the serialized path too —
            # compression is a wire format, not a schedule (the knobs are
            # normalized, so "auto" reaches codec_for unchanged)
            intra_codec=self.compress_intra,
            inter_codec=self.compress_inter,
            flat_resident=self._flat_resident,
            ef_enabled=self._ef_enabled,
        ))

    def _flat_supported(self) -> bool:
        """Whether the flat-resident layout CAN hold this configuration:
        the family implements the contract and every param leaf is in the
        bucket plan (model-parallel axes put sharded leaves outside it, so
        those compositions keep the leaf layout)."""
        return (
            self.algorithm.supports_flat_resident
            and self._shard_axis is None
            and self.expert_axis is None
        )

    def _resolve_flat_resident(self) -> bool:
        """Dispatch gate for the resident layout, resolved once per
        ``init()``.  Explicit on/off wins (``on`` on an unsupported
        configuration already raised at construction); ``auto`` takes the
        resident layout wherever it is supported, the family's flag
        agrees (``Algorithm.flat_resident_auto``, set from cpu-sim),
        and the trainer optimizer commutes with flattening
        (:func:`_optimizer_flattens_safely` — shape-aware transforms fall
        back to the leaf layout instead of silently changing meaning)."""
        if self.flat_resident == "off":
            return False
        if self.flat_resident == "on":
            # supportedness was validated at construction; the optimizer
            # probe still runs — an explicit "on" with a shape-aware
            # transform is a meaning change the user must not get silently
            if not self.algorithm.owns_optimizer and \
                    not _optimizer_flattens_safely(self._flat_opt()):
                raise ValueError(
                    "flat_resident='on' with an optimizer whose update "
                    "does not commute with flattening (shape-aware "
                    "transform, e.g. factored second moments): updating "
                    "a matrix and updating its raveled vector disagree, "
                    "so bucket-flat state would silently change the "
                    "training math.  Use flat_resident='off' (or an "
                    "elementwise transform)."
                )
            return True
        if not (self._flat_supported() and self.algorithm.flat_resident_auto):
            return False
        if not self.algorithm.owns_optimizer and \
                not _optimizer_flattens_safely(self._flat_opt()):
            logger.info(
                "flat_resident auto: optimizer update does not commute "
                "with flattening (shape-aware transform?) — keeping the "
                "leaf layout"
            )
            return False
        return True

    def _flat_opt(self):
        """The transform that would run on the flats (a fused wrapper's
        inner), for the flat-safety probe."""
        inner = getattr(self.optimizer, "fused_inner", None)
        return inner if inner is not None else self.optimizer

    def _overlap_active(self) -> bool:
        """Dispatch gate for the overlap scheduler.  Explicit on/off wins;
        ``auto`` resolves by a gate set from a cpu-sim record, never
        measured on the chip: overlap when there is an accumulation scan to
        stream collectives into (the peel is bit-exact), the serialized
        construction otherwise (ROADMAP Queue 3 item 3) — at
        ``accum_steps == 1`` the backward already feeds the per-bucket
        collectives as open dataflow, so restructuring buys nothing unless
        ring chunking is explicitly requested."""
        if not self.algorithm.supports_overlap:
            return False
        if self.algorithm.sharded_opt_state and not self._flat_resident:
            # ZeRO overlap rides the flat-resident (pure-dp) layout only:
            # the leaf layout's comm happens inside optimizer_update after
            # the leaf->flat round trip, outside the overlap window
            return False
        if self.overlap == "off":
            return False
        if self.overlap == "on":
            return True
        # auto: a gate set from a cpu-sim record (interleaved A/B trials on
        # the 8-dev cpu-sim mesh, record deleted in PR 46), never measured
        # on the chip (ROADMAP Queue 3 item 3): allreduce takes overlap at
        # accum>1 — the peel is bit-exact; ZeRO and bytegrad read slower
        # there (0.9x / 0.99x → overlap_auto=False on those
        # families, overridable with overlap="on").  accum==1 keeps the
        # serialized construction (the backward already feeds the bucket
        # collectives as open dataflow); an explicit chunk size is an
        # opt-in to the ring path at any accum.
        return self.algorithm.overlap_auto and (
            self.accum_steps > 1 or self._any_chunk_bytes()
        )

    def _any_chunk_bytes(self) -> bool:
        """Whether ANY ring chunk target is set (link-agnostic or per-tier)
        — each is an explicit opt-in to the chunked ring path."""
        return bool(
            self.overlap_chunk_bytes
            or self.overlap_chunk_bytes_intra
            or self.overlap_chunk_bytes_inter
        )

    def _reorder_plan_for_overlap(self, state, batch) -> None:
        """One-time host-side re-bucketing by observed gradient readiness
        (reverse execution order) so the overlap scheduler's first-issued
        collective is the first-finalized bucket — the trainer-local analog
        of the autotune service's span-driven re-ordering
        (:meth:`_report_tensor_execution_order`), for runs without the
        sidecar.  Static jaxpr analysis, no compiles; never takes down
        training."""
        try:
            from ..telemetry import profile_tensor_execution_order

            params = self.unstack_params(state)
            spans = profile_tensor_execution_order(self.loss_fn, params, batch)
            order = {s["tensor_name"]: i for i, s in enumerate(spans)}
            decls = [t.declaration() for b in self._plan.buckets
                     for t in b.tensors]
            n = len(order)
            decls.sort(key=lambda d: order.get(d.name, n))
            self.rebucket(split_bucket_by_bucket_size(decls, self.bucket_bytes))
            logger.info(
                "overlap: re-bucketed %d tensors by gradient readiness "
                "(%d buckets)", len(decls), len(self._plan.buckets),
            )
        except Exception as e:
            logger.warning("overlap readiness re-bucketing skipped: %s", e)

    @staticmethod
    def _make_expert_filter(expert_params, expert_keyword):
        if expert_params is not None and expert_keyword is not None:
            raise ValueError("pass expert_params OR expert_keyword, not both")
        if expert_keyword is not None:
            import warnings

            warnings.warn(
                "expert_keyword substring matching is deprecated; pass "
                "expert_params (a name filter or collection of names)",
                DeprecationWarning, stacklevel=3,
            )
            return lambda name: expert_keyword in name
        if expert_params is None:
            from ..model_parallel.moe.layer import is_expert_param

            return is_expert_param
        if callable(expert_params):
            return expert_params
        names = frozenset(expert_params)
        return lambda name: name in names

    def _is_expert_name(self, name: str) -> bool:
        return self.expert_axis is not None and self._expert_filter(name)

    @property
    def _shard_axis(self) -> Optional[str]:
        """Truthy when a model-parallel axis (tp and/or pp) is present;
        param slices of such leaves bypass the bucket plan."""
        return self.tp_axis if self.tp_axis is not None else self.pp_axis

    def _shard_entries(self, name: str) -> Tuple[Tuple[int, str], ...]:
        """((dim, axis), ...) placements for a param leaf — pp stage
        stacking at its reported dim, tp slicing at the tp dim.  When a leaf
        is both pp-stacked and tp-sharded (3-D parallelism), the tp dim —
        reported by ``tp_param_dim`` in per-layer coordinates — shifts one
        right past the leading stage dim.

        Under a sharded-opt-state (ZeRO) algorithm, expert leaves are also
        expressed this way — global ``[n_experts, ...]`` sharded at dim 0
        over the expert axis — instead of the stacked per-rank layout the
        other algorithm families use."""
        entries = []
        if self.pp_axis is not None and self._pp_param_dim is not None:
            d = self._pp_param_dim(name)
            if d is not None:
                entries.append((d, self.pp_axis))
        if self.tp_axis is not None and self._tp_param_dim is not None:
            d = self._tp_param_dim(name)
            if d is not None:
                shift = 1 if entries else 0
                entries.append((d + shift, self.tp_axis))
        if (
            self.expert_axis is not None
            and self.algorithm.sharded_opt_state
            and self._expert_filter(name)
        ):
            entries.append((0, self.expert_axis))
        return tuple(entries)

    def _is_sharded(self, name: str) -> bool:
        return bool(self._shard_entries(name))

    def _build_plan(self, params) -> BucketPlan:
        candidates = [
            p for p in build_params(params)
            if not self._is_expert_name(p.name)
            and not self._is_sharded(p.name)
        ]
        named = self.algorithm.init_tensors(candidates)
        self._named_params = named
        decls = [p.declaration() for p in named]
        decl_buckets = split_bucket_by_bucket_size(decls, self.bucket_bytes)
        return self.algorithm.tensors_to_buckets(decl_buckets, named, self.world_size)

    def _tp_param_spec_tree(self, params):
        """Per-leaf PartitionSpecs: tp/pp leaves sharded along their
        reported dims (both, for 3-D-parallel stacked-and-sliced kernels),
        everything else replicated."""
        def leaf_spec(path, leaf):
            entries = self._shard_entries(_name_of_path(path))
            if not entries:
                return P()
            axes = [None] * (max(d for d, _ in entries) + 1)
            for d, ax in entries:
                axes[d] = ax
            return P(*axes)

        return jax.tree_util.tree_map_with_path(leaf_spec, params)

    def _sharded_specs_by_name(self) -> Dict[str, P]:
        """name -> PartitionSpec for every model-parallel (non-replicated)
        param leaf; requires ``self._param_specs``."""
        sharded = {}
        flat = jax.tree_util.tree_flatten_with_path(self._param_specs)[0]
        for path, spec in flat:
            if spec != P():
                sharded[_name_of_path(path)] = spec
        return sharded

    def _tp_match_spec_tree(self, tree, sharded_by_name):
        """Specs for a param-mirroring tree (optimizer state): a leaf whose
        dotted path ends with a tp param's full name inherits its spec."""
        def leaf_spec(path, leaf):
            name = _name_of_path(path)
            for pn, spec in sharded_by_name.items():
                if name == pn or name.endswith("." + pn):
                    return spec
            return P()

        return jax.tree_util.tree_map_with_path(leaf_spec, tree)

    def rebucket(self, decl_buckets) -> None:
        """Apply an autotune bucketing suggestion (reference
        distributed.py:443-502 ``_bagua_reset_algorithm_buckets``).

        Under the flat-resident layout the training state is laid out IN
        the old plan's buffers, so a plan change queues a flat->flat state
        migration (:func:`bagua_tpu.bucket.relayout_flats` — 1-D segment
        repacking, no leaf round trip) that the next ``train_step``
        applies before dispatching the recompiled step."""
        if self.algorithm.sharded_opt_state:
            raise ValueError(
                "cannot rebucket: the algorithm's optimizer state is sharded "
                "per bucket and would be invalidated by new bucket boundaries"
            )
        old_plan = self._plan
        self._plan = self.algorithm.tensors_to_buckets(
            decl_buckets, self._named_params, self.world_size
        )
        if (
            # the error-feedback residual is plan-keyed algo state even
            # under the leaf layout, so an active EF codec makes a plan
            # change a state migration too (relayout_algo_state carries
            # the residual across the new bucket boundaries)
            (self._flat_resident or self._ef_active())
            and old_plan is not None
            and old_plan.signature() != self._plan.signature()
        ):
            self._queue_state_migration(
                self._make_flat_migration(old_plan, self._plan)
            )

    def _ef_active(self) -> bool:
        """Whether the CURRENT configuration carries the error-feedback
        residual in algo_state (a stateful codec resolved on this family's
        wire) — plan-keyed state, so rebuckets and codec-knob flips must
        migrate it."""
        if self._plan is None:
            return False
        return self.algorithm.ef_codec(self._ctx(self._plan)) is not None

    def _sync_ef_state(self, was_active: bool) -> None:
        """Queue a state migration when a knob change flipped whether the
        error-feedback residual is carried: newly active starts from zero
        residuals (the published EF algorithms' init), newly inactive
        drops the accumulated residual — both loud, both applied before
        the next compiled step dispatches."""
        now = self._ef_active()
        if now == was_active:
            return
        plan = self._plan
        world = self.world_size

        if now:
            def add_ef(state: TrainState) -> TrainState:
                if state.algo_state is not None:
                    return state  # already carried (idempotent re-queue)
                logger.info(
                    "error-feedback residual enabled (codec policy flip): "
                    "starting from zero residuals for %d buckets",
                    len(plan.buckets),
                )
                ef = {"buckets": tuple(
                    jnp.zeros((world,) + b.buffer_shape, jnp.float32)
                    for b in plan.buckets
                )}
                return state._replace(algo_state={"ef": ef})

            self._queue_state_migration(add_ef)
        else:
            def drop_ef(state: TrainState) -> TrainState:
                a = state.algo_state
                if not (isinstance(a, dict) and "ef" in a):
                    return state
                logger.info(
                    "error-feedback residual disabled (codec policy "
                    "flip): dropping the accumulated residual"
                )
                rest = {k: v for k, v in a.items() if k != "ef"}
                return state._replace(algo_state=rest or None)

            self._queue_state_migration(drop_ef)

    def _queue_state_migration(self, fn) -> None:
        """Compose ``fn`` onto the pending state migration (earlier-queued
        migrations run first) — an autotune family switch immediately
        followed by its alignment rebucket must apply both, in order."""
        prev = self._pending_state_migration
        self._pending_state_migration = lambda state: self._place_state(
            fn(state if prev is None else prev(state)))

    @staticmethod
    def _is_flat_container(x) -> bool:
        """The ``{"flats", "local"}`` dict marking a bucket-flat-resident
        subtree — the protocol shared with the algorithm stages.  Optimizer
        states mirror the param pytree, so the same marker locates every
        flat buffer group inside arbitrary optax state nesting."""
        return isinstance(x, dict) and set(x.keys()) == {"flats", "local"}

    def _relayout_tree(self, tree, old_plan, new_plan, consume=False):
        """Migrate every flat-resident subtree of ``tree`` (params, or an
        optimizer state mirroring them) from ``old_plan`` to ``new_plan``.
        Elementwise optimizer state is exactly as relayout-safe as the
        params it mirrors: its flat buffers share the plan's offsets, and
        bucket padding stays zero under elementwise updates.

        ``consume`` frees each old flat buffer as soon as its successor
        exists, as the donating step would have: the caller's reference to
        the old state otherwise keeps a second copy of it on the device
        while the recompiled step loads (RESOURCE_EXHAUSTED for BERT-Large
        at ``accum_steps=4`` on a 16 GB v5e, PR 28)."""
        from ..bucket import relayout_flats

        is_zp = self._is_flat_container
        # a tensor that is its own (shaped) bucket under both plans keeps
        # its buffer: nothing to compute, and no second copy of it
        tensors_of = lambda b: b.signature()[2]  # (name, shape, dtype), ...
        own = {tensors_of(b): i for i, b in enumerate(old_plan.buckets)
               if b.shaped}
        kept = {i: own[tensors_of(b)]
                for i, b in enumerate(new_plan.buckets)
                if b.shaped and tensors_of(b) in own}
        rest = [i for i in range(len(new_plan.buckets)) if i not in kept]
        # one program a tree, not a dispatch (and on a chip a compile) per
        # tensor segment; params and every moment share it by shape
        relayout = jax.jit(lambda flats: [
            relayout_flats(old_plan, new_plan, flats)[i] for i in rest
        ])

        def fix(x):
            if is_zp(x):
                old = tuple(x["flats"])
                flats = [old[kept[i]] if i in kept else None
                         for i in range(len(new_plan.buckets))]
                for i, f in zip(rest, relayout(old)):
                    flats[i] = f
                if consume:
                    # every other new flat is a fresh buffer (slices,
                    # concatenated)
                    for i, f in enumerate(old):
                        if i not in kept.values():
                            f.delete()
                return {"flats": tuple(flats), "local": x["local"]}
            return x

        return jax.tree.map(fix, tree, is_leaf=is_zp)

    def _make_flat_migration(self, old_plan, new_plan):
        def migrate(state: TrainState) -> TrainState:
            logger.info(
                "flat-resident relayout: migrating training state "
                "%d -> %d buckets", len(old_plan.buckets),
                len(new_plan.buckets),
            )
            if self._stashed_opt_state is not None:
                # a displaced optax state stashed across a qadam switch is
                # plan-laid-out too; keep it restorable after the rebucket
                self._stashed_opt_state = self._relayout_tree(
                    self._stashed_opt_state, old_plan, new_plan
                )
            # the state handed to train_step is donated to the step; a
            # migration in front of it consumes it the same way
            consume = self.donate
            return state._replace(
                params=self._relayout_tree(state.params, old_plan, new_plan,
                                           consume),
                opt_state=self._relayout_tree(state.opt_state, old_plan,
                                              new_plan, consume),
                algo_state=self.algorithm.relayout_algo_state(
                    old_plan, new_plan, state.algo_state
                ),
            )

        return migrate

    # ---- state init ------------------------------------------------------

    def init(self, params) -> TrainState:
        # copy: step buffers are donated, the caller keeps their params alive
        params = jax.tree.map(lambda x: jnp.array(x, copy=True), params)
        # structure/shape/dtype template for rebuilding the leaf pytree from
        # flat-resident layouts (ZeRO) in traced code
        self._param_template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
            params,
        )
        self._plan = self._build_plan(params)
        if self.autotune and not self._autotune_completed:
            self._autotune_register_tensors()
            # a family switch during registration needs no migration: the
            # state below is built directly in the new family's layout
            self._pending_state_migration = None
        plan = self._plan
        algo = self.algorithm
        self._flat_resident = self._resolve_flat_resident()
        self._opt = self.optimizer
        if (
            self._flat_resident
            and not algo.owns_optimizer
            and getattr(self.optimizer, "fused_inner", None) is not None
        ):
            # bucket flats already ARE a fused layout (one 1-D buffer per
            # dtype-homogeneous bucket): run the wrapped transform on them
            # natively instead of re-concatenating into the wrapper's
            # private per-dtype buffers every step
            self._opt = self.optimizer.fused_inner
        self._flat_layout_live = True
        ctx = self._ctx(plan)
        mesh = self.mesh
        # everything init returns is COMMITTED to the mesh, like every
        # step's output: jax keys its trace cache on the input arrays' mesh,
        # so a default-device state made the second train_step trace and
        # compile the whole program again (chip_smoke PR 22: 64 s + 64 s
        # for BERT-Large on one v5e chip)
        replicated = NamedSharding(mesh, P())
        step0 = jax.jit(lambda: jnp.zeros((), jnp.int32),
                        out_shardings=replicated)()

        if algo.owns_optimizer:
            opt_init = algo.init_optimizer_state
        else:
            opt_init = self._opt.init

        if self.expert_axis is not None and not algo.sharded_opt_state:
            # everything is stacked per ep-rank (leading axis sharded over
            # 'ep'): expert leaves enter as global [n_experts, ...] and are
            # split; dense leaves are replicated copies kept in lockstep by
            # the dense-grad allreduce
            ep = self.expert_axis

            def leaf_spec(path, leaf):
                return P(ep) if self._is_expert_name(_name_of_path(path)) else P()

            in_specs = jax.tree_util.tree_map_with_path(leaf_spec, params)

            def init_fn(p):
                a = algo.init_state(ctx, p)
                o = opt_init(p)
                return _stack_tree(p), _stack_tree(o), _stack_tree(a)

            out_spec = P((ep,))
            p_stacked, opt_state, algo_state = jax.jit(
                shard_map(init_fn, mesh=mesh, in_specs=(in_specs,),
                          out_specs=(out_spec, out_spec, out_spec),
                          check_vma=False)
            )(params)
            return TrainState(
                step0, p_stacked, opt_state, algo_state
            )

        if algo.replicated_params and algo.sharded_opt_state:
            # ZeRO-1 layout: dense params replicated, their optimizer state
            # sharded over the comm axes (stacked leading axis — the same
            # spec machinery as the gossip algorithms' per-rank state).
            # With tp/pp, the "local" state part mirrors the sharded leaves'
            # own placements (state protocol: {"buckets", "local"}).
            #
            # Pure-dp meshes use the FLAT-RESIDENT layout (resolved above,
            # ``flat_resident="auto"`` default): params live as the bucket
            # flat buffers across steps and the step differentiates w.r.t.
            # the flats directly — the forward unflatten is slicing (a
            # shaped bucket's buffer is the leaf itself: bucket.py) and
            # autodiff's transpose of it IS the gradient flatten, so the
            # per-step leaf->flat->leaf round trip (the measured ~7%
            # single-chip ZeRO overhead, VERDICT r3 #4) disappears.
            # Model-parallel compositions (and flat_resident="off") keep
            # the leaf layout.
            if self._zero_staged() and not self._flat_resident:
                raise NotImplementedError(
                    "hierarchical ZeRO supports the flat-resident (pure-dp) "
                    "layout only; drop hierarchical=True when composing "
                    "with tp/pp/expert axes"
                )
            in_spec = P()
            local_spec = P()
            if self._shard_axis is not None or self.expert_axis is not None:
                self._param_specs = self._tp_param_spec_tree(params)
                sharded = self._sharded_specs_by_name()
                in_spec = self._param_specs
                # axis-free eval_shape on LOCAL slice shapes gives the local
                # state's structure; specs then follow the matching leaf
                local_template = {}
                for p in build_params(params):
                    entries = self._shard_entries(p.name)
                    if entries:
                        shape = list(p.shape)
                        for d, ax in entries:
                            shape[d] //= mesh.shape[ax]
                        local_template[p.name] = jax.ShapeDtypeStruct(
                            tuple(shape), p.dtype
                        )
                local_struct = jax.eval_shape(
                    algo.init_optimizer_state_local, local_template
                )
                local_spec = self._tp_match_spec_tree(local_struct, sharded)
            # staged (hierarchical) ZeRO: chunk states stack over INTRA only
            # and are replicated across inter — must mirror the algorithm's
            # _staged()/_shard_comm() decision exactly
            self._zero_opt_specs = {
                "buckets": (
                    P(("intra",)) if self._zero_staged()
                    else P(self.comm_axes)
                ),
                "local": local_spec,
            }

            if self._flat_resident:

                def init_fn_flat(p):
                    a = algo.init_state(ctx, p)
                    o = algo.init_optimizer_state_sharded(ctx, p)
                    zp = {"flats": tuple(plan.flatten_tree(p)), "local": {}}
                    return zp, {"buckets": _stack_tree(o["buckets"]),
                                "local": o["local"]}, _stack_tree(a)

                zparams, opt_state, algo_state = jax.jit(
                    shard_map(init_fn_flat, mesh=mesh, in_specs=(in_spec,),
                              out_specs=(P(), self._zero_opt_specs,
                                         P(self.comm_axes)),
                              check_vma=False)
                )(params)
                return TrainState(step0, zparams,
                                  opt_state, algo_state)

            def init_fn(p):
                a = algo.init_state(ctx, p)
                o = algo.init_optimizer_state_sharded(ctx, p)
                return {"buckets": _stack_tree(o["buckets"]),
                        "local": o["local"]}, _stack_tree(a)

            opt_state, algo_state = jax.jit(
                shard_map(init_fn, mesh=mesh, in_specs=(in_spec,),
                          out_specs=(self._zero_opt_specs, P(self.comm_axes)),
                          check_vma=False)
            )(params)
            return TrainState(step0, params, opt_state, algo_state)

        if algo.replicated_params:
            # algo-state specs: replicated by default; the error-feedback
            # residual's per-bucket flats stack per rank over the comm axes
            aspecs = algo.algo_state_specs(ctx, P(), P(self.comm_axes))
            if self._flat_resident:
                # flat-resident replicated layout (allreduce/bytegrad/
                # qadam): params live as the bucket flats; optimizer state
                # is built directly IN flat layout, so the update runs on
                # the flats natively — never a leaf-shaped moment in sight
                resident, moments = self._state_shardings(plan, replicated)
                zparams = jax.jit(
                    lambda p: {"flats": tuple(plan.flatten_tree(p)),
                               "local": {}},
                    out_shardings=resident,
                )(params)
                opt_state = jax.jit(opt_init, out_shardings=moments)(zparams)

                def init_fn(p):
                    return algo.init_state(ctx, p)

                algo_state = jax.jit(
                    shard_map(init_fn, mesh=mesh, in_specs=(P(),),
                              out_specs=aspecs, check_vma=False)
                )(params)
                return TrainState(step0, zparams,
                                  opt_state, algo_state)
            if self._shard_axis is None:
                params = jax.jit(lambda p: p,
                                 out_shardings=replicated)(params)
                opt_state = jax.jit(opt_init,
                                    out_shardings=replicated)(params)
            else:
                # tp/pp leaves take their placements from the step's
                # in_specs at the first dispatch
                opt_state = jax.jit(opt_init)(params)

            def init_fn(p):
                return algo.init_state(ctx, p)

            algo_state = jax.jit(
                shard_map(init_fn, mesh=mesh, in_specs=(P(),),
                          out_specs=aspecs, check_vma=False)
            )(params)
            if self._shard_axis is not None:
                if algo_state is not None:
                    # optimizer-owned state (QAdam momenta) IS supported —
                    # it rides the suffix-matched opt_state specs; only
                    # algorithm-side state trees have no spec mapping yet
                    raise NotImplementedError(
                        "tensor/pipeline parallelism with algorithms that "
                        "carry init_state trees is not supported yet"
                    )
                self._param_specs = self._tp_param_spec_tree(params)
                self._opt_specs = self._tp_match_spec_tree(
                    opt_state, self._sharded_specs_by_name()
                )
            return TrainState(step0, params, opt_state, algo_state)

        # per-rank (gossip) state: stack every leaf along a leading rank
        # axis.  Flat-resident gossip keeps the same stacked protocol over
        # the {"flats", "local"} container — each rank's row holds ITS
        # flat weights, which is exactly what the gossip exchanges consume.
        def init_fn(p):
            a = algo.init_state(ctx, p)
            if self._flat_resident:
                p = {"flats": tuple(plan.flatten_tree(p)), "local": {}}
            o = opt_init(p)
            return _stack_tree(p), _stack_tree(o), _stack_tree(a)

        specs = P(self.dp_axes)
        p_stacked, opt_state, algo_state = jax.jit(
            shard_map(init_fn, mesh=mesh, in_specs=(P(),),
                      out_specs=(specs, specs, specs), check_vma=False)
        )(params)
        return TrainState(step0, p_stacked, opt_state, algo_state)

    # ---- gradient-health sentinel (traced helpers) -----------------------

    def _grad_health_vec(self, plan: BucketPlan, grads):
        """Per-bucket finiteness of ``grads`` as a float32 vector (traced):
        1.0 = every element of the bucket is finite.  Leaves outside the
        bucket plan (model-parallel/expert slices, flat-layout ``local``
        entries) share one trailing slot.  Works on both gradient layouts
        — the ``{"flats", "local"}`` container checks its resident buffers
        directly (zero repacking)."""
        extras = []
        if self._is_flat_container(grads):
            flags = [jnp.isfinite(f).all() for f in grads["flats"]]
            extras = [jnp.isfinite(v).all()
                      for v in jax.tree.leaves(grads["local"])]
        else:
            bucket_of = {t.name: i for i, b in enumerate(plan.buckets)
                         for t in b.tensors}
            per = [[] for _ in plan.buckets]
            for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
                flag = jnp.isfinite(leaf).all()
                i = bucket_of.get(_name_of_path(path))
                (per[i] if i is not None else extras).append(flag)
            flags = [jnp.stack(fl).all() if fl else jnp.bool_(True)
                     for fl in per]
        if extras:
            flags.append(jnp.stack(extras).all())
        if not flags:  # nothing to check (empty plan, no leaves)
            return jnp.ones((1,), jnp.float32)
        return jnp.stack(flags).astype(jnp.float32)

    def _apply_grad_poison(self, plan: BucketPlan, grads, step, specs):
        """Chaos: compile armed ``grad.poison`` specs into the step — at
        the spec's (traced) step number, the first element of the target
        bucket's gradient becomes NaN/Inf.  Off-step the gradient passes
        through bitwise (a full select, not ``+0.0`` — that would flip
        ``-0.0`` gradients)."""
        for spec in specs:
            bad = jnp.float32(jnp.nan if spec.kind == "nan" else jnp.inf)
            # a traced fault cannot mutate host fire-counters, so count is
            # compiled in as a step window: step=K fires exactly at K;
            # step=None fires on the first `count` steps (count<0: every
            # step)
            if spec.step is not None:
                fire = step == jnp.int32(spec.step)
            elif spec.count < 0:
                fire = jnp.bool_(True)
            else:
                fire = step < jnp.int32(spec.count)
            b = spec.bucket % max(1, len(plan.buckets))
            if self._is_flat_container(grads):
                flats = list(grads["flats"])
                f = flats[b]
                flats[b] = jnp.where(
                    fire, f.at[(0,) * f.ndim].set(bad.astype(f.dtype)), f)
                grads = {"flats": tuple(flats), "local": grads["local"]}
            else:
                target = plan.buckets[b].tensors[0].name

                def poison_leaf(path, g, _t=target, _fire=fire, _bad=bad):
                    if _name_of_path(path) != _t:
                        return g
                    poisoned = g.at[(0,) * g.ndim].set(_bad.astype(g.dtype))
                    return jnp.where(_fire, poisoned, g)

                grads = jax.tree_util.tree_map_with_path(poison_leaf, grads)
        return grads

    # ---- step ------------------------------------------------------------

    def _make_step_fn(self, plan: BucketPlan):
        algo = self.algorithm
        overlap = self._overlap_active()
        ctx = self._ctx(plan, overlap=overlap)
        mesh = self.mesh
        dp = self.dp_axes
        guard = self.grad_guard
        poison_specs = _inject.armed_traced_specs("grad.poison")
        # post-comm gradients are bitwise-identical on every rank only for
        # dense allreduce-style families on a mesh without model-parallel
        # axes — there the health check rides the already-reduced buffers
        # and needs NO collective of its own (non-finite contributions
        # propagate through the sum); everything else checks locally and
        # combines verdicts with one fused pmin
        replicated_health = (
            algo.grad_health_replicated
            and self.expert_axis is None
            and self._shard_axis is None and not ctx.sharded_update
        )
        # gossip-style families keep PER-RANK weight replicas, so the guard
        # verdict is per-rank too: each rank rewinds its own replica (the
        # next exchange re-syncs a skipped rank) and no health collective
        # is added
        local_health = not algo.replicated_params
        mp_health = (  # (or its own chunks of the parameters alone)
            self.expert_axis is not None or self._shard_axis is not None
            or ctx.sharded_update)
        health_axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
        replicated = algo.replicated_params
        expert = self.expert_axis
        # per-shard state is stacked (leading rank axis) for gossip
        # algorithms and for expert parallelism — except under ZeRO, whose
        # layout expresses expert leaves as dim-0-sharded global arrays
        stacked = (
            (not replicated) or expert is not None
        ) and not algo.sharded_opt_state
        # ZeRO-1: only opt/algo state carries the per-rank stacked axis;
        # params stay replicated (model-parallel leaves: sharded in place)
        opt_stacked = replicated and algo.sharded_opt_state
        _unstack = lambda t: jax.tree.map(lambda x: x[0], t)
        _stack = _stack_tree
        # expert grads average over dp (+sp: partial-sequence contributions)
        # but never over ep, where experts differ
        expert_dp = tuple(
            a for a in dp + ((self.seq_axis,) if self.seq_axis else ())
            if mesh.shape[a] > 1
        )
        leaf_view = self._flat_leaf_view if self._flat_resident else None

        def loss_on(zp, b):
            # ONE scope around the loss: JAX's own transform wrappers split
            # it into forward (jvp(bagua.loss)), backward
            # (transpose(jvp(bagua.loss))) and remat replay
            # (.../rematted_computation/...) in every instruction's op_name
            with phase_scope("bagua.loss"):
                if leaf_view is not None:
                    # flat-resident params: materialize the leaf view —
                    # a shaped bucket's buffer is the leaf; the others are
                    # sliced and reshaped out of their 1-D flats (a physical
                    # re-tiling on a TPU, not a fused slice: bucket.py) and
                    # autodiff w.r.t. zp pads the grads straight back into
                    # that layout.  The slicing names itself bagua.layout.
                    zp = leaf_view(zp)
                return self.loss_fn(zp, b)

        # the compiled module is named after this function (jit_bagua_step).
        # The name is part of the persistent compile-cache key; scope
        # metadata is NOT (JAX strips locations from the key), so a cache
        # shared with a checkout that predates the phase scopes would hand
        # back an executable without them under the old name
        def bagua_step(state: TrainState, batch):
            step, params, opt_state, algo_state = state
            if stacked:
                params, opt_state, algo_state = (
                    _unstack(params), _unstack(opt_state), _unstack(algo_state)
                )
            elif opt_stacked:
                opt_state = {"buckets": _unstack(opt_state["buckets"]),
                             "local": opt_state["local"]}
                algo_state = _unstack(algo_state)
            resident = params  # (sharded update: this rank's chunks, gathered
            if ctx.sharded_update:  # once a step, outside the micro-batch loop)
                params = ctx.gather_resident(resident)

            if self.accum_steps > 1:
                accum = self.accum_steps

                def reshape_mb(x):
                    if x.shape[0] % accum:
                        raise ValueError(
                            f"batch leading dim {x.shape[0]} is not divisible "
                            f"by accum_steps={accum}"
                        )
                    return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

                # what the accumulation itself costs on the device names
                # itself (a plain scope, no ``bagua.*`` phase): the
                # micro-batch views, the carry's zeros, the adds and the
                # final division — not the micro-steps' loss, which reads
                # as forward / backward under ``bagua.loss`` like any other
                with phase_scope(ACCUM_SCOPE):
                    microbatches = jax.tree.map(reshape_mb, batch)

                def micro_step(carry, mb):
                    loss_sum, grad_sum = carry
                    l, g = jax.value_and_grad(loss_on)(params, mb)
                    with phase_scope(ACCUM_SCOPE):
                        return (loss_sum + l,
                                jax.tree.map(jnp.add, grad_sum, g)), None

                # carry dtype must match micro_step's promoted loss dtype
                mb0 = jax.tree.map(lambda x: x[0], microbatches)
                loss_dtype = jax.eval_shape(loss_on, params, mb0).dtype
                with phase_scope(ACCUM_SCOPE):
                    zero = (
                        jnp.zeros((), loss_dtype),
                        jax.tree.map(jnp.zeros_like, params),
                    )
                if overlap:
                    # Overlap scheduler: peel the LAST microbatch out of
                    # the scan.  A scan is one opaque while-op whose
                    # results exist only at loop exit, so every collective
                    # must wait for the whole scan; with the tail peeled,
                    # the final backward is open dataflow — each bucket's
                    # accumulated gradient (carry + tail grad, elementwise)
                    # finalizes as the backward produces that bucket's
                    # leaves, and its collective (issued below) can run
                    # while later buckets are still being computed.  The
                    # gradient sum order is unchanged, so the peeled and
                    # scanned constructions are bit-identical.
                    with phase_scope(ACCUM_SCOPE):
                        head = jax.tree.map(lambda x: x[:-1], microbatches)
                        tail = jax.tree.map(lambda x: x[-1], microbatches)
                    (loss, grads), _ = jax.lax.scan(micro_step, zero, head)
                    (loss, grads), _ = micro_step((loss, grads), tail)
                else:
                    (loss, grads), _ = jax.lax.scan(
                        micro_step, zero, microbatches
                    )
                with phase_scope(ACCUM_SCOPE):
                    loss = loss / accum
                    grads = jax.tree.map(lambda g: g / accum, grads)
            else:
                loss, grads = jax.value_and_grad(loss_on)(params, batch)
            if poison_specs:
                # chaos: traced NaN/Inf injection into the accumulated
                # gradient (pre-comm, so detection sees exactly what the
                # collectives would spread)
                with phase_scope("bagua.guard"):
                    grads = self._apply_grad_poison(plan, grads, step,
                                                    poison_specs)
            health_vec = None
            if self.pp_axis is not None and mesh.shape[self.pp_axis] > 1:
                # replicated-leaf grads are PARTIAL per pipeline stage: the
                # bucket allreduce spans pp, so prescaling by pp_size turns
                # its average into the required cross-stage sum
                pp_size = mesh.shape[self.pp_axis]

                def pp_dense_grad(path, g):
                    if self._is_sharded(_name_of_path(path)):
                        return g
                    return g * pp_size

                grads = jax.tree_util.tree_map_with_path(pp_dense_grad, grads)
            # one comm stage: the overlap scheduler streams the same
            # per-bucket reduction the serialized stage issues (allreduce,
            # bytegrad's codec pipeline, ZeRO's reduce-scatter all plug in
            # via reduce_bucket_grad), on grads the peeled tail micro-batch
            # left as open dataflow.  Under the flat-resident layout the
            # grads already are the bucket flats.  The span runs at TRACE
            # time (host-side only — the jaxpr is unchanged).
            with phase_scope("bagua.comm", "trace/comm_stage",
                             overlap=overlap, buckets=len(plan.buckets)):
                stage = (algo.process_grads_bucketed if overlap
                         else algo.process_grads)
                grads, algo_state = stage(ctx, grads, params, algo_state,
                                          step)
            if expert is not None:
                # Expert grads bypass the bucket plan.  The all_to_all
                # backward already SUMS every ep shard's loss contribution
                # into the owning shard's expert grad, while each shard's
                # loss is a local mean — so the global-mean gradient needs a
                # 1/ep_size rescale, then averaging over the dp(+sp) axes
                # where experts are replicated.
                ep_size = mesh.shape[expert]

                def expert_grad(g):
                    with phase_scope("bagua.comm/expert"):
                        g = g / ep_size
                        return (jax.lax.pmean(g, expert_dp) if expert_dp
                                else g)

                grads = jax.tree_util.tree_map_with_path(
                    lambda path, g: (
                        expert_grad(g)
                        if self._is_expert_name(_name_of_path(path)) else g
                    ),
                    grads,
                )
            if self._shard_axis is not None:
                # tp/pp-slice grads bypass the bucket plan: each shard owns
                # its slice (complete gradient) — average over the data axes
                # only, no rescale
                tp_dp = expert_dp

                def tp_grad(path, g):
                    if not self._is_sharded(_name_of_path(path)) or not tp_dp:
                        return g
                    with phase_scope("bagua.comm/model_parallel"):
                        return jax.lax.pmean(g, tp_dp)

                grads = jax.tree_util.tree_map_with_path(tp_grad, grads)
            if guard != "off" and replicated_health:
                # piggybacked health: the reduced bucket buffers are the
                # SAME array on every rank, and a NaN/Inf contribution from
                # any rank survives the sum — so per-bucket isfinite on
                # them is a globally consistent verdict, no extra
                # collective launched
                with phase_scope("bagua.guard"):
                    health_vec = self._grad_health_vec(plan, grads)
            with phase_scope("bagua.optimizer", "trace/optimizer_apply",
                             owned=algo.owns_optimizer):
                params, algo_state = algo.process_pre_step(
                    ctx, params, algo_state, step)
                if algo.owns_optimizer:
                    params, opt_state, algo_state = algo.optimizer_update(
                        ctx, params, grads, opt_state, algo_state, step
                    )
                else:
                    # sharded update: the comm stage left this rank its own
                    # chunk of each reduced bucket, and the parameters and
                    # the moments rest as those chunks — an elementwise
                    # transform steps a chunk as it steps the whole, and the
                    # next step gathers what it reads
                    owned = resident if ctx.sharded_update else params
                    updates, opt_state = self._opt.update(grads, opt_state,
                                                          owned)
                    params = optax.apply_updates(owned, updates)
                params, algo_state = algo.process_post_step(
                    ctx, params, algo_state, step)
            if guard != "off" and not replicated_health:
                # families whose post-comm gradient representation is not
                # rank-replicated detect on the UPDATED params instead:
                # every elementwise optimizer propagates a NaN/Inf gradient
                # into its parameter, params are materialized outputs (so
                # reading them cannot perturb backward fusion the way
                # reductions over raw grad arrays measurably do), and the
                # family's own comm makes the verdict consistent where it
                # must be — ZeRO's allgather spreads a poisoned chunk into
                # every rank's params, QAdam's momentum allreduce is
                # replicated, gossip replicas are per-rank by design (each
                # rank rewinds its own).  Model-parallel slices, and the
                # sharded update's resident chunks, live only on their
                # shard, so those fuse verdicts with one tiny pmin.
                with phase_scope("bagua.guard"):
                    health_vec = self._grad_health_vec(plan, params)
                    if mp_health and health_axes:
                        with phase_scope("bagua.comm/health"):
                            health_vec = jax.lax.pmin(health_vec, health_axes)

            with phase_scope("bagua.comm/loss"):
                loss = ctx.comm.allreduce(loss, ReduceOp.AVG)
            if stacked:
                params, opt_state, algo_state = (
                    _stack(params), _stack(opt_state), _stack(algo_state)
                )
            elif opt_stacked:
                opt_state = {"buckets": _stack(opt_state["buckets"]),
                             "local": opt_state["local"]}
                algo_state = _stack(algo_state)
            new_state = TrainState(state.step + 1, params, opt_state,
                                   algo_state)
            if guard == "off":
                return new_state, loss
            if guard == "skip":
                # rewind: an unhealthy step keeps the pre-step params/opt/
                # algo state bitwise (the verdict is rank-uniform, so
                # replicated state cannot diverge); the step counter still
                # advances, so a poison armed at one step cannot re-fire
                # forever.  keep=True selects the new values bitwise —
                # with healthy gradients the trajectory is byte-identical
                # to guard "off".
                with phase_scope("bagua.guard"):
                    keep = jnp.min(health_vec) > 0.5

                    def sel(n, o):
                        return jnp.where(keep, n, o)

                    new_state = TrainState(
                        new_state.step,
                        jax.tree.map(sel, new_state.params, state.params),
                        jax.tree.map(sel, new_state.opt_state,
                                     state.opt_state),
                        jax.tree.map(sel, new_state.algo_state,
                                     state.algo_state),
                    )
            # a leading row axis: rank-uniform verdicts replicate ([1, b]),
            # per-rank (gossip) verdicts stack over the dp axes ([ranks, b])
            return new_state, loss, health_vec[None]

        if expert is not None and not algo.sharded_opt_state:
            pspec = P((expert,))
            state_specs = TrainState(step=P(), params=pspec, opt_state=pspec,
                                     algo_state=pspec)
        elif opt_stacked:
            # ZeRO-1: bucket chunk states stacked over the comm axes; with
            # tp/pp/ep, params and the "local" state part carry the model-
            # parallel placements
            pspec = (
                self._param_specs
                if self._shard_axis is not None or expert is not None else P()
            )
            state_specs = TrainState(step=P(), params=pspec,
                                     opt_state=self._zero_opt_specs,
                                     algo_state=P(self.comm_axes))
        elif self._shard_axis is not None:
            state_specs = TrainState(
                step=P(), params=self._param_specs,
                opt_state=self._opt_specs, algo_state=P(),
            )
        else:
            pspec = P() if replicated else P(dp)
            # the EF residual (when an error-feedback codec is active) is
            # the one replicated-family algo state with a per-rank stacked
            # leading axis; shard_map slices each rank's [1, pad] row
            # (sharded update: parameters and moments cut over the ranks)
            cut = ctx.sharded_update
            state_specs = TrainState(
                step=P(), params=self._resident_specs(plan) if cut else pspec,
                opt_state=self._opt_state_specs(plan) if cut else pspec,
                algo_state=algo.algo_state_specs(ctx, pspec,
                                                 P(self.comm_axes)),
            )
        batch_spec = self._batch_spec()
        self._state_specs = state_specs  # reused by eval_step

        health_spec = P(self.dp_axes) if local_health else P()
        out_specs = (
            (state_specs, P()) if guard == "off"
            else (state_specs, P(), health_spec)
        )
        fn = shard_map(
            bagua_step,
            mesh=mesh,
            in_specs=(state_specs, batch_spec),
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(0,) if self.donate else ())

    def _flat_leaf_view(self, zp):
        """Materialize the leaf pytree from the flat-resident layout
        (traceable: a shaped bucket's buffer passes through, the rest is
        sliced out of the 1-D flats).  The ONE implementation of the
        flats->leaves contract, shared by the train step, eval step, and
        ``unstack_params``."""
        from ..tensor import tree_from_named

        got = [tuple(jnp.shape(f)) for f in zp["flats"]]
        want = [b.buffer_shape for b in self._plan.buckets]
        if got != want:
            raise ValueError(
                f"flat-resident state carries bucket buffers shaped {got} "
                f"but this trainer's plan expects {want} — the state was "
                "built under a different bucket plan (another trainer, or "
                "a pre-rebucket checkpoint).  Restore through "
                "restore_checkpoint(), or convert via unstack_params() on "
                "the trainer that owns the state."
            )
        named = self._plan.unflatten_to_named(zp["flats"])
        named.update(zp["local"])
        return tree_from_named(self._param_template, named)

    def _step_key(self) -> Tuple:
        """The step-cache key for the CURRENT configuration — also keys the
        cost/memory-analysis caches (one XLA cost-model query per compiled
        program, not per call)."""
        overlap = self._overlap_active()
        return (
            self._plan.signature(),
            self._phase,
            self.algorithm.hierarchical,
            type(self.algorithm).__name__,
            overlap,
            # chunk bytes (link-agnostic + per-tier) only reach the traced
            # program while overlap is active (_ctx nulls them otherwise) —
            # keying the raw values would recompile bit-identical
            # serialized steps
            self.overlap_chunk_bytes if overlap else 0,
            self.overlap_chunk_bytes_intra if overlap else 0,
            self.overlap_chunk_bytes_inter if overlap else 0,
            # the codec policy changes the traced program in BOTH overlap
            # and serialized constructions (compressed ring hops replace
            # fused collectives), so the raw knob values always key
            self.compress_intra,
            self.compress_inter,
            # the state layout the step is traced against: autotune v2 can
            # flip bucket-flat residency live (_apply_flat_resident), and
            # the flat and leaf constructions are different programs
            self._flat_resident,
            # ... and whether the update is sharded over the comm world (a
            # flip of `hierarchical` or of a codec knob turns it off and on)
            self._update_sharded(),
            # grad guard: "warn" and "abort" trace the same program (the
            # policy difference is host-side), "skip" adds the rewind
            # selects; armed traced faults compile into the step, so their
            # signatures key it too
            ("skip" if self.grad_guard == "skip" else "observe")
            if self.grad_guard != "off" else "off",
            tuple(s.signature()
                  for s in _inject.armed_traced_specs("grad.poison")),
            # topk's payload shape (k per chunk) is compiled into the
            # step from BAGUA_TOPK_RATIO; keying the effective ratio
            # retraces on an env flip instead of reusing a stale k
            env.get_topk_ratio()
            if "topk" in (self.compress_intra, self.compress_inter)
            else None,
            # compile_key stays LAST: introspection (tests, debugging)
            # reads it as key[-1]
            self.algorithm.compile_key(),
        )

    def _get_step_fn(self):
        key = self._step_key()
        self._current_step_key = key
        if key not in self._step_cache:
            logger.info("bagua_tpu: compiling train step (phase=%s, %d buckets)",
                        self._phase, len(self._plan.buckets))
            with trace_span("step/build", phase=self._phase,
                            buckets=len(self._plan.buckets),
                            overlap=self._overlap_active()):
                self._step_cache[key] = self._make_step_fn(self._plan)
            self._note_plan_gauges()
            # the wall window of the step that triggers this compile is
            # garbage-slow: no speed sample, no anomaly, booked to `compile`
            self._observer.note_window_class("compile")
        return self._step_cache[key]

    # the observer's surface other modules call on the trainer
    # (algorithms/async_model_average.py, obs/export.py, the drills)

    def measured_step_dt(self) -> Optional[float]:
        """Host dispatch cadence of the previous step in seconds
        (:meth:`StepObserver.measured_step_dt`)."""
        return self._observer.measured_step_dt()

    def note_injected_stall(self, seconds: float) -> None:
        """Record an injected stall inside the current step
        (:meth:`StepObserver.note_injected_stall`)."""
        self._observer.note_injected_stall(seconds)

    def note_phase_duration(self, phase: str, seconds: float) -> None:
        """Attribute host seconds of the current step to a phase
        (:meth:`StepObserver.note_phase_duration`)."""
        self._observer.note_phase_duration(phase, seconds)

    @property
    def anomaly_detector(self):
        """The step-time anomaly detector (None with the plane off)."""
        return self._observer.anomaly_detector

    def _maybe_prepare_mfu(self, state: TrainState,
                           batch) -> Optional[threading.Thread]:
        """Stash the current compiled step's cost-model flops for the
        cadence hook's MFU gauge.  The cost analysis is cached per
        step-cache key; a MISSING entry is harvested in a background
        thread from abstract avals captured here — jax's AOT
        ``lower().compile()`` does not share the jit dispatch cache, so an
        inline harvest would pay a second full XLA compile on the
        train-step hot path at every new key (first step, autotune
        retunes, phase switches).  Skipped entirely when no silicon peak
        is known — the null-with-rationale record needs no cost model.

        Returns the harvest thread UNSTARTED (or None): the caller starts
        it after the dispatch, whose own compile has by then written the
        program to the persistent compile cache — the harvest's compile of
        the same module is then a cache hit, where starting it before the
        dispatch compiled the whole step twice, concurrently."""
        obs = self._observer
        if obs.peak_flops is None:
            obs.note_mfu()  # publish the rationale once
            return None
        key = self._current_step_key
        cached = self._cost_analysis_cache.get(key)
        if cached is not None:
            obs.flops_per_step = cached.get("flops")
            return None
        # pause the gauge until THIS program's flops land: publishing the
        # previous key's flops against the new program's cadence (for the
        # whole duration of a background compile) would be wrong, not late
        obs.flops_per_step = None
        if key in self._cost_analysis_pending:
            return None
        done = threading.Event()
        self._cost_analysis_pending[key] = done
        # resolved HERE: _get_step_fn writes trainer state, which only the
        # dispatching thread may do
        fn = self._step_cache.get(key)

        def _abstract(x):
            # the dispatched arrays' own placement, so the harvest lowers
            # the SAME module the dispatch compiled (an uncommitted array
            # is placed by jit, exactly as at dispatch)
            if not hasattr(x, "shape"):
                return x
            sharding = x.sharding if getattr(x, "committed", False) else None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        # host metadata only — live buffers are about to be donated to
        # the dispatch, so the thread must not hold them
        a_state, a_batch = jax.tree.map(_abstract, (state, batch))

        def _harvest():
            try:
                try:
                    # deliberately NOT the ledger-mapped span name: this
                    # compile overlaps step windows on another thread, and
                    # a mapped span here would wrongly deduct from them
                    with trace_span("obs/cost_analysis_async"):
                        compiled = self._compile_step(fn, a_state, a_batch)
                        analysis = compiled.cost_analysis()
                except Exception as e:  # noqa: BLE001 - backend-dependent
                    logger.warning(
                        "step_cost_analysis unavailable on %r backend: %s",
                        jax.default_backend(), e,
                    )
                    counters.incr("obs/cost_analysis_unavailable")
                    self._cost_analysis_cache[key] = {}
                    self._memory_analysis_cache[key] = None
                    return
                self._memory_analysis_cache[key] = \
                    self._observer.compiled_memory_analysis(compiled)
                self._cost_analysis_cache[key] = \
                    dict(analysis) if analysis else {}
            finally:
                self._cost_analysis_pending.pop(key, None)
                done.set()

        # NOT a daemon: a process that exits under a live lower()/compile()
        # segfaults in jax's cache teardown (seen on the 4-chip host,
        # PR 22); interpreter shutdown joins this thread first
        return threading.Thread(target=_harvest,
                                name="bagua-obs-cost-analysis")

    def train_step(self, state: TrainState, batch) -> Tuple[TrainState, jax.Array]:
        # the root span of the step (and the profiler's step annotation): all
        # the trainer does on the host for one step, tiled by its five child
        # spans; its self time is check_abort and begin_step alone
        with trace_step_span(self._step_counter + 1):
            return self._train_step(state, batch)

    def _train_step(self, state: TrainState, batch):
        check_abort()  # fail fast once a rank/watchdog flagged an abort
        obs = self._observer
        self._step_counter += 1
        obs.begin_step(self._step_counter)
        with trace_span("step/hooks"):
            if self._profiler is not None:
                self._profiler.on_step(self._step_counter - 1)
            # step.straggle: a slow peer gates this step only when the
            # family's step synchronizes with every rank (per-step gradient
            # collective); async families pay at their own negotiated
            # boundaries instead
            obs.note_injected_stall(_inject.maybe_straggle(
                "step", base_dt=obs.measured_step_dt(),
                gated=self.algorithm.straggler_gates_step,
            ))
            state = self.algorithm.host_pre_step(self, state)
        with trace_span("step/prepare"):
            if self.algorithm.need_reset(self._step_counter - 1):
                self._phase += 1
                # reference re-runs init_tensors + rebucketing at phase
                # switches (distributed.py:427-435); plan shape is identical
                # here, phase key selects the recompiled step.
            if (
                self.autotune
                and not self._autotune_completed
                and self._step_counter % 100 == 0
            ):
                self._autotune_step(state)
            if (
                self.autotune
                and not self._autotune_completed
                and not self._telemetry_reported
                and env.get_autotune_level() >= 2
            ):
                self._report_tensor_execution_order(state, batch)
            if (
                not self._overlap_ordered
                and self._overlap_active()
                and not self.algorithm.sharded_opt_state
                and not self.autotune
            ):
                # one-time readiness re-bucketing (reverse execution order);
                # not under autotune, whose recommendation path owns bucket
                # order (span-driven, _report_tensor_execution_order: a local
                # re-split would discard its boundaries), nor for
                # sharded-opt-state families, whose chunk states are keyed on
                # bucket boundaries (a rebucket would orphan them)
                self._overlap_ordered = True
                self._reorder_plan_for_overlap(state, batch)
            if self._pending_state_migration is not None:
                # queued layout migrations (a family switch across the
                # optimizer-ownership boundary, a relayout after a rebucket)
                # convert the live state before the recompiled step takes it;
                # the span feeds the ledger's state_migration class
                with trace_span("step/state_migration"):
                    state = self._pending_state_migration(state)
                self._pending_state_migration = None
                obs.note_window_class("state_migration")
            fn = self._get_step_fn()
            mfu_harvest = None
            if obs.enabled:
                mfu_harvest = self._maybe_prepare_mfu(state, batch)
                obs.note_static_footprint(self, state)
            # poison accounting reads state.step BEFORE the dispatch donates
            # the buffers: the compiled fault fires on it (it resumes from
            # checkpoints), not on the trainer-local call counter
            self._note_traced_fault_fires(state)
        paused = obs.pause_mark()
        try:
            with trace_span("step/dispatch") as dispatch:
                out = fn(state, batch)
        finally:
            if mfu_harvest is not None:
                # also after a failed dispatch: the thread clears its
                # pending entry, which step_cost_analysis callers wait on
                mfu_harvest.start()
        if dispatch is not None:
            # the anomaly detector's phase breakdown reads the span's own
            # clock pair (obs off: no span, and no detector to feed), less
            # the interpreter's pauses that fell inside the call
            obs.note_dispatch(dispatch.dur_s, paused)
        if self.grad_guard != "off":
            new_state, loss, health_vec = out
            self.step_metrics = {
                "grad_healthy": jnp.min(health_vec),
                "grad_health_buckets": jnp.min(health_vec, axis=0),
            }
            self._note_step_health(health_vec)
            out = (new_state, loss)
        if self._watchdog is not None:
            # asynchronous watching: dispatch continues at full speed while
            # the watchdog's waiter thread reads the scalar loss back
            # inside a watched section.  A cross-rank deadlock pins the
            # waiter past the timeout.
            with trace_span("step/watchdog_handoff"):
                self._watchdog.watch_result(
                    out[1], f"train_step[{self._step_counter}]"
                )
        # the only consumer of the speed tracker is the autotune check-in
        # (every 2 s besides: the device-memory poll and the health beacon).
        # With step/hooks, step/prepare (everything between the hooks and
        # the dispatch: the reset check, the autotune and migration branches,
        # the step-cache key, the MFU preparation, the static footprint, the
        # fault accounting), step/dispatch and step/watchdog_handoff this
        # span tiles the root span.  The lines down to `out = fn(...)` keep
        # their numbers: the kernels' bodies embed them (ROADMAP).
        with trace_span("step/end"):
            obs.end_step(batch, track_speed=not self._autotune_completed)
        return out

    # ---- gradient-health sentinel (host-side policy) ---------------------

    def _note_step_health(self, health_vec) -> None:
        """Queue this step's (async) health verdict and act on the ones
        already complete.  The guard inspects each step's verdict when the
        NEXT step is dispatched — by then the previous program has
        finished, so the readback does not stall the dispatch pipeline."""
        self._pending_health.append((self._step_counter, health_vec))
        while len(self._pending_health) > 1:
            self._consume_health(*self._pending_health.pop(0))

    def flush_grad_health(self) -> None:
        """Drain every not-yet-inspected step verdict (blocking readback).
        Call at a training-loop boundary so the FINAL step's verdict is
        acted on too — per-step inspection always runs one step behind."""
        while self._pending_health:
            self._consume_health(*self._pending_health.pop(0))

    @staticmethod
    def _local_value(arr):
        """Host value of a (possibly multi-process global) array — the
        LOCAL shard when the global cannot be fetched whole, the same
        per-process contract as the watchdog's readback fence."""
        if getattr(arr, "is_fully_addressable", True):
            return np.asarray(arr)
        return np.asarray(arr.addressable_shards[0].data)

    def _consume_health(self, step_no: int, health_vec) -> None:
        # min over verdict rows (rank-uniform verdicts replicate; per-rank
        # gossip verdicts stack — this process acts on ALL its local rows,
        # so multi-device processes see every local replica's verdict)
        _verdict_t0 = time.monotonic()
        with trace_span("step/grad_guard_verdict", step=step_no):
            if getattr(health_vec, "is_fully_addressable", True):
                hv = np.asarray(health_vec)
            else:
                hv = np.concatenate(
                    [np.asarray(s.data)
                     for s in health_vec.addressable_shards], axis=0
                )
        # the verdict readback is host optimizer-adjacent work: it blocks
        # on the previous step's update having completed
        self._observer.note_phase_duration("optimizer",
                                           time.monotonic() - _verdict_t0)
        hv = hv.min(axis=0)
        self._observer.note_grad_verdict(step_no, float(hv.min()))
        if bool(hv.min() > 0.5):
            self._guard_skips = 0
            return
        bad = [i for i, v in enumerate(hv) if v <= 0.5]
        counters.incr("grad_guard/unhealthy_steps")
        abort_msg = None
        if self.grad_guard == "warn":
            logger.warning(
                "grad guard: step %d produced non-finite gradients "
                "(buckets %s) — policy 'warn': the update was APPLIED and "
                "replicated state is now poisoned; use BAGUA_GRAD_GUARD="
                "skip to rewind such steps", step_no, bad,
            )
        elif self.grad_guard == "abort":
            counters.incr("grad_guard/aborts")
            # later queued verdicts describe steps run on the already-
            # poisoned state: acting on them after the operator resets the
            # abort and restores a clean checkpoint would re-trip the
            # guard spuriously
            self._pending_health.clear()
            abort_msg = (
                f"grad guard: step {step_no} produced non-finite gradients "
                f"(buckets {bad})"
            )
        elif self.grad_guard == "skip":
            self._guard_skips += 1
            self._guard_rewinds_total += 1
            counters.incr("grad_guard/skipped_steps")
            self._observer.note_rewind(step_no)
            _inject.record_recovery("grad.poison")
            logger.warning(
                "grad guard: step %d produced non-finite gradients "
                "(buckets %s) — step rewound (params/opt state untouched; "
                "%d/%d consecutive skips)", step_no, bad,
                self._guard_skips, self.grad_guard_budget,
            )
            if self._guard_skips >= self.grad_guard_budget:
                counters.incr("grad_guard/aborts")
                self._pending_health.clear()
                abort_msg = (
                    f"grad guard: {self._guard_skips} consecutive unhealthy "
                    f"steps reached the skip budget "
                    f"({self.grad_guard_budget}) — systematic divergence, "
                    "not a transient bad batch"
                )
        # surface the event to the elastic coordinator AFTER the policy
        # counters above, so the published payload includes this event's
        # skip/abort bookkeeping; the launcher's lease heartbeat carries
        # these counters as a health payload and a rank producing repeated
        # non-finite gradients can be fenced out by the epoch/resize
        # machinery (no-op unless the launcher injected
        # BAGUA_ELASTIC_HEALTH_FILE)
        self._observer.publish_health()
        if abort_msg is not None:
            # flight recorder: grad-guard abort and skip-budget escalation
            # both land here — the post-mortem names the offending step and
            # buckets before the abort flag stops every control loop
            self._observer.dump_flight_record(
                "grad_guard_abort", reason=abort_msg,
                extra={"step": step_no, "unhealthy_buckets": bad,
                       "policy": self.grad_guard,
                       "consecutive_skips": self._guard_skips},
            )
            abort(abort_msg)

    def _note_traced_fault_fires(self, state: TrainState) -> None:
        """Host-side telemetry for traced faults: the compiled step fires
        ``grad.poison`` on its own; mirror the event into the counters by
        reading the step counter the traced condition actually compares
        against — ``state.step``, which survives checkpoint resumes where
        the trainer-local call counter restarts at 0.  The readback only
        happens while a poison spec is armed (drills), never in clean
        runs."""
        specs = _inject.armed_traced_specs("grad.poison")
        if not specs:
            return
        traced_step = int(self._local_value(state.step))
        for spec in specs:
            if spec.step is not None:
                fired = spec.step == traced_step
            else:  # the compiled step-window semantics of _apply_grad_poison
                fired = spec.count < 0 or traced_step < spec.count
            if fired:
                _inject.note_traced_fire(spec)

    def compiled_step(self, state: TrainState, batch) -> jax.stages.Compiled:
        """The ``jax.stages.Compiled`` of the step program the CURRENT
        configuration selects (the step-cache key of the next
        ``train_step``): its optimized HLO text (``.as_text()``, where
        every instruction's ``op_name`` carries the ``bagua.*`` phase
        scope), cost and memory analyses.  Lowered and compiled here on
        every call — jax's AOT path does not share the jit dispatch cache
        (a persistent compile cache makes it a load) — and not retained:
        holding an executable would hold its device memory.  ``state`` and
        ``batch`` may be ``jax.ShapeDtypeStruct`` trees; nothing is
        executed or donated."""
        return self._compile_step(self._get_step_fn(), state, batch)

    @staticmethod
    def _compile_step(fn, state, batch) -> jax.stages.Compiled:
        """Lower and compile a step function of the step cache — the one
        place that does (:meth:`compiled_step`, and the MFU harvest's
        thread, which may not resolve the function itself)."""
        return fn.lower(state, batch).compile()

    def step_cost_analysis(self, state: TrainState, batch) -> Dict[str, Any]:
        """XLA's cost model for the current compiled train step ("flops",
        "bytes accessed", ...) — feeds the per-step ``obs/mfu`` gauge
        (``perfbench/drivers/train.py`` waits for its harvest inside
        ``setup_s``: ROADMAP Queue 3 item 1).  Cached per step-cache
        key (the lower+compile+query round-trip is paid once per compiled
        program, not per call); the same pass harvests
        ``memory_analysis()`` for :meth:`step_memory_analysis`.  Returns {}
        when the backend can't provide one (no reference counterpart;
        NCCL/CUDA expose no per-step cost model) — logged at warning with
        the backend name and counted in ``obs/cost_analysis_unavailable``
        so the silent-{} path is visible in the fleet view."""
        key = self._step_key()
        cached = self._cost_analysis_cache.get(key)
        if cached is not None:
            return dict(cached)
        pending = self._cost_analysis_pending.get(key)
        if pending is not None:
            # a background harvest for this key is already compiling the
            # same program — join it instead of paying a duplicate AOT
            # compile (minutes on large models)
            pending.wait(timeout=1800)
            cached = self._cost_analysis_cache.get(key)
            if cached is not None:
                return dict(cached)
        try:
            with trace_span("step/cost_analysis"):
                compiled = self.compiled_step(state, batch)
                analysis = compiled.cost_analysis()
        except Exception as e:  # pragma: no cover - backend-dependent
            logger.warning(
                "step_cost_analysis unavailable on %r backend: %s",
                jax.default_backend(), e,
            )
            counters.incr("obs/cost_analysis_unavailable")
            self._cost_analysis_cache[key] = {}
            self._memory_analysis_cache[key] = None
            return {}
        self._memory_analysis_cache[key] = \
            self._observer.compiled_memory_analysis(compiled)
        result = dict(analysis) if analysis else {}
        if not result:
            logger.warning(
                "step_cost_analysis empty on %r backend (cost model "
                "returned no entries)", jax.default_backend(),
            )
            counters.incr("obs/cost_analysis_unavailable")
        self._cost_analysis_cache[key] = result
        return dict(result)

    def step_memory_analysis(self, state: TrainState,
                             batch) -> Optional[Dict[str, int]]:
        """XLA's compiled-executable memory analysis for the current step
        (argument/output/temp bytes and a ``peak_bytes`` estimate), cached
        per step-cache key alongside :meth:`step_cost_analysis`.  None when
        the backend provides no analysis (cpu-sim) — the static
        :mod:`bagua_tpu.obs.memory` footprint stays the fit signal there."""
        key = self._step_key()
        if key not in self._memory_analysis_cache:
            self.step_cost_analysis(state, batch)
        return self._memory_analysis_cache.get(key)

    def trace_step(self, state: TrainState, batch):
        """Abstract-eval of the current train-step construction: the jitted
        step's ``ClosedJaxpr``, obtained by tracing only — no compile, no
        execution, ``state``/``batch`` untouched (donation binds at run
        time, not trace time).  This is the entry point the
        :mod:`bagua_tpu.analysis` jaxpr collective-consistency checker uses
        to extract a construction's collective sequence (mesh-axis binding,
        ``cond``-branch divergence, overlap-vs-serialized multiset
        equality)."""
        return self._get_step_fn().trace(state, batch).jaxpr

    def _make_eval_fn(self, state_specs, batch_spec):
        algo = self.algorithm
        expert = self.expert_axis
        stacked = (
            (not algo.replicated_params) or expert is not None
        ) and not algo.sharded_opt_state

        if self._flat_resident:
            leaf_view = self._flat_leaf_view

            def loss_on(zp, b):
                return self.loss_fn(leaf_view(zp), b)
        else:
            loss_on = self.loss_fn

        ctx = self._ctx(self._plan)

        def per_shard(state: TrainState, batch):
            params = state.params
            if stacked:
                params = jax.tree.map(lambda x: x[0], params)
            if ctx.sharded_update:
                params = ctx.gather_resident(params)
            rows = jax.tree.leaves(batch)[0].shape[0]
            accum = self.accum_steps if rows % self.accum_steps == 0 else 1
            if accum > 1:
                # keep eval's working set at the train step's microbatch
                # size — accum_steps exists because the full batch doesn't
                # fit; mean of equal-size microbatch means == full mean
                microbatches = jax.tree.map(
                    lambda x: x.reshape((accum, x.shape[0] // accum)
                                        + x.shape[1:]),
                    batch,
                )
                loss = jnp.mean(jax.lax.map(
                    lambda mb: loss_on(params, mb), microbatches
                ))
            else:
                loss = loss_on(params, batch)
            return self._comm.allreduce(loss, ReduceOp.AVG)

        fn = shard_map(per_shard, mesh=self.mesh,
                       in_specs=(state_specs, batch_spec), out_specs=P(),
                       check_vma=False)
        return jax.jit(fn)

    def eval_step(self, state: TrainState, batch) -> jax.Array:
        """Forward-only mean loss over the global batch — same sharding as
        ``train_step`` (state untouched, nothing donated).  Evaluation has
        no reference counterpart hook (the reference evaluates on the raw
        torch module); here the jitted step owns the sharded params, so the
        trainer provides the entry point."""
        # keyed like _get_step_fn: a rebucket / phase reset / autotune family
        # switch that changes the state layout must not evaluate with stale
        # specs (build or fetch the compiled step first, then lift its specs)
        self._get_step_fn()
        key = (self._plan.signature(), self._phase,
               self.algorithm.hierarchical, type(self.algorithm).__name__,
               self.algorithm.compile_key(),  # eval has no comm-stage overlap
               self._update_sharded())
        if getattr(self, "_eval_key", None) != key:
            self._eval_fn = self._make_eval_fn(self._state_specs,
                                               self._batch_spec())
            self._eval_key = key
        check_abort()
        loss = self._eval_fn(state, batch)
        if self._watchdog is not None:
            # same hang-surfacing contract as train_step: a wedged eval
            # allreduce must pin the watchdog's waiter, not hang silently
            self._watchdog.watch_result(loss, "eval_step")
        return loss

    def _report_tensor_execution_order(self, state, batch) -> None:
        """Feed the sidecar the observed gradient-readiness order (the
        reference's OTel tensor_ready span pipeline,
        bagua-opentelemetry/src/exporter/mod.rs:15-59): one-time, host-side,
        off the hot path.  Enabled at BAGUA_AUTOTUNE >= 2 (profiling costs one
        small compile per tensor)."""
        self._telemetry_reported = True
        try:
            from ..communication import get_hyperparameters_service_client
            from ..telemetry import profile_tensor_execution_order

            params = self.unstack_params(state)
            spans = profile_tensor_execution_order(self.loss_fn, params, batch)
            if self._autotune_client is None:
                self._autotune_client = get_hyperparameters_service_client()
            self._autotune_client.report_tensor_execution_order(
                spans, model_name=self.model_name
            )
            logger.info("telemetry: reported execution order for %d tensors",
                        len(spans))
        except Exception as e:  # telemetry must never take down training
            logger.warning("telemetry report failed: %s", e)

    # ---- autotune check-in (reference distributed.py:213-242) ------------

    def _autotune_register_tensors(self):
        """Declare communicated tensors to the sidecar (reference
        distributed.py:387-406)."""
        from ..communication import get_hyperparameters_service_client

        try:
            if self._autotune_client is None:
                self._autotune_client = get_hyperparameters_service_client()
            rsp = self._autotune_client.register_tensors(
                model_name=self.model_name,
                tensor_list=[p.declaration().model_dump() for p in self._named_params],
                capabilities=self._autotune_capabilities(),
            )
            # apply the service's initial recommendation so trainer and
            # service agree on the config the first score is attributed to
            # (reference distributed.py:387-406)
            from ..define import BaguaHyperparameter

            rec = BaguaHyperparameter(**rsp.get("recommended_hyperparameters", {}))
            self._apply_recommendation(rec)
        except Exception as e:  # autotune must never take down training
            logger.warning("autotune register_tensors failed: %s", e)
            self.autotune = False

    def _autotune_capabilities(self) -> Optional[dict]:
        """What this trainer's mesh / family / layout makes legal — sent
        once at tensor registration so the service builds the
        capability-gated v2 knob space for exactly the knobs this trainer
        can apply (a knob the trainer would refuse is never searched).
        ``None`` keeps the legacy two-knob space
        (``BAGUA_AUTOTUNE_SPACE=legacy``)."""
        if env.get_autotune_space() == "legacy":
            return None
        from ..algorithms import SWITCHABLE_ALGORITHMS

        current = getattr(self.algorithm, "name", None) or ""
        families: list = []
        flat_families: list = []
        if current in SWITCHABLE_ALGORITHMS:
            for name, ctor in SWITCHABLE_ALGORITHMS.items():
                proto = self._user_algorithms.get(name) or ctor(False)
                if name != current:
                    # static mirror of _maybe_switch_algorithm's refusals:
                    # a family the trainer would refuse must not be in the
                    # space (its windows would score the refusal, not the
                    # config)
                    if (
                        self.algorithm.owns_optimizer
                        and not proto.owns_optimizer
                        and self.optimizer is None
                    ):
                        continue
                    if proto.replicated_params != self.algorithm.replicated_params:
                        if self.algorithm.owns_optimizer or proto.owns_optimizer:
                            continue
                        if (
                            self.expert_axis is not None
                            or self._shard_axis is not None
                        ):
                            continue
                families.append(name)
                if proto.supports_flat_resident:
                    flat_families.append(name)
        flat_ok = (
            self._flat_supported()
            and self.algorithm.replicated_params
            and not self.algorithm.owns_optimizer
            and not self.algorithm.sharded_opt_state
            and self.optimizer is not None
            and getattr(self.optimizer, "fused_inner", None) is None
            and _optimizer_flattens_safely(self.optimizer)
        )
        return {
            "space": "v2",
            "two_tier": self._inter is not None and self._intra is not None,
            "ef_ok": bool(self._ef_enabled),
            "flat_ok": bool(flat_ok),
            "families": families,
            "flat_families": flat_families,
            "current_algorithm": current,
        }

    def _apply_recommendation(self, recommended) -> None:
        # snapshot EF-residual activeness: any knob below (family switch,
        # codec policy, hierarchical toggle) can flip it, and the flip is a
        # state migration (_sync_ef_state at the end)
        ef_was = self._ef_active()
        self._maybe_switch_algorithm(recommended)
        # overlap knobs ride the same recommendation path as bucketing so
        # the two compose: a re-bucketed plan keeps the overlap mode, and
        # an overlap flip recompiles via the step-cache key
        if recommended.overlap in ("auto", "on", "off"):
            self.overlap = recommended.overlap
        if recommended.overlap_chunk_bytes:
            self.overlap_chunk_bytes = int(recommended.overlap_chunk_bytes)
        if recommended.overlap_chunk_bytes_intra:
            self.overlap_chunk_bytes_intra = int(
                recommended.overlap_chunk_bytes_intra
            )
        if recommended.overlap_chunk_bytes_inter:
            self.overlap_chunk_bytes_inter = int(
                recommended.overlap_chunk_bytes_inter
            )
        # codec policy rides the same path ("" = keep current): the
        # autopilot's compress_dcn trend hint actuates compress_inter here
        # — every rank applies it at its next check-in (the service's
        # per-train_iter decision cache keeps it SPMD-uniform) and the
        # step-cache key re-jits the compressed construction
        from ..compression.codecs import validate_codec_policy

        for attr in ("compress_intra", "compress_inter"):
            value = getattr(recommended, attr, "")
            if value:
                try:
                    setattr(self, attr, validate_codec_policy(value, attr))
                except ValueError as e:
                    logger.warning("autotune recommendation ignored: %s", e)
        if recommended.buckets:
            named_by_name = {p.name: p for p in self._named_params}
            decl_buckets = [
                [d for d in bucket if d.name in named_by_name]
                for bucket in recommended.buckets
            ]
            decl_buckets = [b for b in decl_buckets if b]
            if decl_buckets:
                self.rebucket(decl_buckets)
                self.bucket_bytes = recommended.bucket_size
        # flat-residency rides the recommendation path AFTER any rebucket
        # so the queued flat<->leaf conversion composes against the plan
        # the step will actually run (migrations apply in queue order)
        if getattr(recommended, "flat_resident", ""):
            self._apply_flat_resident(recommended.flat_resident)
        # hierarchical toggle is only meaningful when the mesh has both
        # tiers, and only for families whose staged path is layout-free.
        # ZeRO is excluded: its staged mode changes the OPT-STATE SHARDING
        # (intra vs world chunks), so flipping the flag mid-run would
        # desync the state layout from the compiled step — autotune is
        # force-disabled for sharded-opt-state families anyway, so this is
        # belt-and-braces
        if (
            self._inter is not None
            and self._intra is not None
            and not self.algorithm.sharded_opt_state
        ):
            self.algorithm.hierarchical = bool(recommended.is_hierarchical_reduce)
        self._sync_ef_state(ef_was)

    def _apply_flat_resident(self, want: str) -> None:
        """Apply a ``flat_resident`` recommendation ("on"/"off"; v2 knob).

        Before ``init()`` resolves the layout, this only adjusts the MODE —
        the state is then built directly in the recommended layout, no
        conversion needed.  After, it queues a live flat<->leaf state
        migration (the same structural conversion ``restore_checkpoint``
        uses for cross-layout restores): every param-shaped subtree of the
        TrainState — params and the optimizer moments that mirror them —
        swaps between the leaf pytree and the ``{"flats", "local"}`` bucket
        container, under the CURRENT plan, so no training math changes.
        The flip re-jits through ``_step_key`` (``self._flat_resident`` is
        keyed) and the migration window lands in the goodput ledger's
        ``state_migration`` class, so the search pays for its own curiosity
        honestly.

        Refusal cases (logged, never raised — a recommendation must not
        take down training): families owning their optimizer or sharding
        opt state (their state is not param-mirrored), unsupported meshes,
        optimizers that don't commute with flattening, and fused-wrapper
        optimizers (the wrapper's leaf state and its inner's flat state
        are not positionally convertible — a live flip would re-init
        momentum)."""
        if want not in ("on", "off"):
            return
        if not self._flat_layout_live:
            # registration-time recommendation: init() is about to build
            # the state — steer _resolve_flat_resident instead of migrating
            if want == "off" or (
                self._flat_supported()
                and not self.algorithm.owns_optimizer
                and not self.algorithm.sharded_opt_state
                and _optimizer_flattens_safely(self._flat_opt())
            ):
                self.flat_resident = want
            else:
                logger.info(
                    "autotune: flat_resident=%s not supported by this "
                    "configuration; keeping mode %r", want, self.flat_resident,
                )
            return
        want_on = want == "on"
        if want_on == self._flat_resident:
            return
        algo = self.algorithm
        if (
            algo.owns_optimizer
            or algo.sharded_opt_state
            or not algo.replicated_params
        ):
            logger.info(
                "autotune: live flat_resident=%s ignored — %s state is not "
                "param-mirrored replicated", want, type(algo).__name__,
            )
            return
        if getattr(self.optimizer, "fused_inner", None) is not None:
            logger.info(
                "autotune: live flat_resident flip ignored — fused-wrapper "
                "optimizer state is not convertible in place",
            )
            return
        if want_on and not (
            self._flat_supported()
            and self.optimizer is not None
            and _optimizer_flattens_safely(self.optimizer)
        ):
            logger.info(
                "autotune: flat_resident=on refused — layout unsupported "
                "or optimizer does not commute with flattening",
            )
            return
        if self._param_template is None or self._plan is None:
            return
        param_def = jax.tree_util.tree_structure(self._param_template)
        if param_def == jax.tree_util.tree_structure(0):
            logger.info(
                "autotune: flat_resident flip needs a structured param "
                "tree (bare-leaf params cannot be located structurally)",
            )
            return
        plan, template = self._plan, self._param_template
        is_zp = self._is_flat_container

        def is_param_tree(x):
            try:
                return jax.tree_util.tree_structure(x) == param_def
            except Exception:  # unhashable/exotic leaves
                return False

        if want_on:

            def convert(state):
                logger.info("autotune: relaying state leaf -> bucket-flat")

                def to_flat(x):
                    if is_param_tree(x):
                        return {"flats": tuple(plan.flatten_tree(x)),
                                "local": {}}
                    return x

                return jax.tree.map(to_flat, state, is_leaf=is_param_tree)
        else:
            from ..tensor import tree_from_named

            def convert(state):
                logger.info("autotune: relaying state bucket-flat -> leaf")

                def from_flat(x):
                    if is_zp(x):
                        named = plan.unflatten_to_named(list(x["flats"]))
                        named.update(x["local"])
                        return tree_from_named(template, named)
                    return x

                return jax.tree.map(from_flat, state, is_leaf=is_zp)

        self._queue_state_migration(convert)
        self._flat_resident = want_on
        logger.info("autotune: flat_resident -> %s (migration queued)", want)

    def _maybe_switch_algorithm(self, recommended) -> None:
        """Swap the algorithm family if the autotuner asked for one
        (BAGUA_AUTOTUNE_ALGORITHM=1).  Stateless replicated families swap
        freely; QAdam rides the state-migration adapter
        (:meth:`_prepare_state_migration`)."""
        from ..algorithms import SWITCHABLE_ALGORITHMS

        target = recommended.algorithm
        current = getattr(self.algorithm, "name", None)
        if (
            not target
            or target == current
            or current not in SWITCHABLE_ALGORITHMS
            or target not in SWITCHABLE_ALGORITHMS
        ):
            return
        old_algorithm = self.algorithm
        new_owns = (
            self._user_algorithms[target].owns_optimizer
            if target in self._user_algorithms
            else SWITCHABLE_ALGORITHMS[target](False).owns_optimizer
        )
        if old_algorithm.owns_optimizer and not new_owns and self.optimizer is None:
            # the user never supplied an optax optimizer (their family owns
            # the update rule); there is nothing to switch back to
            logger.info(
                "autotune: cannot switch %s -> %s without a trainer optimizer",
                current, target,
            )
            return
        if self._flat_resident:
            new_supports = (
                self._user_algorithms[target].supports_flat_resident
                if target in self._user_algorithms
                else SWITCHABLE_ALGORITHMS[target](False).supports_flat_resident
            )
            if not new_supports:
                # the live state is laid out as bucket flats; a family
                # without the flat contract cannot consume it
                logger.info(
                    "autotune: cannot switch %s -> %s — flat-resident "
                    "state needs a supports_flat_resident family",
                    current, target,
                )
                return
        new_replicated = (
            self._user_algorithms[target].replicated_params
            if target in self._user_algorithms
            else SWITCHABLE_ALGORITHMS[target](False).replicated_params
        )
        if old_algorithm.replicated_params != new_replicated:
            # replicated <-> stacked (allreduce <-> async): the state
            # migration below re-lays the whole TrainState out; refuse the
            # combinations it does not cover
            if old_algorithm.owns_optimizer or new_owns:
                logger.info(
                    "autotune: cannot switch %s -> %s — a replication-"
                    "boundary switch cannot also cross the optimizer-"
                    "ownership boundary", current, target,
                )
                return
            if self.expert_axis is not None or self._shard_axis is not None:
                logger.info(
                    "autotune: cannot switch %s -> %s — replication-"
                    "boundary switches need a pure data-parallel mesh",
                    current, target,
                )
                return
        logger.info("autotune: switching algorithm %s -> %s", current, target)
        if target in self._user_algorithms:
            # switching BACK to a family the user configured: reuse their
            # instance so settings beyond the search space (comm_dtype,
            # average, ...) survive the round trip
            self.algorithm = self._user_algorithms[target]
            self.algorithm.hierarchical = bool(recommended.is_hierarchical_reduce)
        else:
            self.algorithm = SWITCHABLE_ALGORITHMS[target](
                bool(recommended.is_hierarchical_reduce)
            )
        self._prepare_state_migration(old_algorithm, self.algorithm)
        self._prepare_replication_migration(old_algorithm, self.algorithm)
        if hasattr(old_algorithm, "reset_schedule"):
            # leaving a scheduled family: drop its in-flight round (it was
            # launched against the stacked layout being migrated away) and
            # forget the negotiated period
            old_algorithm.reset_schedule()
        if hasattr(self.algorithm, "reset_schedule"):
            # entering (or re-entering) a scheduled family mid-run: the
            # averaging period re-calibrates against the CURRENT cadence,
            # and no stale pending round survives from a previous stint
            self.algorithm.reset_schedule()
        if not recommended.buckets:
            # rebuild the plan under the new family's alignment (ByteGrad
            # pads buckets to the world size); skipped when the caller is
            # about to apply the recommendation's own buckets anyway
            self.rebucket([[t.declaration() for t in b.tensors]
                           for b in self._plan.buckets])

    def _prepare_state_migration(self, old, new) -> None:
        """Queue an opt-state layout migration for the next ``train_step``
        when a family switch crosses the trainer-optimizer / owned-optimizer
        boundary (allreduce|bytegrad <-> qadam).

        To QAdam: its momenta are param-shaped, so they are adopted from an
        adam-family optax state when one is found (``mu``/``nu``), else start
        at zeros; either way QAdam's own warmup contract is respected by
        re-anchoring ``warmup_steps`` at the switch step (q_adam.py:113-145 —
        the second moment must build in full precision before the compressed
        phase freezes it).  The displaced optax state is stashed and restored
        on the way back (slightly stale momentum beats a cold restart)."""
        if old.owns_optimizer == new.owns_optimizer:
            return
        from ..algorithms.q_adam import QAdamAlgorithm, QAdamOptState

        if new.owns_optimizer:
            assert isinstance(new, QAdamAlgorithm), type(new)
            # re-anchor warmup at the switch point (configured warmup counts
            # from here, not from training start).  The RELATIVE warmup is
            # remembered on first migration so repeated round trips through
            # qadam don't compound the absolute anchor.
            if not hasattr(new, "_base_warmup"):
                new._base_warmup = new.warmup_steps
            new._compressed = False
            new.warmup_steps = self._step_counter + new._base_warmup

            def to_owned(state):
                # stash a COPY: the adopted moments alias the live buffers,
                # which the next (donating) train step deletes
                self._stashed_opt_state = jax.tree.map(
                    jnp.copy, state.opt_state
                )
                moments = _find_adam_moments(state.opt_state)
                if moments is None:
                    zeros = jax.tree.map(jnp.zeros_like, state.params)
                    moments = (zeros, jax.tree.map(jnp.zeros_like, state.params))
                return state._replace(
                    opt_state=QAdamOptState(exp_avg=moments[0],
                                            exp_avg_sq=moments[1])
                )

            self._queue_state_migration(to_owned)
        else:

            def from_owned(state):
                stashed, self._stashed_opt_state = self._stashed_opt_state, None
                if stashed is not None:
                    return state._replace(opt_state=stashed)
                return state._replace(
                    opt_state=jax.jit(self._opt.init)(state.params)
                )

            self._queue_state_migration(from_owned)

    def _prepare_replication_migration(self, old, new) -> None:
        """Queue a replicated <-> stacked TrainState migration for the
        next ``train_step`` when a family switch crosses the replication
        boundary (gradient_allreduce/bytegrad <-> async model averaging).
        The switch itself is a re-jit — the new family's name/compile_key
        select a fresh compiled step through the step-cache key — and this
        migration converts the live buffers to the layout that step's
        shard_map specs expect.

        To a stacked (gossip) family: every rank's row adopts the
        replicated copy — the rows start bit-identical, exactly as
        ``init`` would build them.  Back to a replicated family: a
        synchronous catch-up average collapses the (possibly diverged)
        rows — the same consensus the async family's bounded-staleness cap
        forces, so the switch point has the semantics of one extra
        catch-up sync.  Integer leaves (step counters) advance in lockstep
        and reduce with MAX: an exact consensus, where integer AVG is not.
        The caller (:meth:`_maybe_switch_algorithm`) has already refused
        flat-resident state, optimizer-ownership crossings, and
        model-parallel meshes."""
        if old.replicated_params == new.replicated_params:
            return
        mesh, specs = self.mesh, P(self.dp_axes)
        ctx = self._ctx(self._plan)

        if not new.replicated_params:

            def migrate(state: TrainState) -> TrainState:
                logger.info(
                    "replication migration: replicated -> per-rank stacked "
                    "(%s)", type(new).__name__,
                )

                def stack_fn(p, o, a):
                    return _stack_tree(p), _stack_tree(o), _stack_tree(a)

                p, o, a = jax.jit(shard_map(
                    stack_fn, mesh=mesh, in_specs=(P(), P(), P()),
                    out_specs=(specs, specs, specs), check_vma=False,
                ))(state.params, state.opt_state, state.algo_state)
                return TrainState(state.step, p, o, a)
        else:

            def migrate(state: TrainState) -> TrainState:
                logger.info(
                    "replication migration: stacked -> replicated via "
                    "catch-up average (%s)", type(new).__name__,
                )

                def avg_fn(p, o, a):
                    def avg(x):
                        x = x[0]
                        if jnp.issubdtype(x.dtype, jnp.inexact):
                            return ctx.comm.allreduce(x, ReduceOp.AVG)
                        return ctx.comm.allreduce(x, ReduceOp.MAX)

                    return (jax.tree.map(avg, p), jax.tree.map(avg, o),
                            jax.tree.map(avg, a))

                p, o, a = jax.jit(shard_map(
                    avg_fn, mesh=mesh, in_specs=(specs, specs, specs),
                    out_specs=(P(), P(), P()), check_vma=False,
                ))(state.params, state.opt_state, state.algo_state)
                return TrainState(state.step, p, o, a)

        self._queue_state_migration(migrate)

    def _autotune_step(self, state):
        from ..communication import get_hyperparameters_service_client
        from ..define import BaguaHyperparameter

        rank = env.get_rank()
        obs = self._observer
        speed = obs.speed_since_last_report()
        hints = obs.drain_perf_hints()
        hints_delivered = False
        try:
            if self._autotune_client is None:
                self._autotune_client = get_hyperparameters_service_client()
            client = self._autotune_client
            rsp = client.report_metrics(
                model_name=self.model_name,
                rank=rank,
                train_iter=self._step_counter,
                hyperparameters=self._current_hyperparameters().model_dump(),
                speed=speed,
                perf_hints=hints or None,
                obs=obs.autotune_window(),
            )
            hints_delivered = True
            rsp = client.ask_hyperparameters(
                model_name=self.model_name, rank=rank, train_iter=self._step_counter
            )
            recommended = BaguaHyperparameter(**rsp["recommended_hyperparameters"])
            self._autotune_completed = bool(rsp.get("is_autotune_completed", False))
            self._apply_recommendation(recommended)
            self._autotune_failures = 0
        except Exception as e:  # autotune must never take down training
            if hints and not hints_delivered:
                # a transient sidecar hiccup must not discard the taint
                # signal — the next successful check-in carries it
                obs.requeue_perf_hints(hints)
            self._autotune_failures += 1
            logger.warning("autotune check-in failed (%d/3): %s",
                           self._autotune_failures, e)
            if self._autotune_failures >= 3:
                # a dead sidecar would otherwise stall every 100th step on
                # connection timeouts for the rest of the run
                logger.warning("autotune disabled after repeated failures")
                self.autotune = False

    def _current_hyperparameters(self):
        from ..define import BaguaHyperparameter

        buckets = [
            [t.declaration().model_dump() for t in b.tensors] for b in self._plan.buckets
        ] if self._plan else []
        from ..define import TensorDeclaration

        return BaguaHyperparameter(
            buckets=[[TensorDeclaration(**d) for d in b] for b in buckets],
            is_hierarchical_reduce=bool(self.algorithm.hierarchical),
            bucket_size=self.bucket_bytes,
            overlap=self.overlap,
            overlap_chunk_bytes=int(self.overlap_chunk_bytes),
            overlap_chunk_bytes_intra=int(self.overlap_chunk_bytes_intra),
            overlap_chunk_bytes_inter=int(self.overlap_chunk_bytes_inter),
            compress_intra=self.compress_intra,
            compress_inter=self.compress_inter,
            flat_resident="on" if self._flat_resident else "off",
        )

    def _batch_spec(self) -> P:
        if self.expert_axis is not None:
            return P(self.dp_axes + (self.expert_axis,))
        return P(self.dp_axes)

    def shard_batch(self, local_batch):
        """Stitch this process's local batch slice into global arrays laid
        out for the train step — the multi-host input path (each process
        feeds its own data shard, as each reference rank feeds its own
        DataLoader split).  Single-process: an explicit device_put with the
        step's input sharding (saves the jit-time relayout)."""
        from ..parallel.mesh import make_global_array

        spec = self._batch_spec()
        shards = 1
        for ax_entry in spec:
            for ax in (ax_entry if isinstance(ax_entry, tuple) else (ax_entry,)):
                if ax is not None:
                    shards *= self.mesh.shape[ax]
            break  # only the leading (batch) dim is sharded

        def check_and_make(x):
            # single-process only: with multiple processes each feeds its
            # own slice, so the per-process row count is a fraction of the
            # global requirement.  Only the shard count is enforced here —
            # accum_steps divisibility is a train-path concern (eval_step
            # consumes any shardable batch) and the step raises its own
            # clear error
            rows = (
                jnp.shape(x)[0]
                if jnp.ndim(x) and jax.process_count() == 1 else None
            )
            if rows is not None and rows % shards:
                raise ValueError(
                    f"batch leading dim {rows} must be divisible by "
                    f"{shards} (the number of batch shards)"
                )
            return make_global_array(self.mesh, spec, x)

        return jax.tree.map(check_and_make, local_batch)

    def _zero_staged(self) -> bool:
        """Whether hierarchical (intra-sharded) ZeRO is active — the
        host-side mirror of ``ZeroOptimizerAlgorithm._staged``; the opt
        state's stacked axis and the algorithm's shard comm must agree.

        The staged collectives span EXACTLY inter × intra, so any extra
        comm axis (sequence parallelism folds ``sp`` into comm_axes for
        partial-grad summation) must fall back to the flat path — staged
        rs/allreduce would silently skip the sp reduction."""
        return bool(
            getattr(self.algorithm, "sharded_opt_state", False)
            and getattr(self.algorithm, "hierarchical", False)
            and self._inter is not None
            and self._intra is not None
            and self._inter is not self._intra
            and self.world_size
            == self._inter.nranks() * self._intra.nranks()
        )

    def checkpoint_layout_metadata(self) -> dict:
        """Layout descriptor to store alongside checkpoints of this trainer's
        ``TrainState`` (pass as ``metadata=`` to
        :meth:`BaguaCheckpointManager.save` and ``expect_metadata=`` on
        restore).

        Flat-resident layouts store params (and optimizer state) as bucket
        flat buffers whose shapes depend on the bucket plan
        (``bucket_bytes`` split + alignment padding): a checkpoint saved
        under one plan can only restore DIRECTLY under the identical plan.
        This signature makes that restriction *detectable* — a raw
        ``BaguaCheckpointManager.restore`` at a different plan/world size
        fails with an actionable error instead of an opaque orbax shape
        mismatch (or, worse, a silent mis-restore) — while the
        ``flat_layout`` descriptor recorded alongside makes it *portable*:
        :meth:`restore_checkpoint` uses it to re-lay-out or leaf-convert
        the state across plans.  Plan-independent layouts record the
        signature too, so any future rebucketing divergence is caught."""
        import hashlib

        if self._plan is None:
            raise RuntimeError(
                "checkpoint_layout_metadata() needs the bucket plan — call "
                "trainer.init(params) first"
            )
        meta = {
            "layout": "flat" if self._flat_resident else "leaf",
            "plan_signature": hashlib.blake2b(
                repr(self._plan.signature()).encode(), digest_size=8
            ).hexdigest(),
            "world_size": int(self._comm.nranks()),
            "bucket_bytes": int(self.bucket_bytes),
            "plan_dependent": bool(self._flat_resident),
            # recorded for every layout: stacked (per-rank) states carry a
            # world-sized leading rank axis, which the cross-world restore
            # paths must know about even for plan-independent leaf layouts
            "stacked": not self.algorithm.replicated_params,
        }
        if self._flat_resident:
            # the full flat layout (bucket -> ordered (name, shape, dtype)
            # + alignment): everything restore_checkpoint needs to unpack
            # or relayout these buffers WITHOUT this trainer's plan
            meta["flat_layout"] = self._plan.layout_descriptor()
        if getattr(self.algorithm, "sharded_opt_state", False):
            # opt-state chunk layout depends on the SHARD count, which for
            # hierarchical ZeRO is the intra size, not the world size — a
            # restart at the same world but different intra must mismatch
            meta["opt_shards"] = int(
                self._intra.nranks() if self._zero_staged()
                else self._comm.nranks()
            )
        if self._ef_active():
            # the error-feedback residual in algo_state is plan- AND
            # world-keyed even under the otherwise plan-independent leaf
            # layout; this sidecar lets restore_checkpoint relayout it
            # across plans, or zero-reset it across world resizes, instead
            # of dying on an opaque orbax shape mismatch
            meta["ef"] = {
                "world": int(self._comm.nranks()),
                "flat_layout": self._plan.layout_descriptor(),
            }
        return meta

    # ---- layout-aware checkpointing --------------------------------------

    def _require_no_pending_migration(self, what: str) -> None:
        """Between a ``rebucket()`` and the next ``train_step``, the live
        state still holds the OLD plan's buffers while ``self._plan`` is
        the new one — a sidecar written in that window would describe the
        wrong layout and a later restore would silently corrupt weights."""
        if self._pending_state_migration is not None:
            raise RuntimeError(
                f"{what} with a state migration pending (a rebucket/"
                "family switch queued a layout change): run one "
                "train_step first so the resident state is migrated to "
                "the new bucket plan"
            )

    def save_checkpoint(self, manager, step: int, state: TrainState) -> bool:
        """Save ``state`` with this trainer's layout sidecar — the portable
        path: a checkpoint saved here restores through
        :meth:`restore_checkpoint` into ANY compatible trainer layout
        (flat or leaf, same plan or not)."""
        self._require_no_pending_migration("save_checkpoint")
        return manager.save(
            int(step), state, metadata=self.checkpoint_layout_metadata()
        )

    def restore_checkpoint(self, manager, state_like: TrainState,
                           step: Optional[int] = None):
        """Restore ``step`` (default: latest) into THIS trainer's state
        layout, converting via the saved layout sidecar when the on-disk
        layout differs:

        - same layout and (for flat) same plan/world: direct restore, the
          sidecar validated as in :meth:`BaguaCheckpointManager.restore`;
        - flat checkpoint -> flat trainer under another plan or world
          size: flat->flat relayout of params and optimizer state
          (:func:`bagua_tpu.bucket.relayout_flats` — no leaf round trip);
        - flat checkpoint -> leaf trainer (``flat_resident="off"``):
          leaves rebuilt from the sidecar's recorded bucket layout — the
          canonical-leaf fallback that keeps flat checkpoints portable;
        - leaf checkpoint -> flat trainer: leaves flattened into the
          current plan.

        Cross-layout conversion relies on optimizer state mirroring the
        param pytree (elementwise optax transforms, QAdam momenta).
        Sharded-opt-state ZeRO's per-chunk states stay plan-locked — a
        cross-plan ZeRO restore raises the manager's actionable layout
        error.  Per-rank (gossip) LEAF state additionally restores across
        an elastic WORLD RESIZE when its rank rows are bit-identical (the
        ``AsyncModelAverageAlgorithm.sync_for_checkpoint`` protocol): row 0
        is verified against every other row and re-tiled onto the live
        world; rows that diverged raise actionably.  Other stacked
        conversions stay identical-plan only.  After a successful restore
        the algorithm's :meth:`~bagua_tpu.algorithms.base.Algorithm.
        on_restore` hook runs — async model averaging resets its
        negotiated schedule there, so the resumed run opens a fresh
        calibration window instead of consuming a stale in-flight round or
        launch anchor.  Returns ``(step, state)``."""
        if self._plan is None:
            raise RuntimeError(
                "restore_checkpoint() needs the bucket plan — call "
                "trainer.init(params) first"
            )
        self._require_no_pending_migration("restore_checkpoint")
        if step is not None:
            result = self._restore_checkpoint_at(manager, state_like,
                                                 int(step))
        else:
            # integrity fallback: with no explicit step, ride the manager's
            # newest-first walk — a corrupted latest checkpoint degrades to
            # the previous verified one instead of crashing the resume
            result = manager._restore_newest_verified(
                lambda s: self._restore_checkpoint_at(manager, state_like, s)
            )
        self.algorithm.on_restore(self)
        return result[0], self._place_state(result[1])

    def _restore_checkpoint_at(self, manager, state_like: TrainState,
                               step: int):
        # error-feedback residual adapter: the residual's algo_state slot
        # is plan- AND world-keyed, so the restore targets the SAVED ef
        # structure (from the "ef" sidecar) and the fixup converts it into
        # the live one — relayout across plans, zero-reset across worlds,
        # zero-init when the checkpoint predates the codec flip, drop (with
        # a warning) when the live trainer no longer carries a residual
        saved_meta = manager.read_layout(step)
        adapted, ef_fixup = self._ef_restore_adapter(state_like, saved_meta)
        step, restored = self._restore_checkpoint_body(manager, adapted,
                                                       step)
        return step, ef_fixup(restored)

    def _ef_restore_adapter(self, state_like: TrainState,
                            saved: Optional[dict]):
        """``(adapted_state_like, fixup)`` for the error-feedback residual:
        ``adapted_state_like`` mirrors the CHECKPOINT's ef presence/shape
        (so orbax restores structurally), ``fixup`` converts the restored
        state back to the LIVE layout.  Identity when neither side carries
        a residual — and when the checkpoint has no sidecar at all, where
        nothing can be known and the direct restore stays the loud
        arbiter."""
        identity = (state_like, lambda s: s)
        a = state_like.algo_state
        has_live = isinstance(a, dict) and "ef" in a
        saved_ef = (saved or {}).get("ef")
        if saved is None or (not has_live and saved_ef is None):
            return identity
        if not has_live and not (isinstance(a, dict) or a is None):
            # non-dict algo state (stacked families) cannot host a saved
            # residual slot; the direct restore will surface the mismatch
            return identity

        ef_plan = None
        saved_container = None
        if saved_ef is not None:
            ef_plan = BucketPlan.from_layout_descriptor(
                saved_ef["flat_layout"]
            )
            # (a sidecar older than the shaped buckets wrote every
            # residual 1-D: restore it so, conform it below)
            ef_shapes = BucketPlan.saved_buffer_shapes(
                saved_ef["flat_layout"])
            saved_container = {"ef": {"buckets": tuple(
                jax.ShapeDtypeStruct((int(saved_ef["world"]),) + shape,
                                     np.dtype(np.float32))
                for shape in ef_shapes
            )}}

        if has_live:
            rest = {k: v for k, v in a.items() if k != "ef"}
            adapted_algo = (
                {**rest, **saved_container} if saved_container is not None
                else (rest or None)
            )
        else:
            adapted_algo = (
                {**a, **saved_container} if isinstance(a, dict)
                else saved_container
            )
        live_world = int(self._comm.nranks())
        live_plan = self._plan

        def fixup(state: TrainState) -> TrainState:
            a2 = state.algo_state
            if not has_live:
                # live trainer carries no residual: drop the restored one
                if isinstance(a2, dict) and "ef" in a2:
                    logger.warning(
                        "restore_checkpoint: discarding the checkpoint's "
                        "error-feedback residual — no stateful codec is "
                        "active in this trainer (compress knobs / "
                        "BAGUA_EF_RESIDUAL).  Re-enable the codec policy "
                        "before restoring to keep the accumulated error."
                    )
                    rest2 = {k: v for k, v in a2.items() if k != "ef"}
                    return state._replace(algo_state=rest2 or None)
                return state
            zeros = {"buckets": tuple(
                jnp.zeros(tuple(b.shape), jnp.float32)
                for b in a["ef"]["buckets"]
            )}
            if saved_container is None:
                logger.warning(
                    "restore_checkpoint: checkpoint carries no "
                    "error-feedback residual (saved before the stateful "
                    "codec was enabled): starting from ZERO residuals — "
                    "convergence-neutral, the error feedback re-warms "
                    "within a few steps"
                )
                merged = dict(a2) if isinstance(a2, dict) else {}
                merged["ef"] = zeros
                return state._replace(algo_state=merged)
            restored_ef = a2["ef"]
            if int(saved_ef["world"]) != live_world:
                logger.warning(
                    "restore_checkpoint: error-feedback residual was saved "
                    "at world_size=%d, trainer runs %d (elastic resize): "
                    "zero-resetting the residual — convergence-neutral, "
                    "the error feedback re-warms within a few steps",
                    int(saved_ef["world"]), live_world,
                )
                return state._replace(
                    algo_state={**a2, "ef": zeros}
                )
            restored_ef = {"buckets": tuple(conform_flats(
                ef_plan, restored_ef["buckets"], ef_shapes))}
            a2 = {**a2, "ef": restored_ef}
            if ef_plan.signature() != live_plan.signature():
                logger.info(
                    "restore_checkpoint: relaying out the error-feedback "
                    "residual %d -> %d buckets",
                    len(ef_plan.buckets), len(live_plan.buckets),
                )
                migrated = self.algorithm.relayout_algo_state(
                    ef_plan, live_plan, {"ef": restored_ef}
                )
                return state._replace(
                    algo_state={**a2, "ef": migrated["ef"]}
                )
            return state._replace(algo_state=a2)

        return state_like._replace(algo_state=adapted_algo), fixup

    def _restore_checkpoint_body(self, manager, state_like: TrainState,
                                 step: int):
        expected = self.checkpoint_layout_metadata()
        saved = manager.read_layout(step)
        # the manager owns legacy-alias normalization ("zero_flat"->"flat")
        saved_layout = (manager._normalize_layout(saved) or {}).get("layout")

        def direct():
            return manager.restore(
                state_like, step=step, expect_metadata=expected,
                mesh=self.mesh,
            )

        same_layout = saved_layout == expected["layout"]
        # the signature pins the concrete flat shapes — a world-size change
        # under an identical plan (alignment-1 buckets) restores directly
        same_plan = (
            saved is not None
            and saved.get("plan_signature") == expected["plan_signature"]
        )
        # the shapes the checkpoint's bucket buffers were written in: a
        # sidecar older than the shaped buckets (bucket.py) holds every
        # buffer 1-D, which the same PLAN may now hold in a tensor's shape
        saved_shapes = (
            BucketPlan.saved_buffer_shapes(saved["flat_layout"])
            if saved_layout == "flat" and "flat_layout" in saved else None
        )
        same_form = saved_shapes is None or not same_plan or saved_shapes == [
            b.buffer_shape for b in self._plan.buckets
        ]
        saved_world = (saved or {}).get("world_size")
        if (
            not self.algorithm.replicated_params
            and not self._flat_resident
            and saved_layout == "leaf"
            and saved_world
            and int(saved_world) != self._comm.nranks()
        ):
            # stacked (per-rank) leaf state across an elastic world resize:
            # the leading rank axis is world-sized, so the direct restore
            # would hit an opaque orbax shape mismatch — take the
            # row-identity re-tiling path instead
            return self._restore_stacked_resized(
                manager, state_like, step, saved, int(saved_world)
            )
        if saved is None or (same_layout and (
                saved_layout == "leaf" or (same_plan and same_form))):
            return direct()
        if saved_layout not in ("flat", "leaf"):
            return direct()
        old_plan = (
            BucketPlan.from_layout_descriptor(saved["flat_layout"])
            if saved_shapes is not None else None
        )
        is_zp = self._is_flat_container

        def saved_sds():
            return {
                "flats": tuple(
                    jax.ShapeDtypeStruct(shape, np.dtype(b.dtype))
                    for b, shape in zip(old_plan.buckets, saved_shapes)
                ),
                "local": {},
            }

        def conform(x):
            # restored in the saved shapes -> old_plan's own buffer shapes
            if is_zp(x):
                return {"flats": tuple(conform_flats(
                    old_plan, x["flats"], saved_shapes)), "local": x["local"]}
            return x

        if (same_layout and same_plan and not same_form
                and self.algorithm.replicated_params):
            # this trainer's own plan, written before its lone big tensors
            # kept their shape: every chunk state and rank stack restores
            # as it is, the resident buffers with one reshape each
            step, restored = manager.restore(
                jax.tree.map(lambda x: saved_sds() if is_zp(x) else x,
                             state_like, is_leaf=is_zp),
                step=step, expect_metadata=saved, mesh=self.mesh)
            return step, jax.tree.map(conform, restored, is_leaf=is_zp)
        if self.algorithm.sharded_opt_state:
            # per-chunk optimizer states are keyed on bucket boundaries AND
            # rank count; no host-side conversion exists — surface the
            # manager's actionable error instead of silently mis-restoring
            return direct()
        stacked = not self.algorithm.replicated_params
        if stacked or saved.get("stacked"):
            # gossip state carries a leading rank axis; cross-plan/layout
            # conversion of stacked rows is not supported
            return direct()
        if saved_layout == "flat" and "flat_layout" not in saved:
            return direct()  # legacy sidecar without the bucket descriptor
        if (
            saved_layout != expected["layout"]
            and getattr(self.optimizer, "fused_inner", None) is not None
        ):
            # a fuse_optimizer wrapper's LEAF-layout state is per-dtype
            # buffers inside _FusedState — neither param-shaped nor a flat
            # container — so cross-layout conversion cannot locate it;
            # raise here instead of an opaque orbax structure mismatch
            want = "on" if saved_layout == "flat" else "off"
            raise ValueError(
                "restore_checkpoint cannot convert across layouts for a "
                "fuse_optimizer-wrapped trainer: the wrapper's leaf-layout "
                "state is per-dtype fused buffers with no leaf/flat "
                "mirror.  Restore into a trainer with the checkpoint's own "
                f"layout (flat_resident='{want}'), or re-save after "
                "unwrapping."
            )
        param_def = jax.tree_util.tree_structure(self._param_template)
        if param_def == jax.tree_util.tree_structure(0):
            # a bare-leaf param "tree" cannot be located structurally
            return direct()

        def is_param_tree(x):
            try:
                return jax.tree_util.tree_structure(x) == param_def
            except Exception:  # unhashable/exotic leaves
                return False

        # 1. rebuild the SAVED state's structure from the live template:
        # optimizer state mirrors the params, so substituting at every
        # flat-container (current=flat) or param-shaped (current=leaf)
        # position reproduces the on-disk pytree
        if self._flat_resident:
            saved_like = jax.tree.map(
                lambda x: (
                    (self._param_template if saved_layout == "leaf"
                     else saved_sds()) if is_zp(x) else x
                ),
                state_like, is_leaf=is_zp,
            )
        else:
            saved_like = jax.tree.map(
                lambda x: saved_sds() if is_param_tree(x) else x,
                state_like, is_leaf=is_param_tree,
            )
        # expect the SAVED layout here: this restore deliberately targets
        # the on-disk structure (the conversion below re-lays it out)
        step, restored = manager.restore(saved_like, step=step,
                                         expect_metadata=saved,
                                         mesh=self.mesh)

        # 2. convert the restored state into the live layout
        from ..tensor import tree_from_named

        def from_flat(x):
            if is_zp(x):
                named = old_plan.unflatten_to_named(list(x["flats"]))
                named.update(x["local"])
                return tree_from_named(self._param_template, named)
            return x

        def to_flat(x):
            if is_param_tree(x):
                return {"flats": tuple(self._plan.flatten_tree(x)),
                        "local": {}}
            return x

        if self._flat_resident and saved_layout == "leaf":
            converted = jax.tree.map(to_flat, restored,
                                     is_leaf=is_param_tree)
        elif self._flat_resident:
            # replicated families only reach here (gossip took direct()),
            # so every plan-keyed buffer is behind a flat-container marker
            converted = self._relayout_tree(
                jax.tree.map(conform, restored, is_leaf=is_zp),
                old_plan, self._plan)
        else:
            converted = jax.tree.map(from_flat, restored, is_leaf=is_zp)
        logger.info(
            "restore_checkpoint: converted step %s from %s layout to %s",
            step, saved_layout, expected["layout"],
        )
        return step, converted

    def _restore_stacked_resized(self, manager, state_like: TrainState,
                                 step: int, saved: dict, saved_world: int):
        """Elastic world-resize restore for stacked (per-rank) LEAF states
        — the async model-average / gossip families, whose every
        params/opt/algo leaf carries a leading world-sized rank axis.

        Protocol: the checkpoint must have been saved with rank-identical
        rows (``AsyncModelAverageAlgorithm.sync_for_checkpoint`` — a
        blocking synchronous model average — right before the save).  The
        restore rebuilds the SAVED world's stacked shapes, verifies every
        row of every leaf is bit-identical to row 0, and re-tiles row 0
        onto the live world size.  Divergent rows raise actionably: they
        mean per-rank replicas that genuinely cannot be resized, and
        silently picking one row would discard other ranks' progress."""
        from jax.sharding import NamedSharding

        live_n = self._comm.nranks()
        stacked_trees = (state_like.params, state_like.opt_state,
                         state_like.algo_state)
        bad = [
            tuple(jnp.shape(x)) for x in jax.tree.leaves(stacked_trees)
            if not jnp.ndim(x) or jnp.shape(x)[0] != live_n
        ]
        if bad:
            raise ValueError(
                f"cross-world stacked restore expects every params/opt/algo "
                f"leaf to carry a leading rank axis of {live_n}, found "
                f"shapes {bad[:3]} — restore at the saved world size "
                f"({saved_world}) instead"
            )

        def to_saved(x):
            return jax.ShapeDtypeStruct(
                (saved_world,) + tuple(jnp.shape(x)[1:]), jnp.result_type(x)
            )

        saved_like = state_like._replace(
            params=jax.tree.map(to_saved, state_like.params),
            opt_state=jax.tree.map(to_saved, state_like.opt_state),
            algo_state=jax.tree.map(to_saved, state_like.algo_state),
        )
        # expect the SAVED metadata: this restore deliberately targets the
        # on-disk world; the re-tiling below moves it onto the live one
        step, restored = manager.restore(saved_like, step=step,
                                         expect_metadata=saved,
                                         mesh=self.mesh)

        def retile(sx, like):
            a = np.asarray(sx)
            row0 = a[0]
            b0 = row0.tobytes()
            for r in range(1, a.shape[0]):
                if b0 != a[r].tobytes():
                    raise ValueError(
                        f"stacked checkpoint step {step} (world "
                        f"{saved_world}) has DIVERGENT per-rank rows — it "
                        "cannot restore onto a resized world "
                        f"({live_n} ranks).  Save resize-portable async "
                        "checkpoints via algorithm.sync_for_checkpoint("
                        "trainer, state) (a blocking synchronous model "
                        "average) right before save_checkpoint, or restore "
                        "at the original world size."
                    )
            out = jnp.asarray(
                np.broadcast_to(row0, (live_n,) + row0.shape).copy()
            )
            sh = getattr(like, "sharding", None)
            if isinstance(sh, NamedSharding):
                out = jax.device_put(out, sh)
            return out

        converted = state_like._replace(
            step=restored.step,
            params=jax.tree.map(retile, restored.params, state_like.params),
            opt_state=jax.tree.map(retile, restored.opt_state,
                                   state_like.opt_state),
            algo_state=jax.tree.map(retile, restored.algo_state,
                                    state_like.algo_state),
        )
        counters.incr("ckpt/stacked_resize_restores")
        logger.info(
            "restore_checkpoint: re-tiled stacked step %s from world %d "
            "onto world %d (rank rows verified bit-identical)",
            step, saved_world, live_n,
        )
        return step, converted

    def unstack_params(self, state: TrainState):
        """Return params in user shape (for eval/checkpoint): rank 0's copy
        for replicated/gossip state; global ``[n_experts, ...]`` expert leaves
        re-assembled from their ep shards."""
        if self._flat_resident:
            # flat-resident layouts: materialize the leaf pytree lazily
            # (this is the ONLY place the unflatten happens off the hot
            # path — eval/checkpoint/user inspection).  The jitted
            # unflatten is cached per bucket plan so periodic
            # checkpoint/eval calls don't retrace it every time.
            zp = state.params
            if not self.algorithm.replicated_params:
                # gossip state is stacked per rank; rank 0's row is the
                # user-facing copy, as in the leaf layout below
                zp = jax.tree.map(lambda x: x[0], zp)
            cache_key = self._plan.signature()
            cached = getattr(self, "_unflatten_cache", None)
            if cached is None or cached[0] != cache_key:
                cached = (cache_key, jax.jit(self._flat_leaf_view))
                self._unflatten_cache = cached
            return cached[1](zp)
        if self.expert_axis is None or self.algorithm.sharded_opt_state:
            # ZeRO keeps expert leaves as global [n_experts, ...] arrays
            # (sharded in place), so no re-assembly is needed
            if self.algorithm.replicated_params:
                return state.params
            return jax.tree.map(lambda x: x[0], state.params)

        def fix(path, leaf):
            if self._is_expert_name(_name_of_path(path)):
                return leaf.reshape((-1,) + leaf.shape[2:])
            return leaf[0]

        return jax.tree_util.tree_map_with_path(fix, state.params)

    def record_speed(self, n_samples: float):
        """Manual override of the automatic per-step speed tracking
        (:meth:`StepObserver.record_speed`)."""
        self._observer.record_speed(n_samples)

    # ---- the sharded update (gradient_allreduce.py's header) ---------------

    #: (optimizer, verdict) of the last elementwise probe
    _elementwise_probed = (None, False)

    def _opt_elementwise(self) -> bool:
        """Whether a rank may step its own chunk of a bucket by itself
        (``zero.is_elementwise`` of the optimizer the step runs), probed
        once an optimizer and only where the sharded update could engage at
        all: one chip's trainer pays nothing for it."""
        if self._elementwise_probed[0] is not self._opt:
            self._elementwise_probed = (self._opt, is_elementwise(self._opt))
        return self._elementwise_probed[1]

    def _mark_sharded_update(self, ctx: AlgorithmContext) -> AlgorithmContext:
        """Set :attr:`AlgorithmContext.sharded_update`: the exact family's
        update is sharded over the comm world wherever what can be observed
        allows it — no option selects it: more than one rank, every one of
        them a data-parallel replica (the flat-resident layout implies no
        tp / pp / ep axis), a flat exchange, an optimizer that steps a chunk
        as it steps the whole, and no error-feedback residual riding whole
        buckets — and a wire no narrower than the parameters: with a
        float32 gather at the end of the step the pair moved three quarters
        of the float32 all-reduce's bytes where the bfloat16 all-reduce
        moves half, and the chip read that 7.9 % slower (PERF.md §6, PR 49;
        whether the clause still earns its place with the gather at the top
        is PERF.md §7's open question, PR 57).  Where it holds, the taken
        buckets of ``state.params`` and of the moments rest between steps as
        a chunk a rank (:meth:`_resident_specs`: global shapes unchanged)
        and the step gathers the parameters at its top.  Everything else
        traces the all-reduce and the replicated update it always did."""
        algo = self.algorithm
        wire = getattr(algo, "comm_dtype", None)
        ctx.sharded_update = bool(
            self.world_size > 1
            and self._flat_resident
            and algo.supports_sharded_update
            and not algo.hierarchical
            and self.seq_axis is None
            and (wire is None or ctx.plan is None or all(
                np.dtype(wire).itemsize >= np.dtype(b.dtype).itemsize
                for b in ctx.plan.buckets))
            and self._opt_elementwise()
            and algo.ef_codec(ctx) is None
        )
        return ctx

    def _update_sharded(self) -> bool:
        """Whether the CURRENT configuration shards the update
        (:attr:`AlgorithmContext.sharded_update`): parameters and optimizer
        state are then laid out over the comm axes (:meth:`_resident_specs`,
        :meth:`_opt_state_specs`)."""
        return self.world_size > 1 and self._ctx(self._plan).sharded_update

    def _resident_specs(self, plan: BucketPlan):
        """``shard_map`` specs of the flat-resident parameter container
        under the sharded update: a bucket buffer whose update is sharded
        (:meth:`AlgorithmContext.update_sharded`) is cut over the comm axes
        along its leading axis — globally it is the buffer the replicated
        layout holds, in the same shape; each rank stores its chunk of it,
        the rows it updates, and the step gathers the rest at its top — and
        every other bucket is replicated."""
        ctx = self._ctx(plan)
        return {"flats": tuple(
            P(self.comm_axes) if ctx.update_sharded(i) else P()
            for i in range(len(plan.buckets))), "local": {}}

    def _opt_state_specs(self, plan: BucketPlan):
        """``shard_map`` specs of the optimizer state under the sharded
        update, a pytree over ``self._opt``'s state: every moment rests as
        the parameters do (:meth:`_resident_specs`), and everything else
        (counts) is replicated."""
        resident = self._resident_specs(plan)
        like = {"flats": tuple(jax.ShapeDtypeStruct(b.buffer_shape, b.dtype)
                               for b in plan.buckets), "local": {}}
        is_zp = self._is_flat_container
        return jax.tree.map(
            lambda x: resident if is_zp(x) else P(),
            jax.eval_shape(self._opt.init, like), is_leaf=is_zp)

    def _state_shardings(self, plan: BucketPlan, replicated):
        """``(parameters', optimizer state's)`` shardings: ``replicated``
        twice, or under the sharded update the pytrees of shardings that
        :meth:`_resident_specs` and :meth:`_opt_state_specs` describe (an
        elementwise ``init`` of the whole, cut, is the init of the chunk)."""
        if not self._update_sharded():
            return replicated, replicated

        def named(specs):
            return jax.tree.map(lambda spec: NamedSharding(self.mesh, spec),
                                specs, is_leaf=lambda x: isinstance(x, P))

        return (named(self._resident_specs(plan)),
                named(self._opt_state_specs(plan)))

    def _opt_state_shardings(self, plan: BucketPlan, replicated):
        """The optimizer state's half of :meth:`_state_shardings`."""
        return self._state_shardings(plan, replicated)[1]

    def _place_state(self, state: TrainState) -> TrainState:
        """``state`` with its parameters and its optimizer state placed as
        the sharded update's step takes them (``init`` builds them so; every
        queued state migration and every restore ends here).  A migration or
        a restore builds them replicated, or as the compiler pleased: the
        step would accept that — the layouts differ in placement, not in
        shape — at the price of a program of its own for that one
        dispatch."""
        placed = {}
        for field, shardings in zip(
                ("params", "opt_state"),
                self._state_shardings(self._plan, None)):
            tree = getattr(state, field)
            # (a displaced family's state is not this layout's)
            if shardings is not None and jax.tree.structure(
                    shardings) == jax.tree.structure(tree):
                placed[field] = jax.device_put(tree, shardings)
        return state._replace(**placed)

    def _note_plan_gauges(self) -> None:
        """What the plan of the step program just built asks for."""
        # what the plan asks of the wire per step; what XLA's combiner
        # makes of it is a count over the compiled text
        counters.set_gauge(
            "comm/buckets_per_step",
            len(self._plan.buckets) if self._comm.nranks() > 1 else 0)
        # how much of the plan is held in its tensors' own shapes
        # (bucket.py: a tensor as large as a bucket is its own bucket)
        nbytes = [b.padded_numel * np.dtype(b.dtype).itemsize
                  for b in self._plan.buckets]
        counters.set_gauge(
            "comm/shaped_bytes_share",
            sum(n for n, b in zip(nbytes, self._plan.buckets)
                if b.shaped) / max(1, sum(nbytes)))
        # ... and how much of it is updated by the rank that owns its
        # chunk alone (all-gather -> loss -> reduce-scatter -> update), which
        # is how much of it rests as a chunk a rank between steps
        ctx = self._ctx(self._plan)
        share = sum(n for i, n in enumerate(nbytes)
                    if ctx.update_sharded(i)) / max(1, sum(nbytes))
        counters.set_gauge("comm/sharded_update_share", share)
        counters.set_gauge("comm/params_sharded_share", share)
