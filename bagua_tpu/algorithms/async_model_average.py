"""Asynchronous model averaging.

Counterpart of /root/reference/bagua/torch_api/algorithms/async_model_average.py
(:156-233) + comm_ops/decentralized_full_precision_asynchronous.rs: a
background loop continuously allreduce-averages the weights while compute
proceeds, with a lock so weights are swapped only between steps, and
``abort``/``resume`` control.

TPU-native mechanism: the reference needs a worker thread + CUDA stream +
weight mutex because torch executes eagerly.  JAX's async dispatch already
gives us a "background stream": the averaging is its own tiny jitted
collective, dispatched without blocking the Python loop; train steps keep
executing on stale local weights while it's in flight (same staleness
semantics as the reference), and the result is swapped into the train state
between steps — the functional equivalent of the reference's weight lock held
during forward/backward (:156-168).  ``warmup_steps`` of synchronous gradient
allreduce match the reference (:60, :125-131).

Multi-process correctness: under XLA every process driving a shared mesh must
dispatch the *same* global programs in the *same* order, so the reference's
"launch a round whenever the local wall clock says so" gate
(async_model_average.py:170-177) cannot be ported as-is — two hosts with
skewed clocks would interleave the averaging collective differently against
train steps and deadlock.  Instead the launch schedule is **deterministic in
the step counter**: after warmup, a short calibration window measures the
local step time, all processes agree on the slowest host's value (the
reference's gloo side-channel, :59-60, here a tiny cross-process allgather),
and rounds launch every ``k``-th step with ``k`` derived from
``sync_interval_ms`` and the agreed step time.  ``abort``/``resume`` are
likewise *negotiated*: a request only takes effect at the next scheduled
boundary, simultaneously on every process (reference RESUME/ABORT
negotiation each background round, :170-233).

Bounded staleness (the straggler/partition story): *launching* a round is
global (the averaging collective needs every rank), but *applying* its delta
is a purely local elementwise combine — so a rank may locally sit a round
out without breaking the SPMD dispatch schedule.  Two things make it do so:
a gradient-guard rewind landed while the round was in flight (applying the
delta on top of a rewound state would smuggle the skipped step's progress
back in), or an armed ``async.partition`` fault dropped it from the round.
Each rank's applied-round counter rides the negotiation gather; when the
worst rank's lag reaches ``max_staleness_rounds``, every process
deterministically agrees to a **synchronous catch-up average**: block on a
full model average and assign it, leaving every rank's replica bit-identical
and the counters equalized.  Slow or flaky ranks therefore degrade round
freshness instead of gating the step — and persistent offenders surface to
the elastic coordinator through the heartbeat health payload
(``async/missed_boundaries``; see docs/robustness.md).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import env
from ..communication import ReduceOp
from ..faults import inject as _inject
from ..obs.spans import trace_span
from ..telemetry import counters
from .base import Algorithm, AlgorithmContext

logger = logging.getLogger(__name__)

_RUNNING = 0
_ABORTED = 1

# per-boundary control intents (edge-triggered: consumed at negotiation, so
# a later resume() from a DIFFERENT rank than the aborter still takes effect)
_REQ_NONE = 0
_REQ_RESUME = 1
_REQ_ABORT = 2  # highest: abort wins when both are requested the same round


def _negotiate(payload, watchdog=None, label: str = "async-negotiate"):
    """All-process gather of a small per-process control vector; returns a
    ``(process_count, len(payload))`` float64 array (single-process: the
    payload itself as one row).

    The cross-process control channel — plays the role of the reference's
    gloo process group used for RESUME/ABORT negotiation
    (async_model_average.py:59-60), generalized from a scalar max to a full
    per-rank gather so the boundary can also exchange applied-round
    counters for bounded-staleness tracking.  Every process must call this
    at the same step boundary (the schedule guarantees that).  The blocking
    gather runs inside a watchdog-watched section when one is supplied: a
    peer dying between rounds would otherwise hang survivors here with no
    active watched section to trip hang detection.
    """
    vec = np.asarray(payload, dtype=np.float64).reshape(1, -1)
    if jax.process_count() == 1:
        return vec
    from contextlib import nullcontext

    from jax.experimental import multihost_utils

    guard = watchdog.watch(label) if watchdog is not None else nullcontext()
    with guard:
        gathered = multihost_utils.process_allgather(vec[0])
    return np.asarray(gathered, dtype=np.float64).reshape(
        jax.process_count(), -1
    )


def _agree_max(value: float, watchdog=None, label: str = "async-negotiate") -> float:
    """All-process max of a host scalar (single-process: identity)."""
    return float(np.max(_negotiate([float(value)], watchdog, label)[:, 0]))


class AsyncModelAverageAlgorithm(Algorithm):
    name = "async"
    replicated_params = False
    #: async steps run on stale local weights — a slow peer binds this
    #: family only at its negotiated boundaries (which call the
    #: ``step.straggle`` hook themselves), never per step
    straggler_gates_step = False

    def __init__(
        self,
        peer_selection_mode: str = "all",
        sync_interval_ms: int = 500,
        warmup_steps: int = 0,
        calibration_steps: int = 4,
        period_steps: Optional[int] = None,
        recalibrate_rounds: Optional[int] = 64,
        max_staleness_rounds: Optional[int] = None,
    ):
        """
        Args:
            peer_selection_mode: Only ``"all"`` is supported (as in the
                reference async op).
            sync_interval_ms: Target milliseconds between averaging rounds
                (reference sync_interval_ms).  Converted to a step period at
                calibration; ``0`` means every step.
            warmup_steps: Initial steps of synchronous gradient allreduce
                before going asynchronous (reference :60).
            calibration_steps: Steps used to measure the (slowest) host's
                step time before the first round launches.
            period_steps: Pin the averaging period to an exact step count and
                skip wall-clock calibration entirely.  Use when the cadence
                must be machine-load-independent (e.g. convergence gates);
                ``sync_interval_ms`` is ignored when set.
            recalibrate_rounds: Re-run the fenced calibration after this many
                averaging rounds so the agreed period tracks sustained step-
                time changes (phase recompiles, rebucketing, input-dependent
                slowdowns).  ``None`` disables; ignored with ``period_steps``.
            max_staleness_rounds: Bounded-staleness cap: when any rank's
                applied-round counter reaches this many rounds behind the
                launched count (gradient-guard rewinds and
                ``async.partition`` drops both stall it), that boundary
                forces a synchronous catch-up average — blocking, applied
                on every rank, leaving replicas bit-identical — so the lag
                NEVER exceeds the cap.  ``0`` disables the bound (purely
                asynchronous); ``None`` reads ``BAGUA_ASYNC_MAX_STALENESS``
                (default 4).
        """
        assert peer_selection_mode == "all"
        self.peer_selection_mode = peer_selection_mode
        self.sync_interval_ms = sync_interval_ms
        self.warmup_steps = warmup_steps
        self.calibration_steps = max(1, calibration_steps)
        self.period_steps = period_steps
        self.recalibrate_rounds = (
            None if recalibrate_rounds is None else max(1, recalibrate_rounds)
        )
        if max_staleness_rounds is None:
            max_staleness_rounds = env.get_async_max_staleness()
        if max_staleness_rounds < 0:
            raise ValueError(
                f"max_staleness_rounds must be >= 0 (0 disables the bound), "
                f"got {max_staleness_rounds}"
            )
        self.max_staleness_rounds = int(max_staleness_rounds)
        self._request = _REQ_NONE    # this rank's pending abort()/resume()
        self._status = _RUNNING      # negotiated, changes only at boundaries
        self._pending: Optional[Any] = None
        self._avg_fn = None
        self._period: Optional[int] = None   # agreed steps between rounds
        self._anchor: Optional[int] = None   # step the schedule starts from
        self._calib_t0: Optional[float] = None
        self._calib_start: Optional[int] = None  # step the window opened at
        self._calib_skip = 1         # steps to skip before opening a window
        self._agreed_dt: Optional[float] = None  # slowest host's step time
        self._rounds = 0             # rounds since the period was agreed
        # bounded-staleness bookkeeping: launches are global (negotiated),
        # applies are local — the counters may diverge per rank
        self._rounds_launched = 0
        self._rounds_applied = 0
        self._rounds_dropped = 0
        self._drop_next = False      # async.partition: sit the next apply out
        self._rewinds_at_launch = 0  # trainer grad-guard rewind count @launch
        self._lock = threading.Lock()
        # _request has its own tiny lock so abort()/resume() callers never
        # block behind the boundary's cross-process gather (held under _lock)
        self._req_lock = threading.Lock()

    # ---- traced stages ---------------------------------------------------

    def process_grads(self, ctx: AlgorithmContext, grads, params, algo_state, step):
        # warmup: plain synchronous allreduce of gradients (reference
        # :125-131 registers a centralized op during warmup)
        if self.warmup_steps > 0:
            flats = ctx.plan.flatten_tree(grads)

            def sync(fs):
                return [ctx.comm.allreduce(f, ReduceOp.AVG) for f in fs]

            flats = jax.lax.cond(step < self.warmup_steps, sync, lambda fs: fs, flats)
            grads = ctx.plan.unflatten_tree(flats, grads)
        return grads, algo_state

    # ---- host-side async loop -------------------------------------------

    def _ensure_avg_fn(self, trainer):
        if self._avg_fn is not None:
            return
        mesh = trainer.mesh
        comm = trainer._comm
        spec = P(comm.axis_name if len(comm.axes) == 1 else comm.axes)

        def avg(params_stacked):
            p = jax.tree.map(lambda x: x[0], params_stacked)
            p = jax.tree.map(lambda x: comm.allreduce(x, ReduceOp.AVG), p)
            return jax.tree.map(lambda x: x[None], p)

        from jax import shard_map

        self._avg_fn = jax.jit(
            shard_map(avg, mesh=mesh, in_specs=spec, out_specs=spec,
                      check_vma=False)
        )
        # apply the averaging as a DELTA onto the current weights, exactly the
        # reference kernel's `x += reduced/n - copy` under the weight lock
        # (decentralized_full_precision_asynchronous.rs:121-126): local
        # progress made while the collective was in flight is preserved.
        self._combine_fn = jax.jit(
            lambda cur, avg_, snap: jax.tree.map(
                lambda c, a, s: c + a - s, cur, avg_, snap
            )
        )
        self._snap_fn = jax.jit(lambda t: jax.tree.map(jnp.copy, t))

    def _warm_compiles(self, trainer, params) -> None:
        """Build + compile the aux jits off the steady-state window (a cache
        hit later): at a boundary they would land inside the user's training
        loop — seconds of compile inside the measured window.

        Done-once per param avals: ``.lower().compile()`` bypasses the jit
        cache and re-lowers every call, so without the guard each periodic
        recalibration (``recalibrate_rounds``) re-paid three compiles on
        unchanged shapes.  The key read is metadata-only
        (``jnp.result_type``, never ``asarray``): materializing every leaf
        just to spell its dtype would copy whole buffers to the host."""
        key = tuple(
            (tuple(jnp.shape(x)), str(jnp.result_type(x)))
            for x in jax.tree.leaves(params)
        )
        if getattr(self, "_warmed_key", None) == key:
            return
        self._ensure_avg_fn(trainer)
        self._snap_fn.lower(params).compile()
        self._avg_fn.lower(params).compile()
        self._combine_fn.lower(params, params, params).compile()
        self._warmed_key = key

    def _apply_pending(self, state, watchdog=None, block=False):
        """Apply the in-flight round to ``state`` (caller holds the lock).

        Deterministic: every process launched the identical round at the
        identical step, so every process applies it at the identical step.
        The scheduled path does NOT wait for completion — the jitted
        combine consumes ``avg_result`` through a device-side data
        dependency, so XLA keeps train steps and the averaging collective
        overlapped (host-blocking here serializes them).  ``block=True`` (barrier/final drain)
        additionally fences, watchdog-guarded: a peer dying mid-collective
        would otherwise hang survivors with no watched section active."""
        avg_result, snapshot = self._pending
        if block:
            from contextlib import nullcontext

            guard = (
                watchdog.watch("async-drain") if watchdog is not None
                else nullcontext()
            )
            with guard:
                jax.block_until_ready(avg_result)
        state = state._replace(
            params=self._combine_fn(state.params, avg_result, snapshot)
        )
        self._pending = None
        self._drop_next = False
        self._rounds_applied += 1
        counters.incr("async/rounds_applied")
        return state

    def _drop_pending(self, why: str, health_event: bool = True) -> None:
        """Discard the in-flight round WITHOUT applying its delta (caller
        holds the lock): the rank sits this round out and its applied
        counter stalls — the staleness the negotiated catch-up bounds.

        ``health_event=False`` for drops that happen on EVERY rank at once
        (catch-up supersede, comm abort): ``async/missed_boundaries`` feeds
        the coordinator's fence scalar, and counting fleet-wide drops there
        would let one chronic straggler push every HEALTHY node past
        ``fence_unhealthy_after`` — the fence must name the offender, whose
        own partition/rewind drops were already counted."""
        self._pending = None
        self._drop_next = False
        self._rounds_dropped += 1
        counters.incr("async/rounds_dropped")
        if health_event:
            counters.incr("async/missed_boundaries")
            # missed rounds are a fenceable health event: publish them to
            # the beacon file so the launcher's heartbeat carries them to
            # the coordinator (grad-guard is the only other writer —
            # without this, a rank that drops rounds with finite gradients
            # never surfaces)
            from ..elastic.membership import write_health_beacon

            write_health_beacon()
        logger.warning(
            "async model average: round %d NOT applied on this rank (%s); "
            "applied %d/%d", self._rounds_launched, why,
            self._rounds_applied, self._rounds_launched,
        )

    def _pending_veto(self, trainer):
        """``(will_drop, reason)`` for the in-flight round — the ONE veto
        both the scheduled boundary and ``_drain_pending`` enforce (caller
        holds the lock).  Flushes not-yet-inspected grad-guard verdicts
        first: the guard runs one step behind, and a rewind the host has
        not seen yet must still veto the delta — applying a round on top
        of a rewound state would smuggle the skipped step's progress back
        in."""
        if self._pending is None:
            return False, None
        if getattr(trainer, "grad_guard", "off") != "off":
            trainer.flush_grad_health()
        if (getattr(trainer, "_guard_rewinds_total", 0)
                != self._rewinds_at_launch):
            return True, "grad-guard rewind during the round"
        if self._drop_next:
            return True, "partitioned out of the negotiation round"
        return False, None

    def _drain_pending(self, trainer, state, watchdog, block=False):
        """Drain the in-flight round under the SAME veto the scheduled
        boundary enforces (caller holds the lock): a grad-guard rewind
        since launch, or a fired partition drop, discards the delta
        instead of applying it.  Without the veto, ``barrier()`` or
        ``sync_for_checkpoint()`` called between boundaries would combine
        a pre-rewind snapshot's delta into the rewound state, or apply the
        very round an armed ``async.partition`` promised this rank never
        applies."""
        if self._pending is None:
            return state
        will_drop, reason = self._pending_veto(trainer)
        if will_drop:
            self._drop_pending(reason)
            return state
        return self._apply_pending(state, watchdog, block=block)

    def _catchup_sync(self, trainer, state, watchdog, step: int,
                      reason: str):
        """Forced synchronous model average (caller holds the lock): drop
        any in-flight round (the full sync supersedes its delta), block on
        an averaging collective over the CURRENT weights, and assign the
        result — every rank's replica is bit-identical afterwards and the
        applied counters equalize to the launched count.  Deterministic:
        the decision derives from the negotiated gather, so every process
        takes this branch at the same boundary."""
        from contextlib import nullcontext

        if self._pending is not None:
            # every rank drops here (launches are global) — not a
            # this-rank fault, so no fenceable health event
            self._drop_pending(f"superseded by catch-up sync ({reason})",
                               health_event=False)
        self._ensure_avg_fn(trainer)
        # a blocking full-fleet collective: the one async point a straggler
        # genuinely gates
        self._gated_straggle(trainer, "async.catchup")
        guard = (
            watchdog.watch("async-catchup") if watchdog is not None
            else nullcontext()
        )
        _t0 = time.monotonic()
        with trace_span("async/catchup", step=step, reason=reason,
                        launched=self._rounds_launched,
                        applied=self._rounds_applied), guard:
            avg = self._avg_fn(state.params)
            jax.block_until_ready(avg)
        self._note_collective_phase(trainer, time.monotonic() - _t0)
        state = state._replace(params=avg)
        self._rounds_applied = self._rounds_launched
        counters.incr("async/catchup_syncs")
        counters.set_gauge("async/staleness_max", 0)
        if reason == "staleness":
            _inject.record_recovery("async.partition")
        logger.warning(
            "async model average: synchronous catch-up average at step %d "
            "(%s) — replicas re-synced bit-identically after %d round(s)",
            step, reason, self._rounds_launched,
        )
        return state

    def _boundary_base_dt(self, trainer) -> Optional[float]:
        """The straggler-dilation base for gated boundaries: the agreed
        (slowest-host) step time when calibrated, else the trainer's own
        measured step cadence."""
        if self._agreed_dt is not None:
            return self._agreed_dt
        fn = getattr(trainer, "measured_step_dt", None)
        return fn() if callable(fn) else None

    @staticmethod
    def _note_collective_phase(trainer, seconds: float) -> None:
        """Attribute a host-visible synchronization wait (negotiate gather,
        catch-up average) to the anomaly detector's ``collective`` phase —
        these boundaries are where a slow peer gates this rank."""
        note = getattr(trainer, "note_phase_duration", None)
        if callable(note):
            note("collective", seconds)

    def _gated_straggle(self, trainer, sync_point: str) -> None:
        """Injected straggler stall at a gated boundary, reported back to
        the trainer's cadence tracker: an unreported boundary sleep lands
        in the next ``measured_step_dt`` sample and becomes the base of the
        next stall — the compounding that method promises to prevent."""
        slept = _inject.maybe_straggle(
            sync_point, base_dt=self._boundary_base_dt(trainer)
        )
        if slept:
            note = getattr(trainer, "note_injected_stall", None)
            if callable(note):
                note(slept)

    def _calibrate(self, trainer, state, step: int, watchdog=None) -> None:
        """Agree a launch period from the slowest host's measured step time
        (replaces the reference's per-host wall-clock gate, :170-177).

        Both window edges are FENCED with a scalar readback of the step
        counter: the host dispatch loop runs far ahead of the device, so an
        unfenced wall-clock window measures dispatch cadence, not step time
        (observed to mis-calibrate the period by 5x either way).  The
        averaging/combine/snapshot jits are also compiled HERE — at the
        first boundary they would land inside the user's steady-state
        window (seconds of compile).

        Restartable: periodic re-calibration (``recalibrate_rounds``) resets
        the window state and re-enters here, so a sustained step-time change
        (recompile, rebucketing) re-derives the period deterministically on
        all processes."""
        if self._calib_skip > 0:
            # skip step(s) right after warmup / a recalibration trigger:
            # they may include trace/compile time
            self._calib_skip -= 1
            return
        if self._calib_start is None:
            self._warm_compiles(trainer, state.params)
            np.asarray(state.step)  # fence: start from a drained pipeline
            self._calib_t0 = time.monotonic()
            self._calib_start = step
        elif step >= self._calib_start + self.calibration_steps:
            np.asarray(state.step)  # fence: include the full device work
            window = step - self._calib_start
            local_dt = (time.monotonic() - self._calib_t0) / window
            agreed_dt = _agree_max(local_dt, watchdog, "async-calibrate")
            self._agreed_dt = agreed_dt
            self._period = max(
                1, int(round(self.sync_interval_ms / (agreed_dt * 1000.0)))
            )
            self._anchor = step
            self._rounds = 0
            logger.info(
                "async model average: agreed step time %.4fs (local %.4fs) "
                "-> averaging every %d step(s)",
                agreed_dt, local_dt, self._period,
            )

    def host_pre_step(self, trainer, state):
        """Between-steps swap point (the reference's weight lock boundary)."""
        from ..communication import is_aborted

        if is_aborted():
            # the global abort flag (watchdog or user) stops the averaging
            # control loop exactly like a local abort() call — no new
            # rounds are launched, pending results are dropped; this process
            # is about to exit for gang restart, so cross-rank agreement is
            # moot here
            with self._lock:
                if self._pending is not None:
                    # abort stops every rank's control loop — fleet-wide,
                    # not a this-rank fault
                    self._drop_pending("comm abort flag raised",
                                       health_event=False)
            return state
        step = trainer._step_counter
        if step <= self.warmup_steps:
            return state
        if trainer._comm.nranks() == 1:
            # the averaging collective is an identity on a 1-rank comm world:
            # skip snapshot/avg/combine entirely (the reference's async CI
            # floor is the HIGHEST of all families — async must never cost;
            # round 4 measured ~10% single-chip overhead from these hops)
            return state
        watchdog = getattr(trainer, "_watchdog", None)
        with self._lock:
            if self._period is None:
                if self.period_steps is not None:
                    # pinned cadence: no wall-clock dependence at all
                    self._warm_compiles(trainer, state.params)
                    self._period = max(1, int(self.period_steps))
                    self._anchor = step
                    self._rounds = 0
                else:
                    self._calibrate(trainer, state, step, watchdog)
                return state
            if (step - self._anchor) % self._period != 0:
                return state
            # ---- scheduled boundary: negotiate, drain, launch ------------
            # every process reaches this branch at the same step, so the
            # control allgather and the collectives below line up globally.
            # A slow peer gates this boundary (the gather blocks on it);
            # the intervening steps ran free on stale local weights.
            self._gated_straggle(trainer, "async.negotiate")
            # the shared veto decides the apply BEFORE the gather so the
            # negotiated applied_after reflects the drop
            will_drop, drop_reason = self._pending_veto(trainer)
            # Requests are edge-triggered: the atomic read-then-clear under
            # _req_lock means an abort()/resume() issued from another thread
            # while the gather below is in flight stays pending for the next
            # boundary instead of being wiped.
            with self._req_lock:
                my_req, self._request = self._request, _REQ_NONE
            applied_after = self._rounds_applied + (
                1 if (self._pending is not None and not will_drop) else 0
            )
            # span: the negotiation gather is where a slow peer gates every
            # rank — its duration IS the straggler wait
            _t0 = time.monotonic()
            with trace_span("async/negotiate", step=step,
                            launched=self._rounds_launched,
                            applied=self._rounds_applied):
                gathered = _negotiate(
                    [float(my_req), float(applied_after)], watchdog
                )
            self._note_collective_phase(trainer, time.monotonic() - _t0)
            req = float(np.max(gathered[:, 0]))
            min_applied = int(np.min(gathered[:, 1]))
            if req >= _REQ_ABORT:
                new_status = _ABORTED
            elif req >= _REQ_RESUME:
                new_status = _RUNNING
            else:
                new_status = self._status
            if new_status != self._status:
                counters.incr(
                    "async/aborts_negotiated" if new_status == _ABORTED
                    else "async/resumes_negotiated"
                )
                logger.info(
                    "async model average: negotiated %s at step %d",
                    "ABORT" if new_status == _ABORTED else "RESUME", step,
                )
            self._status = new_status
            # ---- bounded staleness: rounds the worst rank will still be
            # missing after this boundary's apply/drop decisions (the
            # in-flight round counts as applied when it is about to be).
            # Deterministic on every process: a pure function of the
            # gathered counters and the (negotiated, hence uniform)
            # launched count.
            # the trigger is >= (not >): this boundary may launch a fresh
            # round the lagging rank misses too, so waiting for lag > cap
            # would let the observed lag transiently hit cap+1 — catching
            # up AT the cap is what makes "applied never lags launched by
            # more than max_staleness_rounds" a true invariant
            lag = self._rounds_launched - min_applied
            if (
                self._status == _RUNNING
                and self.max_staleness_rounds
                and lag >= self.max_staleness_rounds
            ):
                return self._catchup_sync(trainer, state, watchdog, step,
                                          "staleness")
            counters.set_gauge("async/staleness_max", lag)
            if self._pending is not None:
                if will_drop:
                    self._drop_pending(drop_reason)
                else:
                    # the previous round was launched by all processes;
                    # drain it deterministically whether we stay running or
                    # just aborted
                    state = self._apply_pending(state, watchdog)
            if self._status != _RUNNING:
                return state
            # ---- RUNNING-only sequence: count the round, maybe
            # recalibrate, else launch.  Aborted windows run none of this —
            # recalibration firing there would repeatedly drain the
            # pipeline and stall a pending resume behind a fresh
            # calibration window.
            self._rounds += 1
            if (
                self.period_steps is None
                and self.recalibrate_rounds is not None
                and self._rounds >= self.recalibrate_rounds
            ):
                # periodic re-calibration: reset the window state machine so
                # the period re-derives from CURRENT step time.  Step-count
                # driven, hence simultaneous on every process.
                self._period = None
                self._calib_start = None
                self._calib_skip = 1
                logger.info(
                    "async model average: recalibrating period at step %d "
                    "after %d rounds", step, self._rounds,
                )
                return state
            self._ensure_avg_fn(trainer)
            # snapshot = explicit copy (the reference op copies weights on
            # the torch stream first, rs:50-60): the train step donates
            # state.params, so the retained snapshot needs its own buffers
            snapshot = self._snap_fn(state.params)
            # the round launched HERE is the one a partition costs the
            # rank — its apply happens one boundary later.  The fire is
            # consumed at launch, not at negotiation, so a boundary that
            # launches nothing (catch-up, abort, recalibration) cannot
            # silently spend a count-limited spec with no round to drop.
            self._drop_next = _inject.maybe_drop_negotiation_round()
            # dispatch is async: train steps keep running while the
            # averaging collective is in flight
            self._pending = (self._avg_fn(snapshot), snapshot)
            self._rounds_launched += 1
            self._rewinds_at_launch = getattr(
                trainer, "_guard_rewinds_total", 0
            )
            counters.incr("async/rounds_launched")
        return state

    # ---- control (reference :203-233) -----------------------------------

    def abort(self):
        """Request a stop of background averaging (e.g. before evaluation).

        Takes effect at the next scheduled boundary on ALL processes
        simultaneously (the reference's negotiated ABORT, :203-218); may be
        called from any single rank — and cleared by a ``resume()`` from any
        rank, not just the one that aborted."""
        with self._req_lock:
            self._request = _REQ_ABORT
        logger.info("async model average abort requested")

    def resume(self):
        """Request that background averaging resumes (negotiated RESUME)."""
        with self._req_lock:
            self._request = _REQ_RESUME
        logger.info("async model average resume requested")

    def barrier(self, trainer, state):
        """Drain any in-flight averaging and apply it (the reference's
        post-abort synchronization), under the boundary's grad-guard /
        partition veto.  Collective: call on every process."""
        with self._lock:
            state = self._drain_pending(
                trainer, state, getattr(trainer, "_watchdog", None),
                block=True,
            )
        return state

    def sync_for_checkpoint(self, trainer, state):
        """Blocking synchronous model average that leaves every rank's
        replica bit-identical — run right before saving a checkpoint that
        must survive an elastic WORLD RESIZE: stacked per-rank rows restore
        across world sizes only when the rows agree
        (``trainer.restore_checkpoint`` verifies row identity and re-tiles
        row 0 onto the new world).  Drains and applies any in-flight round
        first.  Collective: call on every process."""
        if trainer._comm.nranks() == 1:
            return state
        watchdog = getattr(trainer, "_watchdog", None)
        with self._lock:
            state = self._drain_pending(trainer, state, watchdog, block=True)
            return self._catchup_sync(
                trainer, state, watchdog, trainer._step_counter, "checkpoint"
            )

    def reset_schedule(self) -> None:
        """Forget the negotiated schedule and any in-flight round: the next
        post-warmup step re-enters a FRESH calibration window (or re-pins
        ``period_steps``) and the round counters restart from zero.

        Called through :meth:`on_restore` after a checkpoint restore —
        elastic world resizes included: the restored run must not apply a
        round launched against pre-restore weights (a stale ``_pending``),
        nor keep a launch anchor/agreed period negotiated by a world that
        no longer exists."""
        with self._lock:
            if self._pending is not None:
                self._pending = None
                counters.incr("async/rounds_dropped")
            self._period = None
            self._anchor = None
            self._calib_t0 = None
            self._calib_start = None
            self._calib_skip = 1
            self._agreed_dt = None
            self._rounds = 0
            self._rounds_launched = 0
            self._rounds_applied = 0
            self._rounds_dropped = 0
            self._drop_next = False
            self._rewinds_at_launch = 0
            self._status = _RUNNING
            with self._req_lock:
                self._request = _REQ_NONE
        logger.info("async model average: schedule reset — next post-warmup "
                    "step opens a fresh calibration window")

    def on_restore(self, trainer) -> None:
        self.reset_schedule()
