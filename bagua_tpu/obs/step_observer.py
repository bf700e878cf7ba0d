"""StepObserver — the trainer's one seam to the planes above it.

``BaguaTrainer`` drives a step; everything that WATCHES a step on the host
lives here: the dispatch cadence (``measured_step_dt``), the goodput ledger's
step windows, the step-time anomaly detector and its phase breakdown, the
MFU gauge, the static-footprint note, the live device-memory poll, the
health beacon, the autotune speed tracker, and the start-up of the exporter,
HTTP status plane and flight-recorder signal hook::

    BaguaTrainer ──▶ StepObserver ──▶ GoodputLedger      (obs.ledger)
     (core/)          (obs/)      ├─▶ StepAnomalyDetector (obs.anomaly)
                                  ├─▶ exporter / HTTP / recorder
                                  └─▶ health beacon       (elastic.membership)

The arrows point one way: ``core/`` imports ``obs.spans`` and this module,
nothing else of ``obs/`` and nothing of ``elastic/``.

The trainer calls in at the few points where something happens: a step
begins (:meth:`begin_step` — which closes the previous step's wall window),
the window now open holds a compile or a state migration
(:meth:`note_window_class`), a stall was injected or reported
(:meth:`note_injected_stall`), host seconds belong to a phase
(:meth:`note_phase_duration`, :meth:`note_dispatch`), the step ends
(:meth:`end_step`).

With the plane off (``BAGUA_OBS=off``) this is the same class with its
readers absent: the cadence is measured always —
``faults.inject.maybe_straggle`` and ``AsyncModelAverageAlgorithm`` read it
— while ledger, detector, exporter, beacon and polls exist only when
:func:`bagua_tpu.obs.spans.enabled`.  Nothing here touches the step program.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .. import env
from ..faults import inject as _inject
from ..utils import StatisticalAverage
from . import anomaly as _anomaly
from . import export as _export
from . import http as _http
from . import memory as _memory
from . import pauses as _pauses
from . import recorder as _recorder
from . import spans as _spans

logger = logging.getLogger(__name__)

__all__ = ["StepObserver"]


class StepObserver:
    """Host-side bookkeeping of one trainer's steps (module docstring)."""

    def __init__(self):
        # observability plane (docs/observability.md): resolved once — the
        # hooks below gate on this flag so BAGUA_OBS=off restores the exact
        # pre-obs host behavior
        self.enabled = _spans.enabled()
        #: goodput ledger (docs/observability.md, efficiency plane): every
        #: wall-clock second of this process lands in exactly one class —
        #: fed from the step-cadence windows, the span hook, stall reports,
        #: and the grad guard's rewind verdicts below.  All host-side.
        self.ledger = None
        #: MFU denominator: peak silicon FLOP/s for this chip kind (None on
        #: cpu-sim / unknown silicon -> obs/mfu stays null-with-rationale)
        self.peak_flops = None
        #: MFU numerator: the current compiled step's cost-model flops, set
        #: by the trainer per step-cache key (None pauses the gauge)
        self.flops_per_step: Optional[float] = None
        self._mfu_noted_unavailable = False
        self._footprint_noted = False
        self._mem_poll_dead = False
        self._mem_poll_failures = 0
        self._last_beacon_write = 0.0
        if self.enabled:
            from . import ledger as _ledger  # lazy: a CLI entry point

            _export.maybe_start_global_exporter(self)
            # per-process HTTP status plane (off unless the operator sets
            # BAGUA_OBS_HTTP_PORT; the launcher offsets each worker's
            # port): /metrics serves the same prepared snapshot the
            # exporter writes to metrics.prom
            _http.maybe_start_global_http_server()
            _recorder.maybe_install_signal_hook()
            # the interpreter's pauses as spans of the ring (the collector's
            # hook here, the heartbeat thread with the first begin_step)
            _pauses.install()
            self.ledger = _ledger.install()
            self.peak_flops = _ledger.peak_flops_for_device_kind(
                jax.devices()[0].device_kind
            )
        #: step-time anomaly detector (docs/observability.md): rolling
        #: median/MAD baseline over the RAW host cadence (injected stalls
        #: included — a stall IS the anomaly an operator wants flagged,
        #: while measured_step_dt subtracts it to stay an honest dilation
        #: base) plus the per-phase host durations accumulated below
        self.anomaly_detector = None
        if self.enabled and env.get_obs_anomaly_mode() == "on":
            self.anomaly_detector = _anomaly.StepAnomalyDetector()
        #: host phase durations of the step currently being driven
        #: (dispatch / collective / optimizer); harvested into the anomaly
        #: detector when the next cadence sample closes the window
        self._phase_durations: Dict[str, float] = {}
        #: what the detector's phases ``gc`` / ``blocked`` / ``trainer`` /
        #: ``caller`` are differenced from: the pauses' cumulative seconds
        #: (``obs/pauses.py``) when the open window began, the seconds from
        #: there to :meth:`end_step`'s last line — the root span
        #: ``step/train_step`` of the step that opened it, but for
        #: ``check_abort`` before and two span exits after — and the paused
        #: seconds that fell inside them
        self._gc_mark = self._blocked_mark = 0.0
        self._root_s: Optional[float] = None
        self._root_paused_s = 0.0
        #: THE window-class fact: the wall window the current step opened
        #: holds a trace+compile (``"compile"``) or a state migration
        #: (``"state_migration"``); None = productive.  Such a window is
        #: expected to be huge: the ledger books it to that class, the
        #: anomaly detector neither flags it nor takes it into its
        #: baseline, the speed tracker drops its sample.  Set by
        #: :meth:`note_window_class`, consumed when :meth:`begin_step`
        #: closes the window.
        self._window_class: Optional[str] = None
        # host dispatch cadence (one monotonic read per step): the base
        # step time the step.straggle fault point dilates by its factor
        self._last_step_mono: Optional[float] = None
        self._step_dt: Optional[float] = None
        self._stall_s = 0.0
        # throughput tracker of the autotune check-in
        self._speed_tracker = StatisticalAverage()
        self._last_report_time = time.time()
        self._last_speed_time = time.time()
        self._prev_speed_time = self._last_speed_time
        self._manual_speed = False
        #: previous goodput-ledger snapshot at the last check-in: the
        #: ledger reports CUMULATIVE seconds, the autotune score needs the
        #: WINDOW since the last report (same windowing as the speed)
        self._autotune_ledger_prev = None

    # ---- cadence ---------------------------------------------------------

    def measured_step_dt(self) -> Optional[float]:
        """Host dispatch cadence of the previous step in seconds (injected
        straggle stalls subtracted, so a dilation can never compound into
        its own base).  Steady-state dispatch cadence equals device step
        cadence — each dispatch consumes the previous state — which makes
        this the honest base time for the ``step.straggle`` fault point."""
        return self._step_dt

    def begin_step(self, step: int) -> None:
        """Step ``step`` begins: the wall window of step ``step - 1`` closes
        here and is handed, with its class, to every reader."""
        if self.enabled:
            # every span opened while this step is driven (including the
            # watchdog waiter's) carries the step number
            _spans.set_current_step(step)
            _pauses.ensure_heartbeat()
        now = time.monotonic()
        gc_s, blocked_s = _pauses.gc_seconds(), _pauses.blocked_seconds()
        if self._last_step_mono is not None:
            raw = now - self._last_step_mono
            dt = raw - self._stall_s
            if dt > 0:
                self._step_dt = dt
            # a window that contained a trace+compile or a state migration
            # (XLA compiles lazily on first dispatch, so the build span
            # alone under-counts) belongs to that class as a whole
            window_cls, self._window_class = self._window_class, None
            productive = window_cls is None
            if self.ledger is not None and raw > 0:
                # goodput ledger: class windows noted inside the window
                # (checkpoint, async boundaries, stalls) were already
                # deducted by the ledger; the remainder is this class's
                self.ledger.note_step_window(
                    step - 1, raw, window_cls or "productive_step")
            if productive:
                # MFU only from productive windows: a compile/migration
                # window's dt would publish a garbage-low sample that
                # rides the beacon to the fleet view
                self.note_mfu()
            if self.anomaly_detector is not None and raw > 0:
                # the phase attributions were accumulated during the
                # window.  An expected one-off stall must not flag
                # (autotune retunes recompile every sample) nor enter the
                # baseline.
                phases, self._phase_durations = self._phase_durations, {}
                if productive:
                    self._add_span_phases(phases, raw, gc_s, blocked_s)
                    self.anomaly_detector.observe(
                        step - 1, raw, phases,
                        _pauses.take_stall_sample(self._last_step_mono))
        if self.anomaly_detector is not None:
            # one reading closes a window and opens the next: no pause
            # falls between two
            self._gc_mark, self._blocked_mark = gc_s, blocked_s
            self._root_s = None
            # the heartbeat samples every thread once if this window stalls
            _pauses.watch_window(now, self.anomaly_detector.cut_s())
        self._last_step_mono = now
        self._stall_s = 0.0
        if self.enabled:
            # fleet view: the per-rank step/step-dt summary the health
            # beacon (and the metrics exporter) publish
            _export.note_step(step, self._step_dt)

    def _add_span_phases(self, phases: Dict[str, float], raw: float,
                         gc_now: float, blocked_now: float) -> None:
        """What the spans know of the closing window, beside the phases
        noted while it lasted: the interpreter's pauses (``obs/pauses.py``'s
        two cumulative floats as read now, differenced: no scan of the
        ring), the trainer's own host time and the caller's — each net of
        the pauses inside it, so that the phases add up to the window and
        not to more."""
        gc_s = min(raw, gc_now - self._gc_mark)
        blocked_s = min(raw - gc_s, blocked_now - self._blocked_mark)
        phases["gc"], phases["blocked"] = gc_s, blocked_s
        if self._root_s is None:
            return  # the step never ended: ``other`` keeps the rest
        root_paused = min(self._root_paused_s, gc_s + blocked_s)
        phases["trainer"] = max(
            0.0, self._root_s - root_paused - sum(
                phases.get(name, 0.0)
                for name in ("dispatch", "collective", "optimizer")))
        phases["caller"] = max(
            0.0, raw - self._root_s - (gc_s + blocked_s - root_paused))
        # a heartbeat that woke after the window it was late in had closed
        # brings its seconds to this one: whatever the clocks disagree on,
        # the pauses first and then each phase only what is left
        left = raw
        for name in _anomaly.PHASES:
            phases[name] = min(phases.get(name, 0.0), left)
            left -= phases[name]

    def note_window_class(self, cls: str) -> None:
        """The window the current step opened holds a ``compile`` or a
        ``state_migration``.  A migration usually triggers a recompile too,
        which then claims the window — the migration span already fed its
        own execution wall either way."""
        if self._window_class != "compile":
            self._window_class = cls

    def note_injected_stall(self, seconds: float) -> None:
        """Record an injected stall that happened inside the current step
        (the step-begin ``step.straggle`` sleep, an async boundary's) so
        the next cadence sample subtracts it — see
        :meth:`measured_step_dt`."""
        self._stall_s += float(seconds)
        if seconds <= 0:
            return
        if self.anomaly_detector is not None:
            # the straggler's OWN process is locally slow (``dispatch`` —
            # that is what a genuinely slow host looks like), a gated peer
            # is *waiting* (``collective``)
            self.note_phase_duration(
                "dispatch" if _inject.straggle_targets_self()
                else "collective", seconds)
        if self.ledger is not None:
            self.ledger.note_class_window("stall", float(seconds))

    def note_phase_duration(self, phase: str, seconds: float) -> None:
        """Attribute host seconds of the current step to a phase
        (``dispatch`` / ``collective`` / ``optimizer``) for the anomaly
        detector's ``straggler_suspect`` breakdown.  Algorithms call this
        around their host-visible waits (async negotiate/catch-up), inside
        ``train_step``: ``trainer`` is the root span less these."""
        if self.anomaly_detector is None or seconds <= 0:
            return
        self._phase_durations[phase] = (
            self._phase_durations.get(phase, 0.0) + float(seconds)
        )

    def pause_mark(self) -> float:
        """Seconds the interpreter has been paused so far, to hand back to
        :meth:`note_dispatch` (0.0 where no detector reads the phases)."""
        if self.anomaly_detector is None:
            return 0.0
        return _pauses.paused_seconds()

    def note_dispatch(self, seconds: float, mark: float) -> None:
        """The compiled step's dispatch call took ``seconds`` (the span
        ``step/dispatch``'s own clock pair); ``mark`` is :meth:`pause_mark`
        from just before it.  A collection inside the call is the
        collector's, not the dispatch's."""
        if self.anomaly_detector is None:
            return
        paused = min(seconds, _pauses.paused_seconds() - mark)
        self.note_phase_duration("dispatch", seconds - paused)

    def end_step(self, batch, track_speed: bool) -> None:
        """The step was dispatched.  ``track_speed``: someone will read the
        throughput tracker (its only consumer is the autotune check-in)."""
        if track_speed:
            self._auto_record_speed(batch)
        if self.enabled:
            # fleet view, worker half: refresh this rank's beacon so the
            # launcher's heartbeat carries a LIVE step/staleness summary,
            # not only the unhealthy-event snapshots.  Throttled to ~one
            # tiny file write per 2 s; no-op without the launcher-injected
            # beacon path.
            now = time.monotonic()
            if now - self._last_beacon_write > 2.0:
                self._last_beacon_write = now
                self._maybe_poll_device_memory()
                self.publish_health()
        if self.anomaly_detector is not None \
                and self._last_step_mono is not None:
            # the trainer's part of the window ends here; what follows, up
            # to the next begin_step, is the caller's
            self._root_s = time.monotonic() - self._last_step_mono
            self._root_paused_s = (_pauses.paused_seconds() - self._gc_mark
                                   - self._blocked_mark)

    # ---- efficiency plane --------------------------------------------------

    def note_mfu(self) -> None:
        """Per-step MFU gauge: the cost-model flops of the current compiled
        step over (measured step cadence x peak silicon FLOP/s).
        Null-with-rationale where the denominator is unknown (cpu-sim,
        unlisted device kinds) — published once."""
        if not self.enabled:
            return
        if self.peak_flops is None:
            if not self._mfu_noted_unavailable:
                self._mfu_noted_unavailable = True
                _export.note_mfu({
                    "available": False,
                    "rationale": (
                        "no peak-FLOPS table entry for device kind "
                        f"{jax.devices()[0].device_kind!r} (cpu-sim or "
                        "unlisted silicon) — MFU needs a silicon peak "
                        "denominator"
                    ),
                })
            return
        if not self.flops_per_step or not self._step_dt:
            return
        mfu = self.flops_per_step / self._step_dt / self.peak_flops
        _export.note_mfu({
            "available": True,
            "mfu": round(mfu, 4),
            "flops_per_step": self.flops_per_step,
            "peak_flops": self.peak_flops,
            "step_dt": round(self._step_dt, 6),
        })

    #: XLA's memory analysis of a compiled step as plain ints — the
    #: trainer's per-key analysis caches harvest it next to the flops
    compiled_memory_analysis = staticmethod(_memory.compiled_memory_analysis)

    def note_static_footprint(self, trainer, state) -> None:
        """One-shot static HBM footprint of the live training state +
        bucket plan (:func:`bagua_tpu.obs.memory.static_footprint`) into
        the obs summary / exporter gauges.  Host metadata only."""
        if self._footprint_noted:
            return
        self._footprint_noted = True
        try:
            _export.note_hbm_footprint(
                _memory.static_footprint(trainer, state))
        except Exception as e:  # noqa: BLE001 - accounting must not kill
            logger.debug("static footprint not computed: %s", e)

    def _maybe_poll_device_memory(self) -> None:
        """Live ``device.memory_stats()`` poll (real TPU: peak bytes +
        headroom gauges), throttled to the beacon cadence.  A STABLE
        unavailable answer (cpu-sim's "no HBM stats") disables polling
        after publishing the rationale once; transient failures (a runtime
        hiccup mid-run) keep polling until a consecutive-failure budget —
        a multi-day run must not lose its capacity gauges to one flake."""
        if self._mem_poll_dead:
            return
        try:
            record = _memory.live_memory_stats()
            if record.get("available"):
                self._mem_poll_failures = 0
            elif record.get("transient"):
                self._mem_poll_failures += 1
                if self._mem_poll_failures >= 5:
                    self._mem_poll_dead = True
            else:
                self._mem_poll_dead = True
            _export.note_hbm_live(record)
        except Exception as e:  # noqa: BLE001
            self._mem_poll_failures += 1
            if self._mem_poll_failures >= 5:
                self._mem_poll_dead = True
            logger.debug("device memory poll failed: %s", e)

    # ---- grad guard and health events --------------------------------------

    def note_grad_verdict(self, step_no: int, healthy: float) -> None:
        """Host-safe mirror of the grad guard's verdict: the flight
        recorder republishes these from abort paths where touching a
        device array could hang."""
        if self.enabled:
            _export.note_step_metrics({
                "grad_health_step": step_no,
                "grad_healthy": healthy,
            })

    def note_rewind(self, step_no: int) -> None:
        """The step's wall was spent, its update discarded: move its
        recorded productive seconds to the rewind badput class."""
        if self.ledger is not None:
            self.ledger.reclassify_step_rewind(step_no)

    @staticmethod
    def publish_health() -> None:
        """Write this process's health beacon for the elastic coordinator
        (no-op unless the launcher injected ``BAGUA_ELASTIC_HEALTH_FILE``).
        Health EVENTS publish with the plane off too."""
        from ..elastic.membership import write_health_beacon

        write_health_beacon()

    @staticmethod
    def dump_flight_record(trigger: str, **kw) -> Optional[str]:
        """The post-mortem dump (None with the plane off)."""
        return _recorder.dump_flight_record(trigger, **kw)

    # ---- autotune check-in inputs -------------------------------------------

    def _auto_record_speed(self, batch) -> None:
        """Feed the throughput tracker from the step itself (reference
        measures its own speed with paired events in the forward-pre hook,
        distributed.py:340-358).  The global batch's leading dim is the
        sample count; dispatch cadence equals steady-state step cadence
        because each step consumes the previous state, so the host paces to
        device throughput.  An explicit :meth:`record_speed` call switches
        to manual mode — autotune never silently scores 0 either way."""
        if self._manual_speed:
            return
        leaves = jax.tree.leaves(batch)
        if not leaves or not jnp.ndim(leaves[0]):
            return
        now = time.time()
        dt = now - self._last_speed_time
        self._prev_speed_time = self._last_speed_time
        self._last_speed_time = now
        if self._window_class is not None:
            # this interval spanned trace+compile of a (re)built step or a
            # state migration (the first step's always does) — a garbage
            # low sample that would skew the autotune score; start the
            # clock here instead
            return
        if dt > 0:
            self._speed_tracker.record(leaves[0].shape[0] / dt)

    def record_speed(self, n_samples: float) -> None:
        """Manual override of the automatic per-step speed tracking: count
        ``n_samples`` since the previous call (reference's speed metrics,
        distributed.py:340-358).  Use when the batch pytree's leading dim is
        not the sample count (e.g. token-weighted scoring)."""
        now = time.time()
        if not self._manual_speed:
            # first manual call: discard auto-recorded samples (possibly in
            # different units), but DO record this one — against the
            # interval the auto path measured for the same step (its
            # pre-advance timestamp), not the microseconds since it ran —
            # so a check-in landing before the second call never scores 0
            self._manual_speed = True
            self._speed_tracker = StatisticalAverage()
            dt = now - self._prev_speed_time
        else:
            dt = now - self._last_speed_time
        self._last_speed_time = now
        if dt > 0:
            self._speed_tracker.record(n_samples / dt)

    def speed_since_last_report(self) -> float:
        """Windowed throughput since the last check-in (reference
        distributed.py:223), NOT a cumulative total — the score must
        reflect only the current hyperparameter config."""
        now = time.time()
        speed = self._speed_tracker.get(now - self._last_report_time)
        self._last_report_time = now
        return speed

    # perf hints: anomaly detections since the last check-in ride along, so
    # the scorer can tell "this config is slow" from "rank 5 got slow for
    # environmental reasons" — tuning against the wrong one oscillates
    @staticmethod
    def drain_perf_hints() -> list:
        return _anomaly.drain_perf_hints()

    @staticmethod
    def requeue_perf_hints(hints) -> None:
        _anomaly.requeue_perf_hints(hints)

    def autotune_window(self) -> Optional[dict]:
        """The rank's windowed efficiency observations for the check-in
        (the v2 scoring input): goodput fraction of the window since the
        last report — delta of the CUMULATIVE ledger classes, so compile
        and migration badput the current config caused lands in its own
        score — plus MFU, HBM headroom, and the rank-local anomaly flag
        from the obs summary.  ``None`` when the obs plane is off
        (``BAGUA_OBS=off``), goodput reporting is disabled
        (``BAGUA_AUTOTUNE_GOODPUT=off``), or no window has elapsed yet —
        the service then scores on summed speed as before."""
        if self.ledger is None or not env.get_autotune_goodput():
            return None
        try:
            rep = self.ledger.report()
        except Exception:  # the score input must never take down training
            return None
        if not rep:
            return None
        classes = dict(rep.get("classes") or {})
        snap = {"wall_s": float(rep.get("wall_s") or 0.0), "classes": classes}
        prev, self._autotune_ledger_prev = self._autotune_ledger_prev, snap
        if prev is None:
            # first check-in: the window opens at the ledger's first noted
            # second, so the initial config's own compile lands in its own
            # score — and EVERY window is goodput-scored from window one
            # (one speed-scaled sample would dominate best() forever)
            prev = {"wall_s": 0.0, "classes": {}}
        dwall = snap["wall_s"] - prev["wall_s"]
        if dwall <= 0:
            return None
        from .ledger import GOODPUT_CLASSES

        dgood = sum(
            classes.get(c, 0.0) - prev["classes"].get(c, 0.0)
            for c in GOODPUT_CLASSES
        )
        obs: Dict[str, Any] = {
            "goodput_fraction": max(0.0, min(1.0, dgood / dwall)),
            "window_wall_s": round(dwall, 3),
        }
        try:
            summary = _export.local_obs_summary() or {}
        except Exception:
            summary = {}
        if summary.get("mfu") is not None:
            obs["mfu"] = summary["mfu"]
        if summary.get("hbm_headroom_bytes") is not None:
            obs["hbm_headroom_bytes"] = summary["hbm_headroom_bytes"]
        if summary.get("straggler_suspect"):
            # the service discards (re-measures) anomaly-flagged windows
            obs["anomaly"] = True
        return obs
