"""Step-time anomaly detection: notice when a step gets slow, and say why.

Health fencing (PR 6) sees correctness pathology — non-finite gradients,
missed async rounds — but a rank can hurt the fleet while computing
perfectly: a thermal-throttled host, a congested link, a noisy neighbor.
This module watches the one signal every rank already measures (the raw
host step cadence) plus the per-phase host durations the trainer samples
anyway, keeps a rolling ROBUST baseline (median/MAD — a single historic
spike must not inflate the yardstick that judges the next one), and when a
step lands far outside it:

* counts the event (``obs/step_anomalies``),
* triggers a throttled flight-recorder dump of the offending window
  (trigger ``step_anomaly`` — the spans around the slow step are exactly
  the post-mortem an operator wants),
* publishes a ``straggler_suspect`` phase breakdown
  (dispatch / collective / optimizer / gc / blocked / trainer / caller /
  other) into the per-rank obs summary, which rides the health beacon →
  lease heartbeat → coordinator fleet snapshot (the "which rank, which
  phase, since when" answer),
* keeps the window as a rare span ``step/stall`` of the ring (attrs: the
  phases' seconds, ``explained_s``, the detector's median at the time and —
  where the window outlasted half a second and ``obs/pauses.py``'s
  heartbeat could run — what every thread was doing while it lasted), and
* feeds a bounded **perf hint** queue the autotune service consumes
  (``AutotuneClient.report_metrics(perf_hints=...)``) — the scorer's cue
  that measured step time moved for environmental reasons, not because the
  current knob config is bad.

Phase semantics (host-side, honest about what XLA hides): ``dispatch`` is
the compiled-step dispatch call — in steady state its cadence tracks
device time, so a rank whose OWN device/host is slow shows a
dispatch-dominant excess; ``collective`` is host-visible synchronization
wait (async negotiate/catch-up boundaries, and gated straggler stalls —
the wait a slow PEER inflicts); ``optimizer`` is the grad-guard verdict
readback and other host-side optimizer-adjacent work.  The step observer
adds what the spans know: ``gc`` and ``blocked`` are the interpreter's
pauses (``obs/pauses.py``: the collector's seconds, and the seconds no
Python thread could run outside collections), ``trainer`` is the root span
``step/train_step`` less everything above (hooks, the step-cache key, the
beacon), ``caller`` is the window less the root span — the user's loop, the
input pipeline, back-pressure — each net of the pauses that fell inside it,
so that the phases never add up to more than the window.  The residual,
``other``, is then what truly nothing explains.  ``explained_s`` sums the
excess of every phase but ``caller`` and ``other``: a stall with
``explained_s`` near 0 was spent waiting in the caller with nothing of ours
running, which is itself the finding.  Coordinator side,
:func:`fleet_straggler_suspects` applies the
same logic across ranks: dispatch-dominant anomalies name the straggler,
collective-dominant ones its victims.

Rolling baselines are per-rank by construction (one detector per process).
Import-light (no jax).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from statistics import median
from typing import Any, Dict, List, Optional

from .. import env as _env
from ..telemetry import counters

logger = logging.getLogger(__name__)

__all__ = [
    "StepAnomalyDetector", "PHASES", "publish_perf_hint",
    "drain_perf_hints", "peek_perf_hints", "fleet_straggler_suspects",
]

#: the attributed phases of one host step window; anything unattributed
#: lands in "other".  In the order in which the observer gives each its
#: seconds when the clocks disagree: the pauses were timed whole, the noted
#: waits by a clock pair of their own, ``trainer`` and ``caller`` by
#: subtraction
PHASES = ("gc", "blocked", "dispatch", "collective", "optimizer", "trainer",
          "caller")
_KNOWN = frozenset(PHASES) | {"_other"}
#: the phases in which something of the program's, or the interpreter
#: itself, was at work: ``explained_s`` sums their excess
EXPLAINING_PHASES = PHASES[:-1]

#: 1.4826 * MAD estimates the standard deviation for Gaussian data — the
#: usual robust-z scaling
_MAD_SIGMA = 1.4826

#: minimum step-time ratio for an anomaly to become an autotune perf HINT:
#: hints postpone a sampling window (the service re-measures instead of
#: scoring), so 1.5-3x host blips — real anomalies, worth a suspect and a
#: counter — must not stall the Bayesian loop; a genuine straggler is an
#: order of magnitude out
HINT_MIN_RATIO = 3.0


def _median_of_sorted(xs: list) -> float:
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2


class StepAnomalyDetector:
    """Rolling median/MAD anomaly detector over raw step time.

    ``observe(step, raw_dt, phases, sample)`` once per step (host side,
    after the cadence sample; ``sample``: what ``obs/pauses.py``'s heartbeat
    recorded of the threads while the window lasted, if it stalled).
    Returns the ``straggler_suspect`` dict when the step is anomalous, else
    None.  A step is anomalous when, against the
    rolling window of PRIOR samples (after ``warmup`` of them exist)::

        raw_dt > median + threshold * 1.4826 * MAD
        raw_dt > min_ratio * median          # MAD→0 guard on quiet hosts

    Both conditions — a near-zero MAD (perfectly steady cadence) would
    otherwise flag microsecond jitter.  The offending sample still enters
    the window afterwards: median/MAD shrug off minority contamination, so
    one spike cannot mask the next (gated in ``tests/test_anomaly.py``).
    """

    def __init__(self, window: Optional[int] = None,
                 warmup: Optional[int] = None,
                 threshold: Optional[float] = None,
                 min_ratio: float = 1.3,
                 dump_min_interval_s: float = 30.0,
                 rank: Optional[int] = None):
        self.window = int(window if window is not None
                          else _env.get_obs_anomaly_window())
        self.warmup = int(warmup if warmup is not None
                          else _env.get_obs_anomaly_warmup())
        self.threshold = float(threshold if threshold is not None
                               else _env.get_obs_anomaly_threshold())
        if self.window < 4:
            raise ValueError(f"window must be >= 4, got {self.window}")
        if self.warmup < 2:
            raise ValueError(f"warmup must be >= 2, got {self.warmup}")
        self.min_ratio = float(min_ratio)
        self.dump_min_interval_s = float(dump_min_interval_s)
        self.rank = int(_env.get_rank()) if rank is None else int(rank)
        self._dts: deque = deque(maxlen=self.window)
        #: (median, MAD) of ``_dts`` once ``warmup`` samples exist: the
        #: yardstick of the NEXT window, computed when a sample enters
        self._baseline: Optional[tuple] = None
        self._phase_dts: Dict[str, deque] = {
            name: deque(maxlen=self.window) for name in PHASES + ("_other",)}
        self._last_dump_mono: Optional[float] = None
        #: bounded history of flagged suspects (newest last) — drills and
        #: operators read it; the beacon carries only the latest
        self.suspects: deque = deque(maxlen=16)

    # -- core -------------------------------------------------------------

    def cut_s(self) -> Optional[float]:
        """Seconds past which the window now open is anomalous (both
        conditions of the class docstring); None during warm-up.  The
        heartbeat of ``obs/pauses.py`` samples a window that outlasts it."""
        if self._baseline is None:
            return None
        med, mad = self._baseline
        return max(med + self.threshold * _MAD_SIGMA * mad,
                   self.min_ratio * med)

    def observe(self, step: int, raw_dt: Optional[float],
                phases: Optional[Dict[str, float]] = None,
                sample: Optional[dict] = None) -> Optional[dict]:
        if raw_dt is None or raw_dt <= 0:
            return None
        phases = {k: float(v) for k, v in (phases or {}).items() if v > 0}
        other = max(0.0, raw_dt - sum(phases.values()))
        suspect = None
        if self._baseline is not None:
            med, mad = self._baseline
            if raw_dt > self.cut_s() and med > 0:
                suspect = self._flag(step, raw_dt, med, mad, phases, other,
                                     sample)
        self._dts.append(raw_dt)
        if len(self._dts) >= self.warmup:
            # (this runs in every begin_step, inside the root span's self
            # time: two sorts of at most ``window`` floats, no generators)
            base = sorted(self._dts)
            med = _median_of_sorted(base)
            self._baseline = (med, _median_of_sorted(
                sorted([abs(x - med) for x in base])))
        # EVERY known phase gets a sample each step — a phase absent this
        # window contributed 0 s.  Without the zeros, a phase only seen
        # during anomalies (a straggler's collective wait) would have an
        # anomaly-sized baseline by its second occurrence and dominance
        # attribution would flip to whatever phase was still uncontaminated
        history = self._phase_dts
        for name in PHASES:
            history[name].append(phases.get(name, 0.0))
        history["_other"].append(other)
        if len(history) > len(PHASES) + 1 or not _KNOWN.issuperset(phases):
            # a phase an algorithm names itself: rare, so off the fast path
            for name in set(history).union(phases) - _KNOWN:
                if name not in history:
                    history[name] = deque([0.0] * (len(self._dts) - 1),
                                          maxlen=self.window)
                history[name].append(phases.get(name, 0.0))
        return suspect

    def _phase_baseline(self, name: str) -> float:
        hist = self._phase_dts.get(name)
        return median(hist) if hist else 0.0

    def _flag(self, step: int, raw_dt: float, med: float, mad: float,
              phases: Dict[str, float], other: float,
              sample: Optional[dict]) -> dict:
        # phase breakdown of the EXCESS: each attributed phase's duration
        # minus its own rolling median (of PRIOR windows — this window's
        # samples enter the history only after flagging); the residual
        # host time is "other"
        breakdown: Dict[str, float] = {}
        excess: Dict[str, float] = {}
        for name in sorted(set(PHASES) | set(phases)):
            dur = phases.get(name, 0.0)
            breakdown[name] = round(dur, 6)
            excess[name] = dur - self._phase_baseline(name)
        breakdown["other"] = round(other, 6)
        excess["other"] = other - self._phase_baseline("_other")
        dominant = max(excess, key=lambda k: excess[k])
        suspect = {
            "rank": self.rank,
            "step": int(step),
            "step_dt": round(raw_dt, 6),
            "baseline_p50": round(med, 6),
            "baseline_mad": round(mad, 6),
            "ratio": round(raw_dt / med, 3) if med else None,
            "dominant_phase": dominant,
            "phases": breakdown,
            "explained_s": round(sum(
                max(0.0, excess[name]) for name in EXPLAINING_PHASES), 6),
            "detected_at_unix": time.time(),
        }
        if sample is not None:
            # what every thread was doing while the window lasted
            suspect["sampled_after_s"] = sample["sampled_after_s"]
            suspect["stacks"] = sample["stacks"]
            suspect["open_spans"] = sample["open_spans"]
        self.suspects.append(suspect)
        counters.incr("obs/step_anomalies")
        logger.warning(
            "step anomaly: rank %d step %d took %.4fs (baseline p50 "
            "%.4fs, x%.1f) — dominant phase %r (%s; explained %.3fs%s)",
            self.rank, step, raw_dt, med, suspect["ratio"] or 0.0, dominant,
            ", ".join(f"{name} {seconds:.3f}s"
                      for name, seconds in breakdown.items()
                      if seconds >= 0.0005) or "no phase over 0.5 ms",
            suspect["explained_s"],
            "; threads sampled" if sample is not None else "",
        )
        self._record_stall(suspect)
        # the fleet-view half: the latest suspect rides the obs summary
        # (beacon -> heartbeat -> coordinator snapshot)
        from . import export as _export

        _export.note_anomaly(suspect)
        if suspect["ratio"] is not None \
                and suspect["ratio"] >= HINT_MIN_RATIO:
            publish_perf_hint({
                "kind": "step_time_anomaly",
                "rank": self.rank,
                "step": int(step),
                "ratio": suspect["ratio"],
                "dominant_phase": dominant,
            })
        self._maybe_dump(suspect)
        return suspect

    @staticmethod
    def _record_stall(suspect: dict) -> None:
        """The flagged window as a rare span of the ring, ``step/stall``:
        the record a later reader finds when the log is gone.  The window
        closed a few microseconds before this clock read (the observer's
        own, then the ledger's bookkeeping): ``t1`` is late by that much,
        ``dur_s`` is the window's own."""
        from . import spans as _spans

        if not _spans.enabled():
            return
        t1 = time.monotonic()
        attrs = {k: suspect[k] for k in (
            "phases", "dominant_phase", "explained_s", "baseline_p50",
            "sampled_after_s", "stacks", "open_spans") if k in suspect}
        _spans.recorder.record_rare(_spans.finished_span(
            "step/stall", t1 - suspect["step_dt"], t1, step=suspect["step"],
            **attrs))

    def _maybe_dump(self, suspect: dict) -> None:
        """Throttled flight-recorder dump of the offending window: the ring
        around the slow step is the post-mortem; per-anomaly dumps on a
        chronically slow host would turn the recorder into the I/O
        straggler it is hunting."""
        now = time.monotonic()
        if self._last_dump_mono is not None \
                and now - self._last_dump_mono < self.dump_min_interval_s:
            return
        self._last_dump_mono = now
        from . import recorder as _recorder

        _recorder.dump_flight_record(
            "step_anomaly",
            reason=(f"step {suspect['step']} took {suspect['step_dt']}s "
                    f"(baseline p50 {suspect['baseline_p50']}s)"),
            extra={"straggler_suspect": suspect},
        )


# ---- perf hint channel (consumed by the autotune service) -----------------

_HINT_LOCK = threading.Lock()
_HINTS: deque = deque(maxlen=32)


def publish_perf_hint(hint: dict) -> None:
    """Queue a perf hint for the next autotune check-in.  Bounded (oldest
    drop): hints are advisory context, never a backlog to drain at any
    cost."""
    with _HINT_LOCK:
        _HINTS.append(dict(hint))
    counters.incr("obs/perf_hints")


def drain_perf_hints() -> List[dict]:
    """Pop every queued hint (oldest first) — the trainer's autotune
    check-in attaches them to ``report_metrics``."""
    with _HINT_LOCK:
        hints = list(_HINTS)
        _HINTS.clear()
    return hints


def requeue_perf_hints(hints: List[dict]) -> None:
    """Put drained hints BACK (front of the queue, original order) after a
    failed delivery — a transient sidecar hiccup must not silently discard
    the taint signal for the window it described.  No counter increment:
    these hints were already counted when published."""
    if not hints:
        return
    with _HINT_LOCK:
        for hint in reversed(hints):
            _HINTS.appendleft(dict(hint))


def peek_perf_hints() -> List[dict]:
    with _HINT_LOCK:
        return list(_HINTS)


# ---- coordinator-side fleet analysis --------------------------------------


def fleet_straggler_suspects(fleet_record: dict) -> dict:
    """Read a ``bagua-obs-fleet-v1`` snapshot and name the straggler(s).

    A rank whose anomaly is **dispatch**-dominant (or ``other``-dominant —
    locally slow host time) is itself slow: a straggler.  A rank whose
    anomaly is **collective**-dominant is *waiting* on someone else: a
    victim.  Returns ``{"stragglers": [...], "victims": [...]}`` where
    each entry is ``{"rank", "node", "suspect"}`` sorted by excess ratio —
    the consumable answer for the coordinator (and the autotune scorer,
    which must not re-tune knobs to chase an environmental straggler)."""
    stragglers: List[dict] = []
    victims: List[dict] = []
    for node_id, entry in (fleet_record.get("ranks") or {}).items():
        for rank_id, summary in (entry.get("obs") or {}).items():
            suspect = (summary or {}).get("straggler_suspect")
            if not suspect:
                continue
            item = {"rank": int(suspect.get("rank", rank_id)),
                    "node": int(node_id), "suspect": suspect}
            if suspect.get("dominant_phase") == "collective":
                victims.append(item)
            else:
                stragglers.append(item)
    key = lambda it: -(it["suspect"].get("ratio") or 0)  # noqa: E731
    return {"stragglers": sorted(stragglers, key=key),
            "victims": sorted(victims, key=key)}
