"""Unified observability plane (docs/observability.md).

Three coupled pieces, instrumented into the real code paths:

* :mod:`~bagua_tpu.obs.spans` — host-side step-span tracer
  (``trace_span``) with a bounded ring buffer; the trainer, overlap
  scheduler, async boundaries, checkpoint paths, elastic rendezvous, and
  watchdog sections all open spans.
* :mod:`~bagua_tpu.obs.recorder` — crash flight recorder: on watchdog
  abort, grad-guard escalation, health-fence stop, armed-fault fire, or
  SIGTERM, dump spans + counters + step metrics to
  ``BAGUA_OBS_DUMP_DIR``.
* :mod:`~bagua_tpu.obs.export` — ``METRIC_REGISTRY`` (every counter/gauge
  name, lint-enforced), the background metrics exporter
  (JSONL + Prometheus textfile), and the coordinator-side fleet snapshot.

Plus the analysis layer on top of those signals:

* :mod:`~bagua_tpu.obs.timeline` — merge per-rank span dumps into one
  clock-aligned Perfetto/Chrome trace (``python -m bagua_tpu.obs.timeline``).
* :mod:`~bagua_tpu.obs.anomaly` — rolling median/MAD step-time anomaly
  detector: ``straggler_suspect`` phase breakdowns into the health beacon,
  throttled flight dumps, perf hints for the autotune service.

And the efficiency plane over all of it:

* :mod:`~bagua_tpu.obs.ledger` — goodput/badput wall-clock ledger: every
  second lands in one class (productive-step, compile, checkpoint,
  rendezvous, catchup-sync, rewind, stall, idle), exported as gauges,
  rolled up fleet-wide, rendered by ``python -m bagua_tpu.obs.ledger``;
  plus the peak-silicon tables behind the per-step ``obs/mfu`` gauge.
* :mod:`~bagua_tpu.obs.memory` — HBM accounting: static per-plan
  footprint (exact on cpu-sim), per-step-cache ``memory_analysis()``,
  live ``device.memory_stats()`` peaks/headroom on real TPU.

And the fleet-historical layer (ISSUE 14):

* :mod:`~bagua_tpu.obs.historian` — coordinator-side time-series rings
  over the fleet-snapshot stream with windowed rate/percentile/slope
  queries; publishes trend gauges (``obs/goodput_slope``,
  ``obs/hbm_headroom_slope``, ``obs/dcn_comm_share``) back into each
  snapshot and persists through the restart store.
* :mod:`~bagua_tpu.obs.http` — per-process HTTP status plane
  (``/metrics`` from the same prepared snapshot as ``metrics.prom``,
  ``/healthz``, ``/ledger``; the coordinator adds ``/fleet`` and
  ``/history``), gated by ``BAGUA_OBS_HTTP_PORT``.

Master switch: ``BAGUA_OBS`` (default on; ``off`` restores the exact
pre-obs host behavior — the compiled step program is identical either way).
Import-light: importing the package pulls in no jax (``memory``, ``spans``
and ``step_observer`` import it lazily or are imported by tracing code only).
"""

from .export import (  # noqa: F401
    METRIC_REGISTRY,
    MetricsExporter,
    local_obs_summary,
    render_prometheus,
    validate_fleet_snapshot,
    write_fleet_snapshot,
)
from .export import LEDGER_CLASSES  # noqa: F401
from .historian import Historian, maybe_build_historian  # noqa: F401
from .http import ObsHTTPServer, maybe_start_global_http_server  # noqa: F401
from .memory import live_memory_stats, plan_flat_bytes, static_footprint  # noqa: F401
from .recorder import (  # noqa: F401
    dump_flight_record,
    validate_flight_record,
)
# NOTE: the span ring instance is ``spans.recorder`` — deliberately NOT
# re-exported here, where it would shadow the ``obs.recorder`` submodule
from .spans import SpanRecorder, span_ring, trace_span  # noqa: F401
from .anomaly import StepAnomalyDetector, fleet_straggler_suspects  # noqa: F401,E402
# NOTE: obs.timeline and obs.ledger are NOT imported here — both are
# `python -m` entry points, and a package-level import would leave a second
# copy of the module executing under runpy (the ledger singleton lives in
# obs.ledger; consumers import the module lazily)
