"""Profiler integration — the TPU-native tracing subsystem.

SURVEY.md §5.1: the reference's OTel span pipeline exists to recover the
tensor execution order for the autotuner (covered here by
:mod:`bagua_tpu.telemetry`); its *profiling* role — seeing where step time
goes — maps to ``jax.profiler`` traces, which capture XLA op timelines,
collective costs on ICI, and host callstacks viewable in TensorBoard /
Perfetto.

Two entry points:

- :func:`trace`: context manager around any region.
- trainer auto-capture: set ``BAGUA_PROFILE_DIR=/path`` (and optionally
  ``BAGUA_PROFILE_STEPS=start:stop``, default ``2:5`` — skip compile
  steps, keep the trace small).  ``BaguaTrainer.train_step`` starts/stops
  the trace at those step numbers; no code changes in the training script.
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Optional, Tuple

logger = logging.getLogger(__name__)


def profile_dir() -> Optional[str]:
    from . import env

    return env.get_profile_dir()


def profile_steps() -> Tuple[int, int]:
    """[start, stop) step window for trainer auto-capture."""
    from . import env

    raw = env.get_profile_steps_raw()
    try:
        start, stop = raw.split(":")
        return int(start), int(stop)
    except ValueError:
        logger.warning("BAGUA_PROFILE_STEPS=%r is not start:stop; using 2:5",
                       raw)
        return 2, 5


# jax allows only one profile at a time; track the owner (a StepProfiler or
# the trace() context manager) so the other entry point skips its turn
# instead of crashing
_TRACE_OWNER: Optional[object] = None


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a ``jax.profiler`` trace of the enclosed region.

    If a trace is already running (e.g. trainer auto-capture via
    ``BAGUA_PROFILE_DIR`` has its step window open), the region runs
    untraced with a warning — jax allows only one profile at a time."""
    global _TRACE_OWNER
    import jax

    if _TRACE_OWNER is not None:
        logger.warning(
            "profiling.trace(%s): another trace is active; running untraced",
            log_dir,
        )
        yield
        return
    token = object()
    jax.profiler.start_trace(log_dir)
    _TRACE_OWNER = token  # only own it once start_trace succeeded
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        finally:
            if _TRACE_OWNER is token:
                _TRACE_OWNER = None


class StepProfiler:
    """Start/stop a trace across a step-number window (trainer hook).

    Registered with ``atexit`` so a run that ends before the stop step
    still flushes its trace instead of silently losing it.
    """

    def __init__(self, log_dir: str, start: int, stop: int):
        self.log_dir = log_dir
        self.start = start
        self.stop = stop
        self._active = False
        self._done = False

    @classmethod
    def from_env(cls) -> Optional["StepProfiler"]:
        d = profile_dir()
        if not d:
            return None
        start, stop = profile_steps()
        prof = cls(d, start, stop)
        import atexit

        atexit.register(prof.close)
        return prof

    def on_step(self, step: int) -> None:
        """Call once per train step BEFORE dispatching it."""
        global _TRACE_OWNER
        import jax

        if self._done:
            return
        if not self._active and step >= self.start:
            if _TRACE_OWNER is not None:
                # another trainer's window is still open — skip rather
                # than crash on jax's one-profile-at-a-time limit
                return
            jax.profiler.start_trace(self.log_dir)
            _TRACE_OWNER = self
            self._active = True
            logger.info("profiler: tracing steps [%d, %d) -> %s",
                        self.start, self.stop, self.log_dir)
        elif self._active and step >= self.stop:
            self.close()

    def close(self) -> None:
        global _TRACE_OWNER
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            if _TRACE_OWNER is self:
                _TRACE_OWNER = None
            logger.info("profiler: trace written to %s", self.log_dir)


# ---------------------------------------------------------------------------
# Measured memory traffic (the reference measures GB/s with paired CUDA
# events, distributed.py:340-358; on TPU the ground truth is the profiler's
# per-op memory_access_breakdown, which separates HBM from on-chip VMEM/CMEM
# traffic — XLA's cost model "bytes accessed" conflates them, which is why
# cost-model hbm_util can read >1.0)
# ---------------------------------------------------------------------------

def _newest_xplane(log_dir: str) -> Optional[str]:
    """The most recently WRITTEN ``*.xplane.pb`` under ``log_dir``.

    jax names trace files by host+timestamp; a plain ``sorted(...)[-1]``
    picks the lexicographically last one, which is not the newest once a
    directory holds traces from more than one capture (different hosts, or
    timestamp formats that don't sort) — order by mtime instead."""
    import glob

    files = glob.glob(log_dir + "/**/*.xplane.pb", recursive=True)
    if not files:
        return None
    return max(files, key=lambda p: (os.path.getmtime(p), p))


def _load_xspace(xplane_path: str):
    """Parse one serialized ``XSpace`` proto — the load boilerplate every
    xplane parser shares."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: PLC0415

    xs = xplane_pb2.XSpace()
    with open(xplane_path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def _first_tpu_plane(xs):
    return next(
        (p for p in xs.planes if p.name.startswith("/device:TPU")), None
    )

def trace_memory_traffic(run_step, steps: int = 5, log_dir=None,
                         finalize=None) -> dict:
    """Run ``run_step()`` ``steps`` times under a ``jax.profiler`` trace and
    parse the TPU xplane for MEASURED per-memory-space traffic.

    Returns ``{}`` off-TPU or when the trace lacks a device plane; otherwise::

        {"step_s": mean device step seconds (trace Steps line),
         "hbm_gb_per_step": ..., "vmem_gb_per_step": ..., "cmem_gb_per_step": ...,
         "hbm_gbps_measured": hbm_gb_per_step / step_s}

    ``run_step`` should only ENQUEUE its step (no per-step host readback —
    that would serialize dispatch over the transport and inflate the traced
    step time); ``finalize`` runs once inside the trace to fence everything
    (e.g. a final-loss readback).
    """
    import shutil
    import tempfile

    import jax

    owned = log_dir is None
    d = log_dir or tempfile.mkdtemp(prefix="bagua_trace_")
    try:
        with jax.profiler.trace(d):
            for _ in range(steps):
                run_step()
            if finalize is not None:
                finalize()
        newest = _newest_xplane(d)
        if newest is None:
            return {}
        try:
            return parse_xplane_memory_traffic(newest)
        except Exception as e:  # pragma: no cover - proto availability varies
            logger.info("xplane parse unavailable: %s", e)
            return {}
    finally:
        if owned:  # don't leak tens-of-MB traces to /tmp per bench record
            shutil.rmtree(d, ignore_errors=True)


def trace_op_profile(run, log_dir=None, finalize=None) -> dict:
    """Like :func:`trace_memory_traffic` but returns the PER-OP kernel
    profile (:func:`parse_xplane_op_profile`) — the tool for measuring one
    kernel's on-device time and HBM traffic in isolation, where wall-clock
    timing of a microsecond kernel would measure the host dispatch
    instead."""
    import shutil
    import tempfile

    import jax

    owned = log_dir is None
    d = log_dir or tempfile.mkdtemp(prefix="bagua_optrace_")
    try:
        with jax.profiler.trace(d):
            run()
            if finalize is not None:
                finalize()
        newest = _newest_xplane(d)
        if newest is None:
            return {}
        try:
            return parse_xplane_op_profile(newest)
        except Exception as e:  # pragma: no cover - proto availability varies
            logger.info("xplane parse unavailable: %s", e)
            return {}
    finally:
        if owned:
            shutil.rmtree(d, ignore_errors=True)


def parse_xplane_op_profile(xplane_path: str) -> dict:
    """Per-op kernel time + measured memory traffic from the first TPU
    plane's ``XLA Ops`` line (per-chip scope, like
    :func:`parse_xplane_memory_traffic`).

    Returns ``{"ops": {name: {"time_s", "count", "hbm_gb", "vmem_gb",
    "cmem_gb"}}, "total_time_s", "total_hbm_gb", "total_vmem_gb"}`` —
    ``time_s`` is the op's on-device duration summed over occurrences, so
    the totals over a trace window containing ONLY the kernel under test
    are that kernel's true device time/traffic, independent of host
    dispatch latency."""
    from xprof.protobuf import op_metrics_pb2  # noqa: PLC0415

    plane = _first_tpu_plane(_load_xspace(xplane_path))
    if plane is None:
        return {}
    smd = plane.stat_metadata
    emd = plane.event_metadata
    ops: dict = {}
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            name = emd[ev.metadata_id].name
            rec = ops.setdefault(
                name, {"time_s": 0.0, "count": 0,
                       "hbm_gb": 0.0, "cmem_gb": 0.0, "vmem_gb": 0.0}
            )
            rec["time_s"] += ev.duration_ps / 1e12
            rec["count"] += 1
            for s in emd[ev.metadata_id].stats:
                if smd[s.metadata_id].name == "memory_access_breakdown":
                    mab = op_metrics_pb2.MemoryAccessBreakdown()
                    mab.ParseFromString(s.bytes_value)
                    for acc in mab.memory_accessed:
                        key = {1: "hbm_gb", 2: "cmem_gb", 3: "vmem_gb"}.get(
                            acc.memory_space
                        )
                        if key:
                            rec[key] += acc.bytes_accessed / 1e9
    if not ops:
        return {}
    return {
        "ops": ops,
        "total_time_s": sum(r["time_s"] for r in ops.values()),
        "total_hbm_gb": sum(r["hbm_gb"] for r in ops.values()),
        "total_vmem_gb": sum(r["vmem_gb"] for r in ops.values()),
    }


#: HLO instruction-name prefixes that put an op on the wire (ICI/DCN) —
#: async collectives appear as ``<name>-start``/``-done``, which the
#: prefix match also covers
_COMM_OP_PREFIXES = (
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "ragged-all-to-all", "collective-broadcast",
)


def is_comm_op(name: str) -> bool:
    return name.startswith(_COMM_OP_PREFIXES)


def parse_xplane_overlap(xplane_path: str) -> dict:
    """Profiler-derived comm-hidden ratio for the overlap scheduler's bench
    record (ISSUE 2): from the first TPU plane's ``XLA Ops`` line, sum
    on-device time of communication ops (:func:`is_comm_op`) vs everything
    else, against the device step wall (``Steps`` line).

    If comm and compute ran strictly serialized, ``step ≈ comm + compute``;
    every second below that is a second of communication the scheduler hid
    under compute::

        overlap_fraction = clamp((comm + compute - step) / comm, 0, 1)

    Returns ``{}`` off-TPU or when the trace lacks the needed lines —
    callers record ``overlap_fraction: null`` honestly instead of guessing.
    """
    plane = _first_tpu_plane(_load_xspace(xplane_path))
    if plane is None:
        return {}
    emd = plane.event_metadata
    comm_ps = 0
    compute_ps = 0
    n_steps = 0
    step_ps = 0
    for line in plane.lines:
        if line.name == "Steps":
            n_steps = len(line.events)
            step_ps = sum(e.duration_ps for e in line.events)
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            if is_comm_op(emd[ev.metadata_id].name):
                comm_ps += ev.duration_ps
            else:
                compute_ps += ev.duration_ps
    if not n_steps or not step_ps or not comm_ps:
        return {}
    step_s = step_ps / n_steps / 1e12
    comm_s = comm_ps / n_steps / 1e12
    compute_s = compute_ps / n_steps / 1e12
    hidden = max(0.0, min(1.0, (comm_s + compute_s - step_s) / comm_s))
    return {
        "step_s": round(step_s, 6),
        "comm_s_per_step": round(comm_s, 6),
        "compute_s_per_step": round(compute_s, 6),
        "overlap_fraction": round(hidden, 3),
    }


def trace_overlap(run_step, steps: int = 5, finalize=None) -> dict:
    """Run ``run_step()`` under a trace and return
    :func:`parse_xplane_overlap`'s fields ({} off-TPU).  Same enqueue-only
    contract as :func:`trace_memory_traffic`."""
    import shutil
    import tempfile

    import jax

    d = tempfile.mkdtemp(prefix="bagua_overlap_trace_")
    try:
        with jax.profiler.trace(d):
            for _ in range(steps):
                run_step()
            if finalize is not None:
                finalize()
        newest = _newest_xplane(d)
        if newest is None:
            return {}
        try:
            return parse_xplane_overlap(newest)
        except Exception as e:  # pragma: no cover - proto availability varies
            logger.info("xplane parse unavailable: %s", e)
            return {}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def parse_xplane_memory_traffic(xplane_path: str) -> dict:
    """Aggregate per-op ``memory_access_breakdown`` over every executed op
    occurrence in the TPU device plane.  Memory spaces (op_metrics.proto
    ``PerformanceInfo.MemoryAccessed.MemorySpace``): 1=HBM, 2=CMEM, 3=VMEM.

    Scope: the FIRST ``/device:TPU*`` plane only — on a multi-chip trace the
    returned ``hbm_gb_per_step`` / ``hbm_gbps_measured`` are therefore
    **per-chip** figures (one chip's traffic), not totals.  That is the
    convention every bench record uses (``*_per_chip``); do not multiply by
    chip count without checking the sharding actually balances traffic."""
    from xprof.protobuf import op_metrics_pb2  # noqa: PLC0415

    plane = _first_tpu_plane(_load_xspace(xplane_path))
    if plane is None:
        return {}
    smd = plane.stat_metadata
    emd = plane.event_metadata
    by_space = {1: 0, 2: 0, 3: 0}
    n_steps = 0
    step_ps = 0
    for line in plane.lines:
        if line.name == "Steps":
            n_steps = len(line.events)
            step_ps = sum(e.duration_ps for e in line.events)
        if line.name != "XLA Ops":
            continue
        for ev in line.events:  # per OCCURRENCE: metadata stats are static
            for s in emd[ev.metadata_id].stats:
                if smd[s.metadata_id].name == "memory_access_breakdown":
                    mab = op_metrics_pb2.MemoryAccessBreakdown()
                    mab.ParseFromString(s.bytes_value)
                    for acc in mab.memory_accessed:
                        by_space[acc.memory_space] = (
                            by_space.get(acc.memory_space, 0)
                            + acc.bytes_accessed
                        )
    if not n_steps or not step_ps:
        return {}
    step_s = step_ps / n_steps / 1e12
    out = {
        "step_s": round(step_s, 6),
        "hbm_gb_per_step": round(by_space.get(1, 0) / 1e9 / n_steps, 3),
        "cmem_gb_per_step": round(by_space.get(2, 0) / 1e9 / n_steps, 3),
        "vmem_gb_per_step": round(by_space.get(3, 0) / 1e9 / n_steps, 3),
    }
    out["hbm_gbps_measured"] = round(out["hbm_gb_per_step"] / step_s)
    return out
