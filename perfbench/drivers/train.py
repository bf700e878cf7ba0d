"""Driver ``train``: one training cell, once.

The loop is the one a user of ``BaguaTrainer`` writes: ``next(batches)`` from
``prefetch_to_device``, ``trainer.train_step``, nothing else — no fence in
the main loop.  A waiter thread blocks on each step's loss in order (the
trainer's own watchdog pattern) and time-stamps the completion; the main
loop keeps at most ``max_in_flight`` steps dispatched and not completed.

Phases, in order:

  set-up     imports; weights + ``trainer.init``; ``replay_steps`` fenced
             steps on ONE seeded batch (the first of them compiles or loads
             the step; their losses are what ``correct`` compares); the join
             of the obs plane's background lowering; ``warmup_steps``
             pipelined steps on fresh batches.  All of it is ``setup_s``.
  window     pipelined steps on fresh batches for ``--seconds`` (with
             ``--trace 1``: a few seconds less).  Every end-to-end metric
             and every host-clock per-layer metric comes from here.
  trace      ``--trace 1`` only: ``trace_steps`` more steps under
             ``jax.profiler``, then the compiled step's HLO text.
  reference  after peak memory is read and the trainer's state freed: the
             plain reference repeats the replayed steps on one chip.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import math
import os
import queue
import shutil
import tempfile
import threading
import time
import traceback

import jax
import numpy as np

from perfbench import cells, trace_reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: with ``--trace 1`` the untraced window is this much shorter than
#: ``--seconds``, so that the traced steps fall inside the run's length
TRACE_ALLOWANCE_S = 3.0


class Spans:
    """The benchmark's own spans around its calls into the program: kept as
    host-clock durations, and written as ``TraceAnnotation``s so that a
    traced run has them on the profiler's clock next to the device."""

    def __init__(self):
        self.durations: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.durations.setdefault(name, []).append(time.perf_counter() - t0)


class CompileWatch:
    """Times of backend compilations and persistent-cache traffic, from
    ``jax.monitoring``."""

    def __init__(self):
        self.compile_times: list[float] = []
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _seconds: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compile_times.append(time.perf_counter())

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def compiles_between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.compile_times)


@dataclasses.dataclass
class Drive:
    """What one pipelined stretch of steps did."""

    state: object
    begin: float
    end: float
    dispatched: int
    #: (completion time, loss) of every step that completed, in order
    completed: list[tuple[float, float]]
    error: BaseException | None


def drive(trainer, state, batches, spans: Spans, max_in_flight: int,
          stop) -> Drive:
    """Dispatch steps until ``stop(n_dispatched)``; returns once every
    dispatched step has completed."""
    slots = threading.Semaphore(max_in_flight)
    pending: queue.SimpleQueue = queue.SimpleQueue()
    completed: list[tuple[float, float]] = []

    def wait_in_order():
        while True:
            loss = pending.get()
            if loss is None:
                return
            try:
                value = float(loss)
            except Exception:  # noqa: BLE001 - a failed step, reported below
                traceback.print_exc()
                value = math.nan
            completed.append((time.perf_counter(), value))
            slots.release()

    waiter = threading.Thread(target=wait_in_order, name="bench-waiter")
    waiter.start()
    dispatched, error = 0, None
    begin = time.perf_counter()
    try:
        while not stop(dispatched):
            with spans.span("bench/in_flight_wait"):
                slots.acquire()
            with spans.span("bench/next_batch"):
                batch = next(batches)
            with spans.span("bench/train_step"):
                state, loss = trainer.train_step(state, batch)
            dispatched += 1
            pending.put(loss)
    except Exception as e:  # noqa: BLE001 - a step that raised is a failed step
        traceback.print_exc()
        error = e
    finally:
        pending.put(None)
        waiter.join()
    return Drive(state, begin, time.perf_counter(), dispatched, completed,
                 error)


def check_devices(cell: cells.Cell, rehearse: bool) -> tuple[list, dict | None]:
    """The devices the cell runs on and the peak-table entry of their kind."""
    devices = jax.devices()
    first = devices[0]
    peak = None
    if rehearse:
        if first.platform != "cpu":
            raise cells.DeviceError("--rehearse runs on the CPU backend only")
    else:
        if first.platform != "tpu":
            raise cells.DeviceError(
                f"JAX found platform {first.platform!r}, not a TPU; "
                "--rehearse rehearses the command on the CPU")
        with open(cell.bench_dir / "peaks.json", encoding="utf-8") as f:
            peak = json.load(f)["devices"].get(first.device_kind)
        if peak is None:
            raise cells.DeviceError(f"device kind {first.device_kind!r} is "
                                    "not in perfbench/peaks.json")
    if len(devices) < cell.chips:
        raise cells.DeviceError(f"{cell.name} needs {cell.chips} chips, JAX "
                                f"found {len(devices)}")
    return devices[:cell.chips], peak


def peak_memory_bytes(devices) -> int:
    """High-water mark of device memory on the fullest chip, from the
    runtime's own counters.  ``peak_bytes_in_use`` alone is NOT it: on a TPU
    that counter sees buffers only, and a running program's temporaries sit
    in a separate reservation (``bytes_reserved``, sized to the largest
    program run so far; measured on the v5e, PR 23: 5.55 GB of activations
    that ``peak_bytes_in_use`` never showed).  So the mark is the larger of
    the buffers' own peak and buffers + reservation now.  Call it while the
    state is live.  0 where the backend keeps no statistics (the CPU
    rehearsal)."""
    def mark(stats: dict) -> int:
        return max(stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0))

    return max(mark(device.memory_stats() or {}) for device in devices)


def capture_trace(run_steps, keep_dir: str | None):
    """Run ``run_steps()`` under the profiler and reduce the trace (None on
    a machine without a TPU plane)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # Python frames would swamp the trace
    options.enable_hlo_proto = False  # and so would a copy of the program
    trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            result = run_steps()
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            return result, None
        newest = max(files, key=os.path.getmtime)
        if keep_dir:
            os.makedirs(keep_dir, exist_ok=True)
            shutil.copy(newest, keep_dir)
        return result, trace_reduce.load(newest)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer reader (``layer_metrics/<name>.py``) may read.  A
    reader that finds nothing returns None and its metric is left out."""

    chips: int
    #: benchmark spans of the untraced window: name -> seconds per call
    spans: dict
    counters: dict
    #: units of work per second per chip over the untraced window
    rate_per_chip: float | None
    flops_per_unit: float
    peak: dict | None
    trace: trace_reduce.Trace | None = None
    hlo_text: str | None = None


class Parts:
    """Seconds between consecutive marks, by name: where a run's time went."""

    def __init__(self, t0: float):
        self.seconds: dict[str, float] = {}
        self._last = t0

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self._last
        self._last = now


def run(cell: cells.Cell, args, t0: float) -> dict:
    parts = Parts(t0)
    # rehearsal widths come with their own (small) traffic parameters
    traffic = {**cell.traffic, **cell.config.get("traffic_overrides", {})}
    in_flight = int(traffic["max_in_flight"])
    parts.mark("import_s")
    devices, peak = check_devices(cell, args.rehearse)
    parts.mark("device_init_s")
    # also the programs JAX would not bother to keep (under a second of
    # compile): a second run in the same checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    watch = CompileWatch()
    builder = cells.load_plugin("builders", cell.config["builder"],
                                cell.bench_dir)
    parts.mark("import_s")  # jax above, then flax, optax and the program

    # ---- set-up -------------------------------------------------------------
    job = builder.build(cell, traffic, devices, args.seed)
    trainer, state = job.trainer, job.state
    jax.block_until_ready(state)
    parts.mark("weights_s")

    from bagua_tpu.contrib.prefetch import prefetch_to_device

    batches = prefetch_to_device(job.host_batches(), trainer=trainer,
                                 size=int(traffic["prefetch"]))
    replay = trainer.shard_batch(job.replay_batch)
    trainer_losses = []
    for i in range(int(traffic["replay_steps"])):
        state, loss = trainer.train_step(state, replay)
        trainer_losses.append(float(loss))
        if i == 0:
            parts.mark("compile_or_load_s")
    # the obs plane lowers and compiles the step a second time on a thread
    # of its own (``_maybe_prepare_mfu``); wait for it here so that its
    # tracing does not share the interpreter with the measured window
    trainer.step_cost_analysis(state, replay)
    parts.mark("replay_and_obs_harvest_s")
    warm = drive(trainer, state, batches, Spans(), in_flight,
                 lambda n: n >= int(traffic["warmup_steps"]))
    parts.mark("warmup_s")
    setup_s = time.perf_counter() - t0

    # ---- the measured window ---------------------------------------------
    spans = Spans()
    deadline = time.perf_counter() + float(args.seconds) - (
        TRACE_ALLOWANCE_S if args.trace else 0.0)
    window = drive(trainer, warm.state, batches, spans, in_flight,
                   lambda _n: time.perf_counter() >= deadline)
    state = window.state
    peak_bytes = peak_memory_bytes(devices)
    memory_stats = {k: v for k, v in (devices[0].memory_stats() or {}).items()
                    if k.startswith(("bytes_", "peak_bytes_"))}
    times = np.array([t for t, _ in window.completed])
    losses = np.array([v for _, v in window.completed])
    attempted = window.dispatched + (window.error is not None)
    failed = int((~np.isfinite(losses)).sum()) + (window.error is not None)
    compiles = watch.compiles_between(window.begin, window.end)
    ok = warm.error is None and window.error is None
    values: dict[str, float] = {"setup_s": setup_s,
                                "peak_hbm_gb": peak_bytes / 1e9}
    rate_per_chip = None
    if len(times) >= 3:
        # the window opens at the first completion: the steps that count are
        # the ones completed after it
        rate_per_chip = ((len(times) - 1) * job.units_per_step
                         / (times[-1] - times[0]) / cell.chips)
        gaps_ms = 1e3 * np.diff(times)
        values[f"{job.unit}_per_s_per_chip"] = rate_per_chip
        values["step_ms_p90"] = float(np.percentile(gaps_ms, 90))
        values["step_ms_p50"] = float(np.percentile(gaps_ms, 50))

    # ---- the traced steps and the per-layer readers -----------------------
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
    }
    breakdown = None
    if args.trace and ok:
        ctx = ReaderContext(
            chips=cell.chips, spans=spans.durations,
            counters={"compiles_in_window": compiles},
            rate_per_chip=rate_per_chip, flops_per_unit=job.flops_per_unit,
            peak=peak)
        traced, ctx.trace = capture_trace(
            lambda: drive(trainer, state, batches, Spans(), in_flight,
                          lambda n: n >= int(traffic["trace_steps"])),
            args.keep_trace)
        state = traced.state
        ok = traced.error is None
        if ok:
            ctx.hlo_text = job.compiled_text(state, next(batches))
        for metric in cell.per_layer:
            reader = cells.load_plugin("layer_metrics", metric["name"],
                                       cell.bench_dir)
            value = reader.reduce(ctx)
            if value is not None:
                values[metric["name"]] = float(value)
        if ctx.trace is not None:
            per_chip = trace_reduce.busy_and_window(ctx.trace)
            device["busy_s"] = sum(b for b, _ in per_chip) / len(per_chip)
            device["window_s"] = max(w for _, w in per_chip)
            breakdown = {
                "device_ops": trace_reduce.top_device_ops(ctx.trace),
                "idle_gaps": trace_reduce.idle_gaps_by_host_span(ctx.trace),
            }
        del traced
    parts.mark("window_and_trace_s")

    # ---- correctness: the plain reference, after the timed state is gone ---
    # (the memory mark was read above: the reference must not set it)
    del state, loss, replay, batches, warm, window, trainer
    job.trainer = job.state = None
    reference_losses = job.reference_losses(len(trainer_losses)) if ok else []
    parts.mark("reference_check_s")
    correct = bool(
        ok and failed == 0 and compiles == 0
        and job.losses_agree(trainer_losses, reference_losses))
    device["memory_peak_bytes"] = max(peak_bytes, peak_memory_bytes(devices))

    # an earlier line, for the reader of a log: where the run's time went
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "parts": parts.seconds,
        "trainer_losses": trainer_losses,
        "reference_losses": reference_losses,
        "steps_completed": len(times),
        "step_ms_p50": values.get("step_ms_p50"),
        "compiles_in_window": compiles,
        "compile_cache": {"hits": watch.cache_hits,
                          "misses": watch.cache_misses},
        "peak_after_window_bytes": peak_bytes,
        "memory_stats_after_window": memory_stats,
    }), flush=True)

    metrics = {}
    for metric in (cell.per_layer if args.trace else cell.end_to_end):
        if args.rehearse and metric["source"] != "program_counter":
            continue  # a CPU run names no time, rate or share of a device
        if metric["name"] in values:
            metrics[metric["name"]] = {"value": values[metric["name"]],
                                       "unit": metric["unit"]}
        elif not args.trace:
            raise KeyError("driver `train` does not measure the end-to-end "
                           f"metric {metric['name']!r}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result
