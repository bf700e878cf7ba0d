"""Observability plane (ISSUE 7): step-span tracer, crash flight recorder,
metrics exporter, fleet view — plus the guards that tracing is free: the
compiled step program is identical with obs on/off (jaxpr pin) and span
overhead stays under 2% of the measured step time."""

import gc
import glob
import json
import os
import re
import threading
import time

import jax
import numpy as np
import optax
import pytest

import golden
import bagua_tpu
from bagua_tpu import telemetry
from bagua_tpu.algorithms import GradientAllReduceAlgorithm
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.faults.inject import FaultSpec, fault_scope
from bagua_tpu.obs import export as obs_export
from bagua_tpu.obs import pauses as obs_pauses
from bagua_tpu.obs import recorder as obs_recorder
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.parallel.mesh import build_mesh

N_DEVICES = 8


@pytest.fixture()
def obs_on():
    """Tracing on, clean ring, restored to env-driven state afterwards."""
    obs_spans.set_enabled(True)
    obs_spans.recorder.clear()
    obs_spans.set_current_step(None)
    yield obs_spans
    obs_spans.recorder.clear()
    obs_spans.set_current_step(None)
    obs_spans.set_enabled(None)


def _golden_trainer(**kw):
    loss_fn, params, batch = golden.golden_task()
    t = BaguaTrainer(loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                     mesh=build_mesh({"dp": N_DEVICES}), autotune=False, **kw)
    s = t.init(params)
    return t, s, t.shard_batch(batch)


# ---- spans ----------------------------------------------------------------


def test_span_nesting_depth_and_attrs(obs_on):
    obs_spans.set_current_step(7)
    with obs_spans.trace_span("outer", bucket=1):
        with obs_spans.trace_span("inner", bytes=4096, step=9):
            pass
    spans = obs_spans.recorder.snapshot()
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"outer", "inner"}
    inner, outer = by_name["inner"], by_name["outer"]
    assert outer["depth"] == 0 and inner["depth"] == 1
    assert outer["step"] == 7          # inherits the current step
    assert inner["step"] == 9          # explicit step wins
    assert outer["attrs"] == {"bucket": 1}
    assert inner["attrs"] == {"bytes": 4096}
    # inner closed first and nests inside outer's window
    assert outer["t0"] <= inner["t0"] and inner["t1"] <= outer["t1"]
    assert all(s["t1"] >= s["t0"] and "rank" in s and "thread" in s
               for s in spans)


def test_span_ring_truncation(obs_on):
    obs_spans.recorder.set_capacity(8)
    try:
        for i in range(20):
            with obs_spans.trace_span(f"s{i}"):
                pass
        spans = obs_spans.recorder.snapshot()
        assert [s["name"] for s in spans] == [f"s{i}" for i in range(12, 20)]
        assert obs_spans.recorder.dropped == 12
    finally:
        obs_spans.recorder.set_capacity(512)


def test_spans_disabled_is_noop():
    obs_spans.set_enabled(False)
    try:
        obs_spans.recorder.clear()
        with obs_spans.trace_span("never", x=1) as s:
            assert s is None
        assert obs_spans.recorder.snapshot() == []
    finally:
        obs_spans.set_enabled(None)


def test_span_error_annotated(obs_on):
    with pytest.raises(ValueError):
        with obs_spans.trace_span("boom"):
            raise ValueError("x")
    (span,) = obs_spans.recorder.snapshot()
    assert span["error"] == "ValueError"


def test_a_rare_span_outlives_the_chatter(obs_on):
    """A steady step opens 7 spans into a ring of 512: a pause at step 30
    would be gone by step 110.  The rare names are kept beside the ring,
    and the snapshot is one list by start time."""
    with obs_spans.trace_span("step/build", phase=0):
        pass
    t0 = time.monotonic()
    obs_spans.recorder.record_rare(
        obs_spans.finished_span("host/gc", t0, t0 + 0.25, generation=2))
    for i in range(2000):
        with obs_spans.trace_span("step/dispatch", step=i):
            pass
    obs_spans.recorder.record_rare(
        obs_spans.finished_span("host/blocked", t0 - 5.0, t0 - 4.8))
    spans = obs_spans.recorder.snapshot()
    names = [s["name"] for s in spans]
    assert names.count("step/dispatch") == 512
    assert obs_spans.recorder.dropped == 2000 - 512
    assert {"step/build", "host/gc", "host/blocked"} <= set(names)
    starts = [s["t0"] for s in spans]
    assert starts == sorted(starts)
    # recorded last, started first: merged by start, not by arrival
    assert names[:3] == ["host/blocked", "step/build", "host/gc"]
    gc_span = spans[names.index("host/gc")]
    assert gc_span["attrs"] == {"generation": 2}
    assert gc_span["dur_s"] == pytest.approx(0.25)
    assert gc_span["thread"] == threading.current_thread().name


def test_the_rare_deque_is_bounded_too(obs_on):
    t0 = time.monotonic()
    for i in range(obs_spans.RARE_CAPACITY + 40):
        obs_spans.recorder.record_rare(
            obs_spans.finished_span("host/gc", t0 + i, t0 + i + 0.5, step=i))
    kept = obs_spans.recorder.snapshot()
    assert len(kept) == obs_spans.RARE_CAPACITY
    assert kept[0]["step"] == 40 and kept[-1]["step"] == (
        obs_spans.RARE_CAPACITY + 39)
    obs_spans.recorder.clear()
    assert obs_spans.recorder.snapshot() == []


# ---- the interpreter's pauses (obs/pauses.py) --------------------------------


@pytest.fixture()
def pauses_on(obs_on):
    """The collector's hook and the heartbeat installed as the step
    observer installs them, on a clean ring; both taken out afterwards."""
    obs_pauses.uninstall()
    obs_pauses.install()
    obs_pauses.ensure_heartbeat()
    yield obs_pauses
    obs_pauses.uninstall()


def _rare(name):
    return [s for s in obs_spans.recorder.snapshot() if s["name"] == name]


def _hold_the_interpreter(at_least_s):
    """One C call that keeps the interpreter lock for ``at_least_s`` or
    more on this box: ``sum(range(n))``, sized from a short probe.
    Returns the call's (t0, t1, CPU seconds)."""
    probe = time.perf_counter()
    sum(range(1_000_000))
    per_item = (time.perf_counter() - probe) / 1_000_000
    n = int(1.5 * at_least_s / per_item)
    cpu0, t0 = time.process_time(), time.monotonic()
    sum(range(n))
    return t0, time.monotonic(), time.process_time() - cpu0


def test_a_forced_collection_is_a_span_and_two_counters(pauses_on):
    before = telemetry.counters.snapshot()
    total = obs_pauses.gc_seconds()
    t0 = time.monotonic()
    gc.collect()
    t1 = time.monotonic()
    full = [s for s in _rare("host/gc") if s["attrs"]["generation"] == 2
            and t0 <= s["t0"] and s["t1"] <= t1]
    assert len(full) == 1, _rare("host/gc")
    (span,) = full
    assert span["thread"] == threading.current_thread().name
    assert span["attrs"]["collected"] >= 0
    assert span["depth"] == 0 and span["parent"] is None
    after = telemetry.counters.snapshot()
    assert after["host/gc_collections"] >= before.get(
        "host/gc_collections", 0) + 1
    moved = after["host/gc_pause_s"] - before.get("host/gc_pause_s", 0.0)
    assert moved >= span["dur_s"] > 0
    # the cumulative seconds the step observer differences moved alike
    assert obs_pauses.gc_seconds() - total >= span["dur_s"]


def test_a_young_collection_counts_and_is_no_span(pauses_on):
    """Every collection moves the counters; only a full one, or one of a
    millisecond, is a span (about ten young ones a second would be the
    chatter the rare deque is there to outlive)."""
    gc.collect()  # nothing pending afterwards: the next one is cheap
    obs_spans.recorder.clear()
    before = telemetry.counters.get("host/gc_collections")
    gc.collect(0)
    assert telemetry.counters.get("host/gc_collections") == before + 1
    assert all(s["dur_s"] >= obs_pauses.GC_SPAN_MIN_S
               for s in _rare("host/gc"))
    assert not [s for s in _rare("host/gc")
                if s["attrs"]["generation"] == 2]


def test_the_callback_never_waits_for_the_counters_lock(pauses_on):
    """A collection starts at any bytecode boundary, also inside a method
    of the counters that holds their lock: the callback adds what it can
    and carries the rest to the next collection."""
    gc.collect()
    before = telemetry.counters.get("host/gc_collections")
    with telemetry.counters._lock:
        gc.collect()            # would deadlock if the callback waited
        gc.collect()
    assert telemetry.counters.get("host/gc_collections") == before
    gc.collect()
    assert telemetry.counters.get("host/gc_collections") == before + 3
    assert telemetry.counters.add_nowait("host/gc_collections", 0) is True


def test_a_held_interpreter_is_a_blocked_span(pauses_on):
    """A C call that keeps the interpreter lock makes the heartbeat late:
    a ``host/blocked`` span over the call, whose CPU seconds say that the
    process itself was computing."""
    time.sleep(0.1)  # a few beats on time first
    before = obs_pauses.blocked_seconds()
    t0, t1, cpu_held = _hold_the_interpreter(0.3)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        over = [s for s in _rare("host/blocked")
                if s["t0"] < t1 and s["t1"] > t0]
        if over:
            break
        time.sleep(0.02)
    assert over, _rare("host/blocked")
    span = max(over, key=lambda s: s["dur_s"])
    assert span["thread"] == obs_pauses.HEARTBEAT_THREAD
    assert span["dur_s"] >= obs_pauses.BLOCKED_MIN_S
    attrs = span["attrs"]
    assert set(attrs) == {"cpu_s", "gc_s", "involuntary_switches",
                          "major_faults"}
    # of the same order as what the call itself burnt: we held the lock
    # ourselves (a descheduled process would read about nothing)
    assert attrs["cpu_s"] >= 0.5 * cpu_held > 0
    assert obs_pauses.blocked_seconds() > before
    assert telemetry.counters.get("host/blocked_s") > 0


def test_a_collection_under_the_heartbeat_counts_once_as_gc(pauses_on):
    """A full collection holds the interpreter lock, so the heartbeat is
    late under it: the seconds both saw are the collector's."""
    def slow_collector(phase, info):
        # part of the collection as our hook times it (jax's own hook,
        # ``_xla_gc_callback``, sits at the same place)
        if phase == "stop" and info["generation"] == 2:
            _hold_the_interpreter(0.3)

    time.sleep(0.1)
    gc.collect()
    gc.callbacks.insert(0, slow_collector)
    try:
        t0 = time.monotonic()
        gc.collect()
        t1 = time.monotonic()
    finally:
        gc.callbacks.remove(slow_collector)
    (full,) = [s for s in _rare("host/gc") if t0 <= s["t0"] and s["t1"] <= t1
               and s["attrs"]["generation"] == 2]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        over = [s for s in _rare("host/blocked")
                if s["t0"] < full["t1"] and s["t1"] > full["t0"]]
        if over:
            break
        time.sleep(0.02)
    assert over, _rare("host/blocked")
    span = max(over, key=lambda s: s["dur_s"])
    shared = min(span["t1"], full["t1"]) - max(span["t0"], full["t0"])
    assert span["attrs"]["gc_s"] >= 0.5 * shared > 0


@pytest.mark.parametrize("plane", ["off", "on"])
def test_the_pauses_ride_the_master_switch(plane):
    """``BAGUA_OBS=off``: ``gc.callbacks`` is as it was, no heartbeat
    thread lives and a span is the shared null context; ``on``: whatever
    installs the plane (the step observer) installs both."""
    def heartbeats():
        return [t for t in threading.enumerate()
                if t.name == obs_pauses.HEARTBEAT_THREAD]

    obs_pauses.uninstall()
    callbacks = list(gc.callbacks)
    assert heartbeats() == []
    obs_spans.set_enabled(plane == "on")
    try:
        t, s, b = _golden_trainer()
        for _ in range(3):
            s, loss = t.train_step(s, b)
            float(loss)
        if plane == "off":
            assert gc.callbacks == callbacks
            assert heartbeats() == []
            assert obs_spans.trace_span("step/prepare") is obs_spans._NULL
            assert obs_spans.trace_step_span(4) is obs_spans._NULL
            assert t._observer.pause_mark() == 0.0
        else:
            assert gc.callbacks == callbacks + [obs_pauses._on_gc]
            (beat,) = heartbeats()
            assert beat.daemon and beat.is_alive()
            t2, s2, b2 = _golden_trainer()     # a second trainer: still one
            t2.train_step(s2, b2)
            assert gc.callbacks.count(obs_pauses._on_gc) == 1
            assert heartbeats() == [beat]
    finally:
        obs_spans.set_enabled(None)
        obs_pauses.uninstall()
        obs_spans.recorder.clear()
    assert gc.callbacks == callbacks and heartbeats() == []


# ---- telemetry satellites -------------------------------------------------


def test_snapshot_collected_at_and_incr_many():
    c = telemetry.TelemetryCounters()
    s1 = c.snapshot()
    assert isinstance(s1, dict) and isinstance(s1.collected_at, float)
    c.incr_many({"comm/aborts": 2, "comm/abort_resets": 1})
    c.incr_many({"comm/aborts": 1})
    s2 = c.snapshot()
    assert dict(s2) == {"comm/aborts": 3, "comm/abort_resets": 1}
    assert s2.collected_at >= s1.collected_at
    assert json.loads(json.dumps(s2)) == dict(s2)  # still a plain dict


# ---- flight recorder ------------------------------------------------------


def _flight_dumps(dump_dir, **match):
    out = []
    for p in sorted(glob.glob(os.path.join(dump_dir, "flight_*.json"))):
        rec = json.load(open(p))
        if all(rec.get(k) == v for k, v in match.items()):
            out.append(rec)
    return out


def test_flight_dump_on_grad_poison(obs_on, tmp_path, monkeypatch):
    """A seeded ``grad.poison`` fire (traced, via ``fault_scope``) leaves a
    schema-valid dump naming the point, with spans and counters aboard."""
    monkeypatch.setenv("BAGUA_OBS_DUMP_DIR", str(tmp_path))
    with fault_scope(FaultSpec("grad.poison", step=2)):
        t, s, b = _golden_trainer(grad_guard="skip")
        for _ in range(4):
            s, loss = t.train_step(s, b)
        t.flush_grad_health()
    dumps = _flight_dumps(str(tmp_path), trigger="fault_fire",
                          fault_point="grad.poison")
    assert dumps, os.listdir(tmp_path)
    rec = dumps[0]
    assert obs_recorder.validate_flight_record(rec) == []
    assert rec["fired_faults"].get("grad.poison", 0) >= 1
    assert any(sp["name"] == "step/dispatch" for sp in rec["spans"])
    assert rec["armed_faults"][0]["point"] == "grad.poison"
    # the one-step-behind verdict published host-safe step metrics
    assert t.step_metrics["grad_healthy"] is not None
    metrics = obs_export.last_step_metrics()
    assert "grad_healthy" in metrics


def test_flight_dump_on_collective_hang(obs_on, tmp_path, monkeypatch):
    """A seeded ``collective.hang`` (reusing ``fault_scope``) wedges the
    watchdog waiter; the monitor fires and both artifacts appear: the
    fault-fire dump and the watchdog-abort post-mortem."""
    from bagua_tpu.watchdog import HangWatchdog

    monkeypatch.setenv("BAGUA_OBS_DUMP_DIR", str(tmp_path))
    wd = HangWatchdog(timeout_s=0.3, action="abort")
    hang = abort_dump = []
    try:
        # the hang outlasts the wait below and ends only with wd.stop(): the
        # monitor fires while the section is wedged however loaded the host
        # is, and the test waits for the two dumps, not for a clock
        with fault_scope(FaultSpec("collective.hang", duration_s=300)):
            wd.watch_result(np.zeros(()), "wedged-step")
            deadline = time.time() + 120
            while not (hang and abort_dump) and time.time() < deadline:
                time.sleep(0.05)
                hang = _flight_dumps(str(tmp_path), trigger="fault_fire",
                                     fault_point="collective.hang")
                abort_dump = _flight_dumps(str(tmp_path),
                                           trigger="watchdog_abort")
    finally:
        wd.stop()
        bagua_tpu.reset_abort()
    assert wd.fired.is_set()
    assert hang and abort_dump, os.listdir(tmp_path)
    rec = abort_dump[0]
    assert obs_recorder.validate_flight_record(rec) == []
    assert "wedged-step" in rec["reason"]
    # the wedged watched section never exited — it is the headline of the
    # post-mortem's ACTIVE span list, not the finished-span tail
    assert any(sp["name"] == "watchdog/wedged-step"
               for sp in rec["active_spans"])
    assert rec["counters"].get("comm/aborts", 0) >= 1


def test_flight_dump_flushes_elastic_counters(obs_on, tmp_path, monkeypatch):
    """The satellite fix: abort-class dumps flush this process's counters
    to BAGUA_ELASTIC_TELEMETRY_OUT (rank-suffixed) even with no dump dir —
    the watchdog-abort/health-fence exit paths where they used to vanish."""
    out = str(tmp_path / "elastic_telemetry.json")
    monkeypatch.delenv("BAGUA_OBS_DUMP_DIR", raising=False)
    monkeypatch.setenv("BAGUA_ELASTIC_TELEMETRY_OUT", out)
    telemetry.counters.incr("comm/aborts")
    assert obs_recorder.dump_flight_record("watchdog_abort", "test") is None
    flushed = json.load(open(f"{out}.rank0.json"))
    assert flushed["trigger"] == "watchdog_abort"
    assert flushed["counters"].get("comm/aborts", 0) >= 1


def test_flight_dump_retention_cap_prunes_oldest(obs_on, tmp_path,
                                                 monkeypatch):
    """The BAGUA_OBS_DUMP_MAX_FILES satellite: dump files over the cap
    are pruned oldest-first (never the one just written), the pruned
    count lands in obs/flight_dumps_pruned, and span-ring dumps in the
    same directory are not touched."""
    monkeypatch.setenv("BAGUA_OBS_DUMP_DIR", str(tmp_path))
    monkeypatch.setenv("BAGUA_OBS_DUMP_MAX_FILES", "3")
    bystander = tmp_path / "spans_rank0.json"
    bystander.write_text("{}")
    before = telemetry.counters.get("obs/flight_dumps_pruned")
    paths = []
    for i in range(6):
        # distinct fault points mint distinct trigger-keyed filenames
        p = obs_recorder.dump_flight_record("fault_fire", reason=f"d{i}",
                                            fault_point=f"point.{i}")
        assert p is not None
        paths.append(p)
    remaining = sorted(glob.glob(os.path.join(tmp_path, "flight_*.json")))
    assert len(remaining) == 3
    assert paths[-1] in remaining  # the newest write survives
    assert paths[0] not in remaining and paths[1] not in remaining
    assert bystander.exists()  # not ours to reap
    assert telemetry.counters.get("obs/flight_dumps_pruned") == before + 3
    # cap 0 disables pruning
    monkeypatch.setenv("BAGUA_OBS_DUMP_MAX_FILES", "0")
    for i in range(6, 9):
        obs_recorder.dump_flight_record("fault_fire", reason=f"d{i}",
                                        fault_point=f"point.{i}")
    assert len(glob.glob(os.path.join(tmp_path, "flight_*.json"))) == 6


def test_flight_dump_disabled_modes(obs_on, tmp_path, monkeypatch):
    monkeypatch.delenv("BAGUA_OBS_DUMP_DIR", raising=False)
    monkeypatch.delenv("BAGUA_ELASTIC_TELEMETRY_OUT", raising=False)
    assert obs_recorder.dump_flight_record("watchdog_abort") is None
    obs_spans.set_enabled(False)
    monkeypatch.setenv("BAGUA_OBS_DUMP_DIR", str(tmp_path))
    assert obs_recorder.dump_flight_record("watchdog_abort") is None
    assert not os.listdir(tmp_path)


# ---- metrics exporter -----------------------------------------------------


def test_exporter_jsonl_prometheus_roundtrip(obs_on, tmp_path):
    telemetry.counters.incr("comm/abort_resets")
    obs_export.note_step(12, 0.025)
    exporter = obs_export.MetricsExporter(str(tmp_path), interval_s=0.05)
    exporter.start()
    time.sleep(0.2)
    exporter.stop()
    lines = open(tmp_path / "metrics.jsonl").read().splitlines()
    assert len(lines) >= 2  # periodic + final export
    rec = json.loads(lines[-1])
    assert rec["counters"].get("comm/abort_resets", 0) >= 1
    assert isinstance(rec["collected_at"], float)
    assert rec["obs"]["step"] == 12
    prom = open(tmp_path / "metrics.prom").read()
    assert "# TYPE bagua_comm_abort_resets counter" in prom
    assert re.search(r"^bagua_comm_abort_resets \d+$", prom, re.M)
    # round-trip: every exported sample name maps back to a registered
    # metric (the lint rule holds the write sites to the same registry)
    for name in rec["counters"]:
        assert obs_export.is_registered(name), name


def test_prometheus_rendering_kinds_and_mangling():
    snap = telemetry.CounterSnapshot(
        {"faults/grad.poison/fired": 2, "async/staleness_max": 3,
         "not/a/registered-name": 1}, 0.0,
    )
    prom = obs_export.render_prometheus(snap)
    assert "# TYPE bagua_faults_grad_poison_fired counter" in prom
    assert "# TYPE bagua_async_staleness_max gauge" in prom
    assert "# TYPE bagua_not_a_registered_name untyped" in prom


def test_prepared_snapshot_renders_fully_registered_prom():
    """Satellite gate (file side): the prepared snapshot both Prometheus
    surfaces render — metrics.prom and the /metrics endpoint — exposes
    only registered series, each with HELP and TYPE (never untyped)."""
    telemetry.counters.incr("obs/flight_dumps")
    snap = obs_export.prepared_snapshot()
    assert snap, "prepared snapshot should carry at least the gauges"
    for name in snap:
        assert obs_export.is_registered(name), name
    prom = obs_export.render_prometheus(snap)
    assert "untyped" not in prom
    for name in snap:
        pname = obs_export.prometheus_name(name)
        kind = obs_export.METRIC_REGISTRY[name].kind
        assert f"# TYPE {pname} {kind}" in prom
        assert f"# HELP {pname} " in prom


def test_metric_registry_covers_known_names():
    for name in ("comm/aborts", "grad_guard/skipped_steps",
                 "ckpt/integrity_failures", "async/rounds_launched",
                 "elastic/health_fenced", "faults/step.straggle/recovered",
                 "obs/flight_dumps"):
        assert obs_export.is_registered(name), name
    assert obs_export.any_registered_matches("faults/.+/fired")
    assert not obs_export.any_registered_matches("faults/.+/exploded")


# ---- fleet view -----------------------------------------------------------


def test_fleet_snapshot_from_two_rank_heartbeat_exchange(obs_on, tmp_path):
    """Two nodes' heartbeats carry per-rank obs summaries; the coordinator
    tracker harvests them and the fleet snapshot merges per-rank step,
    staleness, skip counts, and step-dt percentiles."""
    from bagua_tpu.contrib.utils.store import InMemoryStore
    from bagua_tpu.elastic.membership import (
        LeaseHeartbeat,
        LeaseTracker,
        MembershipClient,
    )

    store = InMemoryStore()
    client = MembershipClient(store, node_id=0, max_nnodes=2)

    def src(rank, step):
        return lambda: {"obs": {"rank": rank, "step": step,
                                "staleness": rank, "skipped_steps": 0,
                                "step_dt_p50": 0.01, "step_dt_p90": 0.02}}

    hbs = [
        LeaseHeartbeat(lambda: store, node_id=i, epoch=0, interval_s=0.05,
                       max_nnodes=2, health_source=src(i, 100 + i)).start()
        for i in range(2)
    ]
    try:
        tracker = LeaseTracker(client, epoch=0, member_ids=[0, 1], ttl_s=30.0)
        deadline = time.time() + 10
        while time.time() < deadline:
            tracker.poll()
            if all(tracker.health_of(i) for i in (0, 1)):
                break
            time.sleep(0.05)
        path = str(tmp_path / "fleet.json")
        assert obs_export.write_fleet_snapshot(
            path, 0, {i: tracker.health_of(i) for i in (0, 1)}
        )
    finally:
        for hb in hbs:
            hb.stop()
    fleet = json.load(open(path))
    assert obs_export.validate_fleet_snapshot(fleet) == []
    assert fleet["nnodes"] == 2
    for nid in ("0", "1"):
        obs = fleet["ranks"][nid]["obs"]
        (summary,) = obs.values()
        assert summary["step"] == 100 + int(nid)
        assert summary["step_dt_p90"] == 0.02


def test_local_obs_summary_rides_health_beacon(obs_on, tmp_path, monkeypatch):
    """The worker half of the fleet view: after the trainer notes steps,
    the health beacon carries the per-rank summary (and the fence scalar
    still ignores it)."""
    from bagua_tpu.elastic.membership import (
        file_health_source,
        health_event_count,
        local_health_snapshot,
        write_health_beacon,
    )

    obs_export.reset_local_summary()
    for step in range(1, 6):
        obs_export.note_step(step, 0.01 * step)
    snap = local_health_snapshot()
    assert snap and snap["obs"]["step"] == 5
    assert snap["obs"]["step_dt_p50"] > 0
    assert health_event_count(snap) == health_event_count(
        {k: v for k, v in snap.items() if k != "obs"}
    )
    path = str(tmp_path / "beacon.json")
    monkeypatch.setenv("BAGUA_ELASTIC_HEALTH_FILE", path)
    assert write_health_beacon() is True
    assert file_health_source(path)()["obs"]["step"] == 5


def test_merged_health_source_keeps_per_rank_obs(tmp_path):
    from bagua_tpu.elastic.membership import merged_health_source

    paths = [str(tmp_path / f"b.r{i}") for i in range(2)]
    with open(paths[0], "w") as f:
        json.dump({"grad_unhealthy": 1,
                   "obs": {"rank": 4, "step": 9}}, f)
    with open(paths[1], "w") as f:
        json.dump({"obs": {"rank": 5, "step": 11}}, f)
    merged = merged_health_source(paths)()
    assert merged["grad_unhealthy"] == 1
    assert merged["obs"]["4"]["step"] == 9
    assert merged["obs"]["5"]["step"] == 11


# ---- the "tracing is free" guards -----------------------------------------

_ADDR = re.compile(r" at 0x[0-9a-fA-F]+")


def test_step_program_identical_obs_on_off():
    """The acceptance pin: tracing never inserts collectives or host syncs
    into the compiled step — the traced jaxpr is identical (modulo object
    addresses in thunk reprs) with the plane on and off."""
    def traced(enabled):
        obs_spans.set_enabled(enabled)
        try:
            t, s, b = _golden_trainer()
            return _ADDR.sub("", str(t.trace_step(s, b)))
        finally:
            obs_spans.set_enabled(None)

    assert traced(True) == traced(False)


def test_span_overhead_under_two_percent(obs_on):
    """Span overhead budget: (spans per steady step) x (per-span cost),
    each factor bounded on its own (tests/span_budget.py): the count
    exactly, the cost against an absolute ceiling — 7 x 50 us is 0.5 % of
    the shortest step the benchmark measures, well under the 2 % budget —
    and the root span's children tile it."""
    from span_budget import assert_span_budget

    t, s, b = _golden_trainer()
    before = len(obs_spans.recorder.snapshot())
    for _ in range(5):
        s, loss = t.train_step(s, b)
    float(loss)
    assert_span_budget(t, obs_spans.recorder.snapshot()[before:])


# ---- exporter wiring through the trainer ----------------------------------


def test_trainer_starts_exporter_and_notes_steps(obs_on, tmp_path,
                                                 monkeypatch):
    monkeypatch.setenv("BAGUA_OBS_EXPORT_DIR", str(tmp_path / "export"))
    monkeypatch.setenv("BAGUA_OBS_EXPORT_INTERVAL_S", "0.05")
    # the global exporter is process-wide; isolate by resetting it
    monkeypatch.setattr(obs_export, "_GLOBAL_EXPORTER", None)
    obs_export.reset_local_summary()
    t, s, b = _golden_trainer()
    try:
        for _ in range(3):
            s, _ = t.train_step(s, b)
        summary = obs_export.local_obs_summary()
        assert summary and summary["step"] == 3
        deadline = time.time() + 10
        jsonl = tmp_path / "export" / "metrics.jsonl"
        while time.time() < deadline and not jsonl.exists():
            time.sleep(0.05)
        assert jsonl.exists()
        rec = json.loads(open(jsonl).read().splitlines()[-1])
        assert "counters" in rec
    finally:
        exporter = obs_export._GLOBAL_EXPORTER
        if exporter is not None:
            exporter.stop(final_export=False)
        monkeypatch.setattr(obs_export, "_GLOBAL_EXPORTER", None)
