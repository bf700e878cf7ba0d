"""Step-time anomaly detection (ISSUE 9): warmup grace, MAD robustness,
dump throttling, the straggler_suspect beacon payload, perf hints, the
coordinator-side straggler naming, and the trainer integration — and what a
stall says of itself (ISSUE 52): the phases the spans know, the threads
sampled while it lasted, the ``step/stall`` record."""

import gc
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bagua_tpu import telemetry  # noqa: E402
from bagua_tpu.obs import anomaly as an  # noqa: E402
from bagua_tpu.obs import export as obs_export  # noqa: E402
from bagua_tpu.obs import pauses as obs_pauses  # noqa: E402
from bagua_tpu.obs import recorder as obs_recorder  # noqa: E402
from bagua_tpu.obs import spans as obs_spans  # noqa: E402
from bagua_tpu.obs.step_observer import StepObserver  # noqa: E402


@pytest.fixture()
def clean_obs():
    obs_export.reset_local_summary()
    an.drain_perf_hints()
    yield
    obs_export.reset_local_summary()
    an.drain_perf_hints()


def _detector(**kw):
    kw.setdefault("window", 32)
    kw.setdefault("warmup", 6)
    kw.setdefault("threshold", 5.0)
    kw.setdefault("rank", 0)
    return an.StepAnomalyDetector(**kw)


def test_warmup_grace_no_flags(clean_obs):
    """Even a grotesque spike during warmup must not flag: compile steps
    and cold caches are not anomalies."""
    d = _detector(warmup=6)
    for i in range(5):
        assert d.observe(i, 5.0 if i == 2 else 0.01) is None
    assert list(d.suspects) == []


def test_detects_after_warmup_with_phase_breakdown(clean_obs):
    d = _detector()
    for i in range(10):
        assert d.observe(i, 0.010, {"dispatch": 0.009}) is None
    s = d.observe(10, 0.100, {"dispatch": 0.009, "collective": 0.090})
    assert s is not None
    assert s["dominant_phase"] == "collective"
    assert s["ratio"] == pytest.approx(10.0, rel=0.05)
    assert s["baseline_p50"] == pytest.approx(0.010, rel=0.01)
    assert set(s["phases"]) == set(an.PHASES) | {"other"}
    # what was at work explains the excess: the collective wait
    assert s["explained_s"] == pytest.approx(0.090, rel=0.01)
    assert s["rank"] == 0 and s["step"] == 10


def test_mad_robust_to_single_spike(clean_obs):
    """One historic spike must not inflate the baseline enough to mask the
    next one, nor to flag normal steps afterwards."""
    d = _detector(warmup=6)
    for i in range(8):
        d.observe(i, 0.010)
    assert d.observe(8, 0.200) is not None        # spike 1 flagged
    for i in range(9, 15):                        # normal steps stay quiet
        assert d.observe(i, 0.0105) is None
    assert d.observe(15, 0.200) is not None       # spike 2 STILL flagged


def test_steady_cadence_zero_mad_guard(clean_obs):
    """A perfectly steady host (MAD ~ 0) must not flag microsecond jitter:
    the min_ratio guard holds the floor."""
    d = _detector()
    for i in range(10):
        d.observe(i, 0.010)
    assert d.observe(10, 0.0115) is None          # +15% < min_ratio 1.3
    assert d.observe(11, 0.014) is not None       # +40% is real


def test_the_cut_the_heartbeat_watches_is_the_detectors_own(clean_obs):
    d = _detector(warmup=6, threshold=5.0)
    for i in range(5):
        d.observe(i, 0.010)
        assert d.cut_s() is None                  # warm-up: nothing to cut
    d.observe(5, 0.010)
    assert d.cut_s() == pytest.approx(0.013)      # MAD 0: min_ratio x p50
    for just, flagged in ((0.9999, False), (1.0001, True)):
        d = _detector(warmup=6, threshold=5.0)
        for i, dt in enumerate((0.010, 0.010, 0.008, 0.012, 0.009, 0.011,
                                0.007, 0.013)):
            d.observe(i, dt)
        # p50 0.010, MAD 0.0015: 0.010 + 5 x 1.4826 x 0.0015
        assert d.cut_s() == pytest.approx(0.0211195)
        assert (d.observe(8, just * d.cut_s()) is not None) is flagged


_SAMPLE = {
    "window_t0": 100.0, "sampled_after_s": 0.52,
    "stacks": {"MainThread": ["loop.py:12 wait", "loop.py:40 main"],
               "bench-waiter": ["train.py:119 wait_in_order"]},
    "open_spans": [{"name": "watchdog/train_step[7]",
                    "thread": "bagua-watchdog-waiter", "open_for_s": 0.6}],
}


def test_a_flagged_window_is_a_stall_record_with_its_sample(clean_obs):
    """The suspect and the rare span ``step/stall`` carry what every thread
    was doing while the window lasted; ``explained_s`` near 0 (the caller
    waited, nothing of ours ran) is itself the finding."""
    obs_spans.set_enabled(True)
    obs_spans.recorder.clear()
    try:
        d = _detector()
        steady = {"dispatch": 0.001, "trainer": 0.0005, "caller": 0.0085}
        for i in range(10):
            d.observe(i, 0.010, steady)
        assert obs_spans.recorder.snapshot() == []
        s = d.observe(10, 1.500, {**steady, "caller": 1.4985}, _SAMPLE)
        assert s["dominant_phase"] == "caller"
        assert s["explained_s"] == pytest.approx(0.0, abs=1e-6)
        assert s["stacks"] == _SAMPLE["stacks"]
        assert s["open_spans"] == _SAMPLE["open_spans"]
        assert s["sampled_after_s"] == 0.52
        (stall,) = obs_spans.recorder.snapshot()
        assert stall["name"] == "step/stall" and stall["step"] == 10
        assert stall["dur_s"] == pytest.approx(1.5)
        assert stall["t1"] <= time.monotonic()
        attrs = stall["attrs"]
        assert attrs["stacks"] == _SAMPLE["stacks"]
        assert attrs["phases"] == s["phases"]
        assert attrs["baseline_p50"] == pytest.approx(0.010)
        assert attrs["dominant_phase"] == "caller"
        # a blip no heartbeat sampled is a record all the same, less stacks
        s2 = d.observe(11, 0.200, {**steady, "gc": 0.190})
        assert s2["dominant_phase"] == "gc" and "stacks" not in s2
        assert s2["explained_s"] == pytest.approx(0.190, rel=0.01)
        stalls = [sp for sp in obs_spans.recorder.snapshot()
                  if sp["name"] == "step/stall"]
        assert [sp["step"] for sp in stalls] == [10, 11]
        assert "stacks" not in stalls[1]["attrs"]
    finally:
        obs_spans.recorder.clear()
        obs_spans.set_enabled(None)


# seconds: (window, root span, dispatch span, pauses inside the dispatch,
# pauses inside the root, collector in the window, blocked in the window)
_WINDOWS = {
    "steady": (0.100, 0.004, 0.003, 0.0, 0.0, 0.0, 0.0),
    "sleep_in_the_caller": (1.100, 0.004, 0.003, 0.0, 0.0, 0.0, 0.0),
    "collection_in_the_caller": (0.600, 0.004, 0.003, 0.0, 0.0, 0.5, 0.0),
    "collection_in_the_dispatch": (0.600, 0.504, 0.503, 0.5, 0.5, 0.5, 0.0),
    "collection_in_the_hooks": (0.600, 0.504, 0.003, 0.0, 0.5, 0.5, 0.0),
    "held_lock_in_the_caller": (0.700, 0.004, 0.003, 0.0, 0.0, 0.0, 0.6),
    "both_and_a_late_heartbeat": (0.300, 0.104, 0.003, 0.0, 0.1, 0.2, 0.25),
    "the_root_span_is_the_window": (0.050, 0.050, 0.049, 0.0, 0.0, 0.0, 0.0),
}
_EXPECTED = {
    "steady": {"dispatch": 0.003, "trainer": 0.001, "caller": 0.096},
    "sleep_in_the_caller": {"caller": 1.096},
    "collection_in_the_caller": {"gc": 0.5, "caller": 0.096},
    "collection_in_the_dispatch": {"gc": 0.5, "dispatch": 0.003,
                                   "trainer": 0.001, "caller": 0.096},
    "collection_in_the_hooks": {"gc": 0.5, "dispatch": 0.003,
                                "trainer": 0.001, "caller": 0.096},
    "held_lock_in_the_caller": {"blocked": 0.6, "caller": 0.096},
    # the collector's 0.2 first; the heartbeat's lateness fills what is left
    "both_and_a_late_heartbeat": {"gc": 0.2, "blocked": 0.1},
    "the_root_span_is_the_window": {"caller": 0.0, "trainer": 0.001},
}


@pytest.mark.parametrize("case", sorted(_WINDOWS))
def test_the_phases_never_add_up_to_more_than_the_window(
        clean_obs, monkeypatch, case):
    """``gc`` and ``blocked`` from two cumulative floats; ``trainer`` the
    root span less the dispatch, ``caller`` the window less the root span,
    each net of the pauses that fell inside it."""
    raw, root, dispatch, in_dispatch, in_root, gc_s, blocked_s = \
        _WINDOWS[case]
    clock = {"gc": 10.0, "blocked": 20.0}
    monkeypatch.setattr(obs_pauses, "gc_seconds", lambda: clock["gc"])
    monkeypatch.setattr(obs_pauses, "blocked_seconds",
                        lambda: clock["blocked"])
    monkeypatch.setattr(obs_pauses, "paused_seconds",
                        lambda: clock["gc"] + clock["blocked"])
    monkeypatch.setattr(obs_pauses, "ensure_heartbeat", lambda: None)
    monkeypatch.delenv("BAGUA_OBS_EXPORT_DIR", raising=False)
    monkeypatch.delenv("BAGUA_OBS_HTTP_PORT", raising=False)
    obs_spans.set_enabled(True)
    try:
        obs = StepObserver()
        seen = []
        obs.anomaly_detector.observe = (
            lambda step, raw_dt, phases, sample=None:
            seen.append((raw_dt, dict(phases))))
        obs.begin_step(1)
        obs.begin_step(2)                       # window 1 closes: a baseline
        mark = obs.pause_mark()
        clock["gc"] += in_dispatch              # what fell inside the call
        obs.note_dispatch(dispatch, mark)
        clock["gc"] += in_root - in_dispatch    # elsewhere in the root span
        began = obs._last_step_mono
        with monkeypatch.context() as clocked:
            clocked.setattr(time, "monotonic", lambda: began + root)
            obs.end_step({}, track_speed=False)  # the trainer's part ends
        clock["gc"] += gc_s - in_root           # and in the caller
        clock["blocked"] += blocked_s
        phases = obs._phase_durations
        obs._phase_durations = {}
        obs._add_span_phases(phases, raw, clock["gc"], clock["blocked"])
        assert all(v >= 0 for v in phases.values()), phases
        assert sum(phases.values()) <= raw + 1e-9, phases
        for name, seconds in _EXPECTED[case].items():
            assert phases[name] == pytest.approx(seconds, abs=1e-9), phases
        if case not in ("both_and_a_late_heartbeat",):
            # nothing is left for ``other``: the window is all explained
            assert sum(phases.values()) == pytest.approx(raw)
    finally:
        obs_spans.set_current_step(None)
        obs_spans.set_enabled(None)
        from bagua_tpu.obs import ledger

        ledger.ledger.reset()


def test_a_step_that_never_ended_leaves_the_rest_to_other(
        clean_obs, monkeypatch):
    monkeypatch.setattr(obs_pauses, "ensure_heartbeat", lambda: None)
    monkeypatch.delenv("BAGUA_OBS_EXPORT_DIR", raising=False)
    monkeypatch.delenv("BAGUA_OBS_HTTP_PORT", raising=False)
    obs_spans.set_enabled(True)
    try:
        obs = StepObserver()
        obs.begin_step(1)
        phases = {}
        obs._add_span_phases(phases, 0.5, obs_pauses.gc_seconds(),
                             obs_pauses.blocked_seconds())
        assert set(phases) == {"gc", "blocked"}     # end_step never came
    finally:
        obs_spans.set_current_step(None)
        obs_spans.set_enabled(None)
        from bagua_tpu.obs import ledger

        ledger.ledger.reset()


def test_dump_throttling(clean_obs, tmp_path, monkeypatch):
    """Anomaly dumps are throttled: the first flags a flight record, a
    burst within the interval does not write per-anomaly."""
    from bagua_tpu.obs import spans as obs_spans

    monkeypatch.setenv("BAGUA_OBS_DUMP_DIR", str(tmp_path))
    obs_spans.set_enabled(True)
    try:
        d = _detector(dump_min_interval_s=60.0)
        for i in range(10):
            d.observe(i, 0.010)
        for i in range(10, 14):
            d.observe(i, 0.100)
        dumps = [p for p in os.listdir(tmp_path)
                 if p.startswith("flight_step_anomaly")]
        assert len(dumps) == 1
        rec = json.load(open(tmp_path / dumps[0]))
        assert obs_recorder.validate_flight_record(rec) == []
        assert rec["extra"]["straggler_suspect"]["step"] == 10
        assert len(d.suspects) == 4               # all flagged, one dumped
        assert telemetry.counters.get("obs/step_anomalies") >= 4
    finally:
        obs_spans.set_enabled(None)


def test_suspect_rides_beacon_payload(clean_obs, tmp_path, monkeypatch):
    """Beacon payload shape: the latest suspect lands in the per-rank obs
    summary, survives the beacon file round trip, and the fence scalar
    ignores it."""
    from bagua_tpu.elastic.membership import (
        file_health_source,
        health_event_count,
        local_health_snapshot,
        write_health_beacon,
    )

    for step in range(1, 4):
        obs_export.note_step(step, 0.01)
    d = _detector(warmup=2)
    for i in range(4):
        d.observe(i, 0.010)
    d.observe(4, 0.100, {"collective": 0.09})
    summary = obs_export.local_obs_summary()
    suspect = summary["straggler_suspect"]
    assert suspect["dominant_phase"] == "collective"
    assert suspect["step"] == 4
    path = str(tmp_path / "beacon.json")
    monkeypatch.setenv("BAGUA_ELASTIC_HEALTH_FILE", path)
    assert write_health_beacon() is True
    read = file_health_source(path)()
    assert read["obs"]["straggler_suspect"]["step"] == 4
    snap = local_health_snapshot()
    assert health_event_count(snap) == health_event_count(
        {k: v for k, v in snap.items() if k != "obs"})


def test_perf_hints_drain(clean_obs):
    d = _detector(warmup=2)
    for i in range(4):
        d.observe(i, 0.010)
    d.observe(4, 0.100)
    hints = an.drain_perf_hints()
    assert hints and hints[-1]["kind"] == "step_time_anomaly"
    assert hints[-1]["step"] == 4
    assert an.drain_perf_hints() == []            # drained
    assert an.peek_perf_hints() == []


def test_autotune_service_remeasures_hinted_window(clean_obs):
    """Service-side consumption: a sample window that carried perf hints
    is re-measured once instead of scored."""
    from bagua_tpu.service.autotune_service import AutotuneService

    svc = AutotuneService(world_size=1, autotune_level=1,
                          warmup_time_s=0.0,
                          sampling_confidence_time_s=0.0)
    svc._task("m")  # materialize
    svc.register_tensors({"model_name": "m", "tensor_list": []})
    svc.report_metrics({"model_name": "m", "rank": 0, "speed": 100.0,
                        "perf_hints": [{"kind": "step_time_anomaly",
                                        "ratio": 9.0}]})
    task = svc._task("m")
    assert task.perf_hints and task.perf_hints[0]["reported_by"] == 0
    svc.ask_hyperparameters({"model_name": "m", "rank": 0, "train_iter": 1})
    before = task.n_samples
    # the hinted window was reset, not scored
    assert before == 0 and task.sample_retried is True
    # the retry window (no new hints) scores normally
    svc.ask_hyperparameters({"model_name": "m", "rank": 0, "train_iter": 2})
    assert task.n_samples == 1


def test_autotune_service_absorbs_warmup_hints(clean_obs):
    """Hints reported during the warmup period describe windows that are
    never scored — they must not burn the first sampling window's one
    re-measure."""
    from bagua_tpu.service.autotune_service import AutotuneService

    svc = AutotuneService(world_size=1, autotune_level=1,
                          warmup_time_s=3600.0,
                          sampling_confidence_time_s=0.0)
    svc.register_tensors({"model_name": "m", "tensor_list": []})
    svc.report_metrics({"model_name": "m", "rank": 0, "speed": 100.0,
                        "perf_hints": [{"kind": "step_time_anomaly",
                                        "ratio": 9.0}]})
    svc.ask_hyperparameters({"model_name": "m", "rank": 0, "train_iter": 1})
    task = svc._task("m")
    assert task.sample_hint_mark == task.perf_hints_total == 1
    svc.warmup_time_s = 0.0  # warmup ends; no new hints since
    svc.ask_hyperparameters({"model_name": "m", "rank": 0, "train_iter": 2})
    assert task.n_samples == 1 and task.sample_retried is False


def test_fleet_straggler_naming():
    """Coordinator half: dispatch-dominant suspects are stragglers,
    collective-dominant ones their victims."""
    def summary(rank, phase, ratio):
        return {"rank": rank, "step": 50,
                "straggler_suspect": {"rank": rank, "step": 50,
                                      "ratio": ratio,
                                      "dominant_phase": phase}}

    fleet = {"schema": "bagua-obs-fleet-v1", "ranks": {
        "0": {"health": {}, "obs": {"0": summary(0, "collective", 4.0)}},
        "1": {"health": {}, "obs": {"1": summary(1, "dispatch", 9.0),
                                    "2": {"rank": 2, "step": 50}}},
    }}
    out = an.fleet_straggler_suspects(fleet)
    assert [s["rank"] for s in out["stragglers"]] == [1]
    assert [s["rank"] for s in out["victims"]] == [0]


def test_trainer_flags_injected_straggle(clean_obs, monkeypatch):
    """End-to-end on the 8-dev cpu-sim mesh: a gated step.straggle window
    after a clean baseline is flagged collective-dominant by the
    trainer-integrated detector (the chaos drill runs the larger version
    with the fleet plumbing)."""
    import optax

    import golden
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.faults.inject import FaultSpec, fault_scope
    from bagua_tpu.parallel.mesh import build_mesh

    monkeypatch.setenv("BAGUA_OBS_ANOMALY_WARMUP", "4")
    loss_fn, params, batch = golden.golden_task()
    t = BaguaTrainer(loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                     mesh=build_mesh({"dp": 8}), autotune=False)
    assert t.anomaly_detector is not None
    s = t.init(params)
    b = t.shard_batch(batch)
    for _ in range(8):
        s, _ = t.train_step(s, b)
    start = t._step_counter
    with fault_scope(FaultSpec("step.straggle", rank=1, count=-1,
                               base_ms=20.0, factor=10.0)):
        for _ in range(4):
            s, _ = t.train_step(s, b)
    # drain the async dispatch queue before the observe step: its cadence
    # sample must measure the step, not 12 queued steps' device backlog
    import jax

    jax.block_until_ready(s.params)
    s, _ = t.train_step(s, b)  # observe the last straggled window
    flagged = [sp for sp in t.anomaly_detector.suspects
               if sp["step"] >= start]
    assert flagged, list(t.anomaly_detector.suspects)
    assert flagged[-1]["dominant_phase"] == "collective"
    # measured_step_dt stays an honest dilation base (stall subtracted)
    assert t.measured_step_dt() < 0.1


def _steady_trainer(monkeypatch, steps=8):
    import jax
    import optax

    import golden
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    monkeypatch.setenv("BAGUA_OBS_ANOMALY_WARMUP", "4")
    loss_fn, params, batch = golden.golden_task()
    t = BaguaTrainer(loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                     mesh=build_mesh({"dp": 8}), autotune=False)
    s = t.init(params)
    b = t.shard_batch(batch)
    for _ in range(steps):
        s, loss = t.train_step(s, b)
        float(loss)
    jax.block_until_ready(s.params)
    # an earlier window flagged on a loaded box must not throttle the dump
    t.anomaly_detector._last_dump_mono = None
    return t, s, b


def _stalled_caller(net_of_pauses_s):
    """What a user's loop does between two steps, for ``net_of_pauses_s``
    seconds in which the interpreter was free (a loaded box makes the
    heartbeat late: those seconds are ``blocked``, not ours)."""
    t0, paused0 = time.monotonic(), obs_pauses.paused_seconds()
    while True:
        paused = obs_pauses.paused_seconds() - paused0
        if time.monotonic() - t0 - paused > max(net_of_pauses_s, 2 * paused):
            return
        time.sleep(0.05)


@pytest.fixture()
def stall_plane(clean_obs, tmp_path, monkeypatch):
    monkeypatch.setenv("BAGUA_OBS_DUMP_DIR", str(tmp_path))
    obs_pauses.uninstall()
    obs_spans.set_enabled(True)
    obs_spans.recorder.clear()
    yield tmp_path
    obs_pauses.uninstall()
    obs_spans.recorder.clear()
    obs_spans.set_current_step(None)
    obs_spans.set_enabled(None)


def test_a_sleep_in_the_caller_reads_as_caller_with_the_sleeping_frame(
        stall_plane, monkeypatch):
    """The loop outside ``train_step`` stalls: dominant phase ``caller``,
    nothing of ours explains it, and the suspect, the ``step/stall`` span
    and the flight dump hold the stacks sampled WHILE it lasted — the
    sleeping frame among them."""
    t, s, b = _steady_trainer(monkeypatch)
    stalled = t._step_counter
    _stalled_caller(t.anomaly_detector.cut_s() + 1.0)
    s, loss = t.train_step(s, b)        # closes the stalled window
    float(loss)
    (suspect,) = [sp for sp in t.anomaly_detector.suspects
                  if sp["step"] == stalled]
    assert suspect["dominant_phase"] == "caller"
    assert suspect["phases"]["caller"] >= 1.0
    assert suspect["explained_s"] < 0.5 * suspect["phases"]["caller"]
    assert suspect["sampled_after_s"] >= obs_pauses.STALL_SAMPLE_MIN_S

    def sleeping(stacks):
        return any("_stalled_caller" in line and "test_anomaly.py" in line
                   for line in stacks["MainThread"])

    assert sleeping(suspect["stacks"])
    assert obs_pauses.HEARTBEAT_THREAD not in suspect["stacks"]
    assert all(len(lines) <= obs_pauses.STACK_FRAMES
               for lines in suspect["stacks"].values())
    (stall,) = [sp for sp in obs_spans.recorder.snapshot()
                if sp["name"] == "step/stall" and sp["step"] == stalled]
    assert sleeping(stall["attrs"]["stacks"])
    assert stall["attrs"]["dominant_phase"] == "caller"
    assert stall["dur_s"] == pytest.approx(suspect["step_dt"])
    (dump,) = [p for p in os.listdir(stall_plane)
               if p.startswith("flight_step_anomaly")]
    rec = json.load(open(stall_plane / dump))
    assert obs_recorder.validate_flight_record(rec) == []
    assert sleeping(rec["extra"]["straggler_suspect"]["stacks"])
    dumped = [sp for sp in rec["spans"] if sp["name"] == "step/stall"]
    assert dumped and sleeping(dumped[-1]["attrs"]["stacks"])
    # the step that built the program is still there, 60 spans later
    assert any(sp["name"] == "step/build" for sp in rec["spans"])


def test_a_collection_in_the_caller_reads_as_gc(stall_plane, monkeypatch):
    """The same stretch, spent in the collector: dominant phase ``gc``,
    with a ``host/gc`` span of generation 2 inside the stalled window."""
    padding = [[] for _ in range(300_000)]     # a heap worth collecting
    t, s, b = _steady_trainer(monkeypatch)
    stalled = t._step_counter
    target = 2 * t.anomaly_detector.cut_s() + 0.2
    before = obs_pauses.gc_seconds()
    while obs_pauses.gc_seconds() - before < target:
        gc.collect()
    s, loss = t.train_step(s, b)
    float(loss)
    del padding
    (suspect,) = [sp for sp in t.anomaly_detector.suspects
                  if sp["step"] == stalled]
    assert suspect["dominant_phase"] == "gc"
    assert suspect["phases"]["gc"] >= target
    assert suspect["explained_s"] >= target
    (stall,) = [sp for sp in obs_spans.recorder.snapshot()
                if sp["name"] == "step/stall" and sp["step"] == stalled]
    inside = [sp for sp in obs_spans.recorder.snapshot()
              if sp["name"] == "host/gc" and sp["attrs"]["generation"] == 2
              and sp["step"] == stalled]
    assert inside and all(sp["thread"] == "MainThread" for sp in inside)
    assert sum(sp["dur_s"] for sp in inside) <= stall["dur_s"]


def test_anomaly_off_knob(monkeypatch):
    import optax

    import golden
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    monkeypatch.setenv("BAGUA_OBS_ANOMALY", "off")
    loss_fn, params, _ = golden.golden_task()
    t = BaguaTrainer(loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                     mesh=build_mesh({"dp": 8}), autotune=False)
    assert t.anomaly_detector is None
