"""Step-time anomaly detection (ISSUE 9): warmup grace, MAD robustness,
dump throttling, the straggler_suspect beacon payload, perf hints, the
coordinator-side straggler naming, and the trainer integration."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bagua_tpu import telemetry  # noqa: E402
from bagua_tpu.obs import anomaly as an  # noqa: E402
from bagua_tpu.obs import export as obs_export  # noqa: E402
from bagua_tpu.obs import recorder as obs_recorder  # noqa: E402


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
    assert set(s["phases"]) == {"dispatch", "collective", "optimizer",
                                "other"}
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
