"""Efficiency plane (ISSUE 10): goodput/badput wall-clock ledger, MFU +
HBM memory accounting, fleet efficiency rollup.

The acceptance pins: ledger conservation (classes sum to wall-clock within
1% on an instrumented cpu-sim run), compile/migration windows attributed
(not dropped), rewind seconds matching grad-guard skip counts, the static
HBM footprint matching the BucketPlan avals exactly, cost-analysis caching
per step-cache key, metrics.jsonl rotation, the ledger CLI, and the fleet
snapshot's efficiency rollup."""

import json
import os
import time

import numpy as np
import optax
import pytest

import golden
from bagua_tpu import telemetry
from bagua_tpu.algorithms import GradientAllReduceAlgorithm
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.faults.inject import FaultSpec, fault_scope
from bagua_tpu.obs import export as obs_export
from bagua_tpu.obs import ledger as obs_ledger
from bagua_tpu.obs import memory as obs_memory
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.parallel.mesh import build_mesh

N_DEVICES = 8


@pytest.fixture()
def ledger_on():
    """Obs plane on, span sink installed, fresh ledger + summary; restored
    afterwards."""
    obs_spans.set_enabled(True)
    obs_spans.recorder.clear()
    obs_spans.set_current_step(None)
    obs_ledger.install()
    obs_ledger.ledger.reset()
    obs_export.reset_local_summary()
    yield obs_ledger.ledger
    obs_ledger.ledger.reset()
    obs_export.reset_local_summary()
    obs_spans.recorder.clear()
    obs_spans.set_current_step(None)
    obs_spans.set_enabled(None)


def _golden_trainer(**kw):
    loss_fn, params, batch = golden.golden_task()
    t = BaguaTrainer(loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                     mesh=build_mesh({"dp": N_DEVICES}), autotune=False, **kw)
    s = t.init(params)
    return t, s, t.shard_batch(batch)


def _conserved(report, tol=0.01):
    total = sum(report["classes"].values())
    assert total <= report["wall_s"] * (1 + tol) + 1e-6, report
    assert abs(total - report["wall_s"]) <= report["wall_s"] * tol + 1e-6, \
        report


# ---- ledger state machine (unit) ------------------------------------------


def test_ledger_unit_conservation_and_classes():
    led = obs_ledger.GoodputLedger()
    t0 = time.monotonic()
    time.sleep(0.03)
    # the measured wait, not the nominal one: the ledger anchors its wall
    # at this window's start, and a sleep that overshoots on a loaded host
    # would put the excess inside the step window but outside the wall
    led.note_class_window("checkpoint", time.monotonic() - t0)
    time.sleep(0.05)
    led.note_step_window(1, time.monotonic() - t0)  # window spans the save
    t1 = time.monotonic()
    time.sleep(0.02)
    led.note_step_window(2, time.monotonic() - t1)
    rep = led.report()
    _conserved(rep)
    assert rep["classes"]["checkpoint"] >= 0.03
    # the first window's productive part excludes the deducted save
    assert rep["classes"]["productive_step"] < rep["wall_s"] - 0.02
    assert rep["step_windows"] == 2
    assert 0.0 <= rep["goodput_fraction"] <= 1.0
    assert rep["worst_badput_class"] == "checkpoint"


def test_ledger_rewind_reclassification():
    led = obs_ledger.GoodputLedger()
    t0 = time.monotonic()
    time.sleep(0.02)
    led.note_step_window(7, time.monotonic() - t0)
    before = led.report()["classes"]["productive_step"]
    led.reclassify_step_rewind(7)
    rep = led.report()
    assert rep["classes"]["rewind"] == pytest.approx(before)
    assert rep["classes"]["productive_step"] == 0.0
    assert rep["rewind_windows"] == 1
    # a rewind for a never-recorded step falls back to the last window's
    # size estimate instead of dropping the event
    led.reclassify_step_rewind(99)
    assert led.report()["rewind_windows"] == 2


def test_ledger_window_classification_not_dropped():
    """A compile/migration window is attributed to its class — not dropped
    (the anomaly detector skips it; the ledger must not)."""
    led = obs_ledger.GoodputLedger()
    t0 = time.monotonic()
    time.sleep(0.03)
    led.note_step_window(1, time.monotonic() - t0, cls="compile")
    t1 = time.monotonic()
    time.sleep(0.02)
    led.note_step_window(2, time.monotonic() - t1, cls="state_migration")
    rep = led.report()
    _conserved(rep)
    assert rep["classes"]["compile"] >= 0.03
    assert rep["classes"]["state_migration"] >= 0.02
    assert rep["classes"]["productive_step"] == 0.0


def test_span_hook_feeds_checkpoint_class_once(ledger_on):
    """Mapped spans feed their class through the spans sink; a nested
    mapped span (ckpt/verify inside ckpt/restore) must not double-count."""
    with obs_spans.trace_span("ckpt/restore"):
        with obs_spans.trace_span("ckpt/verify"):
            time.sleep(0.03)
    # close the wall with a step window so the report has a denominator
    ledger_on.note_step_window(1, 0.001)
    rep = ledger_on.report()
    durs = {sp["name"]: sp["dur_s"] for sp in obs_spans.recorder.snapshot()}
    # the OUTER span owns the window; counting the nested verify too would
    # read ~(restore + verify)
    assert rep["classes"]["checkpoint"] == pytest.approx(
        durs["ckpt/restore"], rel=0.05), (rep, durs)
    assert rep["classes"]["checkpoint"] < (
        durs["ckpt/restore"] + durs["ckpt/verify"]) * 0.95, (rep, durs)
    _conserved(rep)


# ---- trainer integration: conservation on an instrumented run -------------


def test_trainer_run_conservation_compile_attributed(ledger_on):
    t, s, b = _golden_trainer()
    for _ in range(8):
        s, loss = t.train_step(s, b)
    float(loss)
    rep = ledger_on.report()
    _conserved(rep)
    # the first dispatch's trace+compile wall landed in `compile`, and the
    # steady-state steps in `productive_step` — neither dropped
    assert rep["classes"]["compile"] > 0.0, rep
    assert rep["classes"]["productive_step"] > 0.0, rep
    assert rep["step_windows"] >= 7
    summary = obs_export.local_obs_summary()
    assert 0.0 <= summary["goodput_fraction"] <= 1.0
    assert summary["worst_badput_class"] in obs_ledger.BADPUT_CLASSES
    assert set(summary["badput"]) <= set(obs_ledger.BADPUT_CLASSES)


def test_rewind_seconds_match_grad_guard_skips(ledger_on):
    before_skips = telemetry.counters.get("grad_guard/skipped_steps")
    with fault_scope(FaultSpec("grad.poison", step=4)):
        t, s, b = _golden_trainer(grad_guard="skip")
        for _ in range(8):
            s, loss = t.train_step(s, b)
        t.flush_grad_health()
    skips = telemetry.counters.get("grad_guard/skipped_steps") - before_skips
    rep = ledger_on.report()
    assert skips == 1
    assert rep["rewind_windows"] == skips
    assert rep["classes"]["rewind"] > 0.0
    _conserved(rep)


def test_state_migration_window_attributed(ledger_on):
    t, s, b = _golden_trainer()
    s, _ = t.train_step(s, b)

    def slow_identity(state):
        time.sleep(0.05)
        return state

    t._pending_state_migration = slow_identity
    s, _ = t.train_step(s, b)
    s, _ = t.train_step(s, b)  # close the migration step's window
    rep = ledger_on.report()
    assert rep["classes"]["state_migration"] >= 0.04, rep
    _conserved(rep)


def test_injected_stall_lands_in_stall_class(ledger_on):
    t, s, b = _golden_trainer()
    s, _ = t.train_step(s, b)
    t.note_injected_stall(0.05)
    s, _ = t.train_step(s, b)
    rep = ledger_on.report()
    assert rep["classes"]["stall"] >= 0.05
    _conserved(rep)


# ---- MFU + memory accounting ----------------------------------------------


def test_mfu_null_with_rationale_on_cpu_sim(ledger_on):
    t, s, b = _golden_trainer()
    for _ in range(2):
        s, _ = t.train_step(s, b)
    summary = obs_export.local_obs_summary()
    assert summary["mfu"] is None
    assert "peak-FLOPS" in summary["mfu_rationale"]
    rec = obs_export.last_mfu()
    assert rec["available"] is False and rec["rationale"]


def test_static_footprint_matches_bucket_plan_exactly(ledger_on):
    """The acceptance pin: under the flat-resident layout the footprint's
    params component equals the BucketPlan flats to the byte — a bucket
    whose update is sharded rests as 1/world of it a device."""
    t, s, b = _golden_trainer(flat_resident="on")
    s, _ = t.train_step(s, b)
    fp = obs_memory.static_footprint(t, s)
    plan_bytes = obs_memory.plan_flat_bytes(t._plan)
    sizes = [bs.padded_numel * np.dtype(bs.dtype).itemsize
             for bs in t._plan.buckets]
    assert plan_bytes == sum(sizes)
    ctx = t._ctx(t._plan)
    cut = [ctx.update_sharded(i) for i in range(len(sizes))]
    assert cut == [True]  # (424 elements in one packed flat: 8 divide it)
    assert fp["params_bytes"] == sum(
        n // N_DEVICES if c else n for n, c in zip(sizes, cut))
    assert fp["grad_flats_bytes"] == plan_bytes
    assert fp["flat_resident"] is True
    assert fp["total_bytes"] == (
        fp["params_bytes"] + fp["opt_state_bytes"]
        + fp["algo_state_bytes"] + fp["grad_flats_bytes"]
    )
    # the trainer published it into the summary + gauge on the first step
    summary = obs_export.local_obs_summary()
    assert summary["hbm_static_footprint_bytes"] == fp["total_bytes"]
    assert telemetry.counters.get("obs/hbm_static_footprint_bytes") \
        == fp["total_bytes"]


def test_live_memory_null_with_rationale_on_cpu(ledger_on):
    rec = obs_memory.live_memory_stats()
    assert rec["available"] is False
    assert rec["rationale"]
    t, s, b = _golden_trainer()
    t._observer._last_beacon_write = 0.0
    s, _ = t.train_step(s, b)  # beacon-cadence poll publishes the record
    summary = obs_export.local_obs_summary()
    assert "hbm_live_rationale" in summary


class _FakeDevice:
    device_kind, id = "TPU v5 lite", 0

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


#: the v5e after 365 BERT-Large steps (PERF.md, PR 23): the buffers' own
#: peak is the init staging mark; the step's activations sit in the
#: programs' reservation, which peak_bytes_in_use never shows
_V5E_STATS = {"bytes_in_use": 6_037_000_000, "peak_bytes_in_use": 7_455_000_000,
              "bytes_reserved": 5_550_000_000, "bytes_limit": 16_900_000_000}


@pytest.mark.parametrize("stats, peak", [
    (_V5E_STATS, 11_587_000_000),                       # buffers + reservation
    ({**_V5E_STATS, "bytes_reserved": 0}, 7_455_000_000),  # nothing has run yet
    ({"bytes_in_use": 5, "bytes_limit": 100}, 5),       # a sparse runtime
])
def test_live_memory_peak_sees_the_programs_reservation(stats, peak):
    assert obs_memory.peak_bytes(stats) == peak
    rec = obs_memory.live_memory_stats(_FakeDevice(stats))
    assert rec["available"] and rec["peak_bytes"] == peak
    assert rec["headroom_bytes"] == stats["bytes_limit"] - peak
    obs_export.note_hbm_live(rec)
    try:
        # the gauges keep their names and now carry the true mark
        assert telemetry.counters.get("obs/hbm_peak_bytes") == peak
        assert telemetry.counters.get("obs/hbm_headroom_bytes") \
            == stats["bytes_limit"] - peak
    finally:
        obs_export.reset_local_summary()


def test_memory_analysis_cached_per_step_key(ledger_on):
    t, s, b = _golden_trainer()
    s, _ = t.train_step(s, b)
    mem = t.step_memory_analysis(s, b)
    key = t._current_step_key
    assert key in t._memory_analysis_cache
    if mem is not None:  # jax-version-dependent surface
        assert mem.get("temp_size_in_bytes") is not None or mem


# ---- step_cost_analysis: caching + visible swallow-all --------------------


def test_cost_analysis_cached_per_step_key(ledger_on):
    t, s, b = _golden_trainer()
    s, _ = t.train_step(s, b)
    a1 = t.step_cost_analysis(s, b)
    key = t._current_step_key
    assert key in t._cost_analysis_cache
    # mutate the cache: a second call must come FROM the cache (no
    # re-lower/re-compile), so the sentinel shows up in its copy
    t._cost_analysis_cache[key]["__sentinel__"] = 1
    a2 = t.step_cost_analysis(s, b)
    assert a2.get("__sentinel__") == 1
    assert a1.keys() <= a2.keys()


def test_cost_analysis_unavailable_is_visible(ledger_on, caplog,
                                              monkeypatch):
    t, s, b = _golden_trainer()
    s, _ = t.train_step(s, b)
    key = t._current_step_key

    class _NoCostModel:
        def lower(self, *a, **kw):
            raise RuntimeError("no cost model on this backend")

    monkeypatch.setattr(t, "_get_step_fn", lambda: _NoCostModel())
    t._cost_analysis_cache.pop(key, None)
    before = telemetry.counters.get("obs/cost_analysis_unavailable")
    with caplog.at_level("WARNING", logger="bagua_tpu.core.backend"):
        assert t.step_cost_analysis(s, b) == {}
    assert telemetry.counters.get("obs/cost_analysis_unavailable") \
        == before + 1
    # warning (not info), naming the backend
    messages = [r.getMessage() for r in caplog.records]
    assert any("step_cost_analysis unavailable" in m and "cpu" in m
               for m in messages), messages
    # the {} is cached: repeat calls stay silent instead of re-counting
    assert t.step_cost_analysis(s, b) == {}
    assert telemetry.counters.get("obs/cost_analysis_unavailable") \
        == before + 1


# ---- exporter: ledger gauges + size-capped rotation -----------------------


def test_exporter_carries_ledger_gauges(ledger_on, tmp_path):
    t, s, b = _golden_trainer()
    for _ in range(3):
        s, _ = t.train_step(s, b)
    exporter = obs_export.MetricsExporter(str(tmp_path), interval_s=60)
    os.makedirs(str(tmp_path), exist_ok=True)
    rec = exporter.export_once()
    for cls in obs_ledger.LEDGER_CLASSES:
        name = f"obs/ledger/{cls}_s"
        assert obs_export.is_registered(name), name
        assert name in rec["counters"], name
    assert "obs/ledger/wall_s" in rec["counters"]
    assert 0.0 <= rec["counters"]["obs/goodput_fraction"] <= 1.0
    assert rec["obs"]["goodput_fraction"] is not None


def test_metrics_jsonl_rotation(ledger_on, tmp_path, monkeypatch):
    monkeypatch.setenv("BAGUA_OBS_EXPORT_MAX_BYTES", "1")
    obs_export.note_step(1, 0.01)
    exporter = obs_export.MetricsExporter(str(tmp_path), interval_s=60)
    exporter.export_once()
    assert (tmp_path / "metrics.jsonl").exists()
    assert not (tmp_path / "metrics.jsonl.1").exists()
    exporter.export_once()  # cap hit -> rotate, then append fresh
    assert (tmp_path / "metrics.jsonl.1").exists()
    assert len(open(tmp_path / "metrics.jsonl").read().splitlines()) == 1
    exporter.export_once()  # second rotation replaces the first
    assert len(open(tmp_path / "metrics.jsonl.1").read().splitlines()) == 1
    # unset cap -> unbounded append again
    monkeypatch.setenv("BAGUA_OBS_EXPORT_MAX_BYTES", "0")
    exporter.export_once()
    exporter.export_once()
    assert len(open(tmp_path / "metrics.jsonl").read().splitlines()) >= 2


# ---- CLI ------------------------------------------------------------------


def test_ledger_cli_report_and_check(ledger_on, tmp_path, capsys):
    t, s, b = _golden_trainer()
    for _ in range(4):
        s, _ = t.train_step(s, b)
    export_dir = tmp_path / "export"
    os.makedirs(export_dir)
    obs_export.MetricsExporter(str(export_dir), interval_s=60).export_once()
    rc = obs_ledger.main([str(export_dir), "--check"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "goodput" in out and "compile" in out
    assert "conservation holds" in out


def test_ledger_cli_no_input_fails(tmp_path, capsys):
    assert obs_ledger.main([str(tmp_path)]) == 2
    assert "no ledger gauges" in capsys.readouterr().err


def test_ledger_cli_check_catches_violation(tmp_path, capsys):
    """A hand-broken snapshot (classes exceed wall) must fail --check."""
    export_dir = tmp_path / "export"
    os.makedirs(export_dir)
    counters = {f"obs/ledger/{c}_s": 10.0 for c in obs_ledger.LEDGER_CLASSES}
    counters["obs/ledger/wall_s"] = 10.0  # 9 classes x 10s >> 10s wall
    counters["obs/goodput_fraction"] = 0.5
    with open(export_dir / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"rank": 0, "time_unix": 0.0,
                            "counters": counters}) + "\n")
    assert obs_ledger.main([str(export_dir), "--check"]) == 1
    assert "exceeds wall" in capsys.readouterr().err


# ---- fleet rollup ---------------------------------------------------------


def test_fleet_snapshot_efficiency_rollup(tmp_path):
    def summary(rank, gf, worst):
        return {"rank": rank, "step": 10, "goodput_fraction": gf,
                "badput": {worst: 1.0}, "worst_badput_class": worst}

    members = {
        0: {"obs": summary(0, 0.9, "compile")},
        1: {"obs": summary(1, 0.5, "rewind"), "grad_unhealthy": 2},
    }
    path = str(tmp_path / "fleet.json")
    assert obs_export.write_fleet_snapshot(path, 3, members)
    fleet = json.load(open(path))
    assert obs_export.validate_fleet_snapshot(fleet) == []
    eff = fleet["efficiency"]
    assert eff["goodput_fraction_mean"] == pytest.approx(0.7)
    assert eff["goodput_fraction_min"] == pytest.approx(0.5)
    assert eff["ranks"]["0"]["worst_badput_class"] == "compile"
    assert eff["ranks"]["1"]["worst_badput_class"] == "rewind"
    # a summary-less fleet still writes a valid (empty) rollup
    path2 = str(tmp_path / "fleet2.json")
    assert obs_export.write_fleet_snapshot(path2, 3, {0: None})
    fleet2 = json.load(open(path2))
    assert obs_export.validate_fleet_snapshot(fleet2) == []
    assert fleet2["efficiency"]["ranks"] == {}


# ---- timeline counter track -----------------------------------------------


def test_timeline_ledger_counter_track(ledger_on, tmp_path):
    from bagua_tpu.obs import timeline as obs_timeline

    t, s, b = _golden_trainer()
    for _ in range(4):
        s, _ = t.train_step(s, b)
    dump = str(tmp_path / "spans_rank0.json")
    obs_timeline.dump_span_ring(dump)
    rec = json.load(open(dump))
    assert rec["ledger"]["goodput_fraction"] is not None
    assert len(rec["ledger_samples"]) >= 3
    trace = obs_timeline.assemble_timeline([rec])
    assert obs_timeline.validate_timeline(trace) == []
    counter_events = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert counter_events, "ledger classes must render as a counter track"
    assert counter_events[0]["name"] == "ledger_s"
    assert set(counter_events[-1]["args"]) == set(
        c for c in obs_ledger.LEDGER_CLASSES if c != "idle_other")
    assert trace["metadata"]["ranks"]["0"]["ledger_samples"] >= 3
