"""bench.py's achieved-rate sanity bound: physically impossible numbers must
raise (round 1 shipped a ~10x-inflated img/s from broken timing; the bound
exists so a measurement bug can never be recorded as a result again)."""

import sys

import pytest

sys.path.insert(0, "/root/repo")
import bench  # noqa: E402


class _FakeTrainer:
    """Quacks like BaguaTrainer for _perf_fields: fixed cost analysis."""

    def __init__(self, flops, nbytes):
        self._analysis = {"flops": flops, "bytes accessed": nbytes}

    def step_cost_analysis(self, state, batch):
        return self._analysis


@pytest.fixture
def v5e(monkeypatch):
    """_perf_fields judges a rate against the chip's peak; give it one."""
    monkeypatch.setattr(bench, "_device", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1})


def test_perf_fields_reports_rates(v5e):
    tr = _FakeTrainer(flops=1e12, nbytes=1e9)
    # 10 steps in 1 s -> 10 TFLOP/s, 10 GB/s: plausible everywhere
    fields = bench._perf_fields(tr, None, None, dt=1.0, timed=10)
    assert fields["tflops_achieved"] == 10.0
    assert fields["hbm_gbps"] == 10
    assert 0 < fields["mfu"] < 1
    assert 0 < fields["hbm_util"] < 1


def test_perf_fields_trips_on_impossible_compute(v5e):
    # 1e12 flops/step at 10000 steps/s -> 10,000 TFLOP/s/chip
    tr = _FakeTrainer(flops=1e12, nbytes=1.0)
    with pytest.raises(bench.BenchSanityError):
        bench._perf_fields(tr, None, None, dt=1.0, timed=10000)


def test_perf_fields_refuses_unmeasurable_device(monkeypatch):
    tr = _FakeTrainer(flops=1e12, nbytes=1e9)
    with pytest.raises(bench.BenchDeviceError, match="measure a TPU"):
        bench._perf_fields(tr, None, None, dt=1.0, timed=10)  # cpu
    monkeypatch.setattr(bench, "_device", lambda: {
        "platform": "tpu", "device_kind": "TPU v99", "n_devices": 1})
    with pytest.raises(bench.BenchDeviceError, match="peak table"):
        bench._perf_fields(tr, None, None, dt=1.0, timed=10)


def test_perf_fields_empty_analysis_is_silent():
    class _NoAnalysis:
        def step_cost_analysis(self, state, batch):
            return {}

    fields = bench._perf_fields(_NoAnalysis(), None, None, 1.0, 10)
    # only the methodology marker survives an empty cost analysis
    assert fields == {"timing": "min_of_2_windows_x10_steps"}


def test_emitted_records_name_their_device(capsys):
    import json

    rec = bench._emit({"metric": "m", "value": 1.0})
    assert rec["platform"] == "cpu" and rec["n_devices"] == 8
    assert json.loads(capsys.readouterr().out)["device_kind"] == \
        rec["device_kind"]


def test_bench_autotune_artifact_schema():
    """BENCH_AUTOTUNE.json (benchmarks/autotune_bench.py): the goodput-
    scored v2 search must have completed within the 24-window cap with
    every sample scored on goodput (one speed-scaled sample would poison
    best() across scales), and the tuned-vs-default A/B must carry the
    BENCH_FLAT-style honesty protocol — per-trial ratios + noise_bound —
    with the tuned config no worse than the default (or provably noise)."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_AUTOTUNE.json")
    assert os.path.exists(path), "run benchmarks/autotune_bench.py first"
    rec = json.load(open(path))
    assert rec["schema"] == "bagua-autotune-bench-v1"
    assert rec["platform"] == "cpu-sim" and rec["n_devices"] == 8

    search = rec["search"]
    assert search["completed"] is True
    assert search["goodput_scored"] is True
    assert search["window_cap"] == 24
    assert 0 < search["n_windows"] <= search["window_cap"]
    assert search["n_scored_samples"] >= 8
    # fleet-min goodput + bounded speed tiebreak lives in [0, 1 + 1e-4]
    assert search["score_trajectory"], search
    assert all(0.0 <= s <= 1.0 + 1e-3 for s in search["score_trajectory"])
    # the v2 space actually closed over the full knob set (two-tier mesh)
    for knob in ("bucket_size_2p", "is_hierarchical_reduce", "overlap",
                 "overlap_chunk_bytes_inter_2p", "compress_intra",
                 "compress_inter"):
        assert knob in search["space"], knob
    for fld in ("bucket_size", "is_hierarchical_reduce", "overlap",
                "compress_inter", "flat_resident"):
        assert fld in search["recommended"], fld

    ab = rec["ab"]
    assert isinstance(ab["trials"], list) and len(ab["trials"]) >= 3
    ratios = [r for r in ab["per_trial_goodput_ratios"] if r is not None]
    assert len(ratios) >= 3
    assert isinstance(ab["noise_bound"], bool)
    acc = rec["acceptance"]
    assert acc["n_windows_le_cap"] is True
    assert acc["goodput_scored"] is True
    assert acc["tuned_goodput_ge_baseline_or_noise_bound"] is True
    assert ab["tuned_ge_baseline"] or ab["noise_bound"], ab


def test_bench_flat_artifact_schema():
    """BENCH_FLAT.json (driver-visible artifact of bench.py --flat): the
    interleaved-A/B records must carry the full honesty protocol — the
    per-trial ratio spread and the noise_bound flag — plus the
    fused-optimizer compile audit, and the headline acceptance config
    (gradient_allreduce flat >= leaf on this host's cpu-sim mesh)."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_FLAT.json")
    assert os.path.exists(path), "run bench.py --flat (or benchmarks/" \
                                 "flat_resident_bench.py) first"
    records = json.load(open(path))
    by_metric = {r["metric"]: r for r in records}

    speedups = [r for r in records if r["metric"].startswith("flat_speedup_")]
    assert speedups, records
    for rec in speedups:
        assert isinstance(rec["per_trial_ratios"], list) and len(
            rec["per_trial_ratios"]) >= 3
        assert isinstance(rec["noise_bound"], bool)
        assert rec["faster_path"] in ("on", "off")
        assert rec["value"] > 0
    # each speedup has its paired throughput records with the A/B timing tag
    for rec in speedups:
        key = rec["metric"].removeprefix("flat_speedup_")
        family, accum = key.rsplit("_accum", 1)
        for mode in ("on", "off"):
            pair = [
                r for r in records
                if r.get("family") == family
                and str(r.get("accum_steps")) == accum
                and r.get("flat_resident") == mode
            ]
            assert pair, (family, accum, mode)
            assert "interleaved_ab" in pair[0]["timing"]

    # acceptance config: flat-resident >= leaf for gradient_allreduce
    # (median of interleaved trials; noise_bound records the spread)
    headline = by_metric["flat_speedup_gradient_allreduce_accum1"]
    assert headline["value"] >= 1.0 or headline["noise_bound"], headline

    # fused-optimizer compile audit: flat layout must SHRINK the program
    ratio = by_metric["flat_fused_adam_hlo_op_ratio"]
    assert ratio["flat_hlo_op_count"] < ratio["leaf_hlo_op_count"], ratio
    assert ratio["value"] < 1.0

    gate = by_metric["flat_resident_dispatch_gate"]
    assert "faster_path_by_config" in gate and gate["auto_default"]


def test_bench_hierarchical_artifact_schema():
    """BENCH_HIERARCHICAL.json (driver-visible artifact of
    benchmarks/hierarchical_bench.py): the two-level decomposition's
    acceptance signal — cross-slice (DCN-tier) bytes per step reduced to
    ~1/intra_size of the flat path's, exact jaxpr byte accounting — plus
    the interleaved-A/B honesty protocol on the throughput records and the
    null-with-rationale device-time split on cpu-sim."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_HIERARCHICAL.json")
    assert os.path.exists(path), "run benchmarks/hierarchical_bench.py first"
    records = json.load(open(path))
    by_metric = {r["metric"]: r for r in records}

    header = by_metric["hierarchical_bench_schema"]
    assert header["schema"] == "bagua-bench-hierarchical-v1"
    intra = header["mesh"]["intra"]
    assert intra > 1

    # the acceptance ratio, per family: two-tier DCN bytes ~ flat/intra.
    # allreduce is EXACT 1/intra (pure shard); zero can be below (the flat
    # path's gather legs all cross the boundary); bytegrad sits above (the
    # codec's per-rank min/max scales do not shrink with the shard) but
    # must still cut the slow link's bytes by >= 2x
    for family in ("gradient_allreduce", "zero", "bytegrad"):
        rec = by_metric[f"hierarchical_dcn_bytes_{family}"]
        assert rec["intra_size"] == intra
        assert rec["flat"]["dcn_bytes_per_step"] > 0
        assert rec["two_tier"]["dcn_bytes_per_step"] > 0
        if family == "gradient_allreduce":
            assert rec["value"] == pytest.approx(1.0 / intra, rel=0.01), rec
        else:
            assert rec["value"] <= 0.5, rec
        # the ICI tiers take over the bytes the slow link no longer moves
        assert rec["two_tier"]["ici_bytes_per_step"] > \
            rec["two_tier"]["dcn_bytes_per_step"]

    speedups = [r for r in records
                if r["metric"].startswith("hierarchical_speedup_")]
    assert len(speedups) == 3
    for rec in speedups:
        assert isinstance(rec["per_trial_ratios"], list) and len(
            rec["per_trial_ratios"]) >= 3
        assert isinstance(rec["noise_bound"], bool)
        assert rec["provenance"]  # cpu-sim honesty note

    tier_dev = by_metric["hierarchical_device_tier_seconds"]
    if tier_dev["device_comm_dcn_s_per_step"] is None:
        # cpu-sim: null-with-rationale, never a fabricated number
        assert tier_dev["rationale"]
    assert "obs/device_comm_dcn_s_per_step" in tier_dev["gauges"]


def test_bench_compress_artifact_schema():
    """BENCH_COMPRESS.json (driver-visible artifact of
    benchmarks/compressed_ring_bench.py): the compressed-ring acceptance
    signal — jaxpr-exact DCN wire bytes drop >= 3x for every 1-byte codec
    (and for bytegrad's fused form vs the full-precision-DCN two-level
    decomposition), the fused-vs-discrete honesty record, and the
    interleaved-A/B throughput protocol with cpu-sim provenance (no slow
    link there: the codec pays compute and saves no wire — a TPU record
    must gate or be noise-bound, a cpu-sim record must carry the
    rationale)."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_COMPRESS.json")
    assert os.path.exists(path), "run benchmarks/compressed_ring_bench.py"
    records = json.load(open(path))
    by_metric = {r["metric"]: r for r in records}

    header = by_metric["compress_bench_schema"]
    assert header["schema"] == "bagua-bench-compress-v1"
    assert header["mesh"]["intra"] > 1 and header["mesh"]["inter"] > 1

    # the acceptance ratios: EXACT jaxpr accounting, >= the 3x gate for
    # every 1-byte codec on the forced-compressed exact family AND for
    # bytegrad's native fused form
    for codec in ("minmax_uint8", "int8", "fp8_e4m3", "fp8_e5m2"):
        rec = by_metric[f"compress_dcn_reduction_{codec}"]
        assert rec["value"] >= rec["gate"] == 3.0, rec
        assert rec["compressed"]["dcn_bytes_per_step"] > 0
        assert rec["full_precision"]["dcn_bytes_per_step"] > \
            rec["compressed"]["dcn_bytes_per_step"]
    # the error-feedback codecs carry the steeper ISSUE-17 gate: bit-packed
    # signs (+f32 scale sidecar) and 1% top-k must push DCN >= 12x
    for codec in ("onebit_ef", "topk"):
        rec = by_metric[f"compress_dcn_reduction_{codec}"]
        assert rec["value"] >= rec["gate"] == 12.0, rec
        assert rec["compressed"]["dcn_bytes_per_step"] > 0
    assert by_metric["compress_dcn_reduction_topk"]["topk_ratio"] == 0.01
    bg = by_metric["compress_dcn_reduction_bytegrad"]
    assert bg["value"] >= bg["gate"] == 3.0, bg
    assert bg["codec"] == "minmax_uint8"

    # EF convergence separation: the compensated run matches the
    # uncompressed golden-task trajectory within the committed tolerance;
    # the residual-disabled control does NOT (its gap is the quantization
    # bias the residual exists to cancel — if the control also passed, the
    # task would be too easy to certify the codec)
    for codec in ("onebit_ef", "topk"):
        conv = by_metric[f"compress_ef_convergence_{codec}"]
        assert conv["value"] <= conv["tolerance"], conv
        assert conv["ef_off_gap"] > conv["tolerance"], conv
        assert conv["ef_off_gap"] > conv["value"], conv
        assert conv["steps"] >= 30

    # the honesty record: the discrete scatter-gather stage already moved
    # u8 across DCN — its ratio over the fused form is structural, small,
    # and NOT gated (but must be recorded, with both sides' raw bytes)
    honest = by_metric["compress_dcn_fused_vs_discrete_bytegrad"]
    assert honest["discrete_stage"]["dcn_bytes_per_step"] > 0
    assert honest["fused"]["dcn_bytes_per_step"] > 0
    assert "HONESTY" in honest["note"]

    speedups = [r for r in records
                if r["metric"].startswith("compress_speedup_")]
    assert len(speedups) == 2
    for rec in speedups:
        assert isinstance(rec["per_trial_ratios"], list) and len(
            rec["per_trial_ratios"]) >= 3
        assert isinstance(rec["noise_bound"], bool)
        if rec["platform"] == "tpu":
            # on real silicon the compressed hops must win or wash
            assert rec["value"] >= 1.0 or rec["noise_bound"], rec
        else:
            # cpu-sim: the inversion is expected and must be explained
            assert "cpu-sim" in rec["provenance"], rec

    tier_dev = by_metric["compress_device_tier_seconds"]
    if tier_dev["device_comm_dcn_s_per_step"] is None:
        assert tier_dev["rationale"]


def test_scale_bench_artifact_schema():
    """BENCH_SCALE.json (driver-visible artifact of scripts/scale_drill.py):
    the committed record must show the multi-process drill passing at >= 3
    world sizes with all four control-plane metrics recorded, and both
    identified coordinator bottlenecks measured before AND after their fix
    (regenerate with `python scripts/scale_drill.py`)."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_SCALE.json")
    assert os.path.exists(path), "run scripts/scale_drill.py first"
    record = json.load(open(path))
    assert record["schema"] == "bagua-bench-scale-v1"
    assert record["drill"] == "scale" and record["platform"] == "cpu-sim"
    worlds = record["worlds"]
    assert len(worlds) >= 3, sorted(worlds)
    for w, data in worlds.items():
        live = data["live"]
        # the four scaling signals, per world size
        assert live["cold_start_rendezvous_s"] > 0, w
        assert data["decision_latency"]["p99_ms"] > 0, w
        assert data["historian_ingest"]["records_per_s"] > 0, w
        assert data["http_fleet"]["p99_ms"] > 0, w
        for name, ok in live["checks"].items():
            assert ok is True, (w, name)
    # one world ran the FULL scenario (shaped collectives, shrink/regrow,
    # autopilot fence); the rest may be control-plane-only
    scenarios = {d["live"]["scenario"] for d in worlds.values()}
    assert "full" in scenarios
    # both coordinator bottlenecks: identified, fixed, before/after recorded
    storm = record["bottlenecks"]["tcp_store_listen_backlog"]
    assert storm["before"]["backlog"] == 5
    assert storm["after"]["backlog"] > 5
    assert storm["after"]["connect_p99_ms"] <= storm["before"]["connect_p99_ms"]
    assert storm["after"]["errors"] == 0
    cache = record["bottlenecks"]["fleet_json_rerender"]
    assert cache["after"]["requests_per_s"] >= cache["before"]["requests_per_s"]
    assert cache["after"]["errors"] == 0
    for name, ok in record["checks"].items():
        assert ok is True, name
    assert record["ok"] is True


def test_failover_drill_artifact_schema():
    """FAILOVER_DRILL.json (driver-visible artifact of
    scripts/failover_drill.py): the committed record must show the primary
    coordinator SIGKILLed mid-training at >= 32 ranks with the standby
    promoting inside the member lease TTL, ZERO healthy workers
    restarting, autopilot/historian state resuming (not resetting), plus
    the partition double-primary fence, armed store flakes, and member
    lease expiry all green (regenerate with
    `python scripts/failover_drill.py`)."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "FAILOVER_DRILL.json")
    assert os.path.exists(path), "run scripts/failover_drill.py first"
    record = json.load(open(path))
    assert record["schema"] == "bagua-failover-drill-v1"
    assert record["drill"] == "failover" and record["platform"] == "cpu-sim"
    scenarios = record["scenarios"]
    assert {"coordinator_failover", "partition_fence", "store_flake",
            "heartbeat_loss"} <= set(scenarios)
    kill = scenarios["coordinator_failover"]
    # the headline claim: a 32-rank fleet survives its coordinator dying
    assert kill["world"] >= 32
    assert 0 < kill["takeover_s"] <= kill["member_lease_ttl_s"]
    assert kill["checks"]["zero_worker_restarts"] is True
    assert kill["checks"]["no_stop_event"] is True
    assert kill["checks"]["epoch_unchanged"] is True
    assert kill["checks"]["autopilot_state_resumed"] is True
    assert kill["checks"]["historian_rings_resumed"] is True
    # the double-primary row: the thawed ex-primary must exit DEMOTED
    part = scenarios["partition_fence"]
    assert part["ex_primary_exit"] == 5
    assert part["checks"]["lease_stays_with_standby"] is True
    for name, ok in record["checks"].items():
        assert ok is True, name
    assert record["ok"] is True


def test_chaos_drill_artifact_schema():
    """CHAOS_DRILL.json (driver-visible artifact of scripts/chaos_drill.py):
    the committed record must cover the full fault matrix with every fault
    injected, detected, AND recovered — recovery paths can't rot silently
    (mirrors the BENCH_FLAT gate; regenerate with
    `python scripts/chaos_drill.py`)."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "CHAOS_DRILL.json")
    assert os.path.exists(path), "run scripts/chaos_drill.py first"
    record = json.load(open(path))
    assert record["drill"] == "chaos"
    assert record["platform"] == "cpu-sim" and record["n_devices"] == 8
    required = {
        "store_flake_retry",
        "heartbeat_loss_lease_expiry",
        "checkpoint_corruption_fallback_restore",
        "nan_grad_skip_loss_continuity",
        "grad_guard_on_goldens_unchanged",
        "collective_hang_watchdog_recovery",
        "straggler_throughput_degrades",
        "async_partition_staleness_catchup",
        "health_fence_flight_record",
        # the fleet autopilot's policy matrix (ISSUE 13): every rule
        # injected -> detected -> decided -> actuated -> recovered
        "autopilot_straggler_fence_resize",
        "autopilot_victim_retune_hint",
        "autopilot_slo_escalation_ladder",
        "autopilot_ckpt_quarantine",
        "autopilot_trend_rules",
        # ISSUE 15: the compress_dcn hint actuates the live DCN codec
        "autopilot_compress_actuates_codec",
        "autopilot_off_noop",
    }
    assert required <= set(record["faults"]), sorted(record["faults"])
    for name, fault in record["faults"].items():
        assert fault["injected"] is True, name
        assert fault["detected"] is True, (name, fault["details"])
        assert fault["recovered"] is True, (name, fault["details"])
    # observability plane (ISSUE 7): every fault-driven failure mode left a
    # schema-valid flight-recorder dump naming the firing fault point, and
    # the fence drill's coordinator-side fleet snapshot schema-validated
    flight_points = {
        "store_flake_retry": "store.op",
        "heartbeat_loss_lease_expiry": "elastic.heartbeat",
        "checkpoint_corruption_fallback_restore": "ckpt.write",
        "nan_grad_skip_loss_continuity": "grad.poison",
        "collective_hang_watchdog_recovery": "collective.hang",
        "straggler_throughput_degrades": "step.straggle",
        "async_partition_staleness_catchup": "async.partition",
    }
    for name, point in flight_points.items():
        flight = record["faults"][name]["flight_record"]
        assert flight["schema_valid"] is True, (name, flight)
        assert flight["fault_point"] == point, (name, flight)
    hang_flight = record["faults"]["collective_hang_watchdog_recovery"][
        "flight_record"]
    assert hang_flight["trigger"] == "watchdog_abort", hang_flight
    fence = record["faults"]["health_fence_flight_record"]
    assert fence["flight_record"]["trigger"] == "health_fence", fence
    assert fence["flight_record"]["schema_valid"] is True, fence
    assert fence["fleet_snapshot_valid"] is True, fence
    # the matrix-level verdict and the telemetry trail both recorded
    assert record["pass"] is True
    counters = record["counters"]
    for point in ("store.op", "elastic.heartbeat", "ckpt.write",
                  "grad.poison", "collective.hang", "step.straggle",
                  "async.partition"):
        assert counters.get(f"faults/{point}/fired", 0) >= 1, point
        assert counters.get(f"faults/{point}/recovered", 0) >= 1, point
    # the async robustness trail (ISSUE 6): rounds launched, partition
    # drops surfaced as missed boundaries, and the forced catch-up syncs
    for key in ("async/rounds_launched", "async/rounds_dropped",
                "async/missed_boundaries", "async/catchup_syncs"):
        assert counters.get(key, 0) >= 1, key
    # the flight recorder's own accounting (ISSUE 7)
    assert counters.get("obs/flight_dumps", 0) >= 1
    # the anomaly-detector extension (ISSUE 9): the straggler drill must
    # flag the slow window on BOTH sides of the fault — collective-
    # dominant on the gated peer, dispatch-dominant on the straggler
    # itself — and the fleet snapshot must name the straggling rank
    anomaly = record["faults"]["straggler_throughput_degrades"]["anomaly"]
    assert anomaly["victim_flagged"] is True, anomaly
    assert anomaly["victim_dominant_phase"] == "collective", anomaly
    assert anomaly["straggler_flagged"] is True, anomaly
    assert anomaly["straggler_dominant_phase"] == "dispatch", anomaly
    assert anomaly["fleet_names_straggler_rank"] == [1], anomaly
    assert anomaly["fleet_ok"] is True, anomaly
    assert counters.get("obs/step_anomalies", 0) >= 2
    straggler_flight = record["faults"]["straggler_throughput_degrades"][
        "flight_record"]
    assert straggler_flight["trigger"] == "step_anomaly", straggler_flight
    # and the fleet timeline assembled from the two legs' ring dumps is a
    # schema-valid, clock-aligned 2-rank Perfetto trace (anchored on the
    # legs' shared async/negotiate boundary steps)
    timeline = record["faults"]["straggler_throughput_degrades"]["timeline"]
    assert timeline["schema_valid"] is True, timeline
    assert timeline["aligned"] is True, timeline
    assert timeline["ranks"] == ["0", "1"], timeline
    assert timeline["anchor_spans_rank1"] >= 2, timeline
    # the efficiency plane (ISSUE 10): the rewind, catch-up, and
    # checkpoint-fallback drills each surfaced their badput class in the
    # goodput ledger — a recovery path that stopped feeding its class
    # would pass its recovery verdict yet fail here.  The mapping is the
    # producer's own (one source; a new ledger-checked drill can't
    # silently drop out of this gate).
    from bagua_tpu.obs.ledger import DRILL_BADPUT_EXPECTATIONS

    assert len(DRILL_BADPUT_EXPECTATIONS) >= 3
    for name, cls in DRILL_BADPUT_EXPECTATIONS.items():
        led = record["faults"][name]["ledger"]
        assert led["badput_class"] == cls, (name, led)
        assert led["surfaced"] is True, (name, led)
        assert led["delta_s"] > 0, (name, led)
    assert record["faults"]["nan_grad_skip_loss_continuity"]["ledger"][
        "rewind_windows_delta"] == 1
    # the fleet autopilot (ISSUE 13): every policy rule decided the right
    # action, each decision left an `autopilot_action` flight dump, the
    # escalation ladder walked its rungs IN ORDER, and the telemetry trail
    # recorded both the decisions and the actuations
    autopilot_decisions = {
        "autopilot_straggler_fence_resize": ["fence"],
        "autopilot_victim_retune_hint": ["retune_hint"],
        "autopilot_ckpt_quarantine": ["quarantine_storage"],
        # the historian trend rules (ISSUE 14): pre-OOM resize from the
        # shrinking-headroom window, compression-escalation hint from
        # sustained DCN dominance — both from historian windows only
        "autopilot_trend_rules": ["resize", "compress_dcn"],
    }
    for name, kinds in autopilot_decisions.items():
        fault = record["faults"][name]
        assert fault["decided_actions"] == kinds, (name, fault)
        assert fault["flight_record"]["trigger"] == "autopilot_action", name
        assert fault["flight_record"]["schema_valid"] is True, name
    # the wire-speed compression actuation (ISSUE 15): the compress_dcn
    # hint flipped a LIVE trainer's DCN codec through the autotune
    # check-in path, and the traced step's cross-slice wire bytes provably
    # dropped by at least the 3x acceptance ratio
    compress = record["faults"]["autopilot_compress_actuates_codec"]
    assert compress["dcn_reduction_ratio"] >= 3.0, compress
    assert compress["dcn_wire_bytes_after"] < \
        compress["dcn_wire_bytes_before"], compress
    ladder = record["faults"]["autopilot_slo_escalation_ladder"]
    assert ladder["ladder_order"] == [
        "retune_hint", "retune", "switch_family", "resize"], ladder
    assert ladder["flight_record"]["schema_valid"] is True, ladder
    # the off pin: BAGUA_AUTOPILOT=off leaves the compiled step (jaxpr-
    # identical across modes) and the coordinator path untouched
    off = record["faults"]["autopilot_off_noop"]
    assert off["jaxpr_identical"] is True, off
    for key in ("autopilot/decisions", "autopilot/actions_actuated",
                "autopilot/fences", "autopilot/retunes",
                "autopilot/family_switches", "autopilot/resizes",
                "autopilot/quarantines"):
        assert counters.get(key, 0) >= 1, key


def test_bench_trend_artifact_schema():
    """BENCH_TREND.json (driver-visible artifact of
    `python -m bagua_tpu.obs.regress`): the committed trend record must be
    schema-valid with every comparison carrying a verdict and the
    noise-bound honesty fields — the sentinel's output can't rot into an
    unreadable shape while ci.sh runs it advisory."""
    import json
    import os

    from bagua_tpu.obs.regress import validate_bench_trend

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_TREND.json")
    assert os.path.exists(path), "run python -m bagua_tpu.obs.regress first"
    record = json.load(open(path))
    assert validate_bench_trend(record) == [], validate_bench_trend(record)
    assert record["mode"] in ("quick_probe", "files")
    # the quick probe compares the BENCH_FLAT headline config; its paired
    # speedup comparison must be present and carry the tolerance the
    # committed record's own trial spread dictated
    metrics = {c["metric"] for c in record["comparisons"]}
    assert "flat_speedup_gradient_allreduce_accum1" in metrics
    for c in record["comparisons"]:
        assert c["tolerance"] >= 0.10 - 1e-9, c
        assert isinstance(c["noise_bound"], bool), c
    # advisory contract: a regression verdict is recorded, never hidden
    assert set(record["regressions"]) == {
        c["metric"] for c in record["comparisons"]
        if c["verdict"] == "regressed"
    }


def test_efficiency_artifact_schema():
    """EFFICIENCY.json (driver-visible artifact of
    benchmarks/efficiency_bench.py): the committed efficiency record must
    schema-validate, conserve its ledger (classes sum to wall within 1%),
    prove the instrumented badput classes were FED (compile, checkpoint,
    rewind), carry an exact internally-consistent HBM footprint, keep MFU
    null-with-rationale on cpu-sim, and embed direction-tagged trend
    records for the regress sentinel (regenerate with
    `JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
    python benchmarks/efficiency_bench.py`)."""
    import json
    import os

    from bagua_tpu.obs.ledger import validate_efficiency

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "EFFICIENCY.json")
    assert os.path.exists(path), "run benchmarks/efficiency_bench.py first"
    record = json.load(open(path))
    assert validate_efficiency(record) == [], validate_efficiency(record)
    assert record["platform"] == "cpu-sim" and record["n_devices"] == 8
    led = record["ledger"]
    classes = led["classes"]
    # conservation: every wall second accounted, within the 1% gate
    assert sum(classes.values()) <= led["wall_s"] * 1.01
    assert abs(sum(classes.values()) - led["wall_s"]) <= led["wall_s"] * 0.01
    # the instrumented run deliberately exercised these classes
    for cls in ("productive_step", "compile", "checkpoint", "rewind"):
        assert classes[cls] > 0, (cls, classes)
    assert led["rewind_windows"] == 1  # one seeded grad.poison skip
    assert 0.0 < led["goodput_fraction"] < 1.0
    # footprint: exact avals, internally consistent (the flat-vs-plan
    # byte-for-byte pin lives in tests/test_ledger.py)
    fp = record["footprint"]
    assert fp["total_bytes"] == (fp["params_bytes"] + fp["opt_state_bytes"]
                                 + fp["algo_state_bytes"]
                                 + fp["grad_flats_bytes"])
    assert fp["params_bytes"] > 0 and fp["grad_flats_bytes"] > 0
    # MFU on cpu-sim: null-with-rationale, never a fabricated number
    assert record["mfu"]["available"] is False
    assert record["mfu"]["rationale"]
    # trend records carry explicit directions for the sentinel
    by_metric = {r["metric"]: r for r in record["trend_records"]}
    assert by_metric["efficiency_goodput_fraction"]["higher_better"] is True
    assert by_metric["efficiency_goodput_fraction"]["noise_bound"] is True
    footprint_rec = by_metric["efficiency_hbm_static_footprint_bytes"]
    assert footprint_rec["higher_better"] is False
    assert footprint_rec["noise_bound"] is False
    assert footprint_rec["value"] == fp["total_bytes"]


def test_serve_bench_artifact_schema():
    """BENCH_SERVE.json (driver-visible artifact of
    benchmarks/serve_bench.py): the serving plane's acceptance record —
    TTFT/TPOT percentiles from the Poisson trace, the continuous-vs-static
    throughput A/B under the _ab.py honesty protocol with the >=1.3x gate
    (or an honest noise_bound flag + in-file provenance), and the serving
    goodput-ledger classes proven FED (prefill, decode, weight_load all
    carry real wall; regenerate with
    `JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
    python benchmarks/serve_bench.py`)."""
    import json
    import os

    from bagua_tpu.serve import SERVE_SPEEDUP_GATE, validate_serve_bench

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_SERVE.json")
    assert os.path.exists(path), "run benchmarks/serve_bench.py first"
    records = json.load(open(path))
    assert validate_serve_bench(records) == [], validate_serve_bench(records)
    by_metric = {r["metric"]: r for r in records}

    header = by_metric["serve_bench_schema"]
    assert header["platform"] == "cpu-sim" and header["n_devices"] == 8
    assert header["smoke"] is False, "commit the full trace, not --smoke"
    # mixed lengths are the point of the trace (uniform traffic would
    # flatter static batching)
    lo, hi = header["trace"]["output_range"]
    assert hi - lo >= 8, header["trace"]

    # the acceptance ratio: continuous >= 1.3x static token throughput on
    # the mixed-length backlog, or an honest noise-bound flag
    speedup = by_metric["serve_continuous_over_static_throughput"]
    assert speedup["value"] >= SERVE_SPEEDUP_GATE or \
        speedup["noise_bound"], speedup
    assert len(speedup["per_trial_ratios"]) >= 3
    assert speedup["provenance"]
    assert speedup["gate"] == SERVE_SPEEDUP_GATE

    # latency percentiles ordered sanely
    lat = by_metric["serve_latency"]
    for field in ("ttft_s", "tpot_s"):
        pct = lat[field]
        assert pct["p50"] <= pct["p90"] <= pct["p99"], (field, pct)

    # the serving ledger classes were fed by real walls (the engine's
    # spans + the integrity-verified weight load)
    led = by_metric["serve_ledger_classes"]
    for cls in ("prefill", "decode", "weight_load"):
        assert led["classes"][cls] > 0, (cls, led)
    assert 0.0 < led["goodput_fraction"] <= 1.0
    # the engine's own counters rode along: every admitted request
    # completed (the backpressure paths queue/preempt, never drop)
    counts = header["counters"]
    assert counts["serve/requests_completed"] >= \
        header["trace"]["n_latency_requests"]
    assert counts.get("serve/requests_admitted", 0) >= \
        counts["serve/requests_completed"]


def test_straggler_bench_artifact_schema():
    """BENCH_STRAGGLER.json (driver-visible artifact of
    benchmarks/straggler_bench.py): under the seeded 10× single-rank
    straggler, async model averaging must retain >= 1.5x the throughput of
    synchronous allreduce on the 8-dev cpu-sim mesh — per-trial ratios and
    the noise_bound flag recorded per _ab.py conventions (regenerate with
    `python benchmarks/straggler_bench.py`)."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_STRAGGLER.json")
    assert os.path.exists(path), "run benchmarks/straggler_bench.py first"
    records = json.load(open(path))
    by_metric = {r["metric"]: r for r in records}

    headline = by_metric["straggler_async_over_sync_throughput"]
    assert headline["value"] >= 1.5, headline
    assert headline["noise_bound"] is False, headline
    assert len(headline["per_trial_ratios"]) >= 3
    assert min(headline["per_trial_ratios"]) >= 1.5, headline
    assert headline["straggler"]["factor"] == 10.0
    # both sides measured under the SAME armed fault
    for side in ("straggler_sync_allreduce_straggled_steps_per_sec",
                 "straggler_async_straggled_steps_per_sec"):
        assert by_metric[side]["straggler"]["factor"] == 10.0, side
    # the clean pair attributes the ratio to the fault, not to a baseline
    # throughput gap between the families
    assert "straggler_clean_async_over_sync" in by_metric
