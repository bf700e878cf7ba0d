"""The drills' committed records (BENCH_SCALE.json, FAILOVER_DRILL.json,
CHAOS_DRILL.json): each must show its drill passing with the verdicts the
drill scripts under ``scripts/`` write (regenerate with the script each
docstring names)."""


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
    (regenerate with `python scripts/chaos_drill.py`)."""
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
