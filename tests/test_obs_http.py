"""HTTP status plane (ISSUE 14): per-process pull endpoints — /metrics
identical series-for-series with the exporter's metrics.prom, registered
HELP/TYPE on every series, /healthz, /ledger, and the coordinator's
/fleet + /history — plus the "still free" guards: concurrent scrapes
during a live cpu-sim training run, the jaxpr pin with historian+HTTP
enabled, and the span-overhead budget re-asserted with both on."""

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request

import optax
import pytest

import golden
from bagua_tpu import telemetry
from bagua_tpu.algorithms import GradientAllReduceAlgorithm
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.obs import export as obs_export
from bagua_tpu.obs import http as obs_http
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.obs.historian import Historian
from bagua_tpu.obs.http import ObsHTTPServer
from bagua_tpu.parallel.mesh import build_mesh
from bagua_tpu.podsim.util import reserve_port

N_DEVICES = 8
NOW = 1_754_000_000.0


@pytest.fixture()
def server():
    srv = ObsHTTPServer(port=0).start()
    yield srv
    srv.stop()


def _get(srv, path):
    with urllib.request.urlopen(srv.url + path, timeout=10) as rsp:
        return rsp.status, rsp.headers.get("Content-Type", ""), \
            rsp.read().decode()


def _series(prom_text):
    """Sample-line metric names of a Prometheus exposition text."""
    return {line.split(" ", 1)[0] for line in prom_text.splitlines()
            if line and not line.startswith("#")}


def _golden_trainer(**kw):
    loss_fn, params, batch = golden.golden_task()
    t = BaguaTrainer(loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                     mesh=build_mesh({"dp": N_DEVICES}), autotune=False, **kw)
    s = t.init(params)
    return t, s, t.shard_batch(batch)


# ---- /metrics: the Prometheus surface -------------------------------------


def test_metrics_scrape_parses_with_help_and_type(server):
    """Satellite gate: every exposed series carries the registry's
    # HELP/# TYPE lines, is a registered metric, and none export as
    untyped — the table cannot drift from the live endpoint."""
    telemetry.counters.incr("comm/abort_resets")
    status, ctype, text = _get(server, "/metrics")
    assert status == 200
    assert ctype.startswith("text/plain")
    assert "untyped" not in text
    helped = set()
    typed = set()
    for line in text.splitlines():
        m = re.match(r"# (HELP|TYPE) (\S+)", line)
        if m:
            (helped if m.group(1) == "HELP" else typed).add(m.group(2))
            continue
        if not line:
            continue
        name, _, value = line.partition(" ")
        float(value)  # sample lines parse
        assert name in helped, f"{name} has no # HELP"
        assert name in typed, f"{name} has no # TYPE"
    # reverse-map: every sample series is a registered metric
    prom_names = {obs_export.prometheus_name(n)
                  for n in obs_export.METRIC_REGISTRY}
    for name in _series(text):
        assert name in prom_names, f"{name} not in METRIC_REGISTRY"


def test_metrics_scrape_matches_prom_file_series_for_series(server,
                                                           tmp_path):
    """Acceptance pin: a live /metrics scrape exposes the identical
    series set as the concurrent metrics.prom snapshot (both render the
    same prepared snapshot)."""
    exporter = obs_export.MetricsExporter(str(tmp_path), interval_s=3600)
    os.makedirs(str(tmp_path), exist_ok=True)
    # warm the self-accounting counters so their first appearance is
    # behind us (obs/http_requests via a scrape, obs/export_snapshots via
    # an export), then compare steady state
    _get(server, "/metrics")
    exporter.export_once()
    exporter.export_once()
    _, _, scraped = _get(server, "/metrics")
    on_disk = open(tmp_path / "metrics.prom").read()
    assert _series(scraped) == _series(on_disk)
    # and both carry the typed header block for each series
    for text in (scraped, on_disk):
        assert "# TYPE bagua_obs_export_snapshots counter" in text
        assert "# TYPE bagua_obs_http_requests counter" in text


def test_scrapes_count_requests(server):
    before = telemetry.counters.get("obs/http_requests")
    _get(server, "/metrics")
    _get(server, "/healthz")
    assert telemetry.counters.get("obs/http_requests") == before + 2


# ---- the JSON routes -------------------------------------------------------


def test_healthz_and_ledger_routes(server):
    status, ctype, body = _get(server, "/healthz")
    assert status == 200 and ctype.startswith("application/json")
    payload = json.loads(body)
    assert payload["status"] == "ok"
    assert isinstance(payload["rank"], int)
    status, _, body = _get(server, "/ledger")
    assert status == 200
    json.loads(body)  # report or null-with-rationale, always JSON


def test_unknown_route_404(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        _get(server, "/nope")
    assert e.value.code == 404


def test_fleet_and_history_absent_on_worker_processes(server):
    """A worker's server has no fleet provider / historian: the
    coordinator-only routes answer 404, not garbage."""
    for path in ("/fleet", "/history?metric=step"):
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(server, path)
        assert e.value.code == 404


def test_fleet_and_history_routes_on_coordinator():
    h = Historian(capacity=32, window_s=600.0)
    holder = {}

    def snap(i):
        return {
            "schema": "bagua-obs-fleet-v1", "time_unix": NOW + i,
            "epoch": 1, "nnodes": 1,
            "ranks": {"1": {"health": {}, "obs": {"1": {
                "rank": 1, "step": 50 + i, "goodput_fraction": 0.9,
                "hbm_headroom_bytes": 4e9 - i * 2e8}}}},
            "efficiency": {"ranks": {}, "goodput_fraction_min": 0.9,
                           "goodput_fraction_mean": 0.9},
        }

    for i in range(6):
        holder["record"] = h.ingest(snap(i))
    srv = ObsHTTPServer(port=0, fleet_provider=lambda: holder.get("record"),
                        historian=h).start()
    try:
        _, _, body = _get(srv, "/fleet")
        fleet = json.loads(body)
        assert obs_export.validate_fleet_snapshot(fleet) == []
        # the served record carries the historian's trend augmentation
        assert "trends" in fleet["ranks"]["1"]["obs"]["1"]
        _, _, body = _get(srv, "/history?metric=hbm_headroom_bytes")
        report = json.loads(body)
        assert report["ranks"]["1"]["slope_per_s"] == pytest.approx(-2e8)
        _, _, body = _get(srv,
                          "/history?metric=step&rank=1&window=2.5")
        report = json.loads(body)
        assert len(report["ranks"]["1"]["samples"]) == 3
        # missing metric= -> 400 listing the series
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv, "/history")
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv, "/history?metric=step&window=abc")
        assert e.value.code == 400
    finally:
        srv.stop()


def test_fleet_render_cache_tracks_record_identity():
    """/fleet serializes once per record OBJECT, not once per request —
    and a new record object (the monitor builds one per tick) must bust
    the cache.  cache_fleet_json=False restores the per-request path
    (used by scale_drill's before/after bench) with identical bodies."""
    rec_a = {"schema": "bagua-obs-fleet-v1", "time_unix": NOW, "marker": "a"}
    rec_b = {"schema": "bagua-obs-fleet-v1", "time_unix": NOW + 1,
             "marker": "b"}
    holder = {"record": rec_a}
    for cached in (True, False):
        srv = ObsHTTPServer(port=0, fleet_provider=lambda: holder["record"],
                            cache_fleet_json=cached).start()
        try:
            holder["record"] = rec_a
            _, _, body1 = _get(srv, "/fleet")
            _, _, body2 = _get(srv, "/fleet")
            assert body1 == body2
            assert json.loads(body1)["marker"] == "a"
            holder["record"] = rec_b  # fresh object -> fresh render
            _, _, body3 = _get(srv, "/fleet")
            assert json.loads(body3)["marker"] == "b"
        finally:
            srv.stop()


# ---- bring-up / gating -----------------------------------------------------


def test_disabled_by_default(monkeypatch):
    monkeypatch.delenv("BAGUA_OBS_HTTP_PORT", raising=False)
    monkeypatch.setattr(obs_http, "_GLOBAL_SERVER", None)
    assert obs_http.maybe_start_global_http_server() is None


def test_global_server_starts_once_and_attaches_hooks(monkeypatch):
    monkeypatch.setenv("BAGUA_OBS_HTTP_PORT", "0")
    assert obs_http.maybe_start_global_http_server() is None  # 0 = off
    # an ephemeral-but-on port, reserved so parallel tests can't steal it
    free_port = reserve_port()
    monkeypatch.setenv("BAGUA_OBS_HTTP_PORT", str(free_port))
    monkeypatch.setattr(obs_http, "_GLOBAL_SERVER", None)
    try:
        srv = obs_http.maybe_start_global_http_server()
        assert srv is not None and srv.port == free_port
        again = obs_http.maybe_start_global_http_server(
            fleet_provider=lambda: {"schema": "bagua-obs-fleet-v1"})
        assert again is srv  # one server per process; hooks attach late
        _, _, body = _get(srv, "/fleet")
        assert json.loads(body)["schema"] == "bagua-obs-fleet-v1"
        assert telemetry.counters.get("obs/http_port") == free_port
    finally:
        if obs_http._GLOBAL_SERVER is not None:
            obs_http._GLOBAL_SERVER.stop()
        monkeypatch.setattr(obs_http, "_GLOBAL_SERVER", None)


def test_unbindable_addr_falls_back_to_loopback():
    """A mistyped BAGUA_OBS_HTTP_ADDR must degrade to loopback-ephemeral,
    never kill bring-up (the trainer constructs servers unconditionally
    when the port knob is set)."""
    srv = ObsHTTPServer(port=0, addr="203.0.113.254").start()  # TEST-NET-3
    try:
        assert srv.addr == "127.0.0.1"
        status, _, _ = _get(srv, "/healthz")
        assert status == 200
    finally:
        srv.stop()


def test_stop_clears_global_server_slot(monkeypatch):
    """run_elastic's teardown stops the global server; a later bring-up
    in the same process must get a LIVE server, not the dead socket."""
    free_port = reserve_port()
    monkeypatch.setenv("BAGUA_OBS_HTTP_PORT", str(free_port))
    monkeypatch.setattr(obs_http, "_GLOBAL_SERVER", None)
    try:
        first = obs_http.maybe_start_global_http_server()
        first.stop()
        assert obs_http._GLOBAL_SERVER is None
        second = obs_http.maybe_start_global_http_server()
        assert second is not None and second is not first
        status, _, _ = _get(second, "/healthz")
        assert status == 200
    finally:
        if obs_http._GLOBAL_SERVER is not None:
            obs_http._GLOBAL_SERVER.stop()
        monkeypatch.setattr(obs_http, "_GLOBAL_SERVER", None)


def test_taken_port_falls_back_to_ephemeral():
    first = ObsHTTPServer(port=0).start()
    try:
        second = ObsHTTPServer(port=first.port).start()
        try:
            assert second.port != first.port
            status, _, _ = _get(second, "/healthz")
            assert status == 200
        finally:
            second.stop()
    finally:
        first.stop()


def test_launcher_offsets_worker_ports(monkeypatch):
    from bagua_tpu.distributed.run import build_env, parse_args

    args = parse_args(["--nnodes", "1", "script.py"])
    monkeypatch.delenv("BAGUA_OBS_HTTP_PORT", raising=False)
    assert "BAGUA_OBS_HTTP_PORT" not in build_env(args, 0)
    monkeypatch.setenv("BAGUA_OBS_HTTP_PORT", "9300")
    assert build_env(args, 0)["BAGUA_OBS_HTTP_PORT"] == "9301"
    assert build_env(args, 3)["BAGUA_OBS_HTTP_PORT"] == "9304"


# ---- load + "still free" guards -------------------------------------------


def test_concurrent_scrapes_during_live_training(server):
    """The load satellite: N scraper threads hammer /metrics and
    /healthz while a real cpu-sim training run steps; every scrape
    parses, the run's losses stay finite, and nothing deadlocks."""
    import numpy as np

    t, s, b = _golden_trainer()
    errors = []
    stop = threading.Event()
    counts = [0] * 4

    def scraper(i):
        while not stop.is_set():
            try:
                _, _, text = _get(server, "/metrics")
                assert "# TYPE" in text
                _get(server, "/healthz")
                counts[i] += 1
            except Exception as e:  # noqa: BLE001 - collected for assert
                errors.append(e)
                return

    threads = [threading.Thread(target=scraper, args=(i,), daemon=True)
               for i in range(4)]
    for th in threads:
        th.start()
    try:
        for _ in range(12):
            s, loss = t.train_step(s, b)
        assert np.isfinite(float(loss))
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=10)
    assert not errors, errors
    assert sum(counts) >= 4  # every scraper made progress


_ADDR = re.compile(r" at 0x[0-9a-fA-F]+")


def test_step_program_identical_with_http_and_historian(monkeypatch):
    """Acceptance pin: the compiled step is jaxpr-identical with the HTTP
    plane + historian enabled vs the default-off state — both are
    host-side by construction and must never reach the traced program."""
    def traced(on):
        if on:
            monkeypatch.setenv("BAGUA_OBS_HTTP_PORT", "0")  # routes exist,
            monkeypatch.setenv("BAGUA_OBS_HISTORIAN", "on")  # server off
        else:
            monkeypatch.delenv("BAGUA_OBS_HTTP_PORT", raising=False)
            monkeypatch.delenv("BAGUA_OBS_HISTORIAN", raising=False)
        t, s, b = _golden_trainer()
        return _ADDR.sub("", str(t.trace_step(s, b)))

    srv = ObsHTTPServer(port=0).start()  # a LIVE server during the trace
    try:
        assert traced(True) == traced(False)
    finally:
        srv.stop()


def test_span_overhead_budget_with_http_and_historian(monkeypatch):
    """The span-overhead budget (tests/span_budget.py: exact count per
    steady step, absolute ceiling per span) re-asserted with the HTTP
    server serving and the historian ingesting in-process (ISSUE 7's gate
    must survive ISSUE 14's additions)."""
    from span_budget import assert_span_budget

    obs_spans.set_enabled(True)
    srv = ObsHTTPServer(port=0).start()
    historian = Historian(capacity=64, window_s=600.0)
    try:
        t, s, b = _golden_trainer()
        # the ring is the process's: full of an earlier test's spans, its length
        # no longer moves and the slice below would be empty
        obs_spans.recorder.clear()
        before = len(obs_spans.recorder.snapshot())
        for i in range(5):
            s, loss = t.train_step(s, b)
            historian.ingest({
                "schema": "bagua-obs-fleet-v1", "time_unix": NOW + i,
                "epoch": 0, "nnodes": 1,
                "ranks": {"0": {"health": {}, "obs": {"0": {
                    "rank": 0, "step": i, "goodput_fraction": 0.9}}}},
                "efficiency": {"ranks": {}},
            })
        float(loss)
        assert_span_budget(t, obs_spans.recorder.snapshot()[before:])
    finally:
        srv.stop()
        obs_spans.recorder.clear()
        obs_spans.set_enabled(None)
