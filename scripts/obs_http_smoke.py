#!/usr/bin/env python
"""CI smoke for the HTTP status plane (ISSUE 14): scrape the live
endpoints during a real cpu-sim training run and validate every surface.

What it proves, end-to-end in one process:

1. a short ``BaguaTrainer`` run on the 8-device virtual CPU mesh with the
   metrics exporter AND the HTTP server up;
2. ``GET /metrics`` DURING the run parses as Prometheus text, every
   series is registered with ``# HELP``/``# TYPE`` (none untyped), and
   the series set matches the concurrent on-disk ``metrics.prom``
   snapshot series-for-series (both render the same prepared snapshot);
3. ``GET /fleet`` returns a schema-valid ``bagua-obs-fleet-v1`` record
   (built by the production merge, trend-augmented by a live historian);
4. ``GET /history`` returns the historian's windowed samples + slope;
5. ``GET /healthz`` and ``GET /ledger`` answer.

Exit code 0 iff every check holds.  Usage:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/obs_http_smoke.py [--export-dir DIR] [--steps N]
"""

import argparse
import json
import os
import re
import sys
import tempfile
import time
import urllib.request

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))  # golden.py


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as rsp:
        return rsp.read().decode()


def _series(prom_text):
    return {line.split(" ", 1)[0] for line in prom_text.splitlines()
            if line and not line.startswith("#")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--export-dir", default=None)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args(argv)
    export_dir = args.export_dir or tempfile.mkdtemp(prefix="obs_http_")

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import optax

    import golden
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.obs import export as obs_export
    from bagua_tpu.obs.historian import Historian
    from bagua_tpu.obs.http import ObsHTTPServer
    from bagua_tpu.parallel.mesh import build_mesh

    failures = []

    def check(name, ok, detail=""):
        print(f"  {'ok  ' if ok else 'FAIL'} {name}" +
              (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            failures.append(name)

    historian = Historian(capacity=64, window_s=600.0)
    holder = {"record": None}
    server = ObsHTTPServer(port=0, fleet_provider=lambda: holder["record"],
                           historian=historian).start()
    exporter = obs_export.MetricsExporter(export_dir, interval_s=3600)
    os.makedirs(export_dir, exist_ok=True)
    try:
        loss_fn, params, batch = golden.golden_task()
        trainer = BaguaTrainer(
            loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
            mesh=build_mesh({"dp": 8}), autotune=False,
        )
        state = trainer.init(params)
        sharded = trainer.shard_batch(batch)
        loss = None
        for step in range(args.steps):
            state, loss = trainer.train_step(state, sharded)
            # the coordinator-side monitor tick, in miniature: merge the
            # local summary into a fleet record and trend-augment it
            summary = obs_export.local_obs_summary() or {}
            record = obs_export.build_fleet_record(
                0, {0: {"obs": dict(summary)}} if summary else {0: None})
            holder["record"] = historian.ingest(record)
            if step == args.steps // 2:
                # a mid-run scrape: the endpoint must serve while the
                # step loop is hot
                mid = _get(server.url + "/metrics")
                check("mid-run /metrics scrape parses",
                      "# TYPE" in mid and bool(_series(mid)))
            time.sleep(0.01)
        check("training run finite", loss is not None
              and bool(np.isfinite(float(loss))))

        # warm the self-accounting counters, then compare steady state
        _get(server.url + "/metrics")
        exporter.export_once()
        exporter.export_once()
        scraped = _get(server.url + "/metrics")
        on_disk = open(os.path.join(export_dir, "metrics.prom")).read()
        check("/metrics matches metrics.prom series-for-series",
              _series(scraped) == _series(on_disk),
              f"{len(_series(scraped))} series")
        check("no untyped series", "untyped" not in scraped)
        prom_names = {obs_export.prometheus_name(n)
                      for n in obs_export.METRIC_REGISTRY}
        unregistered = _series(scraped) - prom_names
        check("every scraped series is registered", not unregistered,
              ", ".join(sorted(unregistered)) or "all registered")
        helped = set(re.findall(r"^# HELP (\S+)", scraped, re.M))
        typed = set(re.findall(r"^# TYPE (\S+)", scraped, re.M))
        check("every series has HELP and TYPE",
              _series(scraped) <= helped and _series(scraped) <= typed)

        fleet = json.loads(_get(server.url + "/fleet"))
        problems = obs_export.validate_fleet_snapshot(fleet)
        check("/fleet is schema-valid bagua-obs-fleet-v1", not problems,
              "; ".join(problems) or fleet["schema"])

        history = json.loads(_get(server.url +
                                  "/history?metric=step&window=600"))
        entry = (history.get("ranks") or {}).get("0") or {}
        check("/history serves windowed samples",
              len(entry.get("samples") or []) >= 2
              and entry.get("rate_per_s") is not None,
              f"{len(entry.get('samples') or [])} samples")

        health = json.loads(_get(server.url + "/healthz"))
        check("/healthz ok", health.get("status") == "ok")
        json.loads(_get(server.url + "/ledger"))
        check("/ledger answers JSON", True)
    finally:
        server.stop()
    if failures:
        print(f"obs http smoke: {len(failures)} check(s) FAILED: "
              f"{failures}")
        return 1
    print("obs http smoke: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
