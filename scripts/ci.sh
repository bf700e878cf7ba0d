#!/usr/bin/env bash
# CI gate — the analog of the reference's .buildkite/pipeline.yml
# (pytest job + benchmark gates + lint workflows).  Runs entirely on the
# virtual CPU mesh unless RUN_TPU_BENCH=1.
#
# Usage:  bash scripts/ci.sh            # lint + compile + tests + goldens
#         RUN_TPU_BENCH=1 bash scripts/ci.sh   # + one cell of perfbench/ on the chip
set -euo pipefail
cd "$(dirname "$0")/.."

echo "=== lint (syntax) ==="
python -m compileall -q bagua_tpu tests examples __graft_entry__.py

echo "=== bagua-lint (AST + jaxpr + concurrency + trace-coherence engines) ==="
# All four engines (--engine all is the default): AST hot-path rules, the
# jaxpr collective-consistency sweep, the host-concurrency race detector
# (lock-order inversions, unguarded shared writes, lock-held IO,
# signal-unsafe locking), and the step-cache-key coherence prover (every
# knob that shapes the traced step must ride _step_key; ISSUE 18).
# Fails on any unsuppressed finding not in the shrink-only baseline (stale
# baseline entries fail too — the baseline can only shrink), and proves
# overlap-vs-serialized collective-multiset equality for the algorithm
# families at accum_steps 1 and 4 — including the hierarchical two-level
# configs (family:hier on a 2-slice x 4-chip mesh: intra reduce-scatter,
# inter allreduce on the 1/intra shard, intra allgather; ISSUE 11) and
# the compressed-ring configs (bytegrad:hier-compressed + forced
# int8/fp8 DCN codecs: quantized ppermute payloads with their f32
# sidecars must emit identical multisets streamed vs serialized;
# ISSUE 15).  The historical torch-import gate is now the `torch-import`
# rule.  See docs/analysis.md, docs/hierarchical.md, docs/compression.md.
JAX_PLATFORMS=cpu \
python -m bagua_tpu.analysis bagua_tpu/ --baseline .bagua-lint-baseline.json

echo "=== generated docs in sync (API reference + env-var table) ==="
JAX_PLATFORMS=cpu python scripts/gen_api_docs.py --check
JAX_PLATFORMS=cpu python scripts/gen_env_docs.py --check

echo "=== obs smoke trace (flight recorder on one live drill) ==="
# One drill from the chaos matrix with the observability plane on: the
# drill itself asserts its flight-recorder dump exists, schema-validates,
# names the firing fault point, and surfaces its badput class in the
# goodput ledger (exit code carries the verdict).  The full-matrix
# CHAOS_DRILL.json is schema-gated in tests/test_drill_records.py.
OBS_TMP="$(mktemp -d)"
BAGUA_OBS_EXPORT_DIR="$OBS_TMP/export" BAGUA_OBS_EXPORT_INTERVAL_S=1 \
python scripts/chaos_drill.py --only nan_grad_skip_loss_continuity \
  --dump-dir "$OBS_TMP/dumps"

echo "=== lockdep witness (chaos smoke under BAGUA_LOCKDEP=on) ==="
# The same drill re-run with the runtime lockdep shim recording every real
# lock acquisition order, then cross-checked against the static
# acquisition graph: zero runtime inversions (a live deadlock window the
# drill actually exercised) and every witnessed edge between known locks
# present in the static model (witness ⊆ static — the concurrency
# engine's 'no cycle' verdicts are only trustworthy if it saw every real
# ordering).  See docs/analysis.md, ISSUE 18.
BAGUA_LOCKDEP=on BAGUA_LOCKDEP_OUT="$OBS_TMP/lockdep_witness.json" \
BAGUA_OBS_EXPORT_DIR="$OBS_TMP/export2" BAGUA_OBS_EXPORT_INTERVAL_S=1 \
python scripts/chaos_drill.py --only nan_grad_skip_loss_continuity \
  --dump-dir "$OBS_TMP/dumps2"
JAX_PLATFORMS=cpu \
python -m bagua_tpu.analysis bagua_tpu/ --engine concurrency \
  --witness "$OBS_TMP/lockdep_witness.json" \
  --baseline .bagua-lint-baseline.json

echo "=== obs HTTP plane smoke (live /metrics + /fleet scrape) ==="
# The HTTP status plane scraped DURING a live cpu-sim training run: the
# /metrics scrape must parse as fully registered+typed Prometheus text
# and match the concurrent on-disk metrics.prom series-for-series, and
# /fleet must validate against the bagua-obs-fleet-v1 schema with the
# historian's trend augmentation aboard (ISSUE 14).
python scripts/obs_http_smoke.py --export-dir "$OBS_TMP/http_export"

echo "=== fleet timeline from the drill's flight dumps ==="
# The dumps the smoke trace just wrote must assemble into a schema-valid,
# clock-aligned Perfetto trace — the analysis layer's own end-to-end gate.
python -m bagua_tpu.obs.timeline "$OBS_TMP/dumps" \
  --out "$OBS_TMP/timeline.json" --check

echo "=== goodput ledger over the smoke trace's metrics export ==="
# The drill's exporter wrote metrics.jsonl with the ledger gauges aboard;
# the CLI renders the per-run report and gates conservation (every class
# second accounted, classes sum to wall within 1%).
python -m bagua_tpu.obs.ledger "$OBS_TMP/export" \
  --flight "$OBS_TMP/dumps" --check
rm -rf "$OBS_TMP"

echo "=== autopilot replay smoke (policy engine over a recorded fleet stream) ==="
# The coordinator-side policy matrix in observe mode over the committed
# fleet snapshot stream: the decided action plan (fence -> retune hint ->
# two SLO ladder rungs -> storage quarantine) must match the committed
# expectation exactly — a policy change that re-orders or drops an action
# fails here before it ships.  Full matrix actuation is chaos-drilled in
# CHAOS_DRILL.json (schema-gated in test_drill_records.py); operators can
# replay their own streams with `python -m bagua_tpu.autopilot --replay`.
python -m bagua_tpu.autopilot \
  --replay tests/data/autopilot_fleet_stream.jsonl \
  --expect tests/data/autopilot_expected_plan.json \
  --sustain 2 --cooldown-s 0 --budget 8 --slo-goodput 0.5 \
  --straggler-ratio 3.0 --ckpt-failures 3 --family async > /dev/null

echo "=== autopilot trend-rule replay (historian windows close the loop) ==="
# The historian-backed trend rules over the committed synthetic stream
# (ISSUE 14): the shrinking-HBM-headroom rank decides the pre-OOM
# resize, the DCN-dominant rank decides the compression-escalation
# hint, and the flat control rank decides NOTHING — and without
# --historian the same stream is provably inert (the rules fire only
# from historian trend windows, gated in tests/test_autopilot.py).
python -m bagua_tpu.autopilot \
  --replay tests/data/autopilot_trend_stream.jsonl \
  --expect tests/data/autopilot_trend_plan.json \
  --historian --trend-window-s 600 \
  --sustain 2 --cooldown-s 300 --budget 8 > /dev/null

echo "=== autotune v2 smoke (goodput-scored search round, cpu mesh) ==="
# One live v2 search round: a real trainer on the two-tier cpu-sim mesh
# checks in with windowed goodput observations, the sidecar builds the
# capability-gated knob space from the registration capabilities, and the
# scored window MUST be fleet-min-goodput-scored (not summed speed).
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python scripts/autotune_smoke.py --ci > /dev/null

echo "=== scale smoke (4-process loopback pod drill) ==="
# The pod simulator end to end with REAL worker processes: cold-start
# rendezvous through the restart TCPStore, shaped hierarchical+compressed
# collectives over loopback rings, lease-expiry shrink, standby regrow,
# and an autopilot straggler fence — the full coordinator lifecycle at
# world 4 under a tight timeout.  The committed 32/64/128-rank sweep
# (BENCH_SCALE.json) is schema-gated in tests/test_drill_records.py;
# regenerate it with `python scripts/scale_drill.py`.
timeout -k 10 120 python scripts/scale_drill.py --smoke > /dev/null

echo "=== failover smoke (SIGKILL the live coordinator process) ==="
# Coordinator failover end to end with REAL processes: a replicated
# restart store (primary + follower servers, op-log replication,
# generation fence), a killable coordinator renewing the leadership
# lease, a standby watching it, and 4 workers mid-collective.  The drill
# SIGKILLs the primary and asserts the standby promotes within the
# member lease TTL, ZERO workers restart, and the autopilot/historian
# state RESUMES from the replicated store.  The committed 32-rank fault
# matrix (FAILOVER_DRILL.json) is schema-gated in
# tests/test_drill_records.py; regenerate with
# `python scripts/failover_drill.py`.
timeout -k 10 150 python scripts/failover_drill.py --smoke > /dev/null

echo "=== compressed-ring smoke (1-bit EF codec over the loopback pod) ==="
# The stateful ISSUE-17 wire format end to end over real sockets: the same
# 4-process drill with the DCN stage forced onto bit-packed sign payloads
# + mean-abs sidecars (the numpy mirror of the jax codec) — the workers'
# transport-integrity bounds must hold and the verdict records the codec.
# The jaxpr-exact >=12x DCN byte pins and the EF convergence separation
# are asserted live in tests/test_compressed_ring.py and tests/test_ef_residual.py.
timeout -k 10 120 env BAGUA_SCALE_DCN_CODEC=onebit_ef \
  python scripts/scale_drill.py --smoke > /dev/null

echo "=== chaos fast subset (fault injection -> detection -> recovery) ==="
# The deterministic slice of scripts/chaos_drill.py: every injection point
# fires, every detector sees it, every recovery completes.  The committed
# CHAOS_DRILL.json full-matrix record is schema-gated in
# tests/test_drill_records.py; regenerate it with scripts/chaos_drill.py.
python -m pytest tests/test_faults.py -q

echo "=== unit + integration tests (8-device CPU mesh) ==="
# test_faults.py already ran as the named chaos gate above
python -m pytest tests/ -q --ignore=tests/test_faults.py

echo "=== multichip dryrun (virtual CPU mesh) ==="
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun OK')"

if [[ "${RUN_TPU_BENCH:-0}" == "1" ]]; then
  echo "=== chip smoke, then one cell of the benchmark (BENCHMARK.json) ==="
  python chip_smoke.py
  python3 perfbench/run.py --workload bert-large.squad384-dp1 --seed 0 --trace 0
fi

echo "CI green"
