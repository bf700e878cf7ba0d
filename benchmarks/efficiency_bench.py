"""Efficiency bench — the EFFICIENCY.json artifact for the headline config.

Runs the BENCH_FLAT headline configuration (gradient_allreduce, accum 1,
flat-resident where supported) with the observability plane on and harvests
the **efficiency plane** (docs/observability.md):

* the goodput ledger's class breakdown + goodput fraction over an
  instrumented window that deliberately exercises badput: the initial
  trace+compile window, one mid-run checkpoint save, and one grad-guard
  rewind (seeded ``grad.poison``) — so the committed record proves each
  class is *fed*, not merely declared;
* the static per-device HBM footprint (``obs.memory.static_footprint`` —
  exact on cpu-sim: under the flat-resident layout the params component
  equals the ``BucketPlan`` flats to the byte, pinned in
  ``tests/test_ledger.py``);
* the per-step-cache ``memory_analysis()`` and the MFU record
  (null-with-rationale on cpu-sim, measured on real TPU).

The record (schema ``bagua-efficiency-v1``, validated by
``bagua_tpu.obs.ledger.validate_efficiency`` and gated in
``tests/test_bench_sanity.py``) embeds ``trend_records`` with explicit
``higher_better`` directions so the bench-trend sentinel
(``python -m bagua_tpu.obs.regress``) can watch goodput erosion and —
deterministically — HBM footprint bloat.

Usage (cpu-sim artifact, the committed configuration)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/efficiency_bench.py [--out EFFICIENCY.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: the headline config (BENCH_FLAT's acceptance pair)
FAMILY = "gradient_allreduce"
STEPS = 40
QUICK_STEPS = 12


def measure_efficiency(steps: int = STEPS, quick: bool = False) -> dict:
    """One instrumented run of the headline config; returns the raw pieces
    (ledger report, footprint, mfu, memory analysis, context)."""
    import jax
    import optax

    import bench
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.checkpoint import BaguaCheckpointManager
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.faults.inject import FaultSpec, fault_scope
    from bagua_tpu.obs import export as obs_export
    from bagua_tpu.obs import ledger as obs_ledger
    from bagua_tpu.obs import spans as obs_spans
    from bagua_tpu.obs.memory import static_footprint
    from bagua_tpu.parallel.mesh import build_mesh

    if not obs_spans.enabled():
        raise RuntimeError("efficiency bench needs BAGUA_OBS=on")
    loss_fn, params, batch = bench.golden_task()
    mesh = build_mesh({"dp": len(jax.devices())})
    obs_export.reset_local_summary()
    obs_ledger.ledger.reset()  # the measured window starts clean
    poison_step = max(3, steps // 2)
    with fault_scope(FaultSpec("grad.poison", step=poison_step)):
        trainer = BaguaTrainer(
            loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
            mesh=mesh, autotune=False, grad_guard="skip",
            flat_resident="auto",
        )
        state = trainer.init(params)
        data = trainer.shard_batch(batch)
        ckpt_at = max(2, steps // 3)
        with tempfile.TemporaryDirectory(prefix="eff_ckpt_") as tmp:
            mgr = BaguaCheckpointManager(os.path.join(tmp, "ckpt"),
                                         async_save=False)
            loss = None
            for i in range(steps):
                state, loss = trainer.train_step(state, data)
                if i == ckpt_at:
                    # one mid-run checkpoint: the ledger's checkpoint
                    # class must be fed by a real save wall
                    mgr.save(i, trainer.unstack_params(state))
            float(loss)
            trainer.flush_grad_health()
            mgr.close()
        ledger_report = obs_ledger.ledger.report()
        footprint = static_footprint(trainer, state)
        memory_analysis = trainer.step_memory_analysis(state, data)
        mfu = obs_export.last_mfu() or {
            "available": False,
            "rationale": "trainer published no MFU record",
        }
    return {
        "ledger": ledger_report,
        "footprint": footprint,
        "memory_analysis": memory_analysis,
        "mfu": mfu,
        "steps": steps,
        "quick": quick,
        "platform": ("cpu-sim" if jax.devices()[0].platform == "cpu"
                     else jax.devices()[0].platform),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
    }


def efficiency_trend_records(quick: bool = False) -> list:
    """The regress-consumable slice: goodput fraction (higher better;
    noise_bound — wall-clock class splits vary run to run on shared CI
    hosts) and the static HBM footprint (lower better; deterministic, so a
    memory bloat WILL flag)."""
    raw = measure_efficiency(steps=QUICK_STEPS if quick else STEPS,
                             quick=quick)
    return _trend_records(raw)


def _trend_records(raw: dict) -> list:
    return [
        {
            "metric": "efficiency_goodput_fraction",
            "value": raw["ledger"]["goodput_fraction"],
            "unit": "fraction",
            "higher_better": True,
            # honest flag: a short window's compile share dominates the
            # split and varies with host load — the sentinel may report
            # noise_bound, never a false `regressed`
            "noise_bound": True,
            "steps": raw["steps"],
        },
        {
            "metric": "efficiency_hbm_static_footprint_bytes",
            "value": raw["footprint"]["total_bytes"],
            "unit": "bytes",
            "higher_better": False,
            "noise_bound": False,  # exact: avals, not timing
        },
    ]


def build_efficiency_record(raw: dict) -> dict:
    from bagua_tpu.obs.ledger import EFFICIENCY_SCHEMA

    return {
        "schema": EFFICIENCY_SCHEMA,
        "time_unix": time.time(),
        "platform": raw["platform"],
        "device_kind": raw["device_kind"],
        "n_devices": raw["n_devices"],
        "config": {
            "family": FAMILY,
            "accum_steps": 1,
            "flat_resident": "auto",
            "grad_guard": "skip",
            "steps": raw["steps"],
            "badput_drills": ["compile_window", "checkpoint_save",
                              "grad_poison_rewind"],
        },
        "ledger": raw["ledger"],
        "footprint": raw["footprint"],
        "memory_analysis": raw["memory_analysis"],
        "memory_analysis_rationale": (
            None if raw["memory_analysis"] else
            "backend exposes no compiled-step memory_analysis"
        ),
        "mfu": raw["mfu"],
        "trend_records": _trend_records(raw),
    }


def main(argv=None) -> int:
    # no persistent compile cache here: the ledger's `compile` class is
    # part of the measured attribution
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(REPO, "EFFICIENCY.json"))
    ap.add_argument("--steps", type=int, default=STEPS)
    args = ap.parse_args(argv)

    raw = measure_efficiency(steps=args.steps)
    record = build_efficiency_record(raw)

    from bagua_tpu.obs.ledger import validate_efficiency

    problems = validate_efficiency(record)
    if problems:
        print(f"refusing to write an invalid record: {problems}",
              file=sys.stderr)
        return 1
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")
    led = record["ledger"]
    print(json.dumps({"metric": "efficiency_goodput_fraction",
                      "value": led["goodput_fraction"],
                      "wall_s": led["wall_s"],
                      "worst_badput_class": led["worst_badput_class"],
                      "footprint_bytes":
                          record["footprint"]["total_bytes"]}))
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
