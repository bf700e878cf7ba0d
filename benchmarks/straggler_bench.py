"""Straggler benchmark: sync allreduce vs async model averaging under a
seeded 10× single-rank straggler (ISSUE 6).

The Bagua paper's case for asynchronous model averaging is exactly this
scenario: one slow host in an otherwise healthy fleet.  A synchronous
family pays the straggler on EVERY step (the per-step gradient collective
gates on the slowest rank); the async family's train steps run free on
stale local weights and gate on the straggler only at its negotiated
boundaries (one per ``period_steps``).  The ``step.straggle`` fault point
models that faithfully on the single-process cpu-sim mesh: the armed
straggler is a *peer* rank, so the stall lands wherever the calling code
path genuinely synchronizes with it — per step for sync families
(``Algorithm.straggler_gates_step``), per boundary for async.

The dilation base is pinned (``base_ms`` = the measured clean sync step
time) so the injected delay is deterministic AND proportional to what the
workload actually costs; ``factor=10`` is the acceptance scenario.

Timing is the interleaved A/B best-of-trials protocol shared with the
other paired benchmarks (benchmarks/_ab.py); a clean (no-straggler) pair
is recorded alongside so the straggled ratio is attributable to the fault,
not to a baseline throughput gap between the families.

Usage: python benchmarks/straggler_bench.py [--out BENCH_STRAGGLER.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

STRAGGLE_FACTOR = 10.0
STRAGGLER_RANK = 1   # a PEER of this process (rank 0): the stall lands
#                      only where the code path gates on the slow rank
PERIOD_STEPS = 5     # async negotiated boundary cadence (deterministic)
TIMED_STEPS = 30
WARMUP_STEPS = 3


def _task():
    import jax
    import jax.numpy as jnp
    import optax

    from bagua_tpu.models.mlp import MLP

    n_dev = len(jax.devices())
    model = MLP(features=(256, 256, 8))
    x = jax.random.normal(jax.random.PRNGKey(0), (8 * n_dev, 64))
    y = jnp.argmax(
        x @ jax.random.normal(jax.random.PRNGKey(1), (64, 8)), -1
    )
    params = model.init(jax.random.PRNGKey(2), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    return loss_fn, params, {"x": x, "y": y}


def _trainer(family: str):
    import jax
    import optax

    from bagua_tpu.algorithms import (
        AsyncModelAverageAlgorithm,
        GradientAllReduceAlgorithm,
    )
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    if family == "sync_allreduce":
        algo = GradientAllReduceAlgorithm()
    else:
        algo = AsyncModelAverageAlgorithm(
            warmup_steps=0, period_steps=PERIOD_STEPS
        )
    loss_fn, params, batch = _task()
    trainer = BaguaTrainer(
        loss_fn, optax.sgd(0.1), algo,
        mesh=build_mesh({"dp": len(jax.devices())}), autotune=False,
    )
    state = trainer.init(params)
    data = trainer.shard_batch(batch)
    return trainer, state, data


def _clean_step_ms() -> float:
    """The straggler's base step time: the measured clean sync step, so
    the injected 10× dilation is proportional to real workload cost."""
    trainer, state, data = _trainer("sync_allreduce")
    for _ in range(WARMUP_STEPS):
        state, loss = trainer.train_step(state, data)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        state, loss = trainer.train_step(state, data)
    float(loss)
    return (time.perf_counter() - t0) / TIMED_STEPS * 1000.0


def measure(family: str, base_ms: float, straggle: bool) -> dict:
    """One record: steps/s for one family, with or without the armed 10×
    peer straggler."""
    import contextlib

    from bagua_tpu.faults.inject import FaultSpec, fault_scope

    trainer, state, data = _trainer(family)
    cm = (
        fault_scope(FaultSpec("step.straggle", rank=STRAGGLER_RANK,
                              count=-1, base_ms=base_ms,
                              factor=STRAGGLE_FACTOR))
        if straggle else contextlib.nullcontext()
    )
    with cm:
        for _ in range(WARMUP_STEPS):
            state, loss = trainer.train_step(state, data)
        float(loss)  # drain before the timer starts
        t0 = time.perf_counter()
        for _ in range(TIMED_STEPS):
            state, loss = trainer.train_step(state, data)
        float(loss)  # force the chained steps to completion
        dt = time.perf_counter() - t0
    algo = trainer.algorithm
    if hasattr(algo, "barrier"):
        state = algo.barrier(trainer, state)
    tag = "straggled" if straggle else "clean"
    return {
        "metric": f"straggler_{family}_{tag}_steps_per_sec",
        "value": round(TIMED_STEPS / dt, 2),
        "unit": "steps/s",
        "family": family,
        "straggler": (
            {"rank": STRAGGLER_RANK, "factor": STRAGGLE_FACTOR,
             "base_ms": round(base_ms, 2)} if straggle else None
        ),
        "timing": f"best_of_trials_x{TIMED_STEPS}_steps",
    }


def run_suite(out_path: str = "BENCH_STRAGGLER.json") -> list:
    import jax

    from benchmarks._ab import interleaved_ab, speedup_record

    records = []

    def emit(rec):
        print(json.dumps(rec), flush=True)
        records.append(rec)
        return rec

    base_ms = _clean_step_ms()
    trials = 5

    # ---- clean baseline pair: attribute the straggled ratio honestly ----
    sync_c, async_c, clean_ratios = interleaved_ab(
        lambda: measure("sync_allreduce", base_ms, straggle=False),
        lambda: measure("async", base_ms, straggle=False),
        trials=trials,
    )
    emit(sync_c)
    emit(async_c)
    emit(speedup_record(
        "straggler_clean_async_over_sync", clean_ratios, "async/sync",
        platform=jax.devices()[0].platform,
    ))

    # ---- the acceptance scenario: 10× single-rank straggler -------------
    sync_s, async_s, ratios = interleaved_ab(
        lambda: measure("sync_allreduce", base_ms, straggle=True),
        lambda: measure("async", base_ms, straggle=True),
        trials=trials,
    )
    emit(sync_s)
    emit(async_s)
    emit(speedup_record(
        "straggler_async_over_sync_throughput", ratios, "async/sync",
        platform=jax.devices()[0].platform,
        straggler={"rank": STRAGGLER_RANK, "factor": STRAGGLE_FACTOR,
                   "base_ms": round(base_ms, 2)},
        async_period_steps=PERIOD_STEPS,
        provenance=(
            "sync families gate on the straggler at EVERY step's gradient "
            "collective; async model averaging gates only at its "
            f"negotiated boundary (every {PERIOD_STEPS} steps) and its "
            "train steps run on stale local weights — the Bagua paper's "
            "system-relaxation trade.  Acceptance: async retains >= 1.5x "
            "sync throughput under the seeded 10x single-rank straggler."
        ),
    ))
    with open(out_path, "w") as f:
        json.dump(records, f, indent=1)
        f.write("\n")
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_STRAGGLER.json")
    args = ap.parse_args()
    from bagua_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    jax.config.update("jax_platforms", "cpu")
    run_suite(args.out)


if __name__ == "__main__":
    main()
