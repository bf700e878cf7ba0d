"""Flat-resident layout benchmark — honest flat-vs-leaf measurement (ISSUE 4).

Measures the flat-resident training-state layout end-to-end: throughput
with ``flat_resident="on"`` (params/grads/opt state live as bucket flats
across steps) vs the ``flat_resident="off"`` leaf-pytree construction —
the layouts train bit-identical trajectories (tests/test_flat_resident.py),
so the comparison is purely "what does the per-step leaf<->flat round trip
cost".  Timing is the interleaved A/B best-of-trials protocol shared with
``overlap_bench`` (see benchmarks/_ab.py), reusing its per-platform
workloads (ResNet50 on TPU, the multi-bucket MLP on the cpu-sim mesh).

Also records the compile-audit pair for the fused-on-flats optimizer step
(``compile_audit.audit_fused_optimizer_layouts``): HLO op count + compile
time, leaf vs flat, on a deep many-leaf model.

Usage: python benchmarks/flat_resident_bench.py [--out BENCH_FLAT.json]
Prints one JSON line per record; ``bench.py --flat`` drives the same path.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure(family: str, accum: int, flat_resident: str,
            repeats: int = 1) -> dict:
    """One record: throughput for one (family, accum, layout) config."""
    import jax

    import bench
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh
    from benchmarks.overlap_bench import _TIMED, _algorithm, _workload

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    mesh = build_mesh({"dp": n_dev})
    timed, rows_per_chip = _TIMED.get(platform, _TIMED["cpu"])
    loss_fn, params, batch, bucket_bytes = _workload(
        platform if platform == "tpu" else "cpu", n_dev, accum
    )
    algo, opt = _algorithm(family)
    trainer = BaguaTrainer(
        loss_fn, opt, algo, mesh=mesh, autotune=False, accum_steps=accum,
        bucket_bytes=bucket_bytes, flat_resident=flat_resident,
        # measure the layouts, not the overlap scheduler: serialized comm
        # on both sides so the single moving part is state residency
        overlap="off",
    )
    state = trainer.init(params)
    data = trainer.shard_batch(batch)
    dt = None
    for _ in range(max(1, repeats)):
        w, state, _ = bench._time_steps(trainer, state, data, timed=timed,
                                        warmup=2)
        dt = w if dt is None else min(dt, w)
    samples = rows_per_chip * n_dev * accum
    per_chip = timed * samples / dt / n_dev
    model = "resnet50" if platform == "tpu" else "mlp_256x256"
    unit = "img/s/chip" if platform == "tpu" else "samples/s/chip"
    return {
        "metric": f"flat_{model}_{family}_accum{accum}_{flat_resident}",
        "value": round(per_chip, 1),
        "unit": unit,
        "flat_resident": flat_resident,
        "accum_steps": accum,
        "family": family,
        "model": model,
        "platform": platform,
        "timing": f"best_of_{repeats}_trials_min_of_2_windows_x{timed}_steps",
    }


#: (family, accum_steps) configs compared flat-on vs flat-off.  zero's
#: "off" side is the leaf ZeRO layout — the original measured ~7%
#: leaf->flat->leaf round trip this machinery was built to remove.
CONFIGS = [
    ("gradient_allreduce", 1),
    ("gradient_allreduce", 4),
    ("zero", 1),
    ("bytegrad", 1),
]


def run_suite(out_path: str = "BENCH_FLAT.json") -> list:
    from benchmarks._ab import interleaved_ab, speedup_record
    from benchmarks.compile_audit import audit_fused_optimizer_layouts

    records = []

    def emit(rec):
        print(json.dumps(rec), flush=True)
        records.append(rec)
        return rec

    gate = {}
    trials = 5
    for family, accum in CONFIGS:
        off, on, ratios = interleaved_ab(
            lambda: measure(family, accum, "off", repeats=1),
            lambda: measure(family, accum, "on", repeats=1),
            trials=trials,
        )
        emit(off)
        emit(on)
        faster = "on" if float(np.median(ratios)) >= 1.0 else "off"
        gate[f"{family}_accum{accum}"] = faster
        emit(speedup_record(
            f"flat_speedup_{family}_accum{accum}", ratios, "flat/leaf",
            faster_path=faster, platform=on["platform"],
        ))

    # compile-size audit: the fused optimizer step, leaf vs flat layouts
    audits = audit_fused_optimizer_layouts()
    for rec in audits:
        emit(rec)
    leaf, flat = audits[0], audits[1]
    emit({
        "metric": "flat_fused_adam_hlo_op_ratio",
        "value": round(flat["hlo_op_count"] / leaf["hlo_op_count"], 3),
        "unit": "x (flat/leaf StableHLO op count, fused-adam step, "
                f"{leaf['param_leaves']} param leaves)",
        "leaf_hlo_op_count": leaf["hlo_op_count"],
        "flat_hlo_op_count": flat["hlo_op_count"],
        "leaf_compile_s": leaf["compile_s"],
        "flat_compile_s": flat["compile_s"],
    })

    emit({
        "metric": "flat_resident_dispatch_gate",
        "value": None,
        "unit": None,
        "faster_path_by_config": gate,
        "auto_default": "flat_resident='auto' engages the resident layout "
                        "for every supports_flat_resident family on a "
                        "pure-dp mesh (gradient_allreduce, bytegrad, qadam, "
                        "decentralized, low_precision_decentralized, zero); "
                        "model-parallel (tp/pp/expert) compositions keep "
                        "the leaf layout",
        "gate_provenance": "flat and leaf layouts are bit-equal in "
                           "trajectory (tests/test_flat_resident.py); "
                           "residency is a STATE LAYOUT (checkpoints, "
                           "state access), so auto engages per family, "
                           "not per config.  Repeated runs on this "
                           "host's cpu-sim mesh: allreduce and zero at "
                           "accum=1 measured flat-faster medians every "
                           "run (1.04-1.13x allreduce, 1.07-1.09x zero "
                           "— the removed leaf<->flat round trip); "
                           "bytegrad noise-bound either way (0.94-1.10x "
                           "across runs — codec cost dominates); "
                           "allreduce at accum=4 trends slightly slower "
                           "flat (0.88-0.92x, noise-bound — the "
                           "microbatch scan re-slices leaf views per "
                           "iteration, which XLA:CPU fuses worse than "
                           "the one-shot leaf layout).  Every config is "
                           "trajectory-identical, so auto stays engaged; "
                           "re-measure on real TPU silicon, where the "
                           "ZeRO leaf round trip measured ~7% (VERDICT "
                           "r3 #4) and collectives consume the flats "
                           "directly.",
    })
    with open(out_path, "w") as f:
        json.dump(records, f, indent=1)
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_FLAT.json")
    args = ap.parse_args()
    from bagua_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    run_suite(args.out)


if __name__ == "__main__":
    main()
