"""Overlap-scheduler benchmark — honest on-vs-off measurement (ISSUE 2).

Measures the overlap-aware bucket communication scheduler end-to-end:
throughput with ``overlap=on`` vs the serialized ``overlap=off`` step at
``accum_steps ∈ {1, 4}``, plus the profiler-derived comm-hidden ratio
(:func:`bagua_tpu.profiling.parse_xplane_overlap`) where a device trace is
available (TPU; the CPU-sim mesh records ``overlap_fraction: null`` —
XLA:CPU collectives on one host are memcpy, so a "hidden" ratio there would
be fiction).  Timing is the suite's min-of-2-windows methodology
(``bench._time_steps``).

Workloads: ResNet50 synthetic ImageNet on TPU (the suite's headline
config), an 8-device MLP classifier on the CPU-sim mesh (ResNet50 is not
timeable on host CPU).  Every record names its model and platform.

Usage: python benchmarks/overlap_bench.py [--out BENCH_OVERLAP.json]
Prints one JSON line per record; ``bench.py --overlap`` drives the same
code path.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np
import optax

#: measurement sizing per platform: (timed steps, per-chip batch rows)
_TIMED = {"tpu": (20, 128), "cpu": (30, 32)}


def _workload(platform: str, n_dev: int, accum: int):
    """Returns ``(loss_fn, params, batch, bucket_bytes)`` — the global batch
    already carries ``accum`` microbatches."""
    if platform == "tpu":
        from bagua_tpu.models.resnet import ResNet50, classification_loss_fn

        rows = _TIMED["tpu"][1] * n_dev * accum
        model = ResNet50(num_classes=1000)
        images = jnp.zeros((rows, 224, 224, 3), jnp.bfloat16)
        labels = jnp.zeros((rows,), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
        return (
            classification_loss_fn(model,
                                   batch_stats=variables["batch_stats"]),
            variables["params"],
            {"images": images, "labels": labels},
            None,  # default bucket_bytes
        )
    from bagua_tpu.models.mlp import MLP

    rows = _TIMED["cpu"][1] * n_dev * accum
    dim, nclass = 64, 10
    model = MLP(features=(256, 256, nclass))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, dim)).astype(np.float32)
    y = rng.integers(0, nclass, size=(rows,)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, dim)))["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    # several buckets on a ~340 KB model, so bucket streaming is exercised
    return loss_fn, params, {"x": x, "y": y}, 65536


def _algorithm(family: str):
    if family == "gradient_allreduce":
        from bagua_tpu.algorithms import GradientAllReduceAlgorithm

        return GradientAllReduceAlgorithm(hierarchical=False), (
            optax.sgd(0.1, momentum=0.9)
        )
    if family == "zero":
        from bagua_tpu.algorithms import ZeroOptimizerAlgorithm

        return ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9)), None
    if family == "bytegrad":
        from bagua_tpu.algorithms import ByteGradAlgorithm

        return ByteGradAlgorithm(hierarchical=False), (
            optax.sgd(0.1, momentum=0.9)
        )
    raise ValueError(f"unknown family {family!r}")


def measure(family: str, accum: int, overlap: str, chunk_bytes: int = 0,
            mesh=None, repeats: int = 3) -> dict:
    """One record: throughput + (TPU) comm-hidden ratio for one config.

    ``repeats`` independent min-of-2-windows trials, best kept: single
    ~2 s windows of a small model on a shared host absorb enough one-off
    interference to flip an on/off comparison (observed ±15% on the
    cpu-sim mesh); the fastest trial is the honest "what the machine does"
    figure, exactly like ``_time_steps``'s own min-of-windows rationale."""
    import bench
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.profiling import trace_overlap

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    if mesh is None:
        mesh = build_mesh({"dp": n_dev})
    timed, rows_per_chip = _TIMED.get(platform, _TIMED["cpu"])
    loss_fn, params, batch, bucket_bytes = _workload(
        platform if platform == "tpu" else "cpu", n_dev, accum
    )
    algo, opt = _algorithm(family)
    trainer = BaguaTrainer(
        loss_fn, opt, algo, mesh=mesh, autotune=False,
        accum_steps=accum, overlap=overlap, overlap_chunk_bytes=chunk_bytes,
        bucket_bytes=bucket_bytes,
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

    overlap_fields = {"overlap_fraction": None}
    if platform == "tpu":
        holder = {"state": state}

        def run_step():
            holder["state"], holder["loss"] = trainer.train_step(
                holder["state"], data
            )

        try:
            fields = trace_overlap(
                run_step, steps=5, finalize=lambda: float(holder["loss"])
            )
            if fields:
                overlap_fields = fields
        except Exception as e:  # noqa: BLE001 - trace must not lose a record
            print(f"# overlap trace failed: {e}", flush=True)
    model = "resnet50" if platform == "tpu" else "mlp_256x256"
    unit = "img/s/chip" if platform == "tpu" else "samples/s/chip"
    suffix = f"_chunk{chunk_bytes}" if chunk_bytes else ""
    return {
        "metric": (
            f"overlap_{model}_{family}_accum{accum}_{overlap}{suffix}"
        ),
        "value": round(per_chip, 1),
        "unit": unit,
        "overlap": overlap,
        "accum_steps": accum,
        "chunk_bytes": chunk_bytes,
        "family": family,
        "model": model,
        "platform": platform,
        "timing": f"best_of_{repeats}_trials_min_of_2_windows_x{timed}_steps",
        **overlap_fields,
        "overlap_fraction_rationale": (
            None if platform == "tpu" else
            "cpu-sim collectives are single-host memcpy; a hidden ratio "
            "would not measure anything real"
        ),
    }


#: (family, accum_steps, chunk_bytes) configs compared on vs off
CONFIGS = [
    ("gradient_allreduce", 1, 0),
    ("gradient_allreduce", 4, 0),
    ("zero", 4, 0),
    ("bytegrad", 4, 0),
]


def run_suite(out_path: str = "BENCH_OVERLAP.json",
              chunk_sweep: bool = False) -> list:
    records = []

    def emit(rec):
        print(json.dumps(rec), flush=True)
        records.append(rec)
        return rec

    from benchmarks._ab import interleaved_ab, speedup_record

    gate = {}
    for family, accum, chunk in CONFIGS:
        # interleaved A/B best-of-trials protocol (see benchmarks/_ab.py —
        # shared with flat_resident_bench)
        trials = 5
        off, on, ratios = interleaved_ab(
            lambda: measure(family, accum, "off", chunk, repeats=1),
            lambda: measure(family, accum, "on", chunk, repeats=1),
            trials=trials,
        )
        emit(off)
        emit(on)
        faster = "on" if float(np.median(ratios)) >= 1.0 else "off"
        gate[f"{family}_accum{accum}"] = faster
        emit(speedup_record(
            f"overlap_speedup_{family}_accum{accum}", ratios, "on/off",
            faster_path=faster, platform=on["platform"],
        ))
    # the measured gate BaguaTrainer's overlap="auto" encodes: overlap at
    # accum>1 for families that measured on-par-or-faster across repeated
    # runs, serialized where it lost (Algorithm.overlap_auto=False: zero,
    # bytegrad on this platform) and at accum==1 without explicit chunking
    emit({
        "metric": "overlap_dispatch_gate",
        "value": None,
        "unit": None,
        "faster_path_by_config": gate,
        "auto_default": "overlap at accum_steps>1 for gradient_allreduce; "
                        "serialized for zero and bytegrad "
                        "(overlap_auto=False) and at accum_steps==1 unless "
                        "overlap_chunk_bytes opts into the chunked ring",
        "gate_provenance": "set from repeated interleaved A/B runs on this "
                           "host, not this file alone: the quietest run "
                           "measured allreduce accum4 at 1.10-1.13x with "
                           "all trials >1 while zero/bytegrad never "
                           "cleanly beat 1.0; single runs here are "
                           "noise-bound (per-trial ratios have spanned "
                           "0.44-1.43 across runs — single-host cpu-sim "
                           "has no wire time to hide, so on/off differ "
                           "only by fusion/dispatch noise).  Re-measure "
                           "on a real ICI mesh before trusting either "
                           "direction there.",
    })
    if chunk_sweep:
        # ring sub-collective size A/B on the accum=1 allreduce path
        for chunk in (1 << 18, 1 << 20, 1 << 22):
            emit(measure("gradient_allreduce", 1, "on", chunk))
    with open(out_path, "w") as f:
        json.dump(records, f, indent=1)
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_OVERLAP.json")
    ap.add_argument("--chunk-sweep", action="store_true",
                    help="also sweep ring chunk sizes (overlap=on, accum=1)")
    args = ap.parse_args()
    from bagua_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    run_suite(args.out, chunk_sweep=args.chunk_sweep)


if __name__ == "__main__":
    main()
