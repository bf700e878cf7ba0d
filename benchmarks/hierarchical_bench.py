"""Hierarchical two-level collectives benchmark (ISSUE 11).

Measures the DCN-aware two-level decomposition (slice-local reduce-scatter
-> cross-slice allreduce on the 1/intra shard -> slice-local allgather)
against the flat single-collective path on a 2-slice x 4-chip
``('inter','intra')`` mesh:

* **per-tier bytes on the wire** — exact on any platform, from the traced
  step's jaxpr (the same extractor bagua-lint's collective-consistency
  sweep uses): every collective operand classified ICI (slice-local) vs
  DCN (spans ``inter``).  The headline acceptance number is the DCN ratio:
  two-tier cross-slice bytes / flat cross-slice bytes ~= 1/intra_size.
* **throughput A/B** — the interleaved best-of-trials protocol
  (``benchmarks/_ab.py``).  HONESTY NOTE: cpu-sim has no slow cross-slice
  link — both "tiers" are host memcpy — so wall-clock differences here
  reflect XLA:CPU operand sizes/fusion, not DCN relief; the byte
  accounting is the portable signal, the real win needs a multi-slice
  mesh.  Records carry the rationale.
* **per-tier device seconds** — a null-with-rationale record, like every
  device-time figure in this suite (a chip trace reads them by the
  collectives' ``bagua.comm/bucket_<i>`` op_name).

Usage: python benchmarks/hierarchical_bench.py [--out BENCH_HIERARCHICAL.json]
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

SCHEMA = "bagua-bench-hierarchical-v1"
INTER = 2

#: measurement sizing per platform: (timed steps, per-chip batch rows)
_TIMED = {"tpu": (20, 128), "cpu": (30, 32)}

DEVICE_TIME_RATIONALE = (
    "cpu-sim has no TPU device plane and no cross-slice link — per-tier "
    "device seconds need a real multi-slice capture; the jaxpr byte "
    "accounting above is exact everywhere"
)


def _workload(n_dev: int):
    from bagua_tpu.models.mlp import MLP

    rows = _TIMED["cpu"][1] * n_dev
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

    # several buckets on a ~340 KB model, so the per-bucket schedule and
    # the DCN-dominant-first launch order are exercised
    return loss_fn, params, {"x": x, "y": y}, 65536


def _algorithm(family: str, hierarchical: bool):
    if family == "gradient_allreduce":
        from bagua_tpu.algorithms import GradientAllReduceAlgorithm

        return (GradientAllReduceAlgorithm(hierarchical=hierarchical),
                optax.sgd(0.1, momentum=0.9))
    if family == "zero":
        from bagua_tpu.algorithms import ZeroOptimizerAlgorithm

        return (ZeroOptimizerAlgorithm(optax.sgd(0.1, momentum=0.9),
                                       hierarchical=hierarchical), None)
    if family == "bytegrad":
        from bagua_tpu.algorithms import ByteGradAlgorithm

        return (ByteGradAlgorithm(hierarchical=hierarchical),
                optax.sgd(0.1, momentum=0.9))
    raise ValueError(f"unknown family {family!r}")


def _mesh():
    from bagua_tpu.parallel.mesh import build_mesh

    n_dev = len(jax.devices())
    return build_mesh({"inter": INTER, "intra": n_dev // INTER})


def _trainer(family: str, hierarchical: bool):
    from bagua_tpu.core.backend import BaguaTrainer

    n_dev = len(jax.devices())
    loss_fn, params, batch, bucket_bytes = _workload(n_dev)
    algo, opt = _algorithm(family, hierarchical)
    trainer = BaguaTrainer(
        loss_fn, opt, algo, mesh=_mesh(), autotune=False, overlap="off",
        bucket_bytes=bucket_bytes,
    )
    state = trainer.init(params)
    return trainer, state, batch


def tier_wire_bytes(family: str, hierarchical: bool) -> dict:
    """Per-tier bytes on the wire of ONE traced step, from the jaxpr —
    collective operands spanning ``inter`` cross the slice boundary (DCN),
    everything else is slice-local (ICI)."""
    from bagua_tpu.analysis.jaxpr_check import iter_collectives

    trainer, state, batch = _trainer(family, hierarchical)
    data = trainer.shard_batch(batch)
    jaxpr = trainer.trace_step(state, data)
    dcn = ici = 0
    n = 0
    for c in iter_collectives(jaxpr):
        n += 1
        if "inter" in c.axes:
            dcn += c.nbytes
        else:
            ici += c.nbytes
    return {"dcn_bytes_per_step": int(dcn), "ici_bytes_per_step": int(ici),
            "collectives": n}


def measure(family: str, hierarchical: bool) -> dict:
    """One throughput record (the suite's min-of-2-windows methodology)."""
    import bench

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    timed, rows_per_chip = _TIMED.get(platform, _TIMED["cpu"])
    trainer, state, batch = _trainer(family, hierarchical)
    data = trainer.shard_batch(batch)
    dt, state, _ = bench._time_steps(trainer, state, data, timed=timed,
                                     warmup=2)
    samples = rows_per_chip * n_dev
    per_chip = timed * samples / dt / n_dev
    path = "two_tier" if hierarchical else "flat"
    return {
        "metric": f"hierarchical_mlp_{family}_{path}",
        "value": round(per_chip, 1),
        "unit": "samples/s/chip",
        "family": family,
        "path": path,
        "platform": platform,
        "timing": "min_of_2_windows_x%d_steps" % timed,
    }


FAMILIES = ("gradient_allreduce", "zero", "bytegrad")


def run_suite(out_path: str = "BENCH_HIERARCHICAL.json",
              trials: int = 3) -> list:
    from benchmarks._ab import interleaved_ab, speedup_record

    n_dev = len(jax.devices())
    intra = n_dev // INTER
    records = []

    def emit(rec):
        print(json.dumps(rec), flush=True)
        records.append(rec)
        return rec

    emit({
        "metric": "hierarchical_bench_schema",
        "schema": SCHEMA,
        "mesh": {"inter": INTER, "intra": intra},
        "value": None,
        "unit": None,
    })
    for family in FAMILIES:
        # exact per-tier byte accounting (the acceptance signal)
        flat = tier_wire_bytes(family, False)
        two = tier_wire_bytes(family, True)
        ratio = (
            two["dcn_bytes_per_step"] / flat["dcn_bytes_per_step"]
            if flat["dcn_bytes_per_step"] else None
        )
        emit({
            "metric": f"hierarchical_dcn_bytes_{family}",
            "value": ratio if ratio is None else round(ratio, 4),
            "unit": "two_tier/flat cross-slice bytes per step",
            "family": family,
            "intra_size": intra,
            "flat": flat,
            "two_tier": two,
            "expected_ratio": round(1.0 / intra, 4),
            "note": (
                "jaxpr collective operand bytes, exact on any platform; "
                "the two-tier DCN stage carries the 1/intra_size shard "
                "(+ the scalar loss reduction and, for bytegrad, the "
                "codec's min/max scales)"
            ),
        })
        # interleaved throughput A/B (honest: cpu-sim has no slow link)
        flat_rec, two_rec, ratios = interleaved_ab(
            lambda family=family: measure(family, False),
            lambda family=family: measure(family, True),
            trials=trials,
        )
        emit(flat_rec)
        emit(two_rec)
        emit(speedup_record(
            f"hierarchical_speedup_{family}", ratios, "two_tier/flat",
            platform=two_rec["platform"],
            provenance=(
                "cpu-sim: both tiers are host memcpy — any wall-clock "
                "difference here reflects XLA:CPU operand sizes and "
                "fusion, NOT a slow cross-slice link (observed two_tier "
                "faster on this host: the post-scatter stages run on "
                "1/intra operands).  The DCN win this decomposition "
                "exists for needs a real multi-slice mesh; the byte "
                "accounting above is the portable signal"
            ),
        ))
    # per-tier device seconds: this bench runs without a profiler window,
    # so the record is null-with-rationale on EVERY platform — on cpu-sim
    # there is no device plane at all, on TPU the gauges populate from a
    # live BAGUA_PROFILE_DIR capture, not from this suite
    platform = jax.devices()[0].platform
    emit({
        "metric": "hierarchical_device_tier_seconds",
        "value": None,
        "unit": "s/step",
        "device_comm_ici_s_per_step": None,
        "device_comm_dcn_s_per_step": None,
        "rationale": (
            DEVICE_TIME_RATIONALE if platform != "tpu" else
            "no profiler window captured by this bench — per-tier device "
            "seconds are read from a trace by the collectives' "
            "bagua.comm/bucket_<i> op_name (perfbench/scopes.py)"
        ),
        "gauges": ["obs/device_comm_ici_s_per_step",
                   "obs/device_comm_dcn_s_per_step"],
    })
    with open(out_path, "w") as f:
        json.dump(records, f, indent=1)
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_HIERARCHICAL.json")
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()
    from bagua_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    run_suite(args.out, trials=args.trials)


if __name__ == "__main__":
    main()
