"""Mesh-scale compile audit: trace+compile (never execute) the train step for
every algorithm family on large simulated meshes.

The scale hazard under XLA is different from the reference's: NCCL pays no
compile cost, while a jitted step's program size/compile time can grow with
mesh size (e.g. shift_one's precompiled ``lax.switch`` of pairings,
communication.py `exchange_with_peer`).  This audit pins the growth curve on
32/64-device virtual CPU meshes — the v5p-32/64 shapes — and records a
``BENCH_COMPILE.json`` artifact; ``tests/test_compile_scale.py`` gates on it.

Run standalone (spawns nothing; set the device count *before* jax import):

    python benchmarks/compile_audit.py --devices 32 64 --out BENCH_COMPILE.json
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_trainer(family, mesh):
    import optax

    import bagua_tpu
    from bagua_tpu.algorithms import (
        AsyncModelAverageAlgorithm,
        ByteGradAlgorithm,
        DecentralizedAlgorithm,
        GradientAllReduceAlgorithm,
        LowPrecisionDecentralizedAlgorithm,
        QAdamAlgorithm,
        ZeroOptimizerAlgorithm,
    )

    sgd = optax.sgd(0.1)
    algos = {
        "gradient_allreduce": lambda: (GradientAllReduceAlgorithm(), sgd),
        "bytegrad": lambda: (ByteGradAlgorithm(), sgd),
        "qadam": lambda: (QAdamAlgorithm(warmup_steps=5, hierarchical=False), None),
        "decentralized": lambda: (
            DecentralizedAlgorithm(peer_selection_mode="all"), sgd),
        "decentralized_shift_one": lambda: (
            DecentralizedAlgorithm(peer_selection_mode="shift_one"), sgd),
        "low_precision_decentralized": lambda: (
            LowPrecisionDecentralizedAlgorithm(), sgd),
        "zero": lambda: (ZeroOptimizerAlgorithm(optax.sgd(0.1)), None),
        "async": lambda: (
            AsyncModelAverageAlgorithm(warmup_steps=2), sgd),
    }
    algo, opt = algos[family]()
    return bagua_tpu.BaguaTrainer(
        lambda p, b: loss_fn(p, b), opt, algo, mesh=mesh, bucket_bytes=4096
    )


_MODEL = None


def loss_fn(p, b):
    import optax

    logits = _MODEL.apply({"params": p}, b["x"])
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, b["y"]
    ).mean()


def audit_flagship(n_devices):
    """Compile the FLAGSHIP (transformer LM) train step on a dp x tp mesh —
    the shape the driver's dryrun_multichip exercises — and record its
    compile time at this mesh size."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    import bagua_tpu
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn,
    )

    tp = 2
    dp = n_devices // tp
    devs = np.array(jax.devices()[:n_devices]).reshape(dp, tp)
    mesh = Mesh(devs, ("dp", "tp"))
    kw = dict(vocab_size=512, d_model=64, n_heads=4, n_layers=2, d_ff=128,
              max_seq_len=32)
    cfg = TransformerConfig(tp_axis="tp", tp_size=tp, **kw)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((dp * 2, cfg.max_seq_len + 1), jnp.int32)
    # init with GLOBAL shapes (plain config); the trainer shards tp leaves
    params = TransformerLM(TransformerConfig(**kw)).init(
        jax.random.PRNGKey(0), tokens[:1, :-1]
    )["params"]
    trainer = bagua_tpu.BaguaTrainer(
        lm_loss_fn(model), optax.sgd(0.1), GradientAllReduceAlgorithm(),
        mesh=mesh, dp_axes=("dp",), tp_axis="tp", bucket_bytes=4096,
    )
    t0 = _time.time()
    state = trainer.init(params)
    batch = trainer.shard_batch({"tokens": tokens})
    fn = trainer._get_step_fn()
    lowered = fn.lower(state, batch)
    trace_s = _time.time() - t0
    t1 = _time.time()
    lowered.compile()
    text = lowered.as_text()
    rec = {
        "family": "flagship_transformer_dp_tp",
        "n_devices": n_devices,
        "trace_s": round(trace_s, 3),
        "compile_s": round(_time.time() - t1, 3),
        "stablehlo_bytes": len(text),
        "hlo_op_count": _hlo_op_count(text),
    }
    print(json.dumps(rec), flush=True)
    return [rec]


def _hlo_op_count(stablehlo_text: str) -> int:
    """Rough-but-stable program size proxy: one per op-result assignment in
    the StableHLO module text.  Tracks exactly the growth the fused
    optimizer exists to kill (thousands of tiny per-leaf update ops)."""
    return sum(1 for line in stablehlo_text.splitlines() if " = " in line)


def audit_fused_optimizer_layouts(n_layers: int = 24):
    """Compile the SAME fused-adam train step in the leaf layout vs the
    flat-resident layout and record program size + compile time.

    The workload is a deep narrow MLP (``2 + 2*n_layers`` param leaves), the
    shape where per-leaf optimizer math bloats the program: the leaf layout
    pays the fused wrapper's per-dtype flatten/unflatten every step, while
    the flat-resident layout runs the inner adam straight on the resident
    bucket flats (the wrapper is unwrapped — zero repacking in the HLO).
    Records land in BENCH_FLAT.json via flat_resident_bench."""
    import jax
    import jax.numpy as jnp
    import optax

    import bagua_tpu
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.contrib import fuse_optimizer
    from bagua_tpu.models.mlp import MLP

    model = MLP(features=(32,) * n_layers + (8,))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16)))["params"]
    n_dev = len(jax.devices())
    batch = {
        "x": jnp.zeros((n_dev * 2, 16), jnp.float32),
        "y": jnp.zeros((n_dev * 2,), jnp.int32),
    }

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    records = []
    for layout in ("leaf", "flat"):
        trainer = bagua_tpu.BaguaTrainer(
            loss_fn, fuse_optimizer(optax.adam(1e-3)),
            GradientAllReduceAlgorithm(), bucket_bytes=16384, autotune=False,
            flat_resident="off" if layout == "leaf" else "on",
        )
        t0 = time.time()
        state = trainer.init(params)
        gbatch = trainer.shard_batch(batch)
        fn = trainer._get_step_fn()
        lowered = fn.lower(state, gbatch)
        trace_s = time.time() - t0
        t1 = time.time()
        lowered.compile()
        text = lowered.as_text()
        records.append({
            "metric": f"compile_audit_fused_adam_{layout}",
            "family": "gradient_allreduce_fused_adam",
            "layout": layout,
            "n_devices": n_dev,
            "param_leaves": len(jax.tree_util.tree_leaves(params)),
            "trace_s": round(trace_s, 3),
            "compile_s": round(time.time() - t1, 3),
            "stablehlo_bytes": len(text),
            "hlo_op_count": _hlo_op_count(text),
        })
        print(json.dumps(records[-1]), flush=True)
    return records


def audit(n_devices, families):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from bagua_tpu.models.mlp import MLP

    global _MODEL
    _MODEL = MLP(features=(64, 8))
    devs = np.array(jax.devices()[:n_devices])
    mesh = Mesh(devs, ("dp",))
    params = _MODEL.init(jax.random.PRNGKey(0), jnp.zeros((1, 32)))["params"]
    batch = {
        "x": jnp.zeros((n_devices * 2, 32), jnp.float32),
        "y": jnp.zeros((n_devices * 2,), jnp.int32),
    }
    records = []
    for family in families:
        trainer = build_trainer(family, mesh)
        t0 = time.time()
        state = trainer.init(params)
        gbatch = trainer.shard_batch(batch)
        fn = trainer._get_step_fn()
        lowered = fn.lower(state, gbatch)
        trace_s = time.time() - t0
        t1 = time.time()
        lowered.compile()
        compile_s = time.time() - t1
        text = lowered.as_text()
        rec = {
            "family": family,
            "n_devices": n_devices,
            "trace_s": round(trace_s, 3),
            "compile_s": round(compile_s, 3),
            "stablehlo_bytes": len(text),
            "hlo_op_count": _hlo_op_count(text),
        }
        print(json.dumps(rec), flush=True)
        records.append(rec)
    return records


def main():
    # no persistent compile cache here: compile seconds ARE the measurement
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, nargs="+", default=[32, 64])
    ap.add_argument("--families", nargs="+", default=[
        "gradient_allreduce", "bytegrad", "qadam", "decentralized",
        "decentralized_shift_one", "low_precision_decentralized", "zero",
        "async",
    ])
    ap.add_argument("--flagship", action="store_true",
                    help="also compile the transformer LM step on a dp x tp "
                         "mesh at each device count")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    # one process per device count: the virtual device count is fixed at
    # backend init, so re-exec for each size
    if len(args.devices) > 1:
        all_records = []
        for n in args.devices:
            import subprocess

            cmd = [sys.executable, os.path.abspath(__file__),
                   "--devices", str(n), "--families", *args.families]
            if args.flagship:
                cmd.append("--flagship")
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=1200, env=dict(os.environ))
            if out.returncode != 0:
                sys.stderr.write(out.stdout + out.stderr)
                sys.exit(out.returncode)
            for line in out.stdout.splitlines():
                line = line.strip()
                if line.startswith("{"):
                    all_records.append(json.loads(line))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(all_records, f, indent=1)
        else:
            print(json.dumps(all_records, indent=1))
        return

    n = args.devices[0]
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        .replace("--xla_force_host_platform_device_count=8", "")
        + f" --xla_force_host_platform_device_count={n}"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    records = audit(n, args.families)
    if args.flagship:
        records += audit_flagship(n)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)


if __name__ == "__main__":
    main()
