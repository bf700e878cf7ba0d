"""Collective microbenchmark — the nccl-tests / bagua-net analog.

The reference justified its bagua-net engine with collective throughput
comparisons (/root/reference/rust/bagua-net/README.md:48-81, +50% allreduce
over NCCL's default TCP transport).  On TPU the transport is XLA over
ICI/DCN (SURVEY.md §7.11: nothing to build), but the *measurement* still
matters: this script records bus bandwidth per collective per size on
whatever mesh is available, so regressions in the comm path show up and
multi-chip runs have a baseline table.

Bus-bandwidth convention follows nccl-tests: ``busBW = algBW * 2(n-1)/n``
for allreduce, ``algBW * (n-1)/n`` for allgather/reduce_scatter/alltoall.

Usage: python benchmarks/collective_bench.py [--sizes-mb 1 4 16 64]
Prints one JSON line per (collective, size).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map


def _bench(fn, x, iters=10, warmup=3):
    compiled = jax.jit(fn)
    for _ in range(warmup):
        out = compiled(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", type=float, nargs="+",
                    default=[1, 4, 16, 64])
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    from bagua_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()

    from bagua_tpu.communication import BaguaCommunicator, ReduceOp
    from bagua_tpu.parallel.mesh import build_mesh

    n = len(jax.devices())
    mesh = build_mesh({"dp": n})
    comm = BaguaCommunicator("dp", mesh)

    def wrap(per_shard):
        return shard_map(per_shard, mesh=mesh, in_specs=(P("dp"),),
                         out_specs=P("dp"), check_vma=False)

    cases = {
        "allreduce": (
            wrap(lambda x: comm.allreduce(x[0], ReduceOp.SUM)[None]),
            2.0 * (n - 1) / n,
        ),
        "allgather": (
            wrap(lambda x: comm.allgather(x[0], axis=0, tiled=True)[None, : x.shape[1]]),
            (n - 1) / n,
        ),
        "reduce_scatter": (
            wrap(lambda x: jnp.tile(
                comm.reduce_scatter(x[0], ReduceOp.SUM, axis=0), n
            )[None]),
            (n - 1) / n,
        ),
        "alltoall": (
            wrap(lambda x: comm.alltoall_tiled(x[0], 0, 0)[None]),
            (n - 1) / n,
        ),
    }

    for size_mb in args.sizes_mb:
        elems = int(size_mb * (1 << 20)) // 4
        elems -= elems % (n * n)  # divisibility for scatter/alltoall
        x = jnp.ones((n, elems), jnp.float32)
        per_rank_bytes = elems * 4
        for name, (fn, busbw_factor) in cases.items():
            dt = _bench(fn, x, iters=args.iters)
            alg_bw = per_rank_bytes / dt / 1e9
            print(json.dumps({
                "collective": name,
                "size_mb": round(per_rank_bytes / (1 << 20), 2),
                "n_devices": n,
                "time_us": round(dt * 1e6, 1),
                "algbw_GBps": round(alg_bw, 2),
                "busbw_GBps": round(alg_bw * busbw_factor, 2),
            }), flush=True)


if __name__ == "__main__":
    main()
