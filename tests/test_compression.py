"""Codec + compressed-allreduce correctness.

Mirrors reference test_low_precision_decentralized.py's use of the pure
golden codec (tests/internal/compressor.py) plus a numpy simulation of the
scatter-gather pipeline (centralized_low_precision_synchronous.rs:16-74)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.communication import BaguaCommunicator, get_backend
from bagua_tpu.compression import (
    compress_chunked,
    compressed_scatter_gather_allreduce,
    decompress_chunked,
)
from tests.internal.compressor import MinMaxUInt8Numpy

N = 8


def test_codec_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1024,)).astype(np.float32))
    mn, mx, payload = compress_chunked(x, 4)
    y = decompress_chunked(mn, mx, payload)
    span = float(x.max() - x.min())
    assert float(jnp.abs(y - x).max()) <= span / 255.0 + 1e-6


def test_codec_matches_numpy_golden_single_chunk():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(512,)).astype(np.float32)
    golden = MinMaxUInt8Numpy()
    (gmn, gmx), gpayload = golden.compress(x)
    mn, mx, payload = compress_chunked(jnp.asarray(x), 1)
    np.testing.assert_allclose(float(mn[0]), gmn, rtol=1e-6)
    np.testing.assert_allclose(float(mx[0]), gmx, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(payload[0]), gpayload)
    y = decompress_chunked(mn, mx, payload)
    gy = golden.decompress((gmn, gmx), gpayload)
    np.testing.assert_allclose(np.asarray(y), gy, rtol=1e-6)


def _numpy_quantize_with_bounds(x, mn, mx):
    """The golden codec's quantize half against GIVEN bounds (mirrors
    bagua_tpu.compression.minmax_uint8.quantize_with_bounds)."""
    eps, levels = 1e-7, 255.0
    scale = levels / (mx - mn + eps)
    upper = np.round(mx * scale)
    lower = upper - levels
    level = np.clip(np.round(x.astype(np.float32) * scale), lower, upper)
    return (mn, mx), (level - lower).astype(np.uint8)


def _numpy_scatter_gather(xs: np.ndarray, average=True) -> np.ndarray:
    """Simulate the full pipeline rank by rank in numpy — including the
    allgather leg's scale REUSE: the reduced chunk is quantized against the
    mean/sum of its sources' bounds instead of a recomputed min/max (one
    reduction pass per bucket, ISSUE 15)."""
    golden = MinMaxUInt8Numpy()
    n, size = xs.shape
    chunk = size // n
    # stage 1: every rank compresses its n chunks
    comp = {}
    for r in range(n):
        for c in range(n):
            comp[(r, c)] = golden.compress(xs[r, c * chunk : (c + 1) * chunk])
    # stage 2: alltoall + decompress + reduce own chunk
    reduced = {}
    for r in range(n):
        vals = np.stack([golden.decompress(*comp[(src, r)]) for src in range(n)])
        reduced[r] = vals.mean(0) if average else vals.sum(0)
    # stage 3: quantize own chunk against the sources' combined bounds,
    # allgather, decompress
    out = np.zeros(size, np.float32)
    agg = np.mean if average else np.sum
    for c in range(n):
        mns = np.array([comp[(src, c)][0][0] for src in range(n)])
        mxs = np.array([comp[(src, c)][0][1] for src in range(n)])
        mmx, payload = _numpy_quantize_with_bounds(
            reduced[c], np.float32(agg(mns)), np.float32(agg(mxs))
        )
        out[c * chunk : (c + 1) * chunk] = golden.decompress(mmx, payload)
    return out


@pytest.mark.parametrize("average", [True, False])
def test_compressed_scatter_gather_matches_numpy_sim(average):
    rng = np.random.default_rng(2)
    size = N * 16
    xs = rng.normal(size=(N, size)).astype(np.float32)

    comm = get_backend("").global_communicator
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    fn = jax.jit(
        shard_map(
            lambda x: compressed_scatter_gather_allreduce(comm, x[0], average=average)[None],
            mesh=comm.mesh,
            in_specs=P(comm.axis_name),
            out_specs=P(comm.axis_name),
            check_vma=False,
        )
    )
    out = np.asarray(fn(jnp.asarray(xs)))
    expect = _numpy_scatter_gather(xs, average=average)
    for r in range(N):
        np.testing.assert_allclose(out[r], expect, rtol=1e-5, atol=1e-5)


def test_pallas_codec_matches_jnp_codec():
    """The fused Pallas kernels must be bit-identical to the jnp reference
    codec (same role as the reference's pure-torch golden for its CUDA codec,
    tests/internal/compressor.py).  Runs in interpreter mode on CPU; the same
    check runs compiled on real TPU hardware."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bagua_tpu.compression.minmax_uint8 import (
        compress_chunked, decompress_chunked,
    )
    from bagua_tpu.compression.pallas_codec import (
        compress_chunked_pallas, decompress_chunked_pallas,
    )

    for size, nc in [(8 * 1000, 8), (4 * 4096, 4), (2 * 100, 2)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (size,)).astype(jnp.float32)
        mn, mx, p = compress_chunked(x, nc)
        mn2, mx2, p2 = compress_chunked_pallas(x, nc, True)
        np.testing.assert_allclose(np.asarray(mn), np.asarray(mn2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(mx), np.asarray(mx2), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(p2))
        y = decompress_chunked(mn, mx, p)
        y2 = decompress_chunked_pallas(mn2, mx2, p2, True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-6)


def test_pallas_codec_tiled_large_chunks():
    """Chunks past the single-pass VMEM ceiling must take the tiled two-pass
    and still match the jnp codec exactly (the fused path VMEM-OOMed at
    ~8 MB chunks before the tiling existed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bagua_tpu.compression.pallas_codec as PC
    from bagua_tpu.compression.minmax_uint8 import (
        compress_chunked, decompress_chunked,
    )

    # force the tiled path at test-friendly sizes: ceiling 32 rows, 32-row
    # tiles -> a 2-chunk input of 24000 elems runs 3 tiles per chunk with
    # a ragged final tile
    orig_max, orig_tile = PC._MAX_FUSED_ROWS, PC._TILE_ROWS
    PC._MAX_FUSED_ROWS, PC._TILE_ROWS = 32, 32
    try:
        x = jax.random.normal(jax.random.PRNGKey(3), (2 * 12000,)).astype(
            jnp.float32
        )
        mn, mx, p = compress_chunked(x, 2)
        mn2, mx2, p2 = PC.compress_chunked_pallas(x, 2, True)
        np.testing.assert_allclose(np.asarray(mn), np.asarray(mn2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(mx), np.asarray(mx2), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(p2))
        y = decompress_chunked(mn, mx, p)
        y2 = PC.decompress_chunked_pallas(mn2, mx2, p2, True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-6)
    finally:
        PC._MAX_FUSED_ROWS, PC._TILE_ROWS = orig_max, orig_tile
