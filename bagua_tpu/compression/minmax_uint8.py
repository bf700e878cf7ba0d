"""MinMaxUInt8 chunked codec + compressed scatter-gather allreduce.

TPU-native equivalent of the reference's CUDA codec
(/root/reference/rust/bagua-core/bagua-core-internal/kernels/bagua_kernels.cu:269-572:
CUB per-chunk min/max reduction, then scale-quantize into a per-chunk
[min,max | u8 payload] layout) and of the compressed comm op
(comm_ops/centralized_low_precision_synchronous.rs:16-74: compress →
alltoall → decompress → chunk-reduce → compress own chunk → allgather →
decompress).

Quantization math matches the reference's golden model
(tests/internal/compressor.py):

    scale = 255 / (max - min + eps)
    upper = round(max * scale);  lower = upper - 255
    level = clamp(round(x * scale), lower, upper)
    payload = uint8(level - lower);   x' = (payload + lower) / scale

The payload layout differs deliberately: instead of the reference's packed
32-byte-aligned header+payload byte buffer (a CUDA pointer-arithmetic
concern), min/max travel as a separate small f32 array — XLA fuses the
quantize with the preceding producer, and the two collectives (u8 payload +
f32 minmax) are batched into one ICI transfer by the compiler.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..communication import BaguaCommunicator

EPS = 1e-7
LEVELS = 255.0


def compress_chunked(x: jax.Array, n_chunks: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compress flat f32/bf16 ``x`` (size divisible by ``n_chunks``) into
    per-chunk uint8 payloads.

    Returns ``(mn, mx, payload)`` with ``mn``/``mx`` shaped ``[n_chunks]``
    (f32) and ``payload`` shaped ``[n_chunks, chunk]`` (u8).
    """
    assert x.size % n_chunks == 0, (x.size, n_chunks)
    chunks = x.reshape(n_chunks, -1).astype(jnp.float32)
    mn = chunks.min(axis=1)
    mx = chunks.max(axis=1)
    scale = LEVELS / (mx - mn + EPS)
    upper = jnp.round(mx * scale)
    lower = upper - LEVELS
    level = jnp.round(chunks * scale[:, None])
    level = jnp.clip(level, lower[:, None], upper[:, None])
    payload = (level - lower[:, None]).astype(jnp.uint8)
    return mn, mx, payload


def decompress_chunked(mn: jax.Array, mx: jax.Array, payload: jax.Array) -> jax.Array:
    """Inverse of :func:`compress_chunked`; returns flat f32 of
    ``payload.size`` elements."""
    scale = LEVELS / (mx - mn + EPS)
    upper = jnp.round(mx * scale)
    lower = upper - LEVELS
    vals = (payload.astype(jnp.float32) + lower[:, None]) / scale[:, None]
    return vals.reshape(-1)


# crossover from a round-5 kernel-level profile (v5e, earlier code; record
# deleted in PR 46, not re-measured under jax 0.9 or through perfbench —
# ROADMAP Queue 3 item 3): the fused
# Pallas compress beats the XLA lowering from ~1 MiB chunks up (+9% kernel
# time) but LOSES below (grid/dispatch overhead dominates at 128 KB chunks);
# jnp decompress (one elementwise map, fully fused by XLA) beat the Pallas
# decompress at every measured size.  The crossover is BYTE-based (it is
# grid/dispatch overhead vs bytes streamed), so the gate scales by the
# input itemsize — a bf16/f16 flat must reach the same 1 MiB of payload,
# not half of it, before the Pallas path pays off.
_PALLAS_MIN_CHUNK_BYTES = 1 << 20  # 1 MiB


def _codec(comm: BaguaCommunicator):
    """Pick the codec implementation per the round-5 kernel profile (see
    module docstring of :mod:`.pallas_codec`; ROADMAP Queue 3 item 3):
    Pallas compress on TPU for chunks ≥1 MiB, the XLA lowering otherwise
    and for every decompress.  ``BAGUA_DISABLE_PALLAS_CODEC=1`` forces the
    jnp path for A/B checks.  The gate itself is
    :func:`.codecs._pallas_ok` — ONE place for the crossover, shared with
    the ring codecs — fed this communicator's mesh platform."""
    from .codecs import _pallas_ok

    platform = comm.mesh.devices.flat[0].platform

    def compress(v, n):
        if _pallas_ok((v.size // n) * v.dtype.itemsize, platform):
            from .pallas_codec import compress_chunked_pallas

            return compress_chunked_pallas(v, n)
        return compress_chunked(v, n)

    return compress, decompress_chunked


def quantize_with_bounds(
    x2d: jax.Array, mn: jax.Array, mx: jax.Array
) -> jax.Array:
    """Quantize ``[k, m]`` chunks against GIVEN per-chunk bounds — the
    codec's quantize half without its min/max reduction pass.  Values
    outside the bounds clamp to the grid edge (same clip the full codec
    applies), so sound bounds cost at most one extra grid step of error."""
    scale = LEVELS / (mx - mn + EPS)
    upper = jnp.round(mx * scale)
    lower = upper - LEVELS
    level = jnp.clip(
        jnp.round(x2d.astype(jnp.float32) * scale[:, None]),
        lower[:, None], upper[:, None],
    )
    return (level - lower[:, None]).astype(jnp.uint8)


def compressed_scatter_gather_allreduce(
    comm: BaguaCommunicator, x: jax.Array, average: bool = True
) -> jax.Array:
    """8-bit compressed allreduce over ``comm``'s axis (traced, inside
    shard_map).

    Pipeline (mirrors centralized_low_precision_synchronous.rs:31-70):
    compress all nranks chunks → all_to_all → decompress → reduce own chunk →
    quantize own chunk → all_gather → decompress.  ``x`` must be flat with
    ``size % nranks == 0`` (the bucket layer pads with world-size alignment).

    The allgather leg REUSES the scatter leg's scales (ISSUE 15): the
    reduced chunk provably lies within the mean/sum of its sources'
    ``[mn, mx]`` bounds (each dequantized source is clamped to its own
    grid), so the second quantize runs against those derived bounds —
    ONE min/max reduction pass per bucket instead of two, which counts on
    large buckets where the reduction is the codec's memory-bound half
    (round-5 profile).  Bound slack: a dequantized source can overshoot its
    bound by half a source grid step (``upper = round(mx·scale)``), and
    the derived grid is at most the mean source range wide — the clamp
    below absorbs both, keeping the error within one grid step of the
    recompute-min/max form.  Bits differ from that form, so the loss
    goldens carry regeneration provenance (tests/test_loss_goldens.py).
    """
    n = comm.nranks()
    compress, decompress = _codec(comm)
    mn, mx, payload = compress(x, n)
    # each rank ends up with every rank's chunk r (r = own rank index)
    payload_t = comm.alltoall(payload, split_axis=0, concat_axis=0)
    mn_t = comm.alltoall(mn, split_axis=0, concat_axis=0)
    mx_t = comm.alltoall(mx, split_axis=0, concat_axis=0)
    vals = decompress(mn_t, mx_t, payload_t).reshape(n, -1)
    red = vals.mean(axis=0) if average else vals.sum(axis=0)
    # quantize own reduced chunk against the sources' combined bounds (no
    # second min/max pass) and share it with everyone
    mn2 = (jnp.mean(mn_t) if average else jnp.sum(mn_t)).reshape(1)
    mx2 = (jnp.mean(mx_t) if average else jnp.sum(mx_t)).reshape(1)
    payload2 = quantize_with_bounds(red.reshape(1, -1), mn2, mx2)
    payload_all = comm.allgather(payload2, axis=0, tiled=True)  # [n, chunk]
    mn_all = comm.allgather(mn2, axis=0, tiled=True)            # [n]
    mx_all = comm.allgather(mx2, axis=0, tiled=True)
    return decompress(mn_all, mx_all, payload_all).astype(x.dtype)
