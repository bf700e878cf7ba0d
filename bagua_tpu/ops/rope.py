"""Rotary position embedding as one pass over ``[batch, seq, heads *
head_dim]`` (``rope``) — a Pallas TPU kernel in place of XLA's slice, negate
and ``concatenate``.

The rotate-half form (``models.transformer.rope_rotate``, the golden) turns
a head ``x = [x1, x2]`` into ``x * cos + [-x2, x1] * sin``.  Written in XLA
ops on ``[batch, seq, heads, head_dim]`` the half-swap is a ``concatenate``
along the lanes, and around it the TPU compiler re-lays q and k in float32:
the projections write them sequence-minor, a convert fusion, a pad fusion
and bare ``copy`` / ``reshape`` / ``broadcast`` instructions bring them back
to the row-major ``[b, s, h * d]`` the flash kernels read, forward, replay
and backward (``jnp.roll`` lowers to the same ``concatenate``; a product by
the signed permutation matrix keeps the copies; PERF.md §6, PR 44).  Here
the swap is a lane roll inside the kernel: ``[-x2, x1] = roll(x, d / 2) *
sign`` with ``sign`` -1 on a head's first half and +1 on its second, so a
call reads the tensor once as the projection wrote it and writes it once
as the flash kernels read it — float32 arithmetic, one rounding.

``cos`` and ``sin * sign`` come in as ``[seq, head_dim]`` float32 tables
that XLA builds from ``theta`` and ``start`` outside the call (:func:`tables`;
the angles, ``sin`` and ``cos`` in float32 as ``rope_rotate`` computes
them), so a sequence-parallel chunk's offset needs nothing from the kernel.
The rotation is orthogonal and ``sin`` is equal on both halves, so the VJP
is the same call with ``-sin``; it keeps no residual but ``start``, the
tables are rebuilt.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gmm import _fits, _vmem_limit
from .tiles import LANE, _CANDIDATES


def tables(theta: float, seq: int, head_dim: int, start=0):
    """``(cos, sin * sign)``, each ``[seq, head_dim]`` float32, of positions
    ``start .. start + seq - 1``: ``rope_rotate``'s angles to the bit, with
    the sign of the half-swap (``[-x2, x1]``: -1 on the first half) folded
    into ``sin``.  The two halves are laid side by side in the 128-entry
    frequency vector, not in the tables: one elementwise pass writes both."""
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    inv_freq = jnp.concatenate([inv_freq, inv_freq])          # [d]
    sign = jnp.where(jnp.arange(head_dim) < head_dim // 2, -1.0, 1.0)
    pos = (jnp.arange(seq, dtype=jnp.int32) + start).astype(jnp.float32)
    angles = pos[:, None] * inv_freq[None, :]                 # [seq, d]
    return jnp.cos(angles), jnp.sin(angles) * sign[None, :]


def row_block(seq: int, lanes: int, head_dim: int, itemsize: int) -> int:
    """Rows of a block: the tallest of ``ops/tiles.py``'s candidates that
    divides ``seq`` and whose buffers fit the scoped VMEM
    (:func:`ops.gmm._vmem_limit`) — the block in and out and the two
    tables' blocks, each double-buffered by the pipeline, and a head's
    float32 working set.  0 where none divides ``seq``."""
    limit = _vmem_limit()

    def block_bytes(r):
        return (2 * 2 * itemsize * r * lanes + 2 * 2 * 4 * r * head_dim
                + 6 * 4 * r * head_dim)

    blocks = [r for r in _CANDIDATES if seq % r == 0]
    return next((r for r in blocks if _fits(block_bytes(r), limit)),
                blocks[-1] if blocks else 0)


def rope_supported(seq: int, head_dim: int) -> bool:
    """Whether :func:`rope` covers the shape: a head of whole 128-lane
    tiles (at 64 the swap is inside half a vreg: not this kernel) and a
    sequence its row blocks divide."""
    return head_dim % LANE == 0 and seq % _CANDIDATES[-1] == 0


def _kernel(x_ref, cos_ref, sin_ref, o_ref):
    d = cos_ref.shape[-1]
    cos, sin = cos_ref[...], sin_ref[...]
    for lo in range(0, x_ref.shape[-1], d):        # a head: static lanes
        x = x_ref[0, :, lo:lo + d].astype(jnp.float32)
        o_ref[0, :, lo:lo + d] = (
            x * cos + pltpu.roll(x, d // 2, 1) * sin).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rotate(x, cos, sin, interpret: bool = False):
    """``x`` [b, s, h * d] by the ``[s, d]`` tables.  Jitted: Pallas traces
    a kernel body anew at every call."""
    b, s, lanes = x.shape
    d = cos.shape[-1]
    rows = row_block(s, lanes, d, x.dtype.itemsize)
    tensor = pl.BlockSpec((1, rows, lanes), lambda i, j: (i, j, 0))
    table = pl.BlockSpec((rows, d), lambda i, j: (j, 0))
    return pl.pallas_call(
        _kernel,
        grid=(b, s // rows),
        in_specs=[tensor, table, table],
        out_specs=tensor,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
        name="rope",
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rope(x, start, theta, head_dim, interpret):
    cos, sin = tables(theta, x.shape[1], head_dim, start)
    return _rotate(x, cos, sin, interpret)


def _rope_fwd(x, start, theta, head_dim, interpret):
    return _rope(x, start, theta, head_dim, interpret), start


def _rope_bwd(theta, head_dim, interpret, start, g):
    cos, sin = tables(theta, g.shape[1], head_dim, start)
    return _rotate(g, cos, -sin, interpret), None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope(x, theta: float, start=0, *, interpret: bool = False):
    """``models.transformer.rope_rotate`` by the kernel, the same
    signature: ``x`` [batch, seq, heads, head_dim] at positions ``start ..
    start + seq - 1`` (``start`` may be traced), result in ``x.dtype``.  The
    heads merge into the last axis and split out of it again by reshape, no
    element moves.  No fallback: the caller gates on
    :func:`rope_supported`."""
    b, s, h, d = x.shape
    if not rope_supported(s, d):
        raise ValueError(
            f"rope covers heads of whole 128-lane tiles and sequences of "
            f"whole {_CANDIDATES[-1]}-row blocks, not seq {s} x head_dim "
            f"{d}; it has no fallback")
    start = jnp.asarray(start, jnp.int32)
    return _rope(x.reshape(b, s, h * d), start, float(theta), d,
                 interpret).reshape(x.shape)
