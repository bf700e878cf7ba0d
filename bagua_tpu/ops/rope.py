"""Rotary position embedding as one pass over ``[batch, seq, heads *
head_dim]`` (``rope``) — a Pallas TPU kernel in place of XLA's slice, negate
and ``concatenate`` — and, where each head of q and k is RMS-normalised
first, that norm in the same pass (``norm_rope``, at the end of the file).

The rotate-half form (``models.transformer.rope_rotate``, the golden) turns
a head ``x = [x1, x2]`` into ``x * cos + [-x2, x1] * sin``.  Written in XLA
ops on ``[batch, seq, heads, head_dim]`` the half-swap is a ``concatenate``
along the lanes, and around it the TPU compiler re-lays q and k in float32:
the projections write them sequence-minor, a convert fusion, a pad fusion
and bare ``copy`` / ``reshape`` / ``broadcast`` instructions bring them back
to the row-major ``[b, s, h * d]`` the flash kernels read, forward, replay
and backward (PERF.md §6, PR 44; a per-head ``RMSNorm`` in front costs the
same detour again, PR 51).  Here the swap is a lane roll inside the kernel:
``[-x2, x1] = roll(x, d / 2) * sign`` with ``sign`` -1 on a head's first
half and +1 on its second, so a call reads the tensor once as the
projection wrote it and writes it once as the flash kernels read it —
float32 arithmetic, one rounding.

``cos`` and ``sin * sign`` come in as ``[seq, head_dim]`` float32 tables
that XLA builds from ``theta`` and ``start`` outside the call (:func:`tables`),
so a sequence-parallel chunk's offset needs nothing from the kernel.  The
rotation is orthogonal and ``sin`` is equal on both halves, so its VJP is
the same call with ``-sin``: ``rope`` keeps no residual but ``start``, and
``norm_rope`` only the pre-norm tensor it was handed and the norm's scale.
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


def row_block(seq: int, lanes: int, head_dim: int, itemsize: int,
              tensors: int = 2) -> int:
    """Rows of a block: the tallest of ``ops/tiles.py``'s candidates that
    divides ``seq`` (0 where none does) and whose buffers fit the scoped
    VMEM (:func:`ops.gmm._vmem_limit`): ``tensors`` blocks in and out and two
    table blocks, each double-buffered, and a head's float32 working set."""
    limit = _vmem_limit()

    def block_bytes(r):
        return (2 * tensors * itemsize * r * lanes + 2 * 2 * 4 * r * head_dim
                + (2 + 2 * tensors) * 4 * r * head_dim)

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


def _refuse_uncovered(name: str, seq: int, head_dim: int) -> None:
    if not rope_supported(seq, head_dim):
        raise ValueError(
            f"{name} covers heads of whole 128-lane tiles and sequences of "
            f"whole {_CANDIDATES[-1]}-row blocks, not seq {seq} x head_dim "
            f"{head_dim}; it has no fallback")


def rope(x, theta: float, start=0, *, interpret: bool = False):
    """``models.transformer.rope_rotate`` by the kernel, the same
    signature: ``x`` [batch, seq, heads, head_dim] at positions ``start ..
    start + seq - 1`` (``start`` may be traced), result in ``x.dtype``.  The
    heads merge into the last axis and split out of it again by reshape, no
    element moves.  No fallback: the caller gates on
    :func:`rope_supported`."""
    b, s, h, d = x.shape
    _refuse_uncovered("rope", s, d)
    start = jnp.asarray(start, jnp.int32)
    return _rope(x.reshape(b, s, h * d), start, float(theta), d,
                 interpret).reshape(x.shape)


# ---- a per-head RMSNorm in the rotation's pass --------------------------------
#
# Where every head of q and k is RMS-normalised before it is rotated (Qwen3's
# q / k norm: one ``[head_dim]`` scale for all heads), the norm rides the same
# pass: a head's mean of squares is a reduction inside its own lane tiles, so
# the tensor still goes from the projection to the attention kernel as the
# flat ``[b, s, h * d]`` rows.  As XLA ops the norm reduces over the last axis
# of ``[b, s, h, d]`` in float32, and on the TPU that view is another tiling
# than the rows: the float32 tensor is written, broadcast and re-tiled around
# it, forward, replay and backward (PERF.md §6, PR 51).


def _norm_kernel(x_ref, w_ref, cos_ref, sin_ref, o_ref, *, eps):
    d = cos_ref.shape[-1]
    w, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
    for lo in range(0, x_ref.shape[-1], d):        # a head: static lanes
        x = x_ref[0, :, lo:lo + d].astype(jnp.float32)
        y = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
        o_ref[0, :, lo:lo + d] = (
            y * cos + pltpu.roll(y, d // 2, 1) * sin).astype(o_ref.dtype)


def _norm_bwd_kernel(g_ref, x_ref, w_ref, cos_ref, sin_ref, dx_ref, dw_ref,
                     *, eps):
    """``sin`` comes negated: the cotangent is un-rotated, then taken through
    the norm from the pre-norm ``x``.  ``dw``: this block's rows and heads
    summed, one ``[1, d]`` row a block."""
    d = cos_ref.shape[-1]
    w, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
    dw = jnp.zeros_like(w)
    for lo in range(0, x_ref.shape[-1], d):
        g = g_ref[0, :, lo:lo + d].astype(jnp.float32)
        x = x_ref[0, :, lo:lo + d].astype(jnp.float32)
        dy = g * cos + pltpu.roll(g, d // 2, 1) * sin
        r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        xhat = x * r
        dw += jnp.sum(dy * xhat, axis=0, keepdims=True)
        dxhat = dy * w
        dx_ref[0, :, lo:lo + d] = (r * (dxhat - xhat * jnp.mean(
            dxhat * xhat, axis=-1, keepdims=True))).astype(dx_ref.dtype)
    dw_ref[0] = dw


def _norm_call(kernel, tensors_in, w, cos, sin, eps, interpret):
    """One grid for both directions: ``tensors_in`` [b, s, h * d] each (the
    forward's ``x``; the backward's cotangent and ``x``), the ``[1, d]``
    scale and the ``[s, d]`` tables in; a tensor out and, backward,
    ``[blocks, 1, d]`` partial sums of the scale's gradient, one a row
    block.  Backward the cotangent's buffer becomes ``dx``."""
    b, s, lanes = tensors_in[0].shape
    d, x, with_dw = cos.shape[-1], tensors_in[0], len(tensors_in) == 2
    rows = row_block(s, lanes, d, x.dtype.itemsize, len(tensors_in) + 1)
    tensor = pl.BlockSpec((1, rows, lanes), lambda i, j: (i, j, 0))
    table = pl.BlockSpec((rows, d), lambda i, j: (j, 0))
    scale = pl.BlockSpec((1, d), lambda i, j: (0, 0))
    out_specs, out_shape = tensor, jax.ShapeDtypeStruct(x.shape, x.dtype)
    if with_dw:
        blocks = s // rows
        out_specs = [tensor, pl.BlockSpec(
            (1, 1, d), lambda i, j: (i * blocks + j, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((b * blocks, 1, d),
                                                     jnp.float32)]
    return pl.pallas_call(
        functools.partial(kernel, eps=eps),
        grid=(b, s // rows),
        in_specs=[tensor] * len(tensors_in) + [scale, table, table],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
        input_output_aliases={0: 0} if with_dw else {},
        cost_estimate=pl.CostEstimate(
            flops=12 * len(tensors_in) * x.size,
            transcendentals=x.size // d,
            bytes_accessed=(len(tensors_in) + 1) * x.size * x.dtype.itemsize),
        name="rope",   # still the rotation's pass, with a norm operand
    )(*tensors_in, w, cos, sin)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _norm_rotate(x, w, cos, sin, eps, interpret: bool = False):
    return _norm_call(_norm_kernel, [x], w, cos, sin, eps, interpret)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _norm_unrotate(g, x, w, cos, sin, eps, interpret: bool = False):
    dx, dw = _norm_call(_norm_bwd_kernel, [g, x], w, cos, sin, eps,
                        interpret)
    return dx, dw.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm_rope(x, w, start, theta, head_dim, eps, interpret):
    cos, sin = tables(theta, x.shape[1], head_dim, start)
    return _norm_rotate(x, w, cos, sin, eps, interpret)


def _norm_rope_fwd(x, w, start, theta, head_dim, eps, interpret):
    return (_norm_rope(x, w, start, theta, head_dim, eps, interpret),
            (x, w, start))


def _norm_rope_bwd(theta, head_dim, eps, interpret, residuals, g):
    x, w, start = residuals
    cos, sin = tables(theta, g.shape[1], head_dim, start)
    dx, dw = _norm_unrotate(g, x, w, cos, -sin, eps, interpret)
    return dx, dw, None


_norm_rope.defvjp(_norm_rope_fwd, _norm_rope_bwd)


def norm_rope(x, scale, theta: float, start=0, *, eps: float = 1e-6,
              zero_centered: bool = False, interpret: bool = False):
    """``models.transformer.RMSNorm`` over each head of ``x`` [batch, seq,
    heads, head_dim] (``scale`` [head_dim]; ``1 + scale`` where
    ``zero_centered``) and then :func:`rope` of the result, in one pass over
    the merged rows: float32 from the load to the one rounding at the store,
    where the two modules round the normalised tensor on the way.  The VJP
    is one call too — the cotangent un-rotated and taken through the norm —
    and keeps ``x`` (the projection's result, which a remat policy that
    keeps the matmuls holds already), ``scale`` and ``start``.  The same
    shapes as :func:`rope`, and no fallback either."""
    b, s, h, d = x.shape
    _refuse_uncovered("norm_rope", s, d)
    w = scale.astype(jnp.float32).reshape(1, d)
    if zero_centered:
        w = 1.0 + w
    start = jnp.asarray(start, jnp.int32)
    return _norm_rope(x.reshape(b, s, h * d), w, start, float(theta), d,
                      float(eps), interpret).reshape(x.shape)
