"""The rows of a Mamba-2 layer between its two projections, as Pallas TPU row
passes on the fused projection's own ``[batch, seq, 2 d_inner + 2 G N]``
buffer (columns ``[z | x | B | C]``, ``models/state_space.py``):

- ``ssd_mix``: the causal depthwise convolution WITH its bias and SiLU, one
  call a part (x, B, C), each reading its columns of the buffer where they
  lie (the ``BlockSpec``'s lane-block index: no slice is copied) and writing
  the ``[b, s, H P]`` / ``[b, s, G N]`` array the ``ssd_fwd`` kernel reads.
  The convolution is per channel: a head's width (64, half a lane tile, in
  the published model) does not enter;
- ``ssd_gate``: ``o = w_n * g / rms(g)`` with ``g = y * silu(z)`` — the gate
  FIRST, then the norm over each GROUP's ``d_inner / G`` lanes (several lane
  tiles: the tiles' squares added, then one reduction over the lanes) —,
  ``z`` read from the buffer's first columns in place;
- ``ssd_mix_bwd`` / ``ssd_gate_bwd``: their transposes, which recompute the
  forward's values from the buffer — nothing is kept for them — and write
  the buffer's cotangent where it lands: ``ssd_gate_bwd`` makes a fresh
  array like the buffer and fills its z columns, the three ``ssd_mix_bwd``
  calls fill the x, B and C columns of the same array
  (``input_output_aliases``), so no padded copy is added to another.

Float32 from the load to the one rounding at the store, which is where the
``jax.numpy`` forms (``conv_bias_silu``, ``gated_group_norm``) round too.

The geometry is ``ops/gated_delta_rows.py``'s (the sibling layer's passes):
the blocks of a part, the ``n - 1`` rows in front of a row block through a
second ``BlockSpec`` on the same operand, the transpose's walk over a
sequence's row blocks from the last to the first with the carried rows in
VMEM scratch.  The mathematics is this layer's own: a bias in the
convolution, no L2 norm, the gate before a norm that spans a group.

:func:`ssd_rows` is the layer's middle whole — ``ssd_mix``, the ``ssd_fwd``
kernel of ``ops/ssd.py``, ``ssd_gate`` — with one VJP that assembles the
buffer's cotangent as above and keeps no array of its own: the buffer (a
product's result), the kernel's ``y`` and per-chunk states (tagged as
``ops/ssd.py`` tags them: a remat policy that keeps those runs no
``ssd_fwd`` in the replay) and the scalars; x, B and C are made again by
``ssd_mix`` where the backward pass reads them.  It runs where
:func:`rows_supported` says so and has no fallback: the layer's
``jax.numpy`` code is the path everywhere else.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ssd
from .gated_delta import _padded_chunks
from .gated_delta_rows import (
    _CHUNK, _HALO, _TILE, _ahead, _behind, _block_front, _blocks,
    _chunk_rows, _front_rows, _head_taps, _params, _sigmoid, _tile_sums,
)
from .tiles import LANE, _CANDIDATES

#: float32 elements a value of the gate's loops holds at most: a group's
#: lanes times the rows of a chunk (``gated_delta_rows._CHUNK`` rows of one
#: lane tile: sixteen registers a value).  Read on the chip at ``[1, 8192,
#: 4096]`` in groups of 512 lanes (PERF.md section 6, PR 55): 32 rows a
#: chunk; 16 cost 60 % and 72 % more (forward, backward), 64 a fifteenth
#: less forward
_GATE_ELEMENTS = _CHUNK * LANE
#: rows of a bfloat16 tile: the least a chunk of the gate's loops takes
_MIN_CHUNK = 16


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _widths(dims):
    """``(d_inner, G N)`` of the buffer ``[z | x | B | C]``: x starts at
    ``d_inner``, B at twice it, C a ``G N`` further."""
    h, p, groups, n = dims
    return h * p, groups * n


def rows_covered(seq: int, dims, taps: int, chunk: int, dtype) -> bool:
    """Whether the passes' grids cover the layer: what the ``ssd_*`` kernels
    cover (``ssd._covered``: a group's heads and the state whole 128-lane
    tiles — so ``d_inner``, ``G N`` and each part's first column are whole
    lane blocks and a group's lanes whole tiles), a sequence of whole row
    blocks that the scan does not pad, a convolution that reaches no further
    than a tile of rows, bfloat16 or float32 rows."""
    h, p, groups, n = dims
    return (min(dims) >= 1 and ssd._covered(h, p, groups, n)
            and seq % _CANDIDATES[-1] == 0
            and _padded_chunks(seq, chunk) * chunk == seq
            and 1 <= taps <= _TILE + 1
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def rows_supported(seq: int, dims, taps: int, chunk: int = ssd.CHUNK,
                   dtype=jnp.bfloat16) -> bool:
    """Whether the layer's middle runs :func:`ssd_rows`: on a TPU, where
    :func:`rows_covered` (``ssd_supported`` asks no more)."""
    return _on_tpu() and rows_covered(seq, dims, taps, chunk, dtype)


# ---------------------------------------------------------------------------
# ssd_mix: convolution, bias, SiLU
# ---------------------------------------------------------------------------


def _lanes_at(j, width: int):
    """Lanes ``j width .. (j + 1) width`` of a block, ``j`` a loop's index:
    the tiles (or groups) of a block are walked by a loop, not unrolled — one
    body to trace and to compile whatever the block's width."""
    return pl.ds(pl.multiple_of(j * width, width), width)


def _mix_kernel(u_ref, front_ref, taps_ref, bias_ref, o_ref):
    """A lane tile at a time, and down its rows in chunks that stay in
    registers: a chunk hands the next its last tile of rows."""
    at_start = pl.program_id(1) == 0

    def tile(j, carry):
        lanes = _lanes_at(j, LANE)
        taps = _head_taps(taps_ref, lanes)
        bias = bias_ref[:, lanes]

        def chunk(i, front):
            rows = _chunk_rows(i)
            u = u_ref[0, rows, lanes].astype(jnp.float32)
            a = sum(tap * _behind(u, front, k)
                    for k, tap in enumerate(taps)) + bias
            o_ref[0, rows, lanes] = (a * _sigmoid(a)).astype(o_ref.dtype)
            return u[_CHUNK - _TILE:]

        lax.fori_loop(0, u_ref.shape[1] // _CHUNK, chunk,
                      _block_front(front_ref, lanes, at_start))
        return carry

    lax.fori_loop(0, u_ref.shape[-1] // LANE, tile, 0)


def _mix_bwd_kernel(g_ref, u_ref, front_ref, taps_ref, bias_ref, _, du_ref,
                    dt_ref, db_ref, carry_ref):
    """A sequence's row blocks from the last to the first, and a block's
    chunks of rows the same way: ``carry_ref`` holds the pre-activation's
    cotangent on the first rows of the block behind this one, ``dt_ref`` /
    ``db_ref`` the taps' and the bias's gradients summed over the blocks so
    far."""
    step = pl.program_id(2)
    at_start = step == pl.num_programs(2) - 1
    chunks = u_ref.shape[1] // _CHUNK

    @pl.when(step == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        dt_ref[...] = jnp.zeros_like(dt_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    def tile(j, carry):
        lanes = _lanes_at(j, LANE)
        taps = _head_taps(taps_ref, lanes)
        bias = bias_ref[:, lanes]
        block_front = _block_front(front_ref, lanes, at_start)

        def chunk(at, carry):
            back, sums, bias_sum = carry
            i = chunks - 1 - at
            rows = _chunk_rows(i)
            u = u_ref[0, rows, lanes].astype(jnp.float32)
            before = pl.ds(pl.multiple_of(
                jnp.maximum(i * _CHUNK - _HALO, 0), _HALO), _HALO)
            front = u_ref[0, before, lanes].astype(jnp.float32)[
                _HALO - _TILE:]
            first = jnp.where(i == 0, 1.0, 0.0)
            front = first * block_front + (1.0 - first) * front
            behind = [_behind(u, front, k) for k in range(len(taps))]
            a = sum(tap * uk for tap, uk in zip(taps, behind)) + bias
            sig = _sigmoid(a)
            # d silu = sigmoid(a) (1 + a (1 - sigmoid(a)))
            da = (g_ref[0, rows, lanes].astype(jnp.float32) * sig
                  * (1.0 + a * (1.0 - sig)))
            du = sum(tap * _ahead(da, back, k) for k, tap in enumerate(taps))
            du_ref[0, rows, lanes] = du.astype(du_ref.dtype)
            return da[:_TILE], tuple(
                acc + _tile_sums(da * uk) for acc, uk in zip(sums, behind)
            ), bias_sum + _tile_sums(da)

        zero = jnp.zeros((_TILE, LANE), jnp.float32)
        back, sums, bias_sum = lax.fori_loop(
            0, chunks, chunk,
            (carry_ref[:, lanes], (zero,) * len(taps), zero))
        carry_ref[:, lanes] = back
        n = len(taps)
        for k, acc in enumerate(sums):
            dt_ref[0, n - 1 - k:n - k, lanes] += jnp.sum(acc, axis=0,
                                                         keepdims=True)
        db_ref[0, :, lanes] += jnp.sum(bias_sum, axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, u_ref.shape[-1] // LANE, tile, 0)


def _part_blocks(seq, width, first, inner, itemsize, tensors, caps):
    """``(rows, lanes)`` of a part's blocks (``gated_delta_rows._blocks``)
    and the part's lane-block index in the buffer and in the taps, whose
    columns start at the buffer's x: the lanes divide both offsets."""
    rows, w = _blocks(seq, width, math.gcd(first, inner), LANE, itemsize,
                      tensors, caps)
    return rows, w, first // w, (first - inner) // w


@functools.partial(jax.jit, static_argnums=tuple(range(3, 8)))
def _mix_part(zxbc, taps, bias, first, width, inner, caps, interpret):
    """One part's columns ``first .. first + width`` of the buffer through
    ``ssd_mix``; ``bias`` [1, d_inner + 2 G N] float32.  Jitted: the layers
    of a model share one trace."""
    b, s, _ = zxbc.shape
    n, itemsize = taps.shape[0], zxbc.dtype.itemsize
    rows, w, at, tap_at = _part_blocks(s, width, first, inner, itemsize, 2,
                                       caps)
    front_of = _front_rows(rows)
    return pl.pallas_call(
        _mix_kernel,
        grid=(b, s // rows, width // w),
        in_specs=[
            pl.BlockSpec((1, rows, w), lambda i, r, j: (i, r, at + j)),
            pl.BlockSpec((1, _HALO, w),
                         lambda i, r, j: (i, front_of(r), at + j)),
            pl.BlockSpec((n, w), lambda i, r, j: (0, tap_at + j)),
            pl.BlockSpec((1, w), lambda i, r, j: (0, tap_at + j))],
        out_specs=pl.BlockSpec((1, rows, w), lambda i, r, j: (i, r, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, width), zxbc.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=14 * b * s * width, transcendentals=b * s * width,
            bytes_accessed=2 * b * s * width * itemsize),
        name="ssd_mix",
    )(zxbc, zxbc, taps, bias)


@functools.partial(jax.jit, static_argnums=tuple(range(5, 9)))
def _mix_bwd_part(g, zxbc, taps, bias, buffer, first, inner, caps, interpret):
    """``g`` [b, s, width]: the cotangent of one part of ``ssd_mix``'s
    result -> (``buffer`` with the part's columns filled, the part's taps'
    gradient [b, n, width] and its bias's [b, 1, width], float32)."""
    b, s, width = g.shape
    n, itemsize = taps.shape[0], zxbc.dtype.itemsize
    rows, w, at, tap_at = _part_blocks(s, width, first, inner, itemsize, 3,
                                       caps)
    front_of, blocks = _front_rows(rows), s // rows
    back = lambda r: blocks - 1 - r
    in_place = pl.BlockSpec((1, rows, w),
                            lambda i, j, r: (i, back(r), at + j))
    sums = lambda k: pl.BlockSpec((1, k, w), lambda i, j, r: (i, 0, j))
    return pl.pallas_call(
        _mix_bwd_kernel,
        grid=(b, width // w, blocks),
        in_specs=[
            pl.BlockSpec((1, rows, w), lambda i, j, r: (i, back(r), j)),
            in_place,
            pl.BlockSpec((1, _HALO, w),
                         lambda i, j, r: (i, front_of(back(r)), at + j)),
            pl.BlockSpec((n, w), lambda i, j, r: (0, tap_at + j)),
            pl.BlockSpec((1, w), lambda i, j, r: (0, tap_at + j)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[in_place, sums(n), sums(1)],
        out_shape=[jax.ShapeDtypeStruct(buffer.shape, buffer.dtype),
                   jax.ShapeDtypeStruct((b, n, width), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_TILE, w), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        input_output_aliases={5: 0},
        cost_estimate=pl.CostEstimate(
            flops=40 * b * s * width, transcendentals=b * s * width,
            bytes_accessed=3 * b * s * width * itemsize),
        name="ssd_mix_bwd",
    )(g, zxbc, zxbc, taps, bias, buffer)


def _parts(dims):
    """``(first column, width)`` of x, B and C in the buffer."""
    inner, maps = _widths(dims)
    return ((inner, inner), (2 * inner, maps), (2 * inner + maps, maps))


def _bias_row(bias):
    return bias.astype(jnp.float32).reshape(1, -1)


def mix(zxbc, taps, bias, dims, *, interpret=False, caps=None):
    """``silu(conv(.) + bias)`` of the buffer's x | B | C columns: ``zxbc``
    [b, s, 2 d_inner + 2 G N], ``taps`` [n, d_inner + 2 G N], ``bias``
    [d_inner + 2 G N], ``dims`` ``(H, P, G, N)`` -> ``(x [b, s, H P], B, C
    [b, s, G N])`` in the buffer's dtype."""
    inner, bias = _widths(dims)[0], _bias_row(bias)
    return tuple(_mix_part(zxbc, taps, bias, first, width, inner, caps,
                           interpret)
                 for first, width in _parts(dims))


def mix_bwd(dx, d_b, d_c, zxbc, taps, bias, buffer, dims, *, interpret=False,
            caps=None):
    """:func:`mix`'s transpose: ``buffer`` (like ``zxbc``; its z columns are
    kept as they come) with the x | B | C columns of the buffer's cotangent
    written into it, the taps' gradient [n, d_inner + 2 G N] and the bias's
    [d_inner + 2 G N], float32."""
    inner, bias = _widths(dims)[0], _bias_row(bias)
    d_taps, d_bias = [], []
    for g, (first, _) in zip((dx, d_b, d_c), _parts(dims)):
        buffer, part_taps, part_bias = _mix_bwd_part(
            g, zxbc, taps, bias, buffer, first, inner, caps, interpret)
        d_taps.append(part_taps.sum(axis=0))
        d_bias.append(part_bias.sum(axis=(0, 1)))
    return (buffer, jnp.concatenate(d_taps, axis=-1),
            jnp.concatenate(d_bias, axis=-1))


# ---------------------------------------------------------------------------
# ssd_gate: the gate, then the norm a group
# ---------------------------------------------------------------------------


def _gate_chunk(group: int, block_rows: int) -> int:
    """Rows of a chunk of the gate's loops: the most of a power of two that
    keeps a value of a group's lanes within :data:`_GATE_ELEMENTS`, a
    bfloat16 tile at least, and divides the row block."""
    rows = max(_MIN_CHUNK, _GATE_ELEMENTS // group)
    rows = 1 << (rows.bit_length() - 1)
    return math.gcd(rows, block_rows)


def _group_mean(v):
    """``[rows, group]`` -> ``[rows, 1]``: the group's lane tiles added, then
    one reduction over the lanes."""
    tiles = sum(v[:, lo:lo + LANE] for lo in range(0, v.shape[1], LANE))
    return jnp.sum(tiles, axis=-1, keepdims=True) * (1.0 / v.shape[1])


def _gate_kernel(y_ref, z_ref, w_ref, o_ref, *, group, chunk, eps):
    """A group at a time, and down its rows in chunks."""
    def lanes_of(j, carry):
        lanes = _lanes_at(j, group)
        w = w_ref[:, lanes]

        def rows_of(i, carry):
            rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
            y = y_ref[0, rows, lanes].astype(jnp.float32)
            z = z_ref[0, rows, lanes].astype(jnp.float32)
            g = y * (z * _sigmoid(z))
            r = lax.rsqrt(_group_mean(g * g) + eps)
            o_ref[0, rows, lanes] = (w * (g * r)).astype(o_ref.dtype)
            return carry

        return lax.fori_loop(0, y_ref.shape[1] // chunk, rows_of, carry)

    lax.fori_loop(0, y_ref.shape[-1] // group, lanes_of, 0)


def _gate_bwd_kernel(do_ref, y_ref, z_ref, w_ref, dy_ref, dz_ref, dw_ref, *,
                     group, chunk, eps):
    """``dw``: this block's rows summed, one ``[1, lanes]`` row."""
    def lanes_of(j, carry):
        lanes = _lanes_at(j, group)
        w = w_ref[:, lanes]

        def rows_of(i, dw):
            rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
            do = do_ref[0, rows, lanes].astype(jnp.float32)
            y = y_ref[0, rows, lanes].astype(jnp.float32)
            z = z_ref[0, rows, lanes].astype(jnp.float32)
            sig = _sigmoid(z)
            act = z * sig
            g = y * act
            r = lax.rsqrt(_group_mean(g * g) + eps)
            unit = g * r
            d_unit = do * w
            dg = r * (d_unit - unit * _group_mean(d_unit * unit))
            dy_ref[0, rows, lanes] = (dg * act).astype(dy_ref.dtype)
            dz_ref[0, rows, lanes] = (dg * y * sig * (
                1.0 + z * (1.0 - sig))).astype(dz_ref.dtype)
            return dw + _tile_sums(do * unit)

        dw = lax.fori_loop(0, y_ref.shape[1] // chunk, rows_of,
                           jnp.zeros((_TILE, group), jnp.float32))
        dw_ref[0, :, lanes] = jnp.sum(dw, axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, y_ref.shape[-1] // group, lanes_of, 0)


def _gate_blocks(seq, dims, itemsize, tensors, caps):
    """``(rows, lanes, a group's lanes)`` of the gate's blocks: z lies at
    the buffer's first column, y and o are their own arrays."""
    inner, group = _widths(dims)[0], _widths(dims)[0] // dims[2]
    return (*_blocks(seq, inner, 0, group, itemsize, tensors, caps), group)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def gate(y, zxbc, w_n, dims, eps, interpret=False, caps=None):
    """``w_n * g / rms(g)``, ``g = y * silu(z)``, the mean over each group's
    lanes: ``y`` [b, s, d_inner], ``z`` the first ``d_inner`` columns of
    ``zxbc``, ``w_n`` [d_inner] -> [b, s, d_inner] in ``y.dtype``."""
    b, s, inner = y.shape
    rows, w, group = _gate_blocks(s, dims, y.dtype.itemsize, 3, caps)
    spec = pl.BlockSpec((1, rows, w), lambda i, r, j: (i, r, j))
    return pl.pallas_call(
        functools.partial(_gate_kernel, group=group, eps=eps,
                          chunk=_gate_chunk(group, rows)),
        grid=(b, s // rows, inner // w),
        in_specs=[spec, spec, pl.BlockSpec((1, w), lambda i, r, j: (0, j))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=12 * y.size, transcendentals=y.size,
            bytes_accessed=3 * y.size * y.dtype.itemsize),
        name="ssd_gate",
    )(y, zxbc, w_n.astype(jnp.float32).reshape(1, inner))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def gate_bwd(do, y, zxbc, w_n, dims, eps, interpret=False, caps=None):
    """:func:`gate`'s transpose -> (``dy`` like ``y``, a fresh array like
    ``zxbc`` whose z columns hold ``dz`` — the others are for
    :func:`mix_bwd` to fill —, ``d w_n`` [d_inner] float32)."""
    b, s, inner = y.shape
    rows, w, group = _gate_blocks(s, dims, y.dtype.itemsize, 5, caps)
    blocks = s // rows
    spec = pl.BlockSpec((1, rows, w), lambda i, r, j: (i, r, j))
    dy, buffer, dw = pl.pallas_call(
        functools.partial(_gate_bwd_kernel, group=group, eps=eps,
                          chunk=_gate_chunk(group, rows)),
        grid=(b, blocks, inner // w),
        in_specs=[spec, spec, spec,
                  pl.BlockSpec((1, w), lambda i, r, j: (0, j))],
        out_specs=[spec, spec, pl.BlockSpec(
            (1, 1, w), lambda i, r, j: (i * blocks + r, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(zxbc.shape, zxbc.dtype),
                   jax.ShapeDtypeStruct((b * blocks, 1, inner),
                                        jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=34 * y.size, transcendentals=y.size,
            bytes_accessed=5 * y.size * y.dtype.itemsize),
        name="ssd_gate_bwd",
    )(do, y, zxbc, w_n.astype(jnp.float32).reshape(1, inner))
    return dy, buffer, dw.sum(axis=(0, 1))


# ---------------------------------------------------------------------------
# the layer's middle
# ---------------------------------------------------------------------------


def _middle(zxbc, taps, bias, dt, a, d, w_n, dims, chunk, eps, interpret,
            keep):
    """``(o, y, the scan's scalars by chunk, its states)``; with ``keep``
    the rule's forward also writes the state in front of every chunk."""
    h, p, groups, n = dims
    b, s, _ = zxbc.shape
    x, bm, cm = mix(zxbc, taps, bias, dims, interpret=interpret)
    # (the scan's wrapper takes [b, s, H, P] and [b, s, G, N]: views of the
    # flat rows, which it flattens again)
    y, (*flat, states) = ssd._forward(
        x.reshape(b, s, h, p), dt, a, bm.reshape(b, s, groups, n),
        cm.reshape(b, s, groups, n), d, chunk, True, interpret, keep)
    y = y.reshape(b, s, -1)
    if keep:
        # tagged as ``ssd._ssd_fwd`` tags them — a remat policy that keeps
        # the flash kernels' ``o`` and ``lse`` keeps these, and the replay
        # runs no ``ssd_fwd`` — but ``y`` on the flat rows, as the kernel
        # wrote it and ``ssd_gate_bwd`` reads it: kept as ``[b, s, H, P]``
        # it is another tiling, and a copy
        y = checkpoint_name(y, ssd.KEPT_O)
        states = checkpoint_name(states, ssd.KEPT_LSE)
    return gate(y, zxbc, w_n, dims, eps, interpret), y, tuple(flat[3:]), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _rows(zxbc, taps, bias, dt, a, d, w_n, dims, chunk, eps, interpret):
    return _middle(zxbc, taps, bias, dt, a, d, w_n, dims, chunk, eps,
                   interpret, False)[0]


def _rows_fwd(zxbc, taps, bias, dt, a, d, w_n, dims, chunk, eps, interpret):
    o, y, scalars, states = _middle(zxbc, taps, bias, dt, a, d, w_n, dims,
                                    chunk, eps, interpret, True)
    return o, (zxbc, taps, bias, w_n, y, scalars, states)


def _rows_bwd(dims, chunk, eps, interpret, residuals, do):
    zxbc, taps, bias, w_n, y, scalars, states = residuals
    h, p, groups, n = dims
    b, s, _ = y.shape
    dy, buffer, d_w_n = gate_bwd(do, y, zxbc, w_n, dims, eps, interpret)
    # x, B and C again, from the buffer: they were not kept
    flat = mix(zxbc, taps, bias, dims, interpret=interpret)
    # (empty arrays carry the operands' shapes and dtypes, as the scan's
    # own rule hands them to its transpose)
    f32 = jnp.float32
    like = tuple(jnp.zeros((0,) + tuple(shape), dtype) for shape, dtype in (
        ((h, p), zxbc.dtype), ((h,), f32), ((h,), f32),
        ((groups, n), zxbc.dtype), ((groups, n), zxbc.dtype), ((), f32)))
    dx, d_dt, d_a, d_b, d_c, d_d = ssd._ssd_bwd(
        chunk, True, interpret, ((*flat, *scalars), states, like),
        dy.reshape(b, s, h, p))
    d_zxbc, d_taps, d_bias = mix_bwd(
        *(t.reshape(b, s, -1) for t in (dx, d_b, d_c)), zxbc, taps, bias,
        buffer, dims, interpret=interpret)
    return (d_zxbc, d_taps.astype(taps.dtype), d_bias.astype(bias.dtype),
            d_dt, d_a, d_d, d_w_n.astype(w_n.dtype))


_rows.defvjp(_rows_fwd, _rows_bwd)


def ssd_rows(zxbc, taps, bias, dt, A, D, w_n, dims, *, chunk: int = ssd.CHUNK,
             norm_eps: float, interpret: bool = False):
    """A Mamba-2 layer from its fused projection to its out-projection's
    operand: ``zxbc`` [b, s, 2 d_inner + 2 G N] (columns ``[z | x | B |
    C]``), ``taps`` [n, d_inner + 2 G N], ``bias`` [d_inner + 2 G N], ``dt``
    (the step sizes, after their ``softplus``) [b, s, H], ``A`` (< 0) /
    ``D`` [H], ``w_n`` [d_inner], ``dims`` ``(H, P, G, N)`` -> o [b, s,
    d_inner] in ``zxbc.dtype``.  The caller gates on
    :func:`rows_supported`."""
    if not rows_covered(zxbc.shape[1], dims, taps.shape[0], chunk,
                        zxbc.dtype):
        raise ValueError(
            f"ssd_rows covers groups and states of whole 128-lane tiles and "
            f"sequences of whole {_CANDIDATES[-1]}-row blocks and whole "
            f"blocks of chunks in bfloat16 or float32 under at most "
            f"{_TILE + 1} taps, not seq {zxbc.shape[1]} x (H, P, G, N) "
            f"{tuple(dims)} x {taps.shape[0]} taps in chunks of {chunk} in "
            f"{zxbc.dtype}; it has no fallback")
    f32 = jnp.float32
    dt = dt.astype(f32)
    # the log decay is plain jax.numpy, as in ``ssd.ssd_scan``: A's gradient
    # and the decay's share of dt's are autodiff's
    return _rows(zxbc, taps, bias, dt, dt * A.astype(f32), D.astype(f32),
                 w_n, tuple(dims), int(chunk), float(norm_eps),
                 bool(interpret))
