"""The rows of a Gated DeltaNet layer between its two projections, as Pallas
TPU row passes on the fused projection's own ``[batch, seq, 2 hk dk + 2 hv
dv]`` buffer (columns ``[q | k | v | z]``, ``models/linear_attention.py``):

- ``gdn_mix``: the causal depthwise convolution, SiLU and — for the q and k
  columns — the L2 norm a head (q also scaled by ``d_k^-1/2``), one call a
  part, each reading its columns of the buffer where they lie (the
  ``BlockSpec``'s lane-block index: no slice is copied) and writing the
  ``[b, s, heads * dim]`` array the ``gdn_fwd`` kernel reads.  A column
  block is whole heads and whole 128-lane tiles (384 lanes at 96- or
  192-lane heads), a head a static lane slice inside it.  Where the k
  columns start inside such a block (Olmo-Hybrid: 30 heads of 96 lanes are
  7.5 blocks) no block index addresses them alone, and q | k go through as
  ONE part whose heads are scaled in front of the k columns only
  (:func:`_merged`); its two halves are then sliced apart for the rule's
  kernels and the two cotangents concatenated for ``gdn_mix_bwd`` — the one
  copy of rows the unaligned start costs (47 MB a layer and pass at 8,192
  rows), unpadded;
- ``gdn_gate``: ``y = w_n * o / rms(o) * silu(z)`` a value head, ``z`` read
  from the buffer's last columns in place;
- ``gdn_mix_bwd`` / ``gdn_gate_bwd``: their transposes, which recompute the
  forward's values from the buffer — nothing is kept for them — and write the
  buffer's cotangent where it lands: ``gdn_gate_bwd`` makes a fresh ``[b, s,
  2 hk dk + 2 hv dv]`` array and fills its z columns, the three
  ``gdn_mix_bwd`` calls fill the q, k and v columns of the same array
  (``input_output_aliases``), so no padded copy is added to another.

Float32 from the load to the one rounding at the store (the ``jax.numpy``
form rounds the convolution's sum before SiLU and the SiLU before the norms).

The convolution reads the ``n - 1`` rows in front of a row block through a
second ``BlockSpec`` on the same operand (a 16-row tile, zeros at a
sequence's first block); its transpose reads the cotangent of the ``n - 1``
rows behind the block, which the backward call carries in VMEM scratch while
it walks a sequence's row blocks from the last to the first.

:func:`gated_delta_rows` is the layer's middle whole — ``gdn_mix``, the
``gdn_fwd`` kernel of ``ops/gated_delta.py``, ``gdn_gate`` — with one VJP
that assembles the buffer's cotangent as above.  It runs where
:func:`rows_supported` says so and has no fallback: the layer's ``jax.numpy``
code is the path everywhere else.
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

from . import gated_delta as gd
from .gmm import _fits, _vmem_limit
from .tiles import LANE, _CANDIDATES

#: rows of a float32 sublane tile: what a row block hands the next (the
#: convolution reaches ``n - 1 <= 8`` rows)
_TILE = 8
#: rows of the block in front of a row block that the convolution reads
#: through its own ``BlockSpec``: a whole bfloat16 tile
_HALO = 16
#: lanes of a column block, at most: 16 heads of 128 unrolled a grid step
_LANE_CAP = 2048
#: rows a kernel holds in registers at a time: a head's rows of a block are
#: walked in chunks of as many (sixteen float32 tiles a value: the compiler
#: overlaps nothing across a loop's steps, so a step has to hold enough
#: independent rows to fill the chain from the load to the store).  Read on
#: the chip at ``[2, 4096, 12288]`` (PERF.md section 6, PR 53): 64 rows cost
#: a third more than 128 everywhere; 256 a third less where a head is
#: normalised (q, k) and a sixth more in the v part, which then spills
_CHUNK = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _widths(dims):
    """``(key width, value width)`` of the buffer ``[q | k | v | z]``: k
    starts at the key width, v at twice it, z a value width further."""
    hk, hv, dk, dv = dims
    return hk * dk, hv * dv


def _unit(head: int) -> int:
    """Lanes of the narrowest column block of heads of ``head`` lanes: whole
    heads and whole 128-lane tiles (a head of whole tiles by itself, 384
    lanes at 96- or 192-lane heads)."""
    return math.lcm(head, LANE)


def _merged(dims) -> bool:
    """Whether q and k go through ``gdn_mix`` as ONE part: where the k
    columns do not start at a whole block of key heads (30 heads of 96 lanes
    end half a 384-lane block in), no ``BlockSpec`` addresses them alone; q |
    k together, ``2 hk dk`` lanes from column 0, are whole blocks."""
    hk, _, dk, _ = dims
    return (hk * dk) % _unit(dk) != 0


def rows_covered(seq: int, dims, taps: int, dtype) -> bool:
    """Whether the passes' grids cover the layer: heads the rule's kernels
    cover (``gated_delta_covered``: whole 128-lane tiles alone or, key and
    value heads as many, in blocks of up to four, 128 x 128 as 96 x 192), a
    sequence of whole row blocks,
    the q | k columns and the v columns each whole blocks of their heads
    with v and z starting at a whole block of value heads (a part's columns
    are addressed by lane-block index; inside a block a head is a static
    lane slice), a convolution that reaches no further than a tile of rows,
    bfloat16 or float32 rows."""
    hk, hv, dk, dv = dims
    return (gd.gated_delta_covered(hk, hv, dk, dv, dtype)
            and (2 * hk * dk) % _unit(dk) == 0
            and (2 * hk * dk) % _unit(dv) == 0 and (hv * dv) % _unit(dv) == 0
            and seq % _CANDIDATES[-1] == 0 and 1 <= taps <= _TILE + 1)


def rows_supported(seq: int, dims, taps: int, dtype=jnp.bfloat16) -> bool:
    """Whether the layer's middle runs :func:`gated_delta_rows`: on a TPU,
    where :func:`rows_covered` (``gated_delta_supported`` asks no more)."""
    return _on_tpu() and rows_covered(seq, dims, taps, dtype)


def _blocks(seq, width, first, head, itemsize, tensors, caps):
    """``(rows, lanes)`` of a part's blocks: the widest multiple of a head
    (and of a lane tile: :func:`_unit`) that divides the part's ``width``
    and its ``first`` column, the tallest
    of ``ops/tiles.py``'s candidates that divides ``seq`` and whose buffers
    fit the scoped VMEM — ``tensors`` blocks in and out, double-buffered, and
    a head's float32 working set.  ``caps``: ``(rows, lanes)`` a test holds
    them under, or None."""
    cap_rows, cap_lanes = caps or (None, None)
    whole = math.gcd(width, first)
    unit = _unit(head)
    lanes = max(w for w in range(unit, whole + 1, unit)
                if whole % w == 0 and (w <= (cap_lanes or _LANE_CAP)
                                       or w == unit))
    limit = _vmem_limit()
    fitting = [r for r in _CANDIDATES
               if seq % r == 0 and r <= (cap_rows or r)]
    block_bytes = lambda r: (2 * tensors * itemsize * r * lanes
                             + 16 * 4 * r * head)
    return next((r for r in fitting if _fits(block_bytes(r), limit)),
                fitting[-1]), lanes


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_vmem_limit())


# ---------------------------------------------------------------------------
# gdn_mix: convolution, SiLU, the L2 norm a head
# ---------------------------------------------------------------------------


def _behind(x, front, k: int):
    """``x[t - k]`` down a block's rows ``[rows, d]``, the first ``k`` from
    ``front`` ``[8, d]``: the eight rows in front of the block."""
    if k == 0:
        return x
    row = lax.broadcasted_iota(jnp.int32, front.shape, 0)
    rolled = pltpu.roll(x, k, 0)
    first = jnp.where(row < k, pltpu.roll(front, k, 0), rolled[:_TILE])
    return jnp.concatenate([first, rolled[_TILE:]], axis=0)


def _ahead(x, back, k: int):
    """``x[t + k]``, the last ``k`` rows from ``back`` ``[8, d]``: the eight
    rows behind the block."""
    if k == 0:
        return x
    rows = x.shape[0]
    row = lax.broadcasted_iota(jnp.int32, back.shape, 0)
    rolled = pltpu.roll(x, rows - k, 0)
    last = jnp.where(row >= _TILE - k, pltpu.roll(back, _TILE - k, 0),
                     rolled[rows - _TILE:])
    return jnp.concatenate([rolled[:rows - _TILE], last], axis=0)


def _sigmoid(x):
    """By ``tanh``: one transcendental and no division."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _tile_sums(x):
    """``[rows, d]`` -> ``[8, d]``: the row tiles added up (no reduction
    across sublanes)."""
    return x.reshape(x.shape[0] // _TILE, _TILE, x.shape[1]).sum(axis=0)


def _chunk_rows(i):
    return pl.ds(pl.multiple_of(i * _CHUNK, _CHUNK), _CHUNK)


def _head_taps(taps_ref, lanes):
    """A head's taps as ``[1, d]`` float32 rows, by reach: entry ``k``
    weighs the position ``k`` behind (``taps[n - 1]`` the position itself)."""
    n = taps_ref.shape[0]
    return [taps_ref[n - 1 - k:n - k, lanes].astype(jnp.float32)
            for k in range(n)]


def _block_front(front_ref, lanes, at_start):
    """The eight rows in front of the block, float32; zeros in front of a
    sequence."""
    front = front_ref[0, :, lanes].astype(jnp.float32)[_HALO - _TILE:]
    return front * jnp.where(at_start, 0.0, 1.0)


def _scale_at(scale, split, block, lo, width):
    """A unit head's scale: ``scale`` everywhere, or — q | k as one part —
    only on the columns in front of ``split`` (the q heads; a k head is
    scaled by 1).  ``block``: the column block's index, ``lo``: the head's
    first lane inside it."""
    if split is None:
        return scale
    return jnp.where(block * width + lo < split, scale, 1.0)


def _mix_kernel(x_ref, front_ref, taps_ref, o_ref, *, head, unit, scale,
                split, eps):
    """A head at a time (static lanes), and down its rows in chunks that
    stay in registers: a chunk hands the next its last tile of rows."""
    at_start = pl.program_id(1) == 0
    for lo in range(0, x_ref.shape[-1], head):
        lanes = slice(lo, lo + head)
        taps = _head_taps(taps_ref, lanes)
        by = _scale_at(scale, split, pl.program_id(2), lo, x_ref.shape[-1])

        def chunk(i, front, lanes=lanes, taps=taps, scale=by):
            rows = _chunk_rows(i)
            x = x_ref[0, rows, lanes].astype(jnp.float32)
            c = sum(tap * _behind(x, front, k) for k, tap in enumerate(taps))
            m = c * _sigmoid(c)
            if unit:
                m = m * (scale * lax.rsqrt(
                    jnp.sum(m * m, axis=-1, keepdims=True) + eps))
            o_ref[0, rows, lanes] = m.astype(o_ref.dtype)
            return x[_CHUNK - _TILE:]

        lax.fori_loop(0, x_ref.shape[1] // _CHUNK, chunk,
                      _block_front(front_ref, lanes, at_start))


def _mix_bwd_kernel(g_ref, x_ref, front_ref, taps_ref, _, dx_ref, dt_ref,
                    carry_ref, *, head, unit, scale, split, eps):
    """A sequence's row blocks from the last to the first, and a block's
    chunks of rows the same way: ``carry_ref`` holds the convolution's
    cotangent on the first rows of the block behind this one, ``dt_ref`` the
    taps' gradient summed over the blocks so far."""
    step = pl.program_id(2)
    at_start = step == pl.num_programs(2) - 1
    chunks = x_ref.shape[1] // _CHUNK

    @pl.when(step == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        dt_ref[...] = jnp.zeros_like(dt_ref)

    for lo in range(0, x_ref.shape[-1], head):
        lanes = slice(lo, lo + head)
        taps = _head_taps(taps_ref, lanes)
        block_front = _block_front(front_ref, lanes, at_start)
        by = _scale_at(scale, split, pl.program_id(1), lo, x_ref.shape[-1])

        def chunk(at, carry, lanes=lanes, taps=taps, block_front=block_front,
                  scale=by):
            back, sums = carry
            i = chunks - 1 - at
            rows = _chunk_rows(i)
            x = x_ref[0, rows, lanes].astype(jnp.float32)
            before = pl.ds(pl.multiple_of(
                jnp.maximum(i * _CHUNK - _HALO, 0), _HALO), _HALO)
            front = x_ref[0, before, lanes].astype(jnp.float32)[
                _HALO - _TILE:]
            first = jnp.where(i == 0, 1.0, 0.0)
            front = first * block_front + (1.0 - first) * front
            behind = [_behind(x, front, k) for k in range(len(taps))]
            c = sum(tap * xk for tap, xk in zip(taps, behind))
            sig = _sigmoid(c)
            dm = g_ref[0, rows, lanes].astype(jnp.float32)
            if unit:
                m = c * sig
                r = lax.rsqrt(jnp.sum(m * m, axis=-1, keepdims=True) + eps)
                m = m * r
                dm = scale * r * (dm - m * jnp.sum(dm * m, axis=-1,
                                                   keepdims=True))
            dc = dm * sig * (1.0 + c * (1.0 - sig))
            dx = sum(tap * _ahead(dc, back, k) for k, tap in enumerate(taps))
            dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
            return dc[:_TILE], tuple(
                acc + _tile_sums(dc * xk) for acc, xk in zip(sums, behind))

        zero = jnp.zeros((_TILE, head), jnp.float32)
        back, sums = lax.fori_loop(
            0, chunks, chunk, (carry_ref[:, lanes], (zero,) * len(taps)))
        carry_ref[:, lanes] = back
        n = len(taps)
        for k, acc in enumerate(sums):
            dt_ref[0, n - 1 - k:n - k, lanes] += jnp.sum(acc, axis=0,
                                                         keepdims=True)


def _front_rows(rows: int):
    """Row-block index, in ``_HALO``-row blocks, of the rows in front of row
    block ``r`` (the sequence's first block reads its own head: masked)."""
    return lambda r: jnp.maximum(r * (rows // _HALO) - 1, 0)


@functools.partial(jax.jit, static_argnums=tuple(range(2, 11)))
def _mix_part(qkvz, taps, first, width, head, unit, scale, split, eps, caps,
              interpret):
    """One part's columns ``first .. first + width`` of the buffer through
    ``gdn_mix``.  Jitted: the layers of a model share one trace."""
    b, s, _ = qkvz.shape
    n, itemsize = taps.shape[0], qkvz.dtype.itemsize
    rows, w = _blocks(s, width, first, head, itemsize, 2, caps)
    at, front_of = first // w, _front_rows(rows)
    return pl.pallas_call(
        functools.partial(_mix_kernel, head=head, unit=unit, scale=scale,
                          split=split, eps=eps),
        grid=(b, s // rows, width // w),
        in_specs=[
            pl.BlockSpec((1, rows, w), lambda i, r, j: (i, r, at + j)),
            pl.BlockSpec((1, _HALO, w),
                         lambda i, r, j: (i, front_of(r), at + j)),
            pl.BlockSpec((n, w), lambda i, r, j: (0, at + j))],
        out_specs=pl.BlockSpec((1, rows, w), lambda i, r, j: (i, r, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, width), qkvz.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=24 * b * s * width, transcendentals=b * s * width,
            bytes_accessed=2 * b * s * width * itemsize),
        name="gdn_mix",
    )(qkvz, qkvz, taps)


@functools.partial(jax.jit, static_argnums=tuple(range(4, 12)))
def _mix_bwd_part(g, qkvz, taps, buffer, first, head, unit, scale, split, eps,
                  caps, interpret):
    """``g`` [b, s, width]: the cotangent of one part of ``gdn_mix``'s
    result -> (``buffer`` with the part's columns filled, the part's taps'
    gradient [b, n, width] float32)."""
    b, s, width = g.shape
    n, itemsize = taps.shape[0], qkvz.dtype.itemsize
    rows, w = _blocks(s, width, first, head, itemsize, 3, caps)
    at, front_of, blocks = first // w, _front_rows(rows), s // rows
    back = lambda r: blocks - 1 - r
    in_place = pl.BlockSpec((1, rows, w),
                            lambda i, j, r: (i, back(r), at + j))
    return pl.pallas_call(
        functools.partial(_mix_bwd_kernel, head=head, unit=unit, scale=scale,
                          split=split, eps=eps),
        grid=(b, width // w, blocks),
        in_specs=[
            pl.BlockSpec((1, rows, w), lambda i, j, r: (i, back(r), j)),
            in_place,
            pl.BlockSpec((1, _HALO, w),
                         lambda i, j, r: (i, front_of(back(r)), at + j)),
            pl.BlockSpec((n, w), lambda i, j, r: (0, at + j)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[in_place,
                   pl.BlockSpec((1, n, w), lambda i, j, r: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(buffer.shape, buffer.dtype),
                   jax.ShapeDtypeStruct((b, n, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_TILE, w), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        input_output_aliases={4: 0},
        cost_estimate=pl.CostEstimate(
            flops=60 * b * s * width, transcendentals=b * s * width,
            bytes_accessed=3 * b * s * width * itemsize),
        name="gdn_mix_bwd",
    )(g, qkvz, qkvz, taps, buffer)


def _parts(dims, l2_eps):
    """``(first column, width, head, unit, scale, split, eps)`` of q, k and
    v — or of q | k and v where :func:`_merged`: one part whose heads are
    scaled in front of column ``split`` only."""
    _, _, dk, dv = dims
    kw, vw = _widths(dims)
    value = (2 * kw, vw, dv, False, 1.0, None, l2_eps)
    if _merged(dims):
        return (0, 2 * kw, dk, True, dk ** -0.5, kw, l2_eps), value
    return ((0, kw, dk, True, dk ** -0.5, None, l2_eps),
            (kw, kw, dk, True, 1.0, None, l2_eps), value)


def mix(qkvz, taps, dims, *, l2_eps, interpret=False, caps=None):
    """``silu(conv(.))`` of the buffer's q | k | v columns, q and k
    L2-normalised a head and q scaled: ``qkvz`` [b, s, 2 hk dk + 2 hv dv],
    ``taps`` [n, 2 hk dk + hv dv], ``dims`` ``(hk, hv, dk, dv)`` -> ``(q, k
    [b, s, hk dk], v [b, s, hv dv])`` in the buffer's dtype.  Where q | k
    went through as one part, q and k are its two halves: slices, which the
    rule's kernels need as arrays of their own (the k half starts inside a
    lane tile)."""
    *keys, v = (_mix_part(qkvz, taps, *part, caps, interpret)
                for part in _parts(dims, float(l2_eps)))
    if _merged(dims):
        kw = _widths(dims)[0]
        keys = keys[0][..., :kw], keys[0][..., kw:]
    return (*keys, v)


def mix_bwd(dq, dk, dv, qkvz, taps, buffer, dims, *, l2_eps, interpret=False,
            caps=None):
    """:func:`mix`'s transpose: ``buffer`` (like ``qkvz``; its z columns are
    kept as they come) with the q | k | v columns of the buffer's cotangent
    written into it, and the taps' gradient [n, 2 hk dk + hv dv] float32."""
    cotangents = ((jnp.concatenate([dq, dk], axis=-1), dv) if _merged(dims)
                  else (dq, dk, dv))
    d_taps = []
    for g, (first, _, *part) in zip(cotangents, _parts(dims, float(l2_eps))):
        buffer, part_taps = _mix_bwd_part(g, qkvz, taps, buffer, first, *part,
                                          caps, interpret)
        d_taps.append(part_taps.sum(axis=0))
    return buffer, jnp.concatenate(d_taps, axis=-1)


# ---------------------------------------------------------------------------
# gdn_gate: the gated norm a value head
# ---------------------------------------------------------------------------


def _gate_kernel(o_ref, z_ref, w_ref, y_ref, *, head, eps):
    w = w_ref[...]
    for lo in range(0, o_ref.shape[-1], head):
        lanes = slice(lo, lo + head)

        def chunk(i, carry, lanes=lanes):
            rows = _chunk_rows(i)
            o = o_ref[0, rows, lanes].astype(jnp.float32)
            z = z_ref[0, rows, lanes].astype(jnp.float32)
            r = lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            y_ref[0, rows, lanes] = (w * (o * r) * (z * _sigmoid(z))).astype(
                y_ref.dtype)
            return carry

        lax.fori_loop(0, o_ref.shape[1] // _CHUNK, chunk, 0)


def _gate_bwd_kernel(dy_ref, o_ref, z_ref, w_ref, do_ref, dz_ref, dw_ref, *,
                     head, eps):
    """``dw``: this block's rows and heads summed, one ``[1, d]`` row."""
    w = w_ref[...]
    dw = jnp.zeros((_TILE, head), jnp.float32)
    for lo in range(0, o_ref.shape[-1], head):
        lanes = slice(lo, lo + head)

        def chunk(i, dw, lanes=lanes):
            rows = _chunk_rows(i)
            dy = dy_ref[0, rows, lanes].astype(jnp.float32)
            o = o_ref[0, rows, lanes].astype(jnp.float32)
            z = z_ref[0, rows, lanes].astype(jnp.float32)
            r = lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            unit, sig = o * r, _sigmoid(z)
            scaled = dy * unit                       # d y / d (w gate)
            d_unit = dy * w * (z * sig)
            do_ref[0, rows, lanes] = (r * (d_unit - unit * jnp.mean(
                d_unit * unit, axis=-1, keepdims=True))).astype(do_ref.dtype)
            dz_ref[0, rows, lanes] = (scaled * w * sig * (
                1.0 + z * (1.0 - sig))).astype(dz_ref.dtype)
            return dw + _tile_sums(scaled * (z * sig))

        dw = lax.fori_loop(0, o_ref.shape[1] // _CHUNK, chunk, dw)
    dw_ref[0] = jnp.sum(dw, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def gate(o, qkvz, w_n, dims, eps, interpret=False, caps=None):
    """``w_n * o / rms(o) * silu(z)`` a value head: ``o`` [b, s, hv dv],
    ``z`` the last ``hv dv`` columns of ``qkvz``, ``w_n`` [dv] -> [b, s, hv
    dv] in ``o.dtype``."""
    b, s, vw = o.shape
    dv, z_at = dims[3], 2 * _widths(dims)[0] + vw
    rows, w = _blocks(s, vw, z_at, dv, o.dtype.itemsize, 3, caps)
    rows_of = lambda shift: pl.BlockSpec(
        (1, rows, w), lambda i, r, j: (i, r, shift + j))
    return pl.pallas_call(
        functools.partial(_gate_kernel, head=dv, eps=eps),
        grid=(b, s // rows, vw // w),
        in_specs=[rows_of(0), rows_of(z_at // w),
                  pl.BlockSpec((1, dv), lambda i, r, j: (0, 0))],
        out_specs=rows_of(0),
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=12 * o.size, transcendentals=o.size,
            bytes_accessed=3 * o.size * o.dtype.itemsize),
        name="gdn_gate",
    )(o, qkvz, w_n.astype(jnp.float32).reshape(1, dv))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def gate_bwd(dy, o, qkvz, w_n, dims, eps, interpret=False, caps=None):
    """:func:`gate`'s transpose -> (``do`` like ``o``, a fresh array like
    ``qkvz`` whose z columns hold ``dz`` — the others are for
    :func:`mix_bwd` to fill —, ``d w_n`` [dv] float32)."""
    b, s, vw = o.shape
    dv, z_at = dims[3], 2 * _widths(dims)[0] + vw
    rows, w = _blocks(s, vw, z_at, dv, o.dtype.itemsize, 5, caps)
    blocks, across = s // rows, vw // w
    rows_of = lambda shift: pl.BlockSpec(
        (1, rows, w), lambda i, r, j: (i, r, shift + j))
    do, buffer, dw = pl.pallas_call(
        functools.partial(_gate_bwd_kernel, head=dv, eps=eps),
        grid=(b, blocks, across),
        in_specs=[rows_of(0), rows_of(0), rows_of(z_at // w),
                  pl.BlockSpec((1, dv), lambda i, r, j: (0, 0))],
        out_specs=[rows_of(0), rows_of(z_at // w), pl.BlockSpec(
            (1, 1, dv), lambda i, r, j: ((i * blocks + r) * across + j, 0,
                                         0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(qkvz.shape, qkvz.dtype),
                   jax.ShapeDtypeStruct((b * blocks * across, 1, dv),
                                        jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=30 * o.size, transcendentals=o.size,
            bytes_accessed=5 * o.size * o.dtype.itemsize),
        name="gdn_gate_bwd",
    )(dy, o, qkvz, w_n.astype(jnp.float32).reshape(1, dv))
    return do, buffer, dw.sum(axis=(0, 1))


# ---------------------------------------------------------------------------
# the layer's middle
# ---------------------------------------------------------------------------


def _middle(qkvz, taps, g, beta, w_n, dims, l2_eps, norm_eps, interpret,
            keep):
    """``(y, o, what the rule's backward reads)``; with ``keep`` the rule's
    forward also writes the state in front of every chunk."""
    hk, hv, dk, dv = dims
    b, s, _ = qkvz.shape
    q, k, v = mix(qkvz, taps, dims, l2_eps=l2_eps, interpret=interpret)
    o, (*operands, states) = gd._forward(
        q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk),
        v.reshape(b, s, hv, dv), g, beta, gd.CHUNK, True, interpret, keep)
    o = o.reshape(b, s, hv * dv)
    if keep:
        # tagged as the rule's own forward tags them — a remat policy that
        # keeps the flash kernels' ``o`` and ``lse`` keeps these, and the
        # replay runs no ``gdn_fwd`` (q, k, v are not kept: the replay is
        # ``gdn_mix`` again) — but ``o`` on the flat rows, as the kernel
        # wrote it and ``gdn_gate_bwd`` reads it: kept as ``[b, s, hv, dv]``
        # it is another tiling, and a copy
        o = checkpoint_name(o, gd.KEPT_O)
        states = checkpoint_name(states, gd.KEPT_LSE)
    y = gate(o, qkvz, w_n, dims, norm_eps, interpret)
    return y, o, (*operands, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rows(qkvz, taps, g, beta, w_n, dims, l2_eps, norm_eps, interpret):
    return _middle(qkvz, taps, g, beta, w_n, dims, l2_eps, norm_eps,
                   interpret, False)[0]


def _rows_fwd(qkvz, taps, g, beta, w_n, dims, l2_eps, norm_eps, interpret):
    y, o, kept = _middle(qkvz, taps, g, beta, w_n, dims, l2_eps, norm_eps,
                         interpret, True)
    # (two empty arrays carry the scalars' dtypes to the transpose)
    like = (jnp.zeros((0,), g.dtype), jnp.zeros((0,), beta.dtype))
    return y, (qkvz, taps, w_n, o, (*kept, *like))


def _rows_bwd(dims, l2_eps, norm_eps, interpret, residuals, dy):
    qkvz, taps, w_n, o, kept = residuals
    b, s, _ = o.shape
    hv, dv = dims[1], dims[3]
    do, buffer, d_w_n = gate_bwd(dy, o, qkvz, w_n, dims, norm_eps, interpret)
    dq, dk, dv_, d_g, d_beta = gd._gated_delta_bwd(
        gd.CHUNK, True, interpret, kept, do.reshape(b, s, hv, dv))
    flat = lambda t: t.reshape(b, s, -1)
    d_qkvz, d_taps = mix_bwd(flat(dq), flat(dk), flat(dv_), qkvz, taps,
                             buffer, dims, l2_eps=l2_eps,
                             interpret=interpret)
    return (d_qkvz, d_taps.astype(taps.dtype), d_g, d_beta,
            d_w_n.astype(w_n.dtype))


_rows.defvjp(_rows_fwd, _rows_bwd)


def gated_delta_rows(qkvz, taps, g, beta, w_n, dims, *, l2_eps: float,
                     norm_eps: float, interpret: bool = False):
    """A Gated DeltaNet layer from its fused projection to its
    out-projection's operand: ``qkvz`` [b, s, 2 hk dk + 2 hv dv], ``taps``
    [n, 2 hk dk + hv dv], ``g`` (log decay) / ``beta`` [b, s, hv] float32,
    ``w_n`` [dv], ``dims`` ``(hk, hv, dk, dv)`` -> y [b, s, hv dv] in
    ``qkvz.dtype``.  The caller gates on :func:`rows_supported`."""
    if not rows_covered(qkvz.shape[1], dims, taps.shape[0], qkvz.dtype):
        raise ValueError(
            f"gated_delta_rows covers heads that are whole 128-lane tiles "
            f"alone or in blocks of up to {gd.MAX_HEADS_PER_BLOCK} (128 x 128 "
            f"or 96 x 192) and sequences of whole {_CANDIDATES[-1]}-row blocks "
            f"in bfloat16 or float32 under at most {_TILE + 1} taps, not seq "
            f"{qkvz.shape[1]} "
            f"x (hk, hv, dk, dv) {dims} x {taps.shape[0]} taps in "
            f"{qkvz.dtype}; it has no fallback")
    return _rows(qkvz, taps, g, beta, w_n, tuple(dims), float(l2_eps),
                 float(norm_eps), bool(interpret))
