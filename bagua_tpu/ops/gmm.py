"""Grouped matrix multiply (``gmm``) — Pallas TPU kernel for dropless MoE.

``gmm(lhs, rhs, group_sizes)`` multiplies contiguous row groups of ``lhs``
[rows, d] by per-group matrices ``rhs`` [groups, d, f], returning [rows, f].
This is the expert-FFN primitive of dropless (capacity-free) MoE routing:
tokens sorted by expert form ragged groups, and no token is dropped no
matter how skewed the routing — the fix for GShard capacity overflow
(the reference's gate drops tokens past ``capacity``,
/root/reference/bagua/torch_api/model_parallel/moe/sharded_moe.py:93-238).

TPU-first design: the kernels work on a block-aligned PADDED layout (each
group's rows rounded up to the 128-row MXU tile), in which every row block
belongs to exactly ONE group — a scalar-prefetched per-block group id then
steers the ``rhs`` BlockSpec, so each grid step is a single dense MXU matmul
with no masking.

What each kernel keeps resident and what it streams (Pallas copies no block
whose index the grid step before had; a group's row blocks are consecutive):

``gmm_fwd`` (the forward product, and d_lhs on the matrices as stored)
    grid ``(f / bf, row blocks)``, rows inner.  RESIDENT: the group's
    ``[d, bf]`` slab of its matrix, fetched once per group and ``f`` block —
    the matrices are read once a call.  STREAMED: a ``[128, d]`` row block
    in and a ``[128, bf]`` result block out every step, so the rows are read
    ``f / bf`` times; ``bf`` is the widest block of ``f`` that fits VMEM
    (the whole ``f`` at OLMoE's widths: a 4 MB slab, rows read once).
    d_lhs = cotangent x matrices^T is the same call in its
    transposed-operand form: the stack stays ``[G, d, f]``, the resident
    slab is ``[bd, f]`` of it and the product contracts both last axes —
    the MXU takes a transposed right operand where it lies, so no
    transposed copy of the stack is ever written (``d`` and ``f`` in each
    other's place in everything above).
``gmm_bwd_drhs`` (d_rhs, the grouped outer product)
    grid ``(d / bd, f / bf, row blocks)``, rows innermost.  RESIDENT: the
    group's float32 ``[bd, bf]`` output block, zeroed at the group's first
    row block, accumulated over its rows and written back once.  STREAMED:
    a ``[128, bd]`` and a ``[128, bf]`` row block every step — ``lhs`` is
    read ``f / bf`` times and the cotangent ``d / bd`` times; ``(bd, bf)``
    is the shape that fits VMEM under which that is least (the whole
    ``[d, f]`` at OLMoE's widths: 8 MB of float32, both read once).

The blocks are chosen from what a call can observe — ``d``, ``f``, the
itemsize and the core's VMEM (:func:`_vmem_limit`, which the call also sets
as its scoped limit: Mosaic's default of 16 MiB would be the binding one) —
and the optional ``block_f`` only caps them.

The layout is a value of its own (:class:`PaddedLayout`, from the group
sizes alone), not a detail of one call: a run of products over the same
groups — an expert FFN's two or three, with elementwise work between them —
computes it once, moves its rows in once (:func:`pad_rows`: a gather,
whose transpose is a gather through the layout's inverse map), multiplies
in padded space (:func:`gmm_padded`) and moves the result out once
(:func:`unpad_rows`).
``gmm_padded``'s custom VJP stays in padded space: d_lhs is the forward
kernel contracting the stored matrices' last axis, d_rhs the grouped
outer-product kernel, neither with a scatter, a gather or a transpose.
Padding rows are zero going in, so they come out zero and contribute
nothing to any reduction.  ``gmm`` is pad -> ``gmm_padded`` -> unpad for one
product.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import divisor_blocks

_BLOCK_ROWS = 128


def gmm_reference(lhs, rhs, group_sizes):
    """Dense one-hot reference (test golden; also the CPU fallback)."""
    rows, _ = lhs.shape
    g_of_row = jnp.searchsorted(
        jnp.cumsum(group_sizes), jnp.arange(rows), side="right"
    )
    onehot = jax.nn.one_hot(g_of_row, rhs.shape[0], dtype=lhs.dtype)
    return jnp.einsum(
        "rg,rd,gdf->rf", onehot, lhs, rhs.astype(lhs.dtype)
    ).astype(lhs.dtype)


def _round_up(x, m):
    """Ceiling-round to a multiple; works on ints and traced arrays."""
    return -(-x // m) * m


def _padded_rows(rows: int, n_groups: int, block: int) -> int:
    """Static row count of the padded layout: every group may waste up to
    ``block - 1`` rows."""
    return _round_up(rows + n_groups * (block - 1), block)


class PaddedLayout(NamedTuple):
    """Where rows sorted by group live in the kernels' block-aligned layout.

    ``pos`` [rows]: padded slot of each row; ``src`` [padded rows]: row of
    each slot, ``rows`` (one past the end: :func:`pad_rows` fills zero) for
    a padding slot; ``g_of_block`` [padded rows / block]: group of each row
    block; ``sizes`` [groups].  ``pos`` and ``src`` are each other's
    inverse, so rows move in and out by gathers in both directions of
    autodiff.  Rows beyond ``sizes.sum()`` (an expert-parallel receive
    buffer's empty slots) are not carried in: their ``pos`` points at
    trailing padding slots, which hold zero.
    """

    pos: jax.Array
    src: jax.Array
    g_of_block: jax.Array
    sizes: jax.Array

    @property
    def block_rows(self) -> int:
        return self.src.shape[0] // self.g_of_block.shape[0]


def padded_layout(group_sizes, rows: int, *,
                  block_rows: int = _BLOCK_ROWS) -> PaddedLayout:
    """The layout of ``rows`` rows in groups of ``group_sizes`` (traced:
    routing counts change every step; every shape here is static).  With
    ``block_rows=1`` nothing is padded and both maps are the identity."""
    n_groups = group_sizes.shape[0]
    padded_rows = _padded_rows(rows, n_groups, block_rows)
    sizes = group_sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    padded = _round_up(sizes, block_rows)
    poffs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)])
    # a row moves up by the padding of every group that ends at or before
    # it: an elementwise [rows, G] compare-and-sum, where ``poffs[g_of_row]``
    # would be a gather of ``rows`` scalars — on the TPU a quarter of the
    # time of gathering as many whole rows
    r = jnp.arange(rows, dtype=jnp.int32)
    ends_before = r[:, None] >= offs[None, 1:]
    pos = r + jnp.where(ends_before, (padded - sizes)[None, :], 0).sum(1)
    # slot -> row by block: a block belongs to one group
    starts = jnp.arange(padded_rows // block_rows, dtype=jnp.int32) * block_rows
    g_of_block = jnp.clip(
        jnp.searchsorted(poffs, starts, side="right") - 1, 0, n_groups - 1
    ).astype(jnp.int32)
    within = (starts - poffs[g_of_block])[:, None] + jnp.arange(
        block_rows, dtype=jnp.int32)[None, :]
    src = jnp.where(within < sizes[g_of_block][:, None],
                    offs[g_of_block][:, None] + within, rows).reshape(-1)
    return PaddedLayout(pos, src, g_of_block, sizes)


def take_or_zero(x, idx):
    """``x[idx]`` along the first axis, zero where ``idx`` is ``len(x)``, one
    past the end (a padding slot's source).  The zero is a row appended to
    ``x`` and not a select over the result, which on the TPU is a second
    pass over the gathered rows: ``x`` is the smaller side wherever a
    layout is entered.  This is the body of every move INTO a layout that
    carries the rows as they are (:func:`pad_rows`, :func:`unpad_rows`'
    transpose) on every backend: XLA's gather out of a ``[T, d]`` source
    writes the layout at 550 GB/s, which ``ops/moe_rows.py::rows_in`` does
    not beat (PERF.md §6, PR 48); the weighted move in (the combine's
    transpose) is ``rows_in``'s where it runs."""
    zero = jnp.zeros((1,) + x.shape[1:], x.dtype)
    return jnp.take(jnp.concatenate([x, zero]), idx, axis=0, mode="clip")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def pad_rows(x, src, slots, by_kernel=False):
    """Rows into a layout: ``out[p] = x[src[p]]``, zero where ``src[p]`` is
    ``len(x)`` (:func:`take_or_zero`, on every backend).  ``slots``
    [len(x), m] is the inverse map, the (in-range) ``p`` that read each row
    of ``x``, so the transpose is a gather as well, ``dx[n] = sum_j
    g[slots[n, j]]`` (in float32 where ``m > 1``): no scatter is emitted in
    either direction.  With ``by_kernel`` (the caller's: ``m > 1``, the
    layout block-aligned so that a row of ``x`` is read at most once a
    tile of slots, and ``ops.moe_rows.rows_sum_supported``) the transpose
    is ``ops/moe_rows.py::rows_sum`` over ``src``, which reads ``g`` once
    and writes no ``[len(x), m, d]`` array."""
    return take_or_zero(x, src)


def _pad_rows_fwd(x, src, slots, by_kernel):
    return pad_rows(x, src, slots, by_kernel), (src, slots)


def _pad_rows_bwd(by_kernel, res, g):
    src, slots = res
    if by_kernel:
        from .moe_rows import rows_sum

        return rows_sum(g, src, slots.shape[0]), None, None
    read = g[slots]
    if slots.shape[1] == 1:
        return read[:, 0], None, None
    return read.astype(jnp.float32).sum(1).astype(g.dtype), None, None


pad_rows.defvjp(_pad_rows_fwd, _pad_rows_bwd)


@jax.custom_vjp
def unpad_rows(y_p, slots, src):
    """Rows out of a layout, :func:`pad_rows`' transpose for ``m = 1``:
    ``out[n] = y_p[slots[n]]``; ``src`` [len(y_p)] is the row that reads
    each slot, ``len(slots)`` for a slot none reads, whose cotangent is
    zero."""
    return y_p[slots]


def _unpad_rows_fwd(y_p, slots, src):
    return unpad_rows(y_p, slots, src), src


def _unpad_rows_bwd(src, g):
    return take_or_zero(g, src), None, None


unpad_rows.defvjp(_unpad_rows_fwd, _unpad_rows_bwd)


def _vmem_capacity() -> int:
    """The core's VMEM; where Pallas knows no chip (interpret mode, a
    compile for a described chip) the v5e's 128 MiB."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return 128 << 20


def _vmem_limit() -> int:
    """The scoped VMEM a call asks of Mosaic: half of the core's, never
    under the compiler's own default of 16 MiB (all of a v4's)."""
    return max(_vmem_capacity() // 2, 16 << 20)


def _fits(block_bytes: int, vmem_limit: int) -> bool:
    """A quarter of the limit stays free for the compiler's own scratch."""
    return 4 * block_bytes <= 3 * vmem_limit


def _blocks_under(dim: int, cap: int) -> list[int]:
    """``dim``'s candidate blocks of at most ``cap``, widest first; the
    narrowest alone where ``cap`` is under all of them."""
    blocks = divisor_blocks(dim)
    return [b for b in blocks if b <= cap] or blocks[-1:]


def _fwd_block_f(d, f, block_rows, itemsize, cap, vmem_limit) -> int:
    """The widest block of ``f`` (at most ``cap``) whose blocks fit: matrix
    slab, row block and result block, each double-buffered by the pipeline,
    and the float32 product before it is cast.  The wider, the fewer times
    the rows are streamed (``f / bf``) and the fewer grid steps."""
    def block_bytes(bf):
        return (2 * itemsize * (d * bf + block_rows * d + block_rows * bf)
                + 4 * block_rows * bf)
    blocks = _blocks_under(f, cap)
    return next((bf for bf in blocks if _fits(block_bytes(bf), vmem_limit)),
                blocks[-1])


def _drhs_blocks(d, f, block_rows, itemsize, cap, vmem_limit):
    """``(bd, bf)`` of the resident float32 output block, each at most
    ``cap``: of the shapes that fit (the block double-buffered, a product
    as large before it is added, two double-buffered row blocks) the one
    under which the row operands are read least (``lhs`` ``f / bf`` times,
    the cotangent ``d / bd`` times), the wider ``bf`` on a tie; the
    narrowest where none fits."""
    def block_bytes(bd, bf):
        return 3 * 4 * bd * bf + 2 * itemsize * block_rows * (bd + bf)
    shapes = [(bd, bf) for bd in _blocks_under(d, cap)
              for bf in _blocks_under(f, cap)]
    fitting = [s for s in shapes if _fits(block_bytes(*s), vmem_limit)]
    return min(fitting or shapes[-1:],
               key=lambda s: (1 / s[0] + 1 / s[1], -s[1]))


def _fwd_kernel(gid_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    """One row block times its group's matrix block: ``lhs @ rhs``, or
    with ``transpose_rhs`` ``lhs @ rhs^T``, contracting both last axes (the
    MXU takes a transposed right operand as it lies, as in every
    ``q @ k^T`` of the flash kernels)."""
    out_ref[:] = jax.lax.dot_general(
        lhs_ref[:], rhs_ref[0],
        (((1,), (1 if transpose_rhs else 0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


def _fwd_grid_spec(padded_rows, d, f, block_rows, bf, transpose_rhs=False):
    """``f`` blocks outer, row blocks inner: over a group's consecutive row
    blocks the matrix index ``(gid[i], 0, j)`` does not change, and Pallas
    copies no block whose index the step before had — a group's ``[d, bf]``
    slab is fetched once per ``f`` block, not once per row block.  With
    ``transpose_rhs`` the matrices are ``[G, f, d]`` as stored and the slab
    is ``[bf, d]`` at ``(gid[i], j, 0)``: the same walk."""
    rhs_spec = (pl.BlockSpec((1, bf, d), lambda j, i, gid: (gid[i], j, 0))
                if transpose_rhs else
                pl.BlockSpec((1, d, bf), lambda j, i, gid: (gid[i], 0, j)))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(f // bf, padded_rows // block_rows),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda j, i, gid: (i, 0)),
            rhs_spec,
        ],
        out_specs=pl.BlockSpec((block_rows, bf), lambda j, i, gid: (i, j)),
    )


def _gmm_padded(lhs_p, rhs, g_of_block, block_rows, block_f, interpret,
                transpose_rhs=False):
    """lhs_p: [padded_rows, d] (group-blocked) times rhs: [G, d, f], or
    with ``transpose_rhs`` times the transposes of rhs: [G, f, d], read as
    stored.  Either way the result is [padded_rows, f]."""
    padded_rows, d = lhs_p.shape
    f = rhs.shape[1 if transpose_rhs else 2]
    vmem_limit = _vmem_limit()
    bf = _fwd_block_f(d, f, block_rows, lhs_p.dtype.itemsize, block_f or f,
                      vmem_limit)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, transpose_rhs=transpose_rhs),
        grid_spec=_fwd_grid_spec(padded_rows, d, f, block_rows, bf,
                                 transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((padded_rows, f), lhs_p.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="gmm_fwd",
    )(g_of_block, lhs_p, rhs)


def _drhs_kernel(gid_ref, lhs_ref, g_ref, out_ref):
    k = pl.program_id(2)
    gid = gid_ref[k]
    prev_same = jnp.logical_and(k > 0, gid_ref[jnp.maximum(k - 1, 0)] == gid)

    @pl.when(jnp.logical_not(prev_same))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += jax.lax.dot_general(
        lhs_ref[:], g_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[None].astype(out_ref.dtype)


def _drhs_grid_spec(padded_rows, d, f, block_rows, bd, bf):
    """Row blocks innermost: the output block ``(gid[k], i, j)`` is the
    resident one, accumulated over a group's consecutive row blocks and
    written back once per group."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // bd, f // bf, padded_rows // block_rows),
        in_specs=[
            pl.BlockSpec((block_rows, bd), lambda i, j, k, gid: (k, i)),
            pl.BlockSpec((block_rows, bf), lambda i, j, k, gid: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, bd, bf), lambda i, j, k, gid: (gid[k], i, j)),
    )


def _gmm_drhs_padded(lhs_p, gout_p, n_groups, d, f, g_of_block, block_rows,
                     block_f, interpret):
    """d_rhs[g] = lhs_g^T @ gout_g over padded row blocks: [G, d, f] f32."""
    padded_rows = lhs_p.shape[0]
    vmem_limit = _vmem_limit()
    bd, bf = _drhs_blocks(d, f, block_rows, lhs_p.dtype.itemsize,
                          block_f or max(d, f), vmem_limit)
    return pl.pallas_call(
        _drhs_kernel,
        grid_spec=_drhs_grid_spec(padded_rows, d, f, block_rows, bd, bf),
        out_shape=jax.ShapeDtypeStruct((n_groups, d, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="gmm_bwd_drhs",
    )(g_of_block, lhs_p, gout_p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm_padded_vjp(lhs_p, rhs, layout, block_f, interpret):
    return _gmm_padded(lhs_p, rhs.astype(lhs_p.dtype), layout.g_of_block,
                       layout.block_rows, block_f, interpret)


def _gmm_padded_fwd(lhs_p, rhs, layout, block_f, interpret):
    out_p = _gmm_padded_vjp(lhs_p, rhs, layout, block_f, interpret)
    return out_p, (lhs_p, rhs, layout)


def _gmm_padded_bwd(block_f, interpret, res, gout_p):
    lhs_p, rhs, layout = res
    n_groups, d, f = rhs.shape
    # d_lhs = gout @ rhs^T (same grouped structure), the matrices read as
    # they are stored: no transposed copy of the stack is written
    dlhs_p = _gmm_padded(
        gout_p, rhs.astype(gout_p.dtype), layout.g_of_block,
        layout.block_rows, block_f, interpret, transpose_rhs=True,
    )
    drhs = _gmm_drhs_padded(lhs_p, gout_p, n_groups, d, f, layout.g_of_block,
                            layout.block_rows, block_f, interpret)
    # an empty group owns no row blocks, so its output block is never
    # written — select zero rather than uninitialized memory
    drhs = jnp.where((layout.sizes > 0)[:, None, None], drhs, 0.0)
    return dlhs_p, drhs.astype(rhs.dtype), None


_gmm_padded_vjp.defvjp(_gmm_padded_fwd, _gmm_padded_bwd)


def gmm_padded(lhs_p, rhs, layout: PaddedLayout, *,
               block_f: int | None = None, interpret: bool = False):
    """Grouped matmul in padded space: ``lhs_p`` [padded rows, d] laid out
    by ``layout`` (padding rows zero) times ``rhs`` [G, d, f] -> [padded
    rows, f], padding rows zero.  Differentiable in ``lhs_p`` and ``rhs``
    without leaving the layout; a cotangent's padding rows add nothing to
    d_rhs (``lhs_p`` is zero there), and d_lhs reads ``rhs`` as it is
    stored (no transposed copy of it is made).  ``block_f`` caps the
    kernels' blocks of ``d`` and ``f`` (default: as wide as VMEM holds).  On
    an unpadded layout (``block_rows == 1``: :func:`kernel_layout` where no
    kernel runs) it is the dense reference."""
    if layout.block_rows == 1:
        return gmm_reference(lhs_p, rhs, layout.sizes)
    return _gmm_padded_vjp(lhs_p, rhs, layout, block_f, interpret)


def _use_kernel(rows: int, d: int, f: int, block_rows: int) -> bool:
    return (
        jax.default_backend() == "tpu"
        and d % 128 == 0
        and f % 128 == 0
        and rows >= block_rows
    )


def kernel_layout(group_sizes, rows: int, d: int, f: int) -> PaddedLayout:
    """The layout a run of ``[rows, d] x [G, d, f]`` products (and of
    ``[rows, f] x [G, d, f]^T``, their d_lhs and an FFN's way back) lives
    in: block-aligned where the kernels run, the sorted rows themselves
    (``block_rows=1``) where the dense fallback does."""
    block = _BLOCK_ROWS if _use_kernel(rows, d, f, _BLOCK_ROWS) else 1
    return padded_layout(group_sizes, rows, block_rows=block)


def gmm(lhs, rhs, group_sizes, *, block_rows: int = _BLOCK_ROWS,
        block_f: int | None = None, interpret: bool = False,
        force: bool = False):
    """Grouped matmul: rows of ``lhs`` [rows, d], sorted so group ``g``
    occupies ``group_sizes[:g].sum() : group_sizes[:g+1].sum()``, each
    multiplied by ``rhs[g]`` [d, f].  Differentiable in ``lhs`` and ``rhs``.

    Requires ``d`` and ``f`` to be 128-multiples for the kernel path; falls
    back to the dense one-hot reference off-TPU or for tiny shapes.
    """
    rows, d = lhs.shape
    f = rhs.shape[2]
    if not (force or _use_kernel(rows, d, f, block_rows)):
        return gmm_reference(lhs, rhs, group_sizes)
    layout = padded_layout(group_sizes, rows, block_rows=block_rows)
    lhs_p = pad_rows(lhs, layout.src, layout.pos[:, None])
    out_p = gmm_padded(lhs_p, rhs, layout, block_f=block_f,
                       interpret=interpret)
    return unpad_rows(out_p, layout.pos, layout.src)
