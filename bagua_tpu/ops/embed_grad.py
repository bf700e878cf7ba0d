"""The token table's lookup and its gradient (``embed_grad``) — a Pallas TPU
kernel in place of XLA's scatter-add.

``token_lookup(table, tokens, dtype)`` is ``nn.Embed``'s forward without the
whole-table cast: it gathers the float32 rows the tokens name and rounds
*those* to ``dtype`` (rounding commutes with a gather: the result is bit-equal
to casting the table first, for a few thousand rows' worth of work instead of
the table's).

Its transpose is ``d_table[v] = sum of the cotangent rows whose token is v``.
XLA emits that as a sort of the ids, a gather of the rows into sorted order
and a ``scatter`` with a bf16 ``add`` combiner: a read-modify-write a row,
1.84 us a row at a ``[37984, 2560]`` table (15.1 ms for 8,192 rows whose
bytes need 0.3, against 0.48 for this call; PERF.md §6, PR 41).
``embed_grad`` keeps the sort and the gather and replaces the scatter by a
**segment product over the sorted rows**: the table is walked in blocks of
``R`` rows, and a block's gradient is

    onehot(ids_chunk - b * R)^T [R, C]  @  rows_chunk [C, d]

summed in float32 over the chunks of ``C`` sorted rows that hold the block's
tokens — products by 0 or 1, exact, on the MXU — rounded ONCE to the output
dtype and written once.  A row that an aligned chunk brings along from a
neighbouring block matches no row of the block's iota, so no range mask is
needed; a block with no token writes zeros.

The walk is a flat grid of (block, chunk) **visits**, planned outside the
kernel by compare-and-sum (:func:`visit_plan`; no gather of scalars) and
scalar-prefetched: consecutive visits of one block keep its float32
accumulator resident, consecutive visits of one chunk do not fetch it twice,
and Pallas' own pipeline moves chunks in and blocks out behind the products.
Every block is visited once and every chunk boundary that falls inside a
block adds one visit, so there are at most ``V / R + T / C - 1`` of them
whatever the tokens are — all equal or all distinct — and the grid has
exactly that static length (the visits past the plan's end do nothing): the
kernel's time does not follow the batch.

Where a cotangent row is not finite the one-hot product spreads it over the
rows of the blocks its chunk serves (0 x NaN), where a scatter would have
kept it to one row; the step's gradient is not finite either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gmm import _fits, _vmem_limit
from .tiles import LANE, _CANDIDATES

#: sorted rows a visit multiplies: one lane vector of ids
_CHUNK = LANE
#: a sorted id that matches no table row: the padding behind the last token
_NO_ROW = jnp.iinfo(jnp.int32).max


def block_rows(d: int, itemsize: int) -> int:
    """Table rows a block holds: the tallest of ``ops/tiles.py``'s candidates
    up to 256 whose buffers fit the scoped VMEM (:func:`ops.gmm._vmem_limit`)
    — the float32 accumulator and a product as large before it is added, the
    output block and the chunk of rows double-buffered by the pipeline.  A
    visit's time goes with the block's height (its product and its pass
    over the accumulator), so the call costs about ``V + R T / C`` row
    passes: 128 / 256 / 512 rows read 0.45 / 0.48 / 0.57 ms at a ``[37984,
    2560]`` table under 8,192 tokens and 0.127 / 0.114 / 0.128 ms at
    ``[30528, 1024]`` under 3,072 (one v5e, the call alone; PERF.md §6, PR
    41)."""
    limit = _vmem_limit()

    def block_bytes(r):
        return 2 * 4 * r * d + 2 * itemsize * (r + _CHUNK) * d

    blocks = [r for r in _CANDIDATES if r <= 256]
    return next((r for r in blocks if _fits(block_bytes(r), limit)),
                blocks[-1])


def visit_plan(ids, vocab: int, rows_per_block: int):
    """The kernel's walk over ``ids`` [T] (any order; ``T`` whole chunks of
    ``C`` = :data:`_CHUNK`; padding carries :data:`_NO_ROW`): ``(block [G],
    chunk [G], total [1])`` int32, visit ``i < total`` adds sorted chunk
    ``chunk[i]``
    into table block ``block[i]``.  Blocks ascend, a block's chunks ascend,
    every block has at least one visit (an empty block reads a chunk that
    holds none of its rows and writes zeros), and ``G = cdiv(vocab,
    rows_per_block) + T / C - 1`` is the most the plan can need.  Visits
    past ``total`` repeat the last block and chunk.  All by elementwise
    compares and sums over ``[G, blocks]``: a few hundred by a few hundred."""
    chunk = _CHUNK
    n_blocks = pl.cdiv(vocab, rows_per_block)
    n_chunks = ids.shape[0] // chunk
    n_visits = n_blocks + n_chunks - 1
    # starts[b]: sorted position of the first id >= b * R = how many are under
    bounds = jnp.arange(n_blocks + 1, dtype=jnp.int32) * rows_per_block
    starts = jnp.sum(ids[None, :] < bounds[:, None], axis=1, dtype=jnp.int32)
    lo = jnp.minimum(starts[:-1] // chunk, n_chunks - 1)
    hi = jnp.clip((starts[1:] - 1) // chunk, lo, n_chunks - 1)
    count = hi - lo + 1
    first = jnp.cumsum(count) - count            # a block's first visit
    total = first[-1] + count[-1]
    i = jnp.arange(n_visits, dtype=jnp.int32)
    block = jnp.sum(first[None, :] <= i[:, None], axis=1,
                    dtype=jnp.int32) - 1
    mine = block[:, None] == jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
    chunk_of = i + jnp.sum(jnp.where(mine, (lo - first)[None, :], 0), axis=1,
                           dtype=jnp.int32)
    return block, jnp.minimum(chunk_of, hi[-1]), total[None]


def _kernel(block_ref, chunk_ref, total_ref, ids_ref, rows_ref, out_ref,
            acc_ref):
    i = pl.program_id(0)
    last_visit = pl.num_programs(0) - 1
    b = block_ref[i]
    opens = jnp.logical_or(i == 0, block_ref[jnp.maximum(i - 1, 0)] != b)
    closes = jnp.logical_or(
        i == total_ref[0] - 1,
        block_ref[jnp.minimum(i + 1, last_visit)] != b)

    @pl.when(i < total_ref[0])
    def _():
        r, c = acc_ref.shape[0], rows_ref.shape[0]
        ids = ids_ref[pl.ds(chunk_ref[i], 1), :]                  # [1, C]
        row = jax.lax.broadcasted_iota(jnp.int32, (r, c), 0) + b * r
        onehot = (row == ids).astype(rows_ref.dtype)              # [R, C]
        part = jnp.dot(onehot, rows_ref[...],
                       preferred_element_type=jnp.float32)

        @pl.when(opens)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(opens))
        def _():
            acc_ref[...] += part

        @pl.when(closes)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _segment_product(sorted_ids, sorted_rows, vocab, out_dtype, interpret):
    t, d = sorted_rows.shape
    chunk = _CHUNK
    r = block_rows(d, sorted_rows.dtype.itemsize)
    block, chunk_of, total = visit_plan(sorted_ids, vocab, r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(block.shape[0],),
        in_specs=[
            # every chunk's ids, resident: a visit reads one lane vector
            pl.BlockSpec((t // chunk, chunk), lambda i, *_: (0, 0)),
            pl.BlockSpec((chunk, d), lambda i, blk, chk, tot: (chk[i], 0)),
        ],
        out_specs=pl.BlockSpec((r, d), lambda i, blk, chk, tot: (blk[i], 0)),
        scratch_shapes=[pltpu.VMEM((r, d), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vocab, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit()),
        interpret=interpret,
        name="embed_grad",
    )(block, chunk_of, total, sorted_ids.reshape(t // chunk, chunk),
      sorted_rows)


@functools.partial(jax.jit, static_argnames=("vocab", "out_dtype",
                                              "interpret"))
def embed_grad(ids, rows, *, vocab: int, out_dtype=None,
               interpret: bool = False):
    """``zeros([vocab, d]).at[ids].add(rows)``: the sum of ``rows`` [T, d] by
    the table row ``ids`` [T] names, in float32, rounded once to
    ``out_dtype`` (default: ``rows``'); an id outside ``[0, vocab)`` adds
    nothing.  ``d`` must be a multiple of 128.  Jitted: Pallas traces a
    kernel body anew at every call."""
    t, d = rows.shape
    ids = ids.astype(jnp.int32)
    pad = -t % _CHUNK
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), _NO_ROW, jnp.int32)])
    sorted_ids, order = jax.lax.sort(
        (ids, jnp.arange(t + pad, dtype=jnp.int32)), num_keys=1)
    # a gather INTO sorted order runs at the write rate; the padding reads
    # the last row, whose id matches nothing
    sorted_rows = jnp.take(rows, order, axis=0, mode="clip")
    return _segment_product(sorted_ids, sorted_rows, vocab,
                            out_dtype or rows.dtype, interpret)


def grad_kernel_supported(d: int) -> bool:
    """Whether :func:`token_lookup`'s gradient runs ``embed_grad``: on the
    TPU, rows of whole 128-lane tiles — the test ``ops.gmm._use_kernel``
    and ``flash_supported`` make."""
    return jax.default_backend() == "tpu" and d % LANE == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lookup_by_kernel(table, tokens, dtype, interpret):
    return jnp.take(table, tokens, axis=0).astype(dtype)


def _lookup_fwd(table, tokens, dtype, interpret):
    # the table rides along for its shape alone: a parameter, alive anyway
    return _lookup_by_kernel(table, tokens, dtype, interpret), (table, tokens)


def _lookup_bwd(dtype, interpret, res, g):
    table, tokens = res
    vocab, d = table.shape
    ids = tokens.reshape(-1)
    ids = jnp.where(ids < 0, ids + vocab, ids)        # ``jnp.take`` wraps
    grad = embed_grad(ids, g.reshape(-1, d), vocab=vocab, interpret=interpret)
    # the update reads it through a fused convert, as it read the scatter's
    return grad.astype(table.dtype), None


_lookup_by_kernel.defvjp(_lookup_fwd, _lookup_bwd)


def token_lookup(table, tokens, dtype, *, interpret: bool = False,
                 force: bool = False):
    """Rows of ``table`` [vocab, d] by ``tokens`` [...], rounded to
    ``dtype``: ``nn.Embed``'s result to the bit (``jnp.take``: a negative
    token wraps, one past the end reads NaN), gathering first and rounding
    the gathered rows.  Where :func:`grad_kernel_supported` (or ``force``:
    tests run the kernel in interpret mode) the table's gradient is
    :func:`embed_grad`, elsewhere ``jnp.take``'s own transpose."""
    if force or grad_kernel_supported(table.shape[1]):
        return _lookup_by_kernel(table, tokens, dtype, interpret)
    return jnp.take(table, tokens, axis=0).astype(dtype)
