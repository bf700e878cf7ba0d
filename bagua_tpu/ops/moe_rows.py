"""The dropless expert layer's row movement (``rows_in``, ``rows_sum``) — two
Pallas TPU kernels in place of XLA's gather fusions and the ``[T, k, d]``
intermediates behind them.

A dropless layer moves rows four times (``model_parallel/moe/layer.py``):
tokens into the grouped-matmul layout, that move's transpose, the layout's
rows back to their tokens under the gates, and that one's transpose.  Two of
the four write the large side (``[R, d]``, the layout) from the small one
(``[T, d]``, the tokens), two reduce the large side into the small one.  XLA
compiles each to a ``kCustom`` gather at about a third of the HBM's rate, and
the reducing pair writes a ``[T, k, d]`` array that a second fusion reads
back (PERF.md §5).  Here each is one pass over the bytes it needs:

``rows_in(x [T, d], src [R]) -> [R, d]``
    ``out[p] = x[src[p]]``, zero where ``src[p] == T``; with ``weights`` the
    row times ``weights[p]`` in float32, rounded once; with ``dot`` also the
    float32 products ``x[src[p]] . dot[p]`` a row (the gates' gradient).  The
    SOURCE is resident in VMEM (copied in once, a zero row behind it for the
    sentinel) and the result streams out in row blocks: a row is picked by a
    dynamic sublane index, one strided ``vld`` per eight lane tiles.
``rows_sum(y [R, d], dest [R], n) -> [n, d]``
    ``out[t] = sum of weights[p] * y[p] over the p with dest[p] == t``,
    accumulated in float32 and rounded once; ``dest[p] == n`` drops the row.
    The RESULT's float32 accumulator is resident in VMEM and the large side
    streams in, in row blocks, read once: no ``[T, k, d]`` array exists.

Both walk every slot of the static layout whatever it holds — a padding slot
reads the zero row, or adds into a row behind the accumulator that is never
written out — so their time is a function of the shapes alone.

**Why by slot and not by token.**  Mosaic moves no single row between HBM
and VMEM (a DMA's slice is whole tiles: 8 rows of float32, 16 of bf16), so a
kernel cannot pick rows out of an array that does not fit VMEM; the large
side must stream in order.  ``rows_sum`` therefore takes the layout's inverse
map (``dest``: slot -> token) where ``y[slots]`` took the forward one, and
adds a token's rows in slot order (ascending expert), not in ``j`` order:
the same float32 terms, one rounding, a last-bit difference at most.

**bf16 rows come in pairs.**  Two bf16 rows share the sublanes of a 32-bit
word, so the kernels read and write the tensors through their ``uint32``
view (``[rows / 2, d]``: word ``(i, c)`` holds rows ``2 i`` in its low and
``2 i + 1`` in its high half), pick a half by shift and mask, and put a pair
together with an ``or`` — integer work on the VPU beside the loads.  The
low half shifted up sixteen bits IS the row in float32.

A one-row value ``[1, w]`` read at a dynamic sublane lives a lane tile a
sublane, eight lane tiles a vector register, so wide rows cost few
instructions; what binds is the scalar unit's address arithmetic, which is
why every tensor is handed over as ``[rows / 8, 8, d]`` (:data:`_TILE`) and
``rows_sum``'s accumulator as ``[rows, w / 128, 128]``.  The resident side
may take all of the core's VMEM but a margin (:func:`_vmem_ceiling`; Mosaic's
default scope would not hold it); where it does not fit, the columns are cut
into the widest blocks that do (:func:`column_block`), each a pass of its own
over the index vectors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gmm import _vmem_capacity, _vmem_limit
from .tiles import LANE, _CANDIDATES, divisor_blocks

#: rows of a tile, which is how every tensor is handed to the kernels
#: (``[rows / 8, 8, d]``, a bitcast of the ``[rows, d]`` array in XLA's tiled
#: layout): a row is then ``[tile, row in tile]``, two numbers that XLA
#: works out for all slots at once, and the kernel's scalar unit — which
#: binds, not the loads — adds one product a row where a flat index costs
#: it a dozen shifts and masks
_TILE = 8
#: rows a loop step handles in straight-line code: two bf16 tiles of the
#: large side, one tile of their 32-bit pairs.  ``rows_sum`` reads, adds
#: and writes back a tile's rows together, so within an aligned tile of
#: slots no two may name the same destination (a block-aligned layout holds
#: a token at most once a group, and a group is whole tiles; the sentinel
#: may repeat: its row is never read out)
_STEP = 2 * _TILE
#: the zero rows behind ``rows_in``'s resident source, the dropped rows
#: behind ``rows_sum``'s accumulator
_TAIL = _TILE
_HIGH = 0xFFFF0000
#: slots of an index vector's SMEM block: XLA's tile of a 1-D int32 array
_INDEX_BLOCK = 1024
#: rows of ``rows_sum``'s result that are rounded and sent out together
_OUT_CHUNK = 512


def _row_block(rows: int) -> int:
    """Slots a grid step: the tallest of ``ops/tiles.py``'s candidates that
    divides ``rows`` and an index block; 0 where none does."""
    return next((b for b in _CANDIDATES
                 if rows % b == 0 and _INDEX_BLOCK % b == 0), 0)


def _vmem_ceiling() -> int:
    """What a row kernel may ask of Mosaic: all of the core's VMEM but 24
    megabytes (104 of a v5e's 128 MiB) — the resident side is the whole
    point, and nothing else runs on the core while a call does."""
    return _vmem_capacity() - (24 << 20)


def _in_bytes(t: int, r: int, itemsize: int, with_dot: bool):
    """``rows_in``'s VMEM at ``width`` lanes a pass: the source and its
    zero tile, a block of pairs being put together, the weights' column;
    the result's block and ``dot``'s, double-buffered by the pipeline; four
    megabytes of slack."""
    block = _row_block(r)

    def at(width: int) -> int:
        resident = (t + _TAIL) * itemsize + (2 * block if itemsize == 2 else 0)
        streamed = block * itemsize * (2 if with_dot else 1)
        return (width * (resident + 2 * streamed) + block * LANE * 4 * 3
                + (4 << 20))

    return at


def _sum_bytes(n: int, r: int, itemsize: int):
    """``rows_sum``'s: the float32 accumulator (a row is whole registers:
    its lane tiles rounded up to eight), two chunks of the result on their
    way out; ``y``'s blocks, double-buffered; the slack."""
    block = _row_block(r)

    def at(width: int) -> int:
        lanes = -(-width // (LANE * _TILE)) * _TILE
        return ((n + _TAIL) * lanes * LANE * 4
                + width * itemsize * 2 * (min(_OUT_CHUNK, n) + block)
                + (4 << 20))

    return at


def column_block(d: int, bytes_at) -> int:
    """Lanes a pass: the widest 128-multiple divisor of ``d`` at which the
    call's buffers fit :func:`_vmem_ceiling`; 0 where none does."""
    return next((w for w in divisor_blocks(d)
                 if w % LANE == 0 and bytes_at(w) <= _vmem_ceiling()), 0)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _shapes_ok(small: int, r: int, d: int, dtype) -> bool:
    dtype = jnp.dtype(dtype)
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and d % LANE == 0 and small % _STEP == 0 and _row_block(r) > 0)


def rows_in_supported(t: int, r: int, d: int, dtype, *,
                      with_dot: bool = False) -> bool:
    """Whether :func:`rows_in` covers the call: on the TPU, rows of whole
    128-lane tiles in bf16 or float32, whole row blocks of slots, whole
    tiles of source rows, and a source whose columns fit VMEM at all."""
    return (_on_tpu() and _shapes_ok(t, r, d, dtype) and column_block(
        d, _in_bytes(t, r, jnp.dtype(dtype).itemsize, with_dot)) > 0)


def rows_sum_supported(n: int, r: int, d: int, dtype) -> bool:
    """Whether :func:`rows_sum` covers the call (as
    :func:`rows_in_supported`; the resident side is the float32
    accumulator and the result)."""
    return (_on_tpu() and _shapes_ok(n, r, d, dtype) and column_block(
        d, _sum_bytes(n, r, jnp.dtype(dtype).itemsize)) > 0)


def _tiles(x):
    """``[rows, d]`` as ``[rows / 8, 8, d]``."""
    return x.reshape(x.shape[0] // _TILE, _TILE, x.shape[1])


def _index_operands(vectors, fills, block: int):
    """``(operands, BlockSpecs)`` of per-slot int32 vectors: in SMEM, an
    :data:`_INDEX_BLOCK` a time (XLA tiles a 1-D int32 array by 1,024, and
    a block is whole tiles), padded to whole blocks with ``fills``; a grid
    step reads its ``block`` slots from :func:`_index_base` on."""
    steps = _INDEX_BLOCK // block
    spec = pl.BlockSpec((_INDEX_BLOCK,), lambda c, i: (i // steps,),
                        memory_space=pltpu.SMEM)
    pad = -vectors[0].shape[0] % _INDEX_BLOCK
    operands = [jnp.pad(v.astype(jnp.int32), (0, pad), constant_values=fill)
                for v, fill in zip(vectors, fills)]
    return operands, [spec] * len(operands)


def _index_base(block: int):
    return (pl.program_id(1) % (_INDEX_BLOCK // block)) * block


def _row_address(rows, packed: bool):
    """A row number as the kernels read it: ``(tile, row in tile)``, and for
    a bf16 row the 32-bit row of its pair and which half it is."""
    rows = rows.astype(jnp.int32)
    if packed:
        return rows >> 3, (rows >> 1) & 3, rows & 1
    return rows >> 3, rows & 7


def _as_f32(bits):
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _weights_table(weights):
    """``(table with a zero behind it, index)`` of ``weights``."""
    table, index = weights
    return jnp.concatenate([table.astype(jnp.float32).reshape(-1),
                            jnp.zeros((1,), jnp.float32)]), index


def _weight_column(wcol, table_ref, index_ref, base, tiles: int):
    """A block's weights as a column: slot ``p``'s over the 128 lanes of
    row ``p`` of ``wcol`` [tiles, 8, 128], for the products by whole tiles."""
    def step(g, _):
        for u in range(_TILE):
            w = table_ref[index_ref[base + g * _TILE + u]]
            wcol[g, u:u + 1, :] = jnp.full((1, LANE), w, jnp.float32)
        return 0

    jax.lax.fori_loop(0, tiles, step, 0)


def _lane_tiles(width: int):
    return [slice(lane, lane + LANE) for lane in range(0, width, LANE)]


def _in_kernel(*refs, t, weighted, with_dot, packed):
    """One row block of one column pass, in two phases.  By ROW, on the
    scalar unit's addresses: each slot's source row out of the resident
    source into the block (bf16: a pair of slots into one 32-bit row,
    integer work alone).  Then by TILE, the arithmetic: the products with
    ``dot`` folded to a lane tile, the rows times their weights, float32,
    rounded once.  (A 32-bit pattern becomes a float32 only in a tile's
    layout, eight rows a register: Mosaic's ``bitcast`` of a one-row value
    re-lays it a lane tile a register.)

    ``refs``: per slot, in SMEM, its source's tile and row in the tile
    (packed: and the shift that brings its half of the pair in place),
    weighted: its index into the weights' table, and the table; the source
    in HBM; (``dot``'s block;) the result's block (and the products' fold);
    the resident source, (the pairs' block, the weights' column) and the
    copy's semaphore."""
    refs = list(refs)
    tile_ref, sub_ref = refs.pop(0), refs.pop(0)
    shift_ref = refs.pop(0) if packed else None
    index_ref, table_ref = (refs.pop(0), refs.pop(0)) if weighted else (None,) * 2
    x_hbm = refs.pop(0)
    y_ref = refs.pop(0) if with_dot else None
    o_ref = refs.pop(0)
    fold_ref = refs.pop(0) if with_dot else None
    xs = refs.pop(0)
    stage = refs.pop(0) if packed else None
    wcol = refs.pop(0) if weighted else None
    sem = refs.pop(0)
    c, i = pl.program_id(0), pl.program_id(1)
    width = o_ref.shape[-1]
    block = o_ref.shape[0] * (1 if packed else _TILE)
    tiles = block // _TILE

    @pl.when(i == 0)
    def _():
        # a pass's columns of the source, once; a zero tile behind them
        copy = pltpu.make_async_copy(
            x_hbm.at[:, :, pl.ds(pl.multiple_of(c * width, LANE), width)],
            xs.at[pl.ds(0, t // _TILE)], sem)
        copy.start()
        xs[pl.ds(t // _TILE, 1)] = jnp.zeros((1, _TILE, width), xs.dtype)
        copy.wait()

    base = _index_base(block)
    if weighted:
        _weight_column(wcol, table_ref, index_ref, base, tiles)

    if not packed:
        def rows(g, _):                         # a tile of the result
            for u in range(_TILE):
                p = base + g * _TILE + u
                o_ref[g, u:u + 1, :] = xs[tile_ref[p], pl.ds(sub_ref[p], 1), :]
            return 0

        jax.lax.fori_loop(0, tiles, rows, 0)
    else:
        xu = xs.bitcast(jnp.uint32)             # [tiles, 4, width]

        def half(p):
            word = xu[tile_ref[p], pl.ds(sub_ref[p], 1), :]
            return word, shift_ref[p].astype(jnp.uint32)

        def pairs(g, _):                        # two tiles: eight pairs
            for u in range(_TILE):
                p = base + g * _STEP + 2 * u
                (a, down), (b, up) = half(p), half(p + 1)
                stage[g, u:u + 1, :] = (((a >> down) & jnp.uint32(0xFFFF))
                                        | ((b << up) & jnp.uint32(_HIGH)))
            return 0

        jax.lax.fori_loop(0, tiles // 2, pairs, 0)

    rows_a_step = _STEP if packed else _TILE

    def tile(g, _):
        # eight 32-bit rows are sixteen bf16 rows
        at = pl.ds(pl.multiple_of(g * rows_a_step, rows_a_step), rows_a_step)
        got = (pltpu.bitcast(stage[g], o_ref.dtype) if packed else o_ref[g])
        if not (weighted or with_dot):
            o_ref[at, :] = got
            return 0
        wide = got.astype(jnp.float32)
        if with_dot:
            # a row's products folded to one lane tile; XLA sums the 128
            other = (y_ref[at, :] if packed else y_ref[g]).astype(jnp.float32)
            fold_ref[0, at, :] = functools.reduce(jnp.add, [
                wide[:, lanes] * other[:, lanes]
                for lanes in _lane_tiles(width)])
        if weighted:
            w = (wcol[pl.ds(2 * g, 2)].reshape(_STEP, LANE) if packed
                 else wcol[g])
            for lanes in _lane_tiles(width):
                value = (wide[:, lanes] * w).astype(o_ref.dtype)
                if packed:
                    o_ref[at, lanes] = value
                else:
                    o_ref[g, :, lanes] = value
        elif packed:
            o_ref[at, :] = got
        return 0

    if packed or weighted or with_dot:
        jax.lax.fori_loop(0, block // rows_a_step, tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rows_in(x, src, index, table, dot, interpret: bool = False):
    """Jitted: Pallas traces a kernel body anew at every call."""
    t, d = x.shape
    r = src.shape[0]
    weighted, with_dot = table is not None, dot is not None
    packed = x.dtype.itemsize == 2
    block = _row_block(r)
    bytes_at = _in_bytes(t, r, x.dtype.itemsize, with_dot)
    width = column_block(d, bytes_at)
    passes, tiles = d // width, block // _TILE
    # bf16 blocks of the large side are read and written by whole tiles
    # only, as [rows, d]; float32 ones by row, as [rows / 8, 8, d]
    large = (lambda a: a) if packed else _tiles
    if packed:
        rows = pl.BlockSpec((block, width), lambda c, i: (i, c))
        out_shape = [jax.ShapeDtypeStruct((r, d), x.dtype)]
    else:
        rows = pl.BlockSpec((tiles, _TILE, width), lambda c, i: (i, 0, c))
        out_shape = [jax.ShapeDtypeStruct((r // _TILE, _TILE, d), x.dtype)]

    address = _row_address(src, packed)
    if packed:
        # an even slot is its pair's low half: an odd source row comes down
        # sixteen bits; an odd slot the high one: an even row goes up
        tile, sub, odd = address
        address = tile, sub, 16 * (odd ^ (jnp.arange(r) & 1))
    vectors, fills = list(address), [t // _TILE, 0, 0][:len(address)]
    if weighted:
        vectors.append(index)
        fills.append(table.shape[0] - 1)
    operands, in_specs = _index_operands(vectors, fills, block)
    if weighted:
        operands.append(table)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(_tiles(x))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out_specs = [rows]
    scratch = [pltpu.VMEM((t // _TILE + 1, _TILE, width), x.dtype)]
    if packed:
        scratch.append(pltpu.VMEM((tiles // 2, _TILE, width), jnp.uint32))
    if weighted:
        scratch.append(pltpu.VMEM((tiles, _TILE, LANE), jnp.float32))
    if with_dot:
        operands.append(large(dot.astype(x.dtype)))
        in_specs.append(rows)
        out_shape.append(jax.ShapeDtypeStruct((passes, r, LANE), jnp.float32))
        out_specs.append(pl.BlockSpec((1, block, LANE), lambda c, i: (c, i, 0)))
    scratch.append(pltpu.SemaphoreType.DMA(()))
    out = pl.pallas_call(
        functools.partial(_in_kernel, t=t, weighted=weighted,
                          with_dot=with_dot, packed=packed),
        grid=(passes, r // block),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(_vmem_limit(), bytes_at(width))),
        interpret=interpret,
        name="moe_rows_in",
    )(*operands)
    if with_dot:
        return out[0].reshape(r, d), out[1].sum((0, 2))
    return out[0].reshape(r, d)


def rows_in(x, src, weights=None, *, dot=None, interpret: bool = False):
    """``out[p] = x[src[p]]`` for ``x`` [T, d] and ``src`` [R], zero where
    ``src[p] == T`` (a padding slot's source).  ``weights = (table [N],
    index [R])`` multiplies row ``p`` by ``table[index[p]]`` (by zero where
    ``index[p] == N``) in float32, rounded once — the table rides in SMEM,
    so no gather of ``R`` scalars is emitted.  With ``dot`` [R, d] the
    result is ``(out, products [R])``, ``products[p] = sum_d x[src[p], d] *
    dot[p, d]`` in float32 over the UNweighted rows.  No fallback: the
    caller gates on :func:`rows_in_supported`."""
    t, d = x.shape
    if not _shapes_ok(t, src.shape[0], d, x.dtype):
        raise ValueError(
            f"rows_in covers bf16 / float32 rows of whole 128-lane tiles, "
            f"whole tiles of source rows and whole row blocks of slots, not "
            f"{x.dtype}[{t}, {d}] -> [{src.shape[0]}, {d}]; it has no fallback")
    table, index = (None, None) if weights is None else _weights_table(weights)
    return _rows_in(x, src, index, table, dot, interpret)


def _sum_kernel(*refs, n, weighted, packed):
    """One row block of one column pass: each row, times its weight, added
    into the resident float32 accumulator, a tile's rows together — their
    accumulator rows read, added to and written back as one batch, so that
    a tile's loads do not wait for its stores.  The accumulator holds a row
    as ``[width / 128, 128]``, whole registers at a row's own address: a
    one-row value ``[1, width]`` is the same registers (a lane tile a
    sublane), so a bf16 row is its half of the pair's 32-bit row, shifted
    up, reshaped and only then read as float32.  Behind a pass's last
    block the accumulator is rounded and sent out, ``_OUT_CHUNK`` rows a
    copy."""
    refs = list(refs)
    dest_ref = refs.pop(0)
    index_ref, table_ref = (refs.pop(0), refs.pop(0)) if weighted else (None,) * 2
    y_ref, o_hbm, acc, out_buf, sem = refs
    c, i = pl.program_id(0), pl.program_id(1)
    tiles, _, width = y_ref.shape
    lanes = width // LANE
    base = _index_base(tiles * _TILE)

    @pl.when(i == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    yu = y_ref.bitcast(jnp.uint32) if packed else None

    def add(g, _):
        first = base + g * _TILE
        if packed:
            rows = []
            for u in range(_TILE // 2):
                pair = yu[g, u:u + 1, :]
                rows += [_as_f32((pair << 16).reshape(lanes, LANE)),
                         _as_f32((pair & jnp.uint32(_HIGH)).reshape(lanes, LANE))]
        else:
            rows = [y_ref[g, u:u + 1, :].reshape(lanes, LANE)
                    for u in range(_TILE)]
        if weighted:
            rows = [row * table_ref[index_ref[first + u]]
                    for u, row in enumerate(rows)]
        at = [dest_ref[first + u] for u in range(_TILE)]
        sums = [acc[a] + row for a, row in zip(at, rows)]
        for a, total in zip(at, sums):
            acc[a] = total
        return 0

    jax.lax.fori_loop(0, tiles, add, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        chunk = next(k for k in (_OUT_CHUNK, *_CANDIDATES, _STEP) if n % k == 0)
        columns = pl.ds(pl.multiple_of(c * width, LANE), width)

        def copy(j, slot):
            return pltpu.make_async_copy(
                out_buf.at[slot, pl.ds(0, chunk)],
                o_hbm.at[pl.ds(pl.multiple_of(j * chunk, chunk), chunk), columns],
                sem.at[slot])

        def send(j, _):
            slot = j % 2

            @pl.when(j >= 2)
            def _():
                copy(j - 2, slot).wait()

            rows = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
            for lane, columns_of in enumerate(_lane_tiles(width)):
                out_buf[slot, pl.ds(0, chunk), columns_of] = (
                    acc[rows, lane, :].astype(out_buf.dtype))
            copy(j, slot).start()
            return 0

        chunks = n // chunk
        jax.lax.fori_loop(0, chunks, send, 0)
        for j in range(max(chunks - 2, 0), chunks):
            copy(j, j % 2).wait()


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _rows_sum(y, dest, index, table, n: int, interpret: bool = False):
    r, d = y.shape
    weighted = table is not None
    packed = y.dtype.itemsize == 2
    block = _row_block(r)
    tiles = block // _TILE
    bytes_at = _sum_bytes(n, r, y.dtype.itemsize)
    width = column_block(d, bytes_at)
    vectors, fills = [dest], [n]
    if weighted:
        vectors.append(index)
        fills.append(table.shape[0] - 1)
    operands, in_specs = _index_operands(vectors, fills, block)
    if weighted:
        operands.append(table)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(_tiles(y))
    in_specs.append(pl.BlockSpec((tiles, _TILE, width),
                                 lambda c, i: (i, 0, c)))
    scratch = [pltpu.VMEM((n + _TAIL, width // LANE, LANE), jnp.float32),
               pltpu.VMEM((2, min(_OUT_CHUNK, n), width), y.dtype),
               pltpu.SemaphoreType.DMA((2,))]
    return pl.pallas_call(
        functools.partial(_sum_kernel, n=n, weighted=weighted, packed=packed),
        grid=(d // width, r // block),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n, d), y.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(_vmem_limit(), bytes_at(width))),
        interpret=interpret,
        name="moe_rows_sum",
    )(*operands)


def rows_sum(y, dest, n: int, weights=None, *, interpret: bool = False):
    """``out[t] = sum of y[p] over the p with dest[p] == t`` for ``y`` [R, d],
    ``dest`` [R] and ``t < n``, accumulated in float32 in ``p`` order and
    rounded once; ``dest[p] == n`` drops row ``p``.  ``weights`` as in
    :func:`rows_in`.  Within an aligned tile of eight slots no two may share
    a destination under ``n`` (see ``_STEP``).  No fallback: the caller
    gates on :func:`rows_sum_supported`."""
    r, d = y.shape
    if not _shapes_ok(n, r, d, y.dtype):
        raise ValueError(
            f"rows_sum covers bf16 / float32 rows of whole 128-lane tiles, "
            f"whole tiles of result rows and whole row blocks of slots, not "
            f"{y.dtype}[{r}, {d}] -> [{n}, {d}]; it has no fallback")
    table, index = (None, None) if weights is None else _weights_table(weights)
    return _rows_sum(y, dest, index, table, n, interpret)
