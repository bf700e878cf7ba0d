"""Selective state-space scan — the recurrence of a Mamba-2 layer (Nemotron-H:
four blocks of nine) in its chunked ("state-space duality") form, forward
and backward, as two Pallas TPU kernels (``ssd_fwd`` / ``ssd_bwd``), the same
mathematics in ``jax.numpy`` where the kernels do not run, and the per-token
scan as golden.

Per head ``h`` of width ``P``, with a ``[P, N]`` state ``S`` (``S_0 = 0``),
a step size ``delta_t > 0``, a scalar ``A_h < 0`` and the input and output
maps ``B_t``, ``C_t`` in ``R^N`` of the head's GROUP (head ``h`` reads group
``h // (heads / groups)``):

    S_t = exp(delta_t A_h) S_{t-1} + delta_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

(:func:`reference_ssd_scan` is exactly that, a ``lax.scan`` over the
positions in float32).  The state transition is a scalar times the identity:
no correction of what the state already holds (the gated delta rule's
``k (v - S^T k)^T``, ``ops/gated_delta.py``), so a chunk needs no solve.

The chunked form (chunks of ``Q`` positions, ``s_t`` the running sum of
``delta_r A_h`` inside a chunk: every exponent below is a difference that is
<= 0, no overflow at any decay):

    y_t   = sum_{r <= t} exp(s_t - s_r) (C_t . B_r) delta_r x_r
            + exp(s_t) S_prev C_t + D_h x_t
    S_new = exp(s_Q) S_prev + sum_r exp(s_Q - s_r) delta_r x_r B_r^T

``C B^T`` once a group and chunk, the decay mask once a head, all of it
matrix products; between chunks the state is carried.  The backward pass is
the chunked one too: a reverse walk over the chunks with the state's
cotangent carried, each chunk's incoming state read back from what the
forward pass kept (one ``[heads / groups * P, N]`` a group and chunk, in the
operands' dtype).

Precision: the state, the decays and every accumulation are float32; the
operands of the matrix products are the inputs' dtype (bfloat16 in the
models), as in the flash and ``gdn_*`` kernels.

Kernels: a grid over (batch, group, blocks of 8 chunks) — a step is a GROUP,
not a head: a group's heads share ``C B^T``, and at ``P`` = 64 a head is
half a lane tile, so the heads of a tile of 128 lanes are computed together
and told apart by lane masks.  A grid step walks its chunks in order with
the group's state ``[heads / groups * P, N]`` float32 in VMEM scratch across
the steps of a sequence.  ``x`` / ``y`` are read and written as ``[batch,
seq, heads * P]``, ``B`` / ``C`` as ``[batch, seq, groups * N]``; the
per-position scalars (``delta`` and the running sum ``s``) as ``[batch,
heads, chunks, Q]`` rows.

What sits where (decided on the first chip trace of PR 54 and written down
in PERF.md section 6): ``softplus``, ``delta A`` and its running sum inside
a chunk are XLA's, around the kernels — they are ``[batch, seq, heads]``
float32, 2 MB a layer at 8,192 positions, and as plain ``jax.numpy`` their
transposes (``A_log``, ``dt_bias``, the decay's share of ``delta``'s
gradient) are autodiff's; the ``D x`` skip and its two transposes are inside
the kernels, where ``x`` and ``dy`` are in VMEM anyway: outside they would be
one more pass over both.

Which path runs where: :func:`ssd_scan` runs the kernels where
:func:`ssd_supported` says so (a TPU, a group's heads of whole lane tiles, a
state of whole lane tiles) and the ``jax.numpy`` chunks elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import KEPT_LSE, KEPT_O
from .gated_delta import (
    CHUNKS_PER_BLOCK, NEG_INF, _NN, _NT, _TN, _column, _dot,
    _last_over_lanes, _masks, _padded_chunks, _params, _row,
    _scalars_back, _scalars_by_chunk,
)
from .tiles import LANE

#: positions of a chunk (the family's ``chunk_size``): inside one, matrix
#: products; between two, the carried state
CHUNK = 128


def reference_ssd_scan(x, dt, A, B, C, D):
    """The recurrence position by position, float32: the golden.  ``x``:
    [batch, seq, heads, P]; ``dt`` (the step sizes, > 0): [batch, seq,
    heads]; ``A`` (< 0), ``D``: [heads]; ``B`` / ``C``: [batch, seq, groups,
    N].  -> [batch, seq, heads, P] float32."""
    f32 = jnp.float32
    x, dt, A, B, C, D = (t.astype(f32) for t in (x, dt, A, B, C, D))
    per_group = x.shape[2] // B.shape[2]
    B, C = (jnp.repeat(t, per_group, axis=2) for t in (B, C))

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs            # [b, h, P] / [b, h] / [b, h, N]
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return state, (jnp.einsum("bhpn,bhn->bhp", state, c_t)
                       + D[:, None] * x_t)

    b, _, h, p = x.shape
    state = jnp.zeros((b, h, p, B.shape[-1]), f32)
    _, y = lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


# ---------------------------------------------------------------------------
# one chunk of one group: the kernels' bodies and the jnp form share it
# ---------------------------------------------------------------------------


def _tile(width: int, p: int) -> int:
    """Lanes of the group's ``width`` that are computed together: a head
    where it fills whole lane tiles, a lane tile of heads where heads are
    narrower, and all of the group where neither divides (the ``jax.numpy``
    form at test widths)."""
    if p % LANE == 0:
        return p
    return LANE if width % LANE == 0 and LANE % p == 0 else width


def _by_head(values, shape, p: int, axis: int):
    """A ``shape`` value that is ``values[k]`` (a full value, or a column /
    row that broadcasts) on the lanes (``axis`` 1) or rows (``axis`` 0) of
    head ``k``, heads ``p`` wide."""
    if len(values) == 1:
        return jnp.broadcast_to(values[0], shape)
    head = lax.broadcasted_iota(jnp.int32, shape, axis) // p
    out = values[-1]
    for k in range(len(values) - 2, -1, -1):
        out = jnp.where(head == k, values[k], out)
    return out


def _head_terms(g, s_row, dt_row, masks, like_state):
    """What both passes make of a head's scalars in a chunk: the decay mask
    ``L`` [Q, Q], the intra-chunk map ``M = (C B^T) L delta`` before its
    cast, and the decays to and from the chunk's ends."""
    _, incl, eye = masks
    q = s_row.shape[1]
    s_col, dt_col = _column(s_row, eye), _column(dt_row, eye)
    decay = jnp.exp(jnp.where(incl, s_col - s_row, NEG_INF))      # L
    s_last = s_row[:, q - 1:q]                                     # [1, 1]
    from_start = jnp.exp(s_col)                                    # exp(s_t)
    to_end = jnp.exp(s_last - s_col)                               # exp(s_Q - s_r)
    return dict(decay=decay, m=g * decay * dt_row, from_start=from_start,
                to_end=to_end, write=to_end * dt_col,
                whole=jnp.exp(_last_over_lanes(s_col, like_state)),
                whole_scalar=jnp.exp(s_last))


def _chunk_fwd(x, bm, cm, s_rows, dt_rows, d_row, state, p, emit):
    """One chunk of one group forward.  ``x`` [Q, W] (the group's heads side
    by side), ``bm`` / ``cm`` [Q, N] in the operands' dtype; ``s_rows`` /
    ``dt_rows``: per head of the group a ``[1, Q]`` float32 row (the running
    sum of the log decay inside the chunk, the step sizes); ``d_row`` [1, W]
    float32 (``D`` laid over its head's lanes; an array or the kernel's
    ``Ref``, read a tile of lanes at a time: Mosaic does not slice the
    lanes of a ``[1, W]`` value); ``state`` [W, N] float32.
    ``emit(lanes, y [Q, T] float32, state behind the chunk [T, N])`` is
    called a tile of lanes."""
    f32, dt_ = jnp.float32, x.dtype
    q, w = x.shape
    t = _tile(w, p)
    masks = _masks(q)
    g = _dot(cm, bm, _NT)                                    # C B^T
    inter = _dot(cm, state.astype(dt_), _NT)                 # C S^T  [Q, W]
    for ti in range(w // t):
        lanes = slice(ti * t, (ti + 1) * t)
        x_t = x[:, lanes]
        heads = [_head_terms(g, s_rows[ti * (t // p) + k],
                             dt_rows[ti * (t // p) + k], masks, state)
                 for k in range(t // p)]
        shape = (q, t)
        pick = lambda name, shape=shape, axis=1: _by_head(
            [h[name] for h in heads], shape, p, axis)
        y = (_by_head([_dot(h["m"].astype(dt_), x_t, _NN) for h in heads],
                      shape, p, 1)
             + pick("from_start") * inter[:, lanes]
             + d_row[:, lanes] * x_t.astype(f32))
        written = (x_t.astype(f32) * pick("write")).astype(dt_)
        old = state[lanes, :]
        emit(lanes, y,
             pick("whole", old.shape, 0) * old + _dot(written, bm, _TN))


def _chunk_bwd(x, bm, cm, s_rows, dt_rows, d_row, s_in, dy, d_state, p,
               emit_tile, emit_head):
    """One chunk of one group backward.  ``s_in`` [W, N]: the state in front
    of the chunk, in the operands' dtype; ``dy`` [Q, W]; ``d_state`` [W, N]
    float32: the cotangent of the state behind it.  ``emit_tile(lanes, dx
    [Q, T], the cotangent of the state in front [T, N], sum_t dy x [1,
    T])`` a tile of lanes, ``emit_head(j, d s_row, d dt_row [1, Q])`` a head
    (``d dt_row``: through ``delta x`` alone — the decay's share rides ``d
    s_row``); -> (dB, dC [Q, N]), all float32."""
    f32, dt_ = jnp.float32, x.dtype
    q, w = x.shape
    t = _tile(w, p)
    masks = _masks(q)
    eye = masks[2]
    g = _dot(cm, bm, _NT)
    ds_out = d_state.astype(dt_)
    b_ds = _dot(bm, ds_out, _NT)                             # B dS'^T  [Q, W]
    c_s = _dot(cm, s_in, _NT)                                # C S^T    [Q, W]
    last = lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    d_g = jnp.zeros((q, q), f32)
    d_b = d_c = jnp.zeros(bm.shape, f32)
    for ti in range(w // t):
        lanes = slice(ti * t, (ti + 1) * t)
        x_t, dy_t = x[:, lanes], dy[:, lanes]
        x32, dy32 = x_t.astype(f32), dy_t.astype(f32)
        b_ds_t, c_s_t = b_ds[:, lanes], c_s[:, lanes]
        shape = (q, t)
        lane_head = lax.broadcasted_iota(jnp.int32, shape, 1) // p
        heads, dx_heads = [], []
        for k in range(t // p):
            j = ti * (t // p) + k
            own = lane_head == k if t > p else None
            only = lambda v, own=own: v if own is None else jnp.where(
                own, v, jnp.zeros_like(v))
            h = _head_terms(g, s_rows[j], dt_rows[j], masks, d_state)
            heads.append(h)
            dt_row = dt_rows[j]
            d_l = _dot(only(dy_t), x_t, _NT) * h["decay"]    # (dy x^T) L
            e = d_l * g
            f = e * dt_row
            d_g = d_g + d_l * dt_row
            dx_heads.append(_dot(h["m"].astype(dt_), dy_t, _TN))   # M^T dy
            d_write = jnp.sum(only(x32 * b_ds_t), axis=1, keepdims=True)
            read = jnp.sum(only(dy32 * c_s_t), axis=1, keepdims=True)
            at_end = d_write * h["write"]
            rows = slice(ti * t + k * p, ti * t + (k + 1) * p)
            at_last = (jnp.sum(at_end, keepdims=True) + h["whole_scalar"]
                       * jnp.sum(s_in[rows, :].astype(f32) * d_state[rows, :],
                                 keepdims=True))              # [1, 1]
            d_s = (_row(jnp.sum(f, axis=1, keepdims=True)
                        + h["from_start"] * read - at_end, eye)
                   - jnp.sum(f, axis=0, keepdims=True)
                   + jnp.where(last, at_last, 0.0))
            d_dt = (jnp.sum(e, axis=0, keepdims=True)
                    + _row(d_write * h["to_end"], eye))
            emit_head(j, d_s, d_dt)
        pick = lambda name, shape=shape, axis=1: _by_head(
            [h[name] for h in heads], shape, p, axis)
        write = pick("write")
        dx = (_by_head(dx_heads, shape, p, 1) + write * b_ds_t
              + d_row[:, lanes] * dy32)
        read_out = (dy32 * pick("from_start")).astype(dt_)   # exp(s_t) dy
        written = (x32 * write).astype(dt_)
        d_c = d_c + _dot(read_out, s_in[lanes, :], _NN)
        d_b = d_b + _dot(written, ds_out[lanes, :], _NN)
        old = d_state[lanes, :]
        emit_tile(lanes, dx,
                  pick("whole", old.shape, 0) * old
                  + _dot(read_out, cm, _TN),
                  jnp.sum(dy32 * x32, axis=0, keepdims=True))
    d_g = d_g.astype(dt_)
    return d_b + _dot(d_g, cm, _TN), d_c + _dot(d_g, bm, _NN)


# ---------------------------------------------------------------------------
# the jax.numpy form: the same chunks under vmap and scan
# ---------------------------------------------------------------------------


def _by_group(x, chunks, c, groups):
    """[b, T, groups * W] -> [b, groups, chunks, C, W]."""
    b = x.shape[0]
    return jnp.moveaxis(x.reshape(b, chunks, c, groups, -1), 3, 1)


def _from_group(x):
    """[b, groups, chunks, C, W] -> [b, T, groups * W]."""
    b, groups, chunks, c, w = x.shape
    return jnp.moveaxis(x, 1, 3).reshape(b, chunks * c, groups * w)


def _rows_of(scalars):
    """[heads of the group, Q] -> the list of ``[1, Q]`` rows."""
    return [scalars[j:j + 1] for j in range(scalars.shape[0])]


def _scalars_by_group(x, groups):
    """[b, h, chunks, C] -> [b, groups, chunks, heads of a group, C]."""
    b, h, chunks, c = x.shape
    return jnp.moveaxis(x.reshape(b, groups, h // groups, chunks, c), 2, 3)


def _jnp_fwd(x, bm, cm, s, dt, d_lanes, groups, p):
    """x [b, T, H P], bm / cm [b, T, G N], s / dt [b, H, chunks, C], d_lanes
    [1, H P] -> (y like x, the state in front of every chunk [b, G, chunks,
    W, N] in x's dtype)."""
    chunks, c = s.shape[2:]
    xg, bg, cg = (_by_group(t, chunks, c, groups) for t in (x, bm, cm))
    n = bg.shape[-1]

    def group(xg, bg, cg, s, dt, d_row):
        def step(state, inputs):
            x_c, b_c, c_c, s_c, dt_c = inputs
            ys, states = [], []
            _chunk_fwd(x_c, b_c, c_c, _rows_of(s_c), _rows_of(dt_c), d_row,
                       state, p, lambda _, y, new: (ys.append(y),
                                                    states.append(new)))
            return jnp.concatenate(states, axis=0), (
                jnp.concatenate(ys, axis=1).astype(x.dtype),
                state.astype(x.dtype))

        state = jnp.zeros((xg.shape[-1], n), jnp.float32)
        return lax.scan(step, state, (xg, bg, cg, s, dt))[1]

    per_group = jax.vmap(group, in_axes=(0, 0, 0, 0, 0, 0))
    y, states = jax.vmap(per_group, in_axes=(0, 0, 0, 0, 0, None))(
        xg, bg, cg, _scalars_by_group(s, groups),
        _scalars_by_group(dt, groups), d_lanes.reshape(groups, 1, -1))
    return _from_group(y), states


def _jnp_bwd(x, bm, cm, s, dt, d_lanes, states, dy, groups, p):
    chunks, c = s.shape[2:]
    xg, bg, cg, dyg = (_by_group(t, chunks, c, groups)
                       for t in (x, bm, cm, dy))

    def group(xg, bg, cg, s, dt, d_row, states, dyg):
        def step(d_state, inputs):
            x_c, b_c, c_c, s_c, dt_c, s_in, dy_c = inputs
            dxs, d_states, dds, d_ss, d_dts = [], [], [], [], []
            d_b, d_c = _chunk_bwd(
                x_c, b_c, c_c, _rows_of(s_c), _rows_of(dt_c), d_row, s_in,
                dy_c, d_state, p,
                lambda _, dx, d_in, dd: (dxs.append(dx), d_states.append(d_in),
                                         dds.append(dd)),
                lambda _, d_s, d_dt: (d_ss.append(d_s), d_dts.append(d_dt)))
            return jnp.concatenate(d_states, axis=0), (
                jnp.concatenate(dxs, axis=1), d_b, d_c,
                jnp.concatenate(d_ss, axis=0), jnp.concatenate(d_dts, axis=0),
                jnp.concatenate(dds, axis=1))

        d_state = jnp.zeros(states.shape[1:], jnp.float32)
        return lax.scan(step, d_state, (xg, bg, cg, s, dt, states, dyg),
                        reverse=True)[1]

    per_group = jax.vmap(group)
    dx, d_b, d_c, d_s, d_dt, dd = jax.vmap(
        per_group, in_axes=(0, 0, 0, 0, 0, None, 0, 0))(
            xg, bg, cg, _scalars_by_group(s, groups),
            _scalars_by_group(dt, groups), d_lanes.reshape(groups, 1, -1),
            states, dyg)

    def scalars(t):         # [b, G, chunks, heads of a group, C] -> [b, h, ..]
        b, g, chunks_, hg, c_ = t.shape
        return jnp.moveaxis(t, 3, 2).reshape(b, g * hg, chunks_, c_)

    # dd: [b, G, chunks, 1, W] -> a sum a lane over batch and chunks
    return (_from_group(dx).astype(x.dtype), _from_group(d_b).astype(bm.dtype),
            _from_group(d_c).astype(cm.dtype), scalars(d_s), scalars(d_dt),
            dd.sum((0, 2)).reshape(1, -1))


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, b_ref, c_ref, s_ref, dt_ref, d_ref, y_ref, *rest, c,
                p):
    """A block of chunks of one group, in order."""
    states_ref = rest[0] if len(rest) == 2 else None
    state_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    heads = s_ref.shape[1]

    def chunk(i, carry):
        rows = pl.ds(pl.multiple_of(i * c, c), c)
        state = state_ref[...]
        if states_ref is not None:
            states_ref[0, 0, i] = state.astype(states_ref.dtype)

        def emit(lanes, y, new):
            y_ref[0, rows, lanes] = y.astype(y_ref.dtype)
            state_ref[lanes, :] = new

        _chunk_fwd(x_ref[0, rows, :], b_ref[0, rows, :], c_ref[0, rows, :],
                   [s_ref[0, j, pl.ds(i, 1), :] for j in range(heads)],
                   [dt_ref[0, j, pl.ds(i, 1), :] for j in range(heads)],
                   d_ref, state, p, emit)
        return carry

    lax.fori_loop(0, s_ref.shape[2], chunk, 0)


def _bwd_kernel(x_ref, b_ref, c_ref, s_ref, dt_ref, d_ref, states_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ds_ref, ddt_ref, dd_ref, d_state_ref,
                *, c, p):
    """The same block, its chunks from the last to the first; the grid
    walks the blocks from the last to the first too."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    heads, n = s_ref.shape[1], s_ref.shape[2]

    def chunk(step, carry):
        i = n - 1 - step
        rows = pl.ds(pl.multiple_of(i * c, c), c)

        def emit_tile(lanes, dx, d_in, dd):
            dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
            d_state_ref[lanes, :] = d_in
            dd_ref[0, :, lanes] += dd

        def emit_head(j, d_s, d_dt):
            ds_ref[0, j, pl.ds(i, 1), :] = d_s
            ddt_ref[0, j, pl.ds(i, 1), :] = d_dt

        d_b, d_c = _chunk_bwd(
            x_ref[0, rows, :], b_ref[0, rows, :], c_ref[0, rows, :],
            [s_ref[0, j, pl.ds(i, 1), :] for j in range(heads)],
            [dt_ref[0, j, pl.ds(i, 1), :] for j in range(heads)],
            d_ref, states_ref[0, 0, i], dy_ref[0, rows, :],
            d_state_ref[...], p, emit_tile, emit_head)
        db_ref[0, rows, :] = d_b.astype(db_ref.dtype)
        dc_ref[0, rows, :] = d_c.astype(dc_ref.dtype)
        return carry

    lax.fori_loop(0, n, chunk, 0)


def _specs(block_chunks, c, w, n, heads, blocks, reverse):
    """``BlockSpec``s over the [b, T, H P] and [b, T, G N] tensors, the [b,
    H, chunks, C] scalars, the [1, H P] skip, the [b, G, chunks, W, N]
    states and the [b, 1, H P] per-lane sums, for grid (batch, group, block
    of chunks)."""
    at = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    rows = block_chunks * c
    wide = pl.BlockSpec((1, rows, w), lambda b, g, i: (b, at(i), g))
    maps = pl.BlockSpec((1, rows, n), lambda b, g, i: (b, at(i), g))
    scalar = pl.BlockSpec((1, heads, block_chunks, c),
                          lambda b, g, i: (b, g, at(i), 0))
    skip = pl.BlockSpec((1, w), lambda b, g, i: (0, g))
    states = pl.BlockSpec((1, 1, block_chunks, w, n),
                          lambda b, g, i: (b, g, at(i), 0, 0))
    lane_sums = pl.BlockSpec((1, 1, w), lambda b, g, i: (b, 0, g))
    return wide, maps, scalar, skip, states, lane_sums


# jitted, as the flash kernels' wrappers are: the layers of a model share
# one trace of a kernel body
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _kernel_fwd(x, bm, cm, s, dt, d_lanes, groups, block_chunks, keep_states,
                interpret):
    """x [b, T, H P], bm / cm [b, T, G N], s / dt [b, H, chunks, C], d_lanes
    [1, H P] -> y like x (and the states [b, G, chunks, W, N])."""
    b, h, chunks, c = s.shape
    w, n = x.shape[2] // groups, bm.shape[2] // groups
    wide, maps, scalar, skip, states, _ = _specs(
        block_chunks, c, w, n, h // groups, chunks // block_chunks, False)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [wide]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct((b, groups, chunks, w, n),
                                              x.dtype))
        out_specs.append(states)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, p=x.shape[2] // h),
        grid=(b, groups, chunks // block_chunks),
        in_specs=[wide, maps, maps, scalar, scalar, skip],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((w, n), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_fwd",
    )(x, bm, cm, s, dt, d_lanes)
    return tuple(out) if keep_states else (out[0], None)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _kernel_bwd(x, bm, cm, s, dt, d_lanes, states, dy, groups, block_chunks,
                interpret):
    b, h, chunks, c = s.shape
    w, n = x.shape[2] // groups, bm.shape[2] // groups
    wide, maps, scalar, skip, state_spec, lane_sums = _specs(
        block_chunks, c, w, n, h // groups, chunks // block_chunks, True)
    *grads, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, c=c, p=x.shape[2] // h),
        grid=(b, groups, chunks // block_chunks),
        in_specs=[wide, maps, maps, scalar, scalar, skip, state_spec, wide],
        out_specs=[wide, maps, maps, scalar, scalar, lane_sums],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                   jax.ShapeDtypeStruct(cm.shape, cm.dtype),
                   jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, x.shape[2]), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((w, n), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_bwd",
    )(x, bm, cm, s, dt, d_lanes, states, dy)
    return (*grads, dd.sum(0))


# ---------------------------------------------------------------------------
# the operation
# ---------------------------------------------------------------------------


def _covered(heads: int, head_dim: int, groups: int, state: int) -> bool:
    """Whether the kernels' grid covers the shape: a group's heads fill
    whole 128-lane tiles (a group is a ``BlockSpec``'s lanes of ``[b, T,
    heads * P]``) with a head a whole number of tiles or a tile a whole
    number of heads, and the state's ``N`` is whole tiles."""
    if heads % groups:
        return False
    width = heads // groups * head_dim
    return (width % LANE == 0 and state % LANE == 0
            and (head_dim % LANE == 0 or LANE % head_dim == 0))


def ssd_supported(heads: int, head_dim: int, groups: int, state: int,
                  dtype=jnp.bfloat16) -> bool:
    """Whether the kernels take the call: on a TPU, at shapes their grid
    covers (:func:`_covered`), bfloat16 or float32 operands.  Any sequence
    length: the rows are padded to whole blocks of chunks."""
    return (jax.default_backend() == "tpu"
            and _covered(heads, head_dim, groups, state)
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _forward(x, dt, a, bm, cm, d, c, by_kernel, interpret, keep_states):
    b, seq, h, p = x.shape
    groups, n = bm.shape[2:]
    chunks = _padded_chunks(seq, c)
    pad = chunks * c - seq
    if pad:
        # a zero step writes nothing and decays nothing: the rows behind the
        # sequence change no row of it
        x, dt, a, bm, cm = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, a, bm, cm))
    # s: the log decay's running sum inside each chunk
    s = jnp.cumsum(_scalars_by_chunk(a, chunks, c), axis=-1)
    dt_rows = _scalars_by_chunk(dt, chunks, c)
    d_lanes = jnp.repeat(d.astype(jnp.float32), p)[None]
    flat = (x.reshape(b, chunks * c, h * p),
            bm.reshape(b, chunks * c, groups * n),
            cm.reshape(b, chunks * c, groups * n), s, dt_rows, d_lanes)
    if by_kernel:
        y, states = _kernel_fwd(*flat, groups, min(chunks, CHUNKS_PER_BLOCK),
                                keep_states, interpret)
    else:
        y, states = _jnp_fwd(*flat, groups, p)
    return y.reshape(b, chunks * c, h, p)[:, :seq], flat + (states,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd(x, dt, a, bm, cm, d, c, by_kernel, interpret):
    return _forward(x, dt, a, bm, cm, d, c, by_kernel, interpret, False)[0]


def _ssd_fwd(x, dt, a, bm, cm, d, c, by_kernel, interpret):
    y, (*flat, states) = _forward(x, dt, a, bm, cm, d, c, by_kernel,
                                  interpret, True)
    # tagged like the flash kernels' ``o`` and ``lse`` (and ``gdn_fwd``'s):
    # a remat policy that keeps those keeps these, and the replay does not
    # run ``ssd_fwd`` again
    y = checkpoint_name(y, KEPT_O)
    states = checkpoint_name(states, KEPT_LSE)
    # (the empty arrays carry the shapes and dtypes to the transpose)
    like = tuple(jnp.zeros((0,) + t.shape[2:], t.dtype)
                 for t in (x, dt, a, bm, cm, d))
    return y, (tuple(flat), states, like)


def _ssd_bwd(c, by_kernel, interpret, res, dy):
    (x, bm, cm, s, dt_rows, d_lanes), states, like = res
    b, padded = x.shape[:2]
    h, p = like[0].shape[1:]
    groups, n = like[3].shape[1:]
    seq = dy.shape[1]
    dy = jnp.pad(dy.astype(x.dtype).reshape(b, seq, h * p),
                 ((0, 0), (0, padded - seq), (0, 0)))
    if by_kernel:
        dx, d_b, d_c, d_s, d_dt, dd = _kernel_bwd(
            x, bm, cm, s, dt_rows, d_lanes, states, dy, groups,
            min(s.shape[2], CHUNKS_PER_BLOCK), interpret)
    else:
        dx, d_b, d_c, d_s, d_dt, dd = _jnp_bwd(
            x, bm, cm, s, dt_rows, d_lanes, states, dy, groups, p)
    # s is the log decay's running sum inside a chunk: its transpose runs
    # from the chunk's end
    d_a = _scalars_back(jnp.cumsum(d_s[..., ::-1], axis=-1)[..., ::-1])
    return (dx.reshape(b, padded, h, p)[:, :seq],
            _scalars_back(d_dt)[:, :seq].astype(like[1].dtype),
            d_a[:, :seq].astype(like[2].dtype),
            d_b.reshape(b, padded, groups, n)[:, :seq],
            d_c.reshape(b, padded, groups, n)[:, :seq],
            dd.reshape(h, p).sum(1).astype(like[5].dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = CHUNK,
             interpret: bool = False, force: bool = False):
    """The selective state-space scan over a sequence, chunked, with a
    chunked VJP.  ``x``: [batch, seq, heads, P]; ``dt`` (the step sizes,
    after their ``softplus``: > 0): [batch, seq, heads]; ``A`` (< 0), ``D``:
    [heads]; ``B`` / ``C``: [batch, seq, groups, N] (head ``h`` reads group
    ``h // (heads / groups)``).  -> [batch, seq, heads, P] in ``x.dtype``.

    The kernels run where :func:`ssd_supported` says so; elsewhere the same
    chunks in ``jax.numpy``.  ``force`` skips the platform check (tests run
    the kernels in interpret mode on the CPU); shapes no grid covers still
    take the ``jax.numpy`` form."""
    h, p = x.shape[2:]
    groups, n = B.shape[2:]
    covered = (_covered(h, p, groups, n) and x.dtype == B.dtype == C.dtype)
    by_kernel = covered and (force or ssd_supported(h, p, groups, n, x.dtype))
    f32 = jnp.float32
    dt = dt.astype(f32)
    # the log decay is plain jax.numpy: A's gradient and the decay's share
    # of dt's are autodiff's
    return _ssd(x, dt, dt * A.astype(f32), B, C, D.astype(f32), int(chunk),
                bool(by_kernel), bool(interpret))
