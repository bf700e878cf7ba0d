"""Gated delta rule — the linear-attention recurrence of Gated DeltaNet
(three layers of four of Qwen3-Next and of Olmo-Hybrid) in its chunked form,
forward and backward, as two Pallas TPU kernels (``gdn_fwd`` / ``gdn_bwd``),
the same mathematics in ``jax.numpy`` where the kernels do not run, and the
per-token scan as golden.

Per value head, with a ``[d_k, d_v]`` state ``S`` (``S_0 = 0``), a decay
``alpha_t = exp(g_t)`` (``g_t <= 0``) and a write strength ``beta_t`` (in (0,
1) as ``sigmoid(b)``, in (0, 2) as Olmo-Hybrid's ``2 sigmoid(b)``, whose
transitions have eigenvalues down to -1):

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

(:func:`reference_gated_delta_rule` is exactly that, a ``lax.scan`` over the
positions in float32).  A key head serves ``value_heads // key_heads``
consecutive value heads.  ``q`` and ``k`` come in as the layer made them:
L2-normalised, ``q`` scaled.

The chunked form (chunks of ``C`` positions, ``gamma_i`` the running sum of
``g`` inside a chunk, ``u_i = beta_i (v_i - alpha_i S_{i-1}^T k_i)`` the
"delta" a position writes; arXiv:2412.06464, the WY form of arXiv:2406.06484):

    D_ij = exp(gamma_i - gamma_j)  (i >= j)
    A    = strictly-lower(beta_i D_ij k_i.k_j)
    (I + A) U = beta (V - exp(gamma) K S)         unit lower-triangular solve
    O    = exp(gamma) Q S + (D * Q K^T) U
    S'   = exp(gamma_C) S + (exp(gamma_C - gamma) K)^T U

Inside a chunk everything but the solve is a matrix product; between chunks
the state is carried.  The backward pass is the chunked one too: a reverse
walk over the chunks with the state's cotangent carried, each chunk's
incoming state read back from what the forward pass kept (one ``[d_k, d_v]``
a chunk, in the operands' dtype: what the products read of it anyway).

Precision: the state, the decays (every ``exp`` is of a difference that is
<= 0: no overflow at any decay), the solve and every accumulation are
float32; the operands of the matrix products are the inputs' dtype (bfloat16
in the models), as in the flash kernels.

The solve: ``T = (I + A)^-1`` by forward substitution in its outer-product
form — ``C - 1`` steps ``T -= A[:, j] T[j, :]`` on the rows below ``j`` of a
``[C, C]`` float32 value in registers, no reduction in the dependent chain —
inside the kernels, where ``A`` never leaves VMEM.  A Neumann series (``(I - A)(I + A^2)(I + A^4)..``)
would be all matrix products but loses every digit on strongly correlated
keys (``A^k`` grows binomially while the inverse stays O(1)); substitution
is backward-stable.  At write strengths near 2 ``A``'s entries double and
the inverse's are of size 2 with alternating signs all over the chunk:
substitution still holds the scan's numbers there
(``tests/test_qwen3_next.py``'s ``test_the_solve_survives_keys_that_are_
nearly_one_vector``, ``tests/test_olmo_hybrid.py::test_the_rule_holds_the_
probe``, and on the chip the fourth comparison of the Olmo-Hybrid cell).

Kernels: a grid over (batch, block of key heads, blocks of 8 chunks); a grid
step walks its chunks in order for each value head of its key heads (their
chains are independent: the scheduler interleaves them) with the states in
VMEM scratch across the steps of a sequence.  q / k / v / o are read and
written as ``[batch, seq, heads * dim]``, the projections' own layout; the
per-position scalars as ``[batch, value_heads, chunks, C]`` rows.  A
``BlockSpec`` addresses the lanes of such an array by whole 128-lane tiles,
so a block of key heads is the fewest whose lanes (and their value heads')
are whole tiles (:func:`heads_per_block`): one head of 128 lanes, four of 96
under values of 192 — three tiles of keys, six of values — with each head a
static lane slice inside the block; a head count that is no multiple (30 =
7 x 4 + 2) ends in a ragged block whose absent heads are skipped.  No row is
copied or padded in HBM for it and no width is widened.

Which path runs where: :func:`gated_delta_rule` runs the kernels where
:func:`gated_delta_supported` says so (a TPU, heads that are whole lane
tiles alone or, key and value heads as many, in blocks of up to four) and
the ``jax.numpy`` chunks elsewhere.  Where the layer's rows around the rule
are Pallas passes too, ``ops/gated_delta_rows.py`` calls the rule's forward
and backward (:func:`_forward`, :func:`_gated_delta_bwd`) from inside its own
VJP, always by the kernels: the calls and their operands are the same.
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

from .flash_attention import KEPT_LSE, KEPT_O
from .gmm import _vmem_limit
from .tiles import LANE

NEG_INF = -1e30
#: positions of a chunk (the family's kernels'): inside one, matrix products
#: and the solve; between two, the carried state
CHUNK = 64
#: chunks a grid step walks: the per-position scalars' block is ``[8, C]``,
#: a whole sublane tile
CHUNKS_PER_BLOCK = 8

_NT = ((1,), (1,))  # a @ b.T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a.T @ b


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def reference_gated_delta_rule(q, k, v, g, beta):
    """The recurrence position by position, float32: the golden.  ``q`` /
    ``k``: [batch, seq, key_heads, d_k]; ``v``: [batch, seq, value_heads,
    d_v]; ``g`` / ``beta``: [batch, seq, value_heads].  -> [batch, seq,
    value_heads, d_v] float32."""
    group = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(t.astype(jnp.float32), group, axis=2) for t in (q, k))
    v, g, beta = (t.astype(jnp.float32) for t in (v, g, beta))

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x               # [b, h, d] / [b, h]
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        delta = b_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    b, _, h, d_v = v.shape
    state = jnp.zeros((b, h, k.shape[-1], d_v), jnp.float32)
    _, o = lax.scan(step, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------
# one chunk of one value head: the kernels' bodies and the jnp form share it
# ---------------------------------------------------------------------------


def _masks(c: int):
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row > col, row >= col, row == col


def _column(row_vector, eye):
    """``[1, C]`` -> ``[C, 1]``: the diagonal's lanes summed out."""
    return jnp.sum(jnp.where(eye, row_vector, 0.0), axis=1, keepdims=True)


def _row(column_vector, eye):
    """``[C, 1]`` -> ``[1, C]``."""
    return jnp.sum(jnp.where(eye, column_vector, 0.0), axis=0, keepdims=True)


def _last_over_lanes(column, like):
    """The last entry of ``column`` [C, 1] on every lane of a row as wide as
    ``like``, [1, lanes]: by a lane broadcast and a sublane sum, because
    Mosaic broadcasts a ``[1, 1]`` along one of the two tiled axes only."""
    c, lanes = column.shape[0], like.shape[1]
    row = lax.broadcasted_iota(jnp.int32, (c, lanes), 0)
    return jnp.sum(jnp.where(row == c - 1, column, 0.0), axis=0,
                   keepdims=True)


def _solve(a):
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` [C, C] float32 by
    forward substitution, outer-product form: step ``j`` takes ``a[:, j]
    x row j`` off the rows below ``j``, after which row ``j + 1`` is final.
    The rows are kept as sublane tiles of 8 and a step touches only the
    tiles that hold a row below ``j`` (half of them on average: the lane
    broadcast of ``a``'s column is the XLU's work and what a step costs)."""
    c = a.shape[0]
    tile = 8 if c % 8 == 0 else c
    row = lax.broadcasted_iota(jnp.int32, (tile, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (tile, c), 1)
    starts = range(0, c, tile)
    tiles = [jnp.where(col == row + r, 1.0, 0.0).astype(jnp.float32)
             for r in starts]
    a_tiles = [a[r:r + tile, :] for r in starts]
    for j in range(c - 1):
        done = tiles[j // tile][j % tile:j % tile + 1, :]       # row j
        for t in range((j + 1) // tile, len(tiles)):
            tiles[t] = tiles[t] - a_tiles[t][:, j:j + 1] * done
    return jnp.concatenate(tiles, axis=0)


def _key_products(q, k):
    """``(K K^T, Q K^T)`` of a chunk, float32: what the value heads of one
    key head share."""
    return _dot(k, k, _NT), _dot(q, k, _NT)


def _chunk_terms(products, g_row, beta_row):
    """What both passes make of a chunk before they touch the state."""
    kk, qk = products
    c = kk.shape[0]
    strict, incl, eye = _masks(c)
    g_col, beta_col = _column(g_row, eye), _column(beta_row, eye)
    decay = jnp.exp(jnp.where(incl, g_col - g_row, NEG_INF))      # D
    t = _solve(jnp.where(strict, beta_col * decay * kk, 0.0))
    g_last = g_row[:, c - 1:c]                                     # [1, 1]
    return dict(strict=strict, eye=eye, g_col=g_col, beta_col=beta_col,
                decay=decay, kk=kk, qk=qk, t=t, g_last=g_last)


def _chunk_fwd(q, k, v, g_row, beta_row, state, products=None):
    """One chunk forward.  ``q`` / ``k`` [C, d_k], ``v`` [C, d_v] in the
    operands' dtype; ``g_row`` (the running sum of ``g`` inside the chunk) /
    ``beta_row`` [1, C] float32; ``state`` [d_k, d_v] float32; ``products``:
    :func:`_key_products` of the chunk where the caller has them -> (o [C,
    d_v] float32, the state behind the chunk)."""
    dt = k.dtype
    m = _chunk_terms(products or _key_products(q, k), g_row, beta_row)
    t, g_col = m["t"], m["g_col"]
    w = _dot((t * (beta_row * jnp.exp(g_row))).astype(dt), k, _NN)
    u = _dot((t * beta_row).astype(dt), v, _NN)
    s_in = state.astype(dt)
    u = (u - _dot(w.astype(dt), s_in, _NN)).astype(dt)
    o = (jnp.exp(g_col) * _dot(q, s_in, _NN)
         + _dot((m["decay"] * m["qk"]).astype(dt), u, _NN))
    k_out = (jnp.exp(m["g_last"] - g_col) * k.astype(jnp.float32)).astype(dt)
    return o, jnp.exp(_last_over_lanes(g_col, state)) * state + _dot(
        k_out, u, _TN)


def _chunk_bwd(q, k, v, g_row, beta_row, s_in, do, d_state, products=None):
    """One chunk backward.  ``s_in`` [d_k, d_v]: the state in front of the
    chunk, in the operands' dtype; ``do`` [C, d_v]; ``d_state`` [d_k, d_v]
    float32: the cotangent of the state behind it.  -> (dq, dk [C, d_k], dv
    [C, d_v], d g_row, d beta_row [1, C], the cotangent of the state in
    front), all float32."""
    dt, f32 = k.dtype, jnp.float32
    c = k.shape[0]
    m = _chunk_terms(products or _key_products(q, k), g_row, beta_row)
    strict, eye, t, decay = m["strict"], m["eye"], m["t"], m["decay"]
    g_col, beta_col, g_last = m["g_col"], m["beta_col"], m["g_last"]
    a_col, b_col, a_last = (jnp.exp(g_col), jnp.exp(g_last - g_col),
                            jnp.exp(g_last))
    q32, k32 = q.astype(f32), k.astype(f32)
    # the forward's values again
    ks = _dot(k, s_in, _NN)                                  # K S
    x = v.astype(f32) - a_col * ks
    u = _dot((t * beta_row).astype(dt), x.astype(dt), _NN).astype(dt)
    p = (decay * m["qk"]).astype(dt)
    ds_out = d_state.astype(dt)
    # the deltas' cotangent, back through the solve
    du = _dot(p, do, _TN) + _dot((b_col * k32).astype(dt), ds_out, _NN)
    z = _dot(t.astype(dt), du.astype(dt), _TN)               # T^T dU
    zb = beta_col * z
    zb_dt = zb.astype(dt)
    d_a = -_dot(z.astype(dt), u, _NT)                        # [C, C]
    d_p = _dot(do, u, _NT)
    e = jnp.where(strict, d_a * beta_col * decay, 0.0)       # d (K K^T)
    f_p = d_p * decay                                        # d (Q K^T)
    e_dt, f_dt = e.astype(dt), f_p.astype(dt)
    do_st = _dot(do, s_in, _NT)                              # dO S^T
    u_dst = _dot(u, ds_out, _NT)                             # U dS'^T
    dq = a_col * do_st + _dot(f_dt, k, _NN)
    dk = (_dot(e_dt, k, _NN) + _dot(e_dt, k, _TN) + _dot(f_dt, q, _TN)
          + b_col * u_dst - a_col * _dot(zb_dt, s_in, _NT))
    d_beta = (jnp.sum(z * x, axis=1, keepdims=True)
              + jnp.sum(jnp.where(strict, d_a * decay * m["kk"], 0.0),
                        axis=1, keepdims=True))
    # the decays: D's exponent, exp(gamma), exp(gamma_C - gamma), exp(gamma_C)
    f = f_p * m["qk"] + e * m["kk"]
    d_a_col = (jnp.sum(q32 * do_st, axis=1, keepdims=True)
               - jnp.sum(zb * ks, axis=1, keepdims=True))
    d_b_col = jnp.sum(k32 * u_dst, axis=1, keepdims=True)
    d_g = _row(jnp.sum(f, axis=1, keepdims=True) + a_col * d_a_col
               - b_col * d_b_col, eye) - jnp.sum(f, axis=0, keepdims=True)
    at_last = (a_last * jnp.sum(s_in.astype(f32) * d_state, keepdims=True)
               + jnp.sum(b_col * d_b_col, keepdims=True))    # [1, 1]
    lane = lax.broadcasted_iota(jnp.int32, (1, c), 1)
    d_g = d_g + jnp.where(lane == c - 1, at_last, 0.0)
    d_state_in = (jnp.exp(_last_over_lanes(g_col, d_state)) * d_state
                  + _dot((a_col * q32).astype(dt), do, _TN)
                  - _dot((a_col * k32).astype(dt), zb_dt, _TN))
    return dq, dk, zb, d_g, _row(d_beta, eye), d_state_in


# ---------------------------------------------------------------------------
# the jax.numpy form: the same chunks under vmap and scan
# ---------------------------------------------------------------------------


def _by_head(x, chunks, c):
    """[b, T, h, d] -> [b, h, chunks, C, d]."""
    b, _, h, d = x.shape
    return jnp.moveaxis(x.reshape(b, chunks, c, h, d), 3, 1)


def _from_head(x):
    """[b, h, chunks, C, d] -> [b, T, h, d]."""
    b, h, chunks, c, d = x.shape
    return jnp.moveaxis(x, 1, 3).reshape(b, chunks * c, h, d)


def _jnp_fwd(q, k, v, gamma, beta, c):
    """q / k [b, T, hk, dk], v [b, T, hv, dv], gamma / beta [b, hv, chunks,
    C] -> (o [b, T, hv, dv] in v's dtype, the state in front of every chunk
    [b, hv, chunks, dk, dv] in k's dtype)."""
    chunks = gamma.shape[2]
    group = v.shape[2] // k.shape[2]
    qh, kh = (jnp.repeat(_by_head(t, chunks, c), group, axis=1)
              for t in (q, k))
    vh = _by_head(v, chunks, c)

    def head(qh, kh, vh, gamma, beta):
        def step(state, x):
            q_c, k_c, v_c, g_c, b_c = x
            o, out = _chunk_fwd(q_c, k_c, v_c, g_c[None], b_c[None], state)
            return out, (o.astype(v.dtype), state.astype(k.dtype))

        state = jnp.zeros((k.shape[-1], v.shape[-1]), jnp.float32)
        return lax.scan(step, state, (qh, kh, vh, gamma, beta))[1]

    o, states = jax.vmap(jax.vmap(head))(qh, kh, vh, gamma, beta)
    return _from_head(o), states


def _jnp_bwd(q, k, v, gamma, beta, states, do, c):
    chunks = gamma.shape[2]
    hk, group = k.shape[2], v.shape[2] // k.shape[2]
    qh, kh = (jnp.repeat(_by_head(t, chunks, c), group, axis=1)
              for t in (q, k))
    vh, doh = _by_head(v, chunks, c), _by_head(do.astype(v.dtype), chunks, c)

    def head(qh, kh, vh, gamma, beta, states, doh):
        def step(d_state, x):
            q_c, k_c, v_c, g_c, b_c, s_c, do_c = x
            dq, dk, dv, d_g, d_b, d_state = _chunk_bwd(
                q_c, k_c, v_c, g_c[None], b_c[None], s_c, do_c, d_state)
            return d_state, (dq, dk, dv, d_g[0], d_b[0])

        d_state = jnp.zeros((k.shape[-1], v.shape[-1]), jnp.float32)
        return lax.scan(step, d_state,
                        (qh, kh, vh, gamma, beta, states, doh),
                        reverse=True)[1]

    dq, dk, dv, d_gamma, d_beta = jax.vmap(jax.vmap(head))(
        qh, kh, vh, gamma, beta, states, doh)

    def per_key_head(x):            # the value heads of a key head add up
        b, _, chunks_, c_, d = x.shape
        return _from_head(x.reshape(b, hk, group, chunks_, c_, d).sum(2))

    return (per_key_head(dq).astype(q.dtype), per_key_head(dk).astype(k.dtype),
            _from_head(dv).astype(v.dtype), d_gamma, d_beta)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _heads_of_block(body, per_block, key_heads):
    """``chunk(i, rows)`` that runs ``body(ki, i, rows)`` for each key head
    of this grid step's block of heads that exists: the last block of a head
    count that is no multiple of the block is ragged."""
    first = pl.program_id(1) * per_block    # (read outside the chunks' loop)

    def chunk(i, rows):
        for ki in range(per_block):
            if key_heads % per_block:
                pl.when(first + ki < key_heads)(
                    functools.partial(body, ki, i, rows))
            else:
                body(ki, i, rows)

    return chunk


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, c, group,
                d_k, d_v, key_heads):
    """A block of chunks of a block of key heads' value heads, in order."""
    states_ref = rest[0] if len(rest) == 2 else None
    state_ref = rest[-1]
    per_block = q_ref.shape[-1] // d_k

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def key_head(ki, i, rows):
        at = slice(ki * d_k, (ki + 1) * d_k)
        q, k = q_ref[0, rows, at], k_ref[0, rows, at]
        products = _key_products(q, k)
        for gi in range(ki * group, (ki + 1) * group):
            lanes = slice(gi * d_v, (gi + 1) * d_v)
            state = state_ref[gi]
            if states_ref is not None:
                states_ref[0, gi, i] = state.astype(states_ref.dtype)
            o, state_ref[gi] = _chunk_fwd(
                q, k, v_ref[0, rows, lanes], g_ref[0, gi, pl.ds(i, 1), :],
                b_ref[0, gi, pl.ds(i, 1), :], state, products)
            o_ref[0, rows, lanes] = o.astype(o_ref.dtype)

    heads = _heads_of_block(key_head, per_block, key_heads)

    def chunk(i, carry):
        heads(i, pl.ds(pl.multiple_of(i * c, c), c))
        return carry

    lax.fori_loop(0, g_ref.shape[2], chunk, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, d_state_ref, *, c, group,
                d_k, d_v, key_heads):
    """The same block, its chunks from the last to the first; the grid
    walks the blocks from the last to the first too."""
    per_block = q_ref.shape[-1] // d_k

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros_like(d_state_ref)

    n = g_ref.shape[2]

    def key_head(ki, i, rows):
        at = slice(ki * d_k, (ki + 1) * d_k)
        q, k = q_ref[0, rows, at], k_ref[0, rows, at]
        products = _key_products(q, k)
        dq = dk = 0.0
        for gi in range(ki * group, (ki + 1) * group):
            lanes = slice(gi * d_v, (gi + 1) * d_v)
            dq_g, dk_g, dv, d_g, d_b, d_state_ref[gi] = _chunk_bwd(
                q, k, v_ref[0, rows, lanes], g_ref[0, gi, pl.ds(i, 1), :],
                b_ref[0, gi, pl.ds(i, 1), :], s_ref[0, gi, i],
                do_ref[0, rows, lanes], d_state_ref[gi], products)
            dq, dk = dq + dq_g, dk + dk_g
            dv_ref[0, rows, lanes] = dv.astype(dv_ref.dtype)
            dg_ref[0, gi, pl.ds(i, 1), :] = d_g
            db_ref[0, gi, pl.ds(i, 1), :] = d_b
        dq_ref[0, rows, at] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, at] = dk.astype(dk_ref.dtype)

    heads = _heads_of_block(key_head, per_block, key_heads)

    def chunk(step, carry):
        i = n - 1 - step
        heads(i, pl.ds(pl.multiple_of(i * c, c), c))
        return carry

    lax.fori_loop(0, n, chunk, 0)


def heads_per_block(d_k: int, d_v: int, group: int) -> int:
    """Key heads a grid step takes: the fewest whose lanes (and their value
    heads') are whole 128-lane tiles — one where a head is, four at 96-lane
    keys under 192-lane values.  A ``BlockSpec`` addresses ``[b, T, heads *
    dim]`` by whole tiles; inside the block a head is a static lane slice."""
    whole = lambda width: LANE // math.gcd(width, LANE)
    return math.lcm(whole(d_k), whole(group * d_v))


def _specs(block_chunks, c, d_k, d_v, group, blocks, reverse):
    """``BlockSpec``s over [b, T, heads * dim] tensors, the [b, hv, chunks,
    C] scalars and the [b, hv, chunks, dk, dv] states, for grid (batch, block
    of key heads, block of chunks).  Where the key heads are no whole number
    of blocks the last block is ragged: what it reads past the heads is not
    used, what it writes there is dropped."""
    at = (lambda n: blocks - 1 - n) if reverse else (lambda n: n)
    rows = block_chunks * c
    per_block = heads_per_block(d_k, d_v, group)
    values = per_block * group
    key = pl.BlockSpec((1, rows, per_block * d_k),
                       lambda b, h, n: (b, at(n), h))
    value = pl.BlockSpec((1, rows, values * d_v),
                         lambda b, h, n: (b, at(n), h))
    scalar = pl.BlockSpec((1, values, block_chunks, c),
                          lambda b, h, n: (b, h, at(n), 0))
    states = pl.BlockSpec((1, values, block_chunks, d_k, d_v),
                          lambda b, h, n: (b, h, at(n), 0, 0))
    return key, value, scalar, states


def _widths(q, v, gamma, key_heads):
    """``(d_k, d_v, value heads a key head)`` of a call's operands."""
    value_heads = gamma.shape[1]
    return (q.shape[2] // key_heads, v.shape[2] // value_heads,
            value_heads // key_heads)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit())


def _grid(b, key_heads, d_k, d_v, group, blocks):
    """What the two calls share beside their specs: the grid (batch, blocks
    of key heads, blocks of chunks), the states' scratch of a block of heads
    and the compiler's parameters."""
    per_block = heads_per_block(d_k, d_v, group)
    return dict(
        grid=(b, -(-key_heads // per_block), blocks),
        scratch_shapes=[pltpu.VMEM((per_block * group, d_k, d_v),
                                   jnp.float32)],
        compiler_params=_params())


# jitted, as the flash kernels' wrappers are: the layers of a model share
# one trace of a kernel body
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _kernel_fwd(q, k, v, gamma, beta, key_heads, block_chunks, keep_states,
                interpret):
    """q / k [b, T, hk * dk], v [b, T, hv * dv], gamma / beta [b, hv,
    chunks, C] -> o like v (and the states [b, hv, chunks, dk, dv])."""
    b, hv, chunks, c = gamma.shape
    d_k, d_v, group = _widths(q, v, gamma, key_heads)
    key, value, scalar, states = _specs(block_chunks, c, d_k, d_v, group,
                                        chunks // block_chunks, False)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [value]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct((b, hv, chunks, d_k, d_v),
                                              k.dtype))
        out_specs.append(states)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, group=group, d_k=d_k, d_v=d_v,
                          key_heads=key_heads),
        in_specs=[key, key, value, scalar, scalar],
        out_specs=out_specs, out_shape=out_shape, interpret=interpret,
        name="gdn_fwd",
        **_grid(b, key_heads, d_k, d_v, group, chunks // block_chunks),
    )(q, k, v, gamma, beta)
    return tuple(out) if keep_states else (out[0], None)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _kernel_bwd(q, k, v, gamma, beta, states, do, key_heads, block_chunks,
                interpret):
    b, _, chunks, c = gamma.shape
    d_k, d_v, group = _widths(q, v, gamma, key_heads)
    key, value, scalar, state_spec = _specs(block_chunks, c, d_k, d_v, group,
                                            chunks // block_chunks, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, c=c, group=group, d_k=d_k, d_v=d_v,
                          key_heads=key_heads),
        in_specs=[key, key, value, scalar, scalar, state_spec, value],
        out_specs=[key, key, value, scalar, scalar],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(gamma.shape, jnp.float32),
                   jax.ShapeDtypeStruct(gamma.shape, jnp.float32)],
        interpret=interpret, name="gdn_bwd",
        **_grid(b, key_heads, d_k, d_v, group, chunks // block_chunks),
    )(q, k, v, gamma, beta, states, do)


# ---------------------------------------------------------------------------
# the operation
# ---------------------------------------------------------------------------


#: key heads a grid step unrolls, at most: what :func:`heads_per_block` may
#: ask for (four at 96-lane keys; a narrower head would ask for eight or more
#: bodies a chunk and takes the ``jax.numpy`` chunks)
MAX_HEADS_PER_BLOCK = 4


def gated_delta_covered(key_heads: int, value_heads: int, d_k: int,
                        d_v: int, dtype=jnp.bfloat16) -> bool:
    """Whether the kernels' grid covers the heads: value heads a multiple of
    the key heads, bfloat16 or float32 operands, and heads that are whole
    128-lane tiles one by one (128 x 128, any number of value heads a key
    head) or, where key heads and value heads are as many, in blocks of at
    most ``MAX_HEADS_PER_BLOCK`` — 96-lane keys under 192-lane values four
    to a block, the last block ragged where the head count is no multiple
    (30 heads are seven blocks and a half).  Grouped heads of which several
    make a tile (64-lane keys under 128-lane values at 16 / 32 heads) take
    the ``jax.numpy`` chunks: no configuration has them and no test runs
    them on the chip.  Any head count, any sequence length (the rows are
    padded to whole blocks of chunks)."""
    group = value_heads // max(key_heads, 1)
    per_block = heads_per_block(d_k, d_v, group)
    return (value_heads % key_heads == 0 and d_k % 8 == 0
            and (per_block == 1
                 or group == 1 and per_block <= MAX_HEADS_PER_BLOCK)
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def gated_delta_supported(key_heads: int, value_heads: int, d_k: int,
                          d_v: int, dtype=jnp.bfloat16) -> bool:
    """Whether the kernels take the call: on a TPU, heads that
    :func:`gated_delta_covered` (whole lane tiles alone at any group, or in
    blocks of up to four where key and value heads are as many: 128 x 128
    and 96 x 192 do, 16-lane keys and grouped 64-lane keys do not)."""
    return (jax.default_backend() == "tpu" and gated_delta_covered(
        key_heads, value_heads, d_k, d_v, dtype))


def _padded_chunks(seq: int, c: int) -> int:
    """Chunks of the padded sequence: whole chunks, and whole blocks of
    ``CHUNKS_PER_BLOCK`` of them where there are more than one block's."""
    chunks = -(-seq // c)
    if chunks > CHUNKS_PER_BLOCK:
        chunks = -(-chunks // CHUNKS_PER_BLOCK) * CHUNKS_PER_BLOCK
    return chunks


def _scalars_by_chunk(x, chunks, c):
    """[b, T, hv] -> [b, hv, chunks, C], float32."""
    b, _, hv = x.shape
    return jnp.moveaxis(
        x.astype(jnp.float32).reshape(b, chunks, c, hv), 3, 1)


def _scalars_back(x):
    """[b, hv, chunks, C] -> [b, T, hv]."""
    b, hv, chunks, c = x.shape
    return jnp.moveaxis(x, 1, 3).reshape(b, chunks * c, hv)


def _forward(q, k, v, g, beta, c, by_kernel, interpret, keep_states):
    b, seq, hk, d_k = q.shape
    hv, d_v = v.shape[2:]
    chunks = _padded_chunks(seq, c)
    pad = chunks * c - seq
    if pad:
        # zero keys write nothing, g = 0 keeps the state: the rows behind
        # the sequence change no row of it
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    # gamma: g's running sum inside each chunk
    gamma = jnp.cumsum(_scalars_by_chunk(g, chunks, c), axis=-1)
    beta_rows = _scalars_by_chunk(beta, chunks, c)
    if by_kernel:
        o, states = _kernel_fwd(
            q.reshape(b, chunks * c, hk * d_k),
            k.reshape(b, chunks * c, hk * d_k),
            v.reshape(b, chunks * c, hv * d_v), gamma, beta_rows, hk,
            min(chunks, CHUNKS_PER_BLOCK), keep_states, interpret)
        o = o.reshape(b, chunks * c, hv, d_v)
    else:
        o, states = _jnp_fwd(q, k, v, gamma, beta_rows, c)
    return o[:, :seq], (q, k, v, gamma, beta_rows, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gated_delta(q, k, v, g, beta, c, by_kernel, interpret):
    return _forward(q, k, v, g, beta, c, by_kernel, interpret, False)[0]


def _gated_delta_fwd(q, k, v, g, beta, c, by_kernel, interpret):
    o, (q, k, v, gamma, beta_rows, states) = _forward(
        q, k, v, g, beta, c, by_kernel, interpret, True)
    # tagged like the flash kernels' ``o`` and ``lse``: a remat policy that
    # keeps those keeps these, and the replay does not run ``gdn_fwd`` again
    o = checkpoint_name(o, KEPT_O)
    states = checkpoint_name(states, KEPT_LSE)
    # (the two empty arrays carry the scalars' dtypes to the transpose)
    return o, (q, k, v, gamma, beta_rows, states, jnp.zeros((0,), g.dtype),
               jnp.zeros((0,), beta.dtype))


def _gated_delta_bwd(c, by_kernel, interpret, res, do):
    q, k, v, gamma, beta_rows, states, like_g, like_beta = res
    b, padded, hk, d_k = q.shape
    hv, d_v = v.shape[2:]
    seq, chunks = do.shape[1], gamma.shape[2]
    do = jnp.pad(do.astype(v.dtype),
                 ((0, 0), (0, padded - seq), (0, 0), (0, 0)))
    if by_kernel:
        dq, dk, dv, d_gamma, d_beta = _kernel_bwd(
            q.reshape(b, padded, hk * d_k), k.reshape(b, padded, hk * d_k),
            v.reshape(b, padded, hv * d_v), gamma, beta_rows, states,
            do.reshape(b, padded, hv * d_v), hk,
            min(chunks, CHUNKS_PER_BLOCK), interpret)
        dq, dk = (t.reshape(b, padded, hk, d_k) for t in (dq, dk))
        dv = dv.reshape(b, padded, hv, d_v)
    else:
        dq, dk, dv, d_gamma, d_beta = _jnp_bwd(q, k, v, gamma, beta_rows,
                                               states, do, c)
    # gamma is g's running sum inside a chunk: its transpose runs from the
    # chunk's end
    d_g = _scalars_back(jnp.cumsum(d_gamma[..., ::-1], axis=-1)[..., ::-1])
    return (dq[:, :seq], dk[:, :seq], dv[:, :seq],
            d_g[:, :seq].astype(like_g.dtype),
            _scalars_back(d_beta)[:, :seq].astype(like_beta.dtype))


_gated_delta.defvjp(_gated_delta_fwd, _gated_delta_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK,
                     interpret: bool = False, force: bool = False):
    """The gated delta rule over a sequence, chunked, with a chunked VJP.
    ``q`` / ``k``: [batch, seq, key_heads, d_k] (L2-normalised, ``q``
    scaled); ``v``: [batch, seq, value_heads, d_v]; ``g`` (log decay, <= 0)
    / ``beta``: [batch, seq, value_heads].  -> [batch, seq, value_heads,
    d_v] in ``v.dtype``.

    The kernels run where :func:`gated_delta_supported` says so; elsewhere
    the same chunks in ``jax.numpy``.  ``force`` skips the platform check
    (tests run the kernels in interpret mode on the CPU); shapes no grid
    covers still take the ``jax.numpy`` form."""
    hk, d_k = q.shape[2:]
    hv, d_v = v.shape[2:]
    covered = (k.dtype == v.dtype == q.dtype
               and gated_delta_covered(hk, hv, d_k, d_v, k.dtype))
    by_kernel = covered and (force or jax.default_backend() == "tpu")
    return _gated_delta(q, k, v, g, beta, int(chunk), bool(by_kernel),
                        bool(interpret))
