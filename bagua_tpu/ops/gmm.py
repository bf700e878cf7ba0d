"""Grouped matrix multiply (``gmm``) — Pallas TPU kernel for dropless MoE.

``gmm(lhs, rhs, group_sizes)`` multiplies contiguous row groups of ``lhs``
[rows, d] by per-group matrices ``rhs`` [groups, d, f], returning [rows, f].
This is the expert-FFN primitive of dropless (capacity-free) MoE routing:
tokens sorted by expert form ragged groups, and no token is dropped no
matter how skewed the routing — the fix for GShard capacity overflow
(the reference's gate drops tokens past ``capacity``,
/root/reference/bagua/torch_api/model_parallel/moe/sharded_moe.py:93-238).

TPU-first design: ragged row groups are scattered into block-aligned slots
(each group padded up to the 128-row MXU tile), after which every row block
belongs to exactly ONE group — a scalar-prefetched per-block group id then
steers the ``rhs`` BlockSpec, so each grid step is a single dense MXU matmul
with no masking.  The dK accumulation kernel walks row blocks innermost and
revisits its (group, d, f) output block across consecutive steps, the
standard Pallas accumulation pattern.  Backward is a custom VJP: d_lhs is
the same kernel with ``rhs`` transposed; d_rhs is the grouped outer-product
kernel.  Padded rows are zero, so they contribute nothing to any reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import pick_block

_BLOCK_ROWS = 128
_BLOCK_F = 512


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


def _padded_layout(group_sizes, rows: int, n_groups: int, block: int):
    """Map ragged rows to block-aligned padded slots.

    Returns (pos [rows] padded position per row, g_of_block [n_blocks],
    padded_rows static int).
    """
    padded_rows = _padded_rows(rows, n_groups, block)
    sizes = group_sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    padded = _round_up(sizes, block)
    poffs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)])
    r = jnp.arange(rows, dtype=jnp.int32)
    g_of_row = jnp.searchsorted(offs[1:], r, side="right").astype(jnp.int32)
    pos = poffs[g_of_row] + (r - offs[g_of_row])
    starts = jnp.arange(padded_rows // block, dtype=jnp.int32) * block
    g_of_block = jnp.clip(
        jnp.searchsorted(poffs, starts, side="right") - 1, 0, n_groups - 1
    ).astype(jnp.int32)
    return pos, g_of_block, padded_rows


def _fwd_kernel(gid_ref, lhs_ref, rhs_ref, out_ref):
    out_ref[:] = jnp.dot(
        lhs_ref[:], rhs_ref[0], preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


def _gmm_padded(lhs_p, rhs, g_of_block, block_rows, block_f, interpret):
    """lhs_p: [padded_rows, d] (group-blocked), rhs: [G, d, f]."""
    padded_rows, d = lhs_p.shape
    _, _, f = rhs.shape
    bf = pick_block(f, block_f)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(padded_rows // block_rows, f // bf),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i, j, gid: (i, 0)),
            pl.BlockSpec((1, d, bf), lambda i, j, gid: (gid[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, bf), lambda i, j, gid: (i, j)),
    )
    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded_rows, f), lhs_p.dtype),
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


def _gmm_drhs_padded(lhs_p, gout_p, n_groups, d, f, g_of_block, block_rows,
                     block_f, interpret):
    """d_rhs[g] = lhs_g^T @ gout_g over padded row blocks: [G, d, f] f32."""
    padded_rows = lhs_p.shape[0]
    bf = pick_block(f, block_f)
    bd = pick_block(d, block_f)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // bd, f // bf, padded_rows // block_rows),
        in_specs=[
            pl.BlockSpec((block_rows, bd), lambda i, j, k, gid: (k, i)),
            pl.BlockSpec((block_rows, bf), lambda i, j, k, gid: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, bd, bf), lambda i, j, k, gid: (gid[k], i, j)),
    )
    return pl.pallas_call(
        _drhs_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, d, f), jnp.float32),
        interpret=interpret,
        name="gmm_bwd_drhs",
    )(g_of_block, lhs_p, gout_p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, rhs, group_sizes, block_rows, block_f, interpret):
    out, _ = _gmm_fwd_impl(lhs, rhs, group_sizes, block_rows, block_f,
                           interpret)
    return out


def _gmm_fwd_impl(lhs, rhs, group_sizes, block_rows, block_f, interpret):
    rows, d = lhs.shape
    n_groups = rhs.shape[0]
    pos, g_of_block, padded_rows = _padded_layout(
        group_sizes, rows, n_groups, block_rows
    )
    lhs_p = jnp.zeros((padded_rows, d), lhs.dtype).at[pos].set(lhs)
    out_p = _gmm_padded(lhs_p, rhs.astype(lhs.dtype), g_of_block, block_rows,
                        block_f, interpret)
    return out_p[pos], (pos, g_of_block, padded_rows)


def _gmm_vjp_fwd(lhs, rhs, group_sizes, block_rows, block_f, interpret):
    out, layout = _gmm_fwd_impl(lhs, rhs, group_sizes, block_rows, block_f,
                                interpret)
    return out, (lhs, rhs, group_sizes, layout)


def _gmm_vjp_bwd(block_rows, block_f, interpret, res, gout):
    lhs, rhs, group_sizes, (pos, g_of_block, padded_rows) = res
    rows, d = lhs.shape
    n_groups, _, f = rhs.shape
    gout_p = jnp.zeros((padded_rows, f), gout.dtype).at[pos].set(gout)
    # d_lhs = gout @ rhs^T (same grouped structure)
    dlhs_p = _gmm_padded(
        gout_p, jnp.swapaxes(rhs, 1, 2).astype(gout.dtype), g_of_block,
        block_rows, block_f, interpret,
    )
    lhs_p = jnp.zeros((padded_rows, d), lhs.dtype).at[pos].set(lhs)
    drhs = _gmm_drhs_padded(lhs_p, gout_p, n_groups, d, f, g_of_block,
                            block_rows, block_f, interpret)
    # an empty group owns no row blocks, so its output block is never
    # written — select zero rather than uninitialized memory
    mask = (group_sizes.astype(jnp.int32) > 0)[:, None, None]
    drhs = jnp.where(mask, drhs, 0.0)
    return dlhs_p[pos], drhs.astype(rhs.dtype), None


_gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def _use_kernel(rows: int, d: int, f: int, block_rows: int) -> bool:
    return (
        jax.default_backend() == "tpu"
        and d % 128 == 0
        and f % 128 == 0
        and rows >= block_rows
    )


def kernel_rows(rows: int, n_groups: int, d: int, f: int, *,
                block_rows: int = _BLOCK_ROWS) -> int:
    """Rows one :func:`gmm` call of these shapes really multiplies: the
    block-aligned padded layout's static row count where the kernel runs
    (every group rounded up to ``block_rows``, at the worst case of the
    group sizes), ``rows`` where the dense fallback does."""
    if not _use_kernel(rows, d, f, block_rows):
        return rows
    return _padded_rows(rows, n_groups, block_rows)


def gmm(lhs, rhs, group_sizes, *, block_rows: int = _BLOCK_ROWS,
        block_f: int = _BLOCK_F, interpret: bool = False,
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
    return _gmm(lhs, rhs, group_sizes, block_rows, block_f, interpret)
