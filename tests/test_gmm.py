"""Grouped-matmul kernel vs dense one-hot reference (golden-model pattern,
SURVEY.md §4), including ragged/empty groups and the custom VJP."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.ops import gmm as G
from bagua_tpu.ops.gmm import (
    gmm, gmm_padded, gmm_reference, kernel_layout, pad_rows, padded_layout,
    take_or_zero, unpad_rows,
)
from tests.internal.jaxpr_walk import primitives


def _case(key, rows, d, f, sizes):
    k1, k2 = jax.random.split(key)
    lhs = jax.random.normal(k1, (rows, d), jnp.float32)
    rhs = jax.random.normal(k2, (len(sizes), d, f), jnp.float32)
    return lhs, rhs, jnp.array(sizes, jnp.int32)


@pytest.mark.parametrize("sizes", [
    [100, 156],               # ragged, non-aligned
    [0, 256, 0],              # empty groups at both ends
    [256, 0, 0],              # everything in the first group
    [37, 1, 218],             # tiny group
])
def test_forward_matches_reference(sizes):
    rows = int(np.sum(sizes))
    lhs, rhs, gs = _case(jax.random.PRNGKey(0), rows, 128, 256, sizes)
    want = gmm_reference(lhs, rhs, gs)
    got = gmm(lhs, rhs, gs, interpret=True, force=True)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_grads_match_reference():
    sizes = [60, 0, 196]
    rows = int(np.sum(sizes))
    lhs, rhs, gs = _case(jax.random.PRNGKey(1), rows, 128, 128, sizes)
    g = jax.random.normal(jax.random.PRNGKey(2), (rows, 128), jnp.float32)

    def loss(fn):
        return jax.grad(lambda l, r: (fn(l, r, gs) * g).sum(), argnums=(0, 1))

    want = loss(gmm_reference)(lhs, rhs)
    got = loss(lambda l, r, s: gmm(l, r, s, interpret=True, force=True))(
        lhs, rhs
    )
    np.testing.assert_allclose(got[0], want[0], atol=1e-4, rtol=1e-4,
                               err_msg="d_lhs")
    np.testing.assert_allclose(got[1], want[1], atol=1e-4, rtol=1e-4,
                               err_msg="d_rhs")


def test_cpu_fallback():
    lhs, rhs, gs = _case(jax.random.PRNGKey(3), 16, 8, 8, [10, 6])
    np.testing.assert_allclose(
        gmm(lhs, rhs, gs), gmm_reference(lhs, rhs, gs), atol=1e-6
    )


def test_jit_with_traced_sizes():
    # group sizes are data (routing counts change every step): the kernel
    # must not force a recompile per distribution
    lhs, rhs, _ = _case(jax.random.PRNGKey(4), 256, 128, 128, [1])
    rhs = jnp.broadcast_to(rhs, (4,) + rhs.shape[1:])

    @jax.jit
    def f(lhs, rhs, gs):
        return gmm(lhs, rhs, gs, interpret=True, force=True)

    for sizes in ([64, 64, 64, 64], [0, 256, 0, 0], [1, 2, 3, 250]):
        gs = jnp.array(sizes, jnp.int32)
        np.testing.assert_allclose(
            f(lhs, rhs, gs), gmm_reference(lhs, rhs, gs), atol=1e-4,
            rtol=1e-4,
        )


# ---------------------------------------------------------------------------
# the padded layout as a value, and the products that stay in it
# ---------------------------------------------------------------------------

#: ragged, empty at both ends and in the middle, one group, full blocks only
LAYOUTS = [
    [100, 156], [0, 256, 0], [37, 0, 1, 218], [256], [128, 128], [1, 0, 0, 0],
]


@pytest.mark.parametrize("block", [1, 8, 128])
@pytest.mark.parametrize("sizes", LAYOUTS)
def test_the_layout_maps_are_each_others_inverse(sizes, block):
    rows = int(np.sum(sizes))
    layout = padded_layout(jnp.array(sizes, jnp.int32), rows, block_rows=block)
    pos, src = np.asarray(layout.pos), np.asarray(layout.src)
    assert layout.block_rows == block
    assert len(src) % block == 0 and len(src) >= rows
    np.testing.assert_array_equal(src[pos], np.arange(rows))
    used = src < rows
    assert used.sum() == rows
    np.testing.assert_array_equal(pos[src[used]], np.nonzero(used)[0])
    # every group starts on a block, and a block holds rows of one group
    starts = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    for g, (start, size) in enumerate(zip(starts, sizes)):
        if size:
            assert pos[start] % block == 0
            blocks = pos[start:start + size] // block
            np.testing.assert_array_equal(
                np.asarray(layout.g_of_block)[blocks], g)
    if block == 1:
        np.testing.assert_array_equal(pos, np.arange(rows))


@pytest.mark.parametrize("sizes", LAYOUTS)
def test_rows_beyond_the_groups_are_left_out_of_the_layout(sizes):
    """An expert-parallel receive buffer: more rows than the groups hold."""
    rows = int(np.sum(sizes)) + 40
    layout = padded_layout(jnp.array(sizes, jnp.int32), rows, block_rows=128)
    pos, src = np.asarray(layout.pos), np.asarray(layout.src)
    assert pos.max() < len(src) and len(set(pos)) == rows
    assert (src < rows).sum() == rows - 40
    assert (src[pos[rows - 40:]] == rows).all()     # they read back zero


@pytest.mark.parametrize("sizes", LAYOUTS)
def test_the_padded_product_matches_the_reference(sizes):
    """``gmm_padded`` (interpret mode) between a pad and an unpad: values,
    both gradients, and exact zeros in every padding row on the way."""
    rows = int(np.sum(sizes))
    lhs, rhs, gs = _case(jax.random.PRNGKey(5), rows, 128, 256, sizes)
    g = jax.random.normal(jax.random.PRNGKey(6), (rows, 256), jnp.float32)
    layout = padded_layout(gs, rows)
    padding = np.asarray(layout.src) == rows
    assert padding.any()

    def padded(l, r):
        l_p = pad_rows(l, layout.src, layout.pos[:, None])
        return l_p, gmm_padded(l_p, r, layout, interpret=True)

    lhs_p, out_p = padded(lhs, rhs)
    assert not np.asarray(lhs_p)[padding].any()
    assert not np.asarray(out_p)[padding].any()
    want = gmm_reference(lhs, rhs, gs)
    np.testing.assert_allclose(out_p[layout.pos], want, atol=1e-4, rtol=1e-4)

    # the cotangent enters the layout zero-padded and d_lhs stays so
    g_p = take_or_zero(g, layout.src)
    _, vjp = jax.vjp(lambda l_p, r: gmm_padded(l_p, r, layout, interpret=True),
                     lhs_p, rhs)
    dlhs_p, drhs = vjp(g_p)
    assert not np.asarray(dlhs_p)[padding].any()
    want_l, want_r = jax.grad(
        lambda l, r: (gmm_reference(l, r, gs) * g).sum(), argnums=(0, 1))(
            lhs, rhs)
    np.testing.assert_allclose(dlhs_p[layout.pos], want_l, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(drhs, want_r, atol=1e-4, rtol=1e-4)
    # and the same through pad and unpad, end to end
    got = jax.grad(lambda l, r: (unpad_rows(
        padded(l, r)[1], layout.pos, layout.src) * g).sum(), argnums=(0, 1))(
            lhs, rhs)
    np.testing.assert_allclose(got[0], want_l, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(got[1], want_r, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
@pytest.mark.parametrize("sizes", [[100, 60, 0, 96], [256, 0, 0, 0],
                                   [1, 127, 127, 1]])
def test_an_expert_ffn_stays_zero_in_its_padding_rows(sizes, gated):
    """Two or three products with elementwise work between them, all in
    the layout: every padded intermediate and every padded cotangent is
    exactly zero in the padding rows, and the whole agrees with the dense
    form in values and in all gradients."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    rows = jax.random.normal(keys[0], (256, 128))
    w_gate, w_up, w_down = (0.1 * jax.random.normal(k, (4, 128, 128))
                            for k in keys[1:4])
    g = jax.random.normal(keys[4], (256, 128))
    gs = jnp.array(sizes, jnp.int32)
    layout = padded_layout(gs, 256)
    padding = np.asarray(layout.src) == 256
    seen = {}

    def ffn(mm, x, wg, wu, wd):
        up = mm(x, wu)
        h = jax.nn.silu(mm(x, wg)) * up if gated else jax.nn.silu(up)
        seen.update(up=up, h=h)
        return mm(h, wd)

    def resident(x, wg, wu, wd):
        x_p = pad_rows(x, layout.src, layout.pos[:, None])
        y_p = ffn(lambda l, r: gmm_padded(l, r, layout, interpret=True),
                  x_p, wg, wu, wd)
        seen.update(x_p=x_p, y_p=y_p)
        return unpad_rows(y_p, layout.pos, layout.src)

    def dense(x, wg, wu, wd):
        return ffn(lambda l, r: gmm_reference(l, r, gs), x, wg, wu, wd)

    args = (rows, w_gate, w_up, w_down)
    want = dense(*args)
    np.testing.assert_allclose(resident(*args), want, atol=1e-4)
    for name in ("x_p", "up", "h", "y_p"):
        assert not np.asarray(seen[name])[padding].any(), name
    x_p = seen["x_p"]
    got = jax.grad(lambda *a: (resident(*a) * g).sum(), argnums=(0, 1, 2, 3))(
        *args)
    want = jax.grad(lambda *a: (dense(*a) * g).sum(), argnums=(0, 1, 2, 3))(
        *args)
    for name, a, b in zip(("x", "gate", "up", "down"), got, want):
        if name == "gate" and not gated:
            continue
        np.testing.assert_allclose(a, b, atol=1e-3 * float(jnp.abs(b).max()),
                                   err_msg=name)
    # the cotangent of the rows in the layout, before it leaves it
    dx_p = jax.grad(lambda x_p: (unpad_rows(ffn(
        lambda l, r: gmm_padded(l, r, layout, interpret=True),
        x_p, w_gate, w_up, w_down), layout.pos, layout.src) * g).sum())(x_p)
    assert not np.asarray(dx_p)[padding].any()


@pytest.mark.parametrize("m", [1, 4])
def test_rows_move_by_gathers_in_both_directions(m):
    """``pad_rows`` / ``unpad_rows`` against plain indexing under autodiff,
    and no scatter in their gradients."""
    n, d = 24, 8
    x = jax.random.normal(jax.random.PRNGKey(8), (n, d))
    perm = jax.random.permutation(jax.random.PRNGKey(9), n * m)
    slots = (2 * perm).reshape(n, m)            # every other slot is padding
    src = jnp.full((2 * n * m,), n).at[slots.reshape(-1)].set(
        jnp.repeat(jnp.arange(n), m))
    g = jax.random.normal(jax.random.PRNGKey(10), (2 * n * m, d))
    plain = lambda x: jnp.where((src < n)[:, None], x[jnp.minimum(src, n - 1)], 0)
    np.testing.assert_array_equal(pad_rows(x, src, slots), plain(x))
    loss = lambda fn: lambda x: (fn(x) * g).sum()
    np.testing.assert_allclose(
        jax.grad(loss(lambda x: pad_rows(x, src, slots)))(x),
        jax.grad(loss(plain))(x), atol=1e-5)
    ops = primitives(jax.grad(loss(lambda x: pad_rows(x, src, slots))), x)
    assert not [name for name, _ in ops if name.startswith("scatter")]
    if m == 1:
        y_p = jax.random.normal(jax.random.PRNGKey(11), (2 * n, d))
        out = lambda y_p: unpad_rows(y_p, slots[:, 0], src)
        np.testing.assert_array_equal(out(y_p), y_p[slots[:, 0]])
        h = jax.random.normal(jax.random.PRNGKey(12), (n, d))
        np.testing.assert_allclose(
            jax.grad(lambda y_p: (out(y_p) * h).sum())(y_p),
            jax.grad(lambda y_p: (y_p[slots[:, 0]] * h).sum())(y_p), atol=1e-6)
        ops = primitives(jax.grad(lambda y_p: (out(y_p) * h).sum()), y_p)
        assert not [name for name, _ in ops if name.startswith("scatter")]


def test_a_forced_gmm_is_three_kernel_calls_and_no_row_scatter():
    lhs, rhs, gs = _case(jax.random.PRNGKey(13), 256, 128, 128, [60, 0, 196])
    ops = primitives(jax.grad(
        lambda l, r: gmm(l, r, gs, interpret=True, force=True).sum(),
        argnums=(0, 1)), lhs, rhs)
    names = [name for name, _ in ops]
    assert names.count("gmm_fwd") == 2 and names.count("gmm_bwd_drhs") == 1
    assert not [n for n in names if n.startswith("scatter")]


def test_off_the_kernel_the_layout_is_the_rows_themselves():
    gs = jnp.array([10, 0, 6], jnp.int32)
    layout = kernel_layout(gs, 16, 8, 8)        # the CPU, tiny widths
    assert layout.block_rows == 1 and layout.src.shape == (16,)
    lhs, rhs, _ = _case(jax.random.PRNGKey(14), 16, 8, 8, [10, 0, 6])
    np.testing.assert_allclose(gmm_padded(lhs, rhs, layout),
                               gmm_reference(lhs, rhs, gs), atol=1e-6)


# ---------------------------------------------------------------------------
# the kernels' block shapes and grid order (PR 31): every operand block is
# fetched once per reuse
# ---------------------------------------------------------------------------

#: name -> (d, f, group sizes, block_f).  OLMoE's two orientations at reduced
#: rows (blocks as wide as ``d`` and ``f``: one ``f`` block, the rows streamed
#: once), uneven groups with an empty one, one row block a group (no slab is
#: reused), and widths that only 128 divides under a cap (several blocks of
#: ``d`` and of ``f``: every index map with more than one value an axis)
SHAPES = {
    "olmoe_up": (2048, 1024, [300, 0, 129, 95], None),
    "olmoe_down": (1024, 2048, [300, 0, 129, 95], None),
    "uneven_groups": (256, 384, [1, 255, 130, 0, 126], None),
    "one_block_a_group": (256, 256, [128, 100, 128, 7], None),
    "only_128_divides": (640, 384, [200, 0, 57, 140], 256),
}


@functools.lru_cache(maxsize=None)
def _products_of(shape, dtype):
    """(got, want) of the forward product, d_lhs and d_rhs for one case:
    the kernels in interpret mode against ``gmm_reference`` and its
    ``jax.grad`` in float32, on the same (``dtype``-rounded) operands."""
    d, f, sizes, block_f = SHAPES[shape]
    rows = int(np.sum(sizes))
    lhs, rhs, gs = _case(jax.random.PRNGKey(15), rows, d, f, sizes)
    lhs, rhs = lhs.astype(dtype), (rhs / np.sqrt(d)).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(16), (rows, f)).astype(dtype)

    def all_three(fn, *operands):
        out, vjp = jax.vjp(lambda l, r: fn(l, r, gs), *operands[:2])
        return (out,) + vjp(operands[2].astype(out.dtype))

    got = all_three(lambda l, r, s: gmm(l, r, s, block_f=block_f,
                                        interpret=True, force=True),
                    lhs, rhs, g)
    want = all_three(gmm_reference, *(x.astype(jnp.float32)
                                      for x in (lhs, rhs, g)))
    # the transposed-operand form by itself: rows [R, f] times the stack
    # [G, d, f] as stored, against the reference on the transposed stack
    layout = padded_layout(gs, rows)
    on_stored = G._gmm_padded(
        take_or_zero(g, layout.src), rhs, layout.g_of_block, 128, block_f,
        True, transpose_rhs=True)[layout.pos]
    on_transposed = gmm_reference(
        g.astype(jnp.float32), jnp.swapaxes(rhs, 1, 2).astype(jnp.float32),
        gs)
    return got + (on_stored,), want + (on_transposed,)


PRODUCTS = ["forward", "d_lhs", "d_rhs", "transposed_operand"]


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_each_product_matches_the_reference(shape, dtype, product):
    got, want = _products_of(shape, dtype)
    i = PRODUCTS.index(product)
    assert got[i].dtype == jnp.dtype(dtype) and got[i].shape == want[i].shape
    tol = 1e-4 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        got[i].astype(jnp.float32), want[i], rtol=tol,
        atol=tol * float(jnp.abs(want[i]).max()))
    if product == "d_rhs":      # the group with no rows: exact zeros
        empty = np.asarray(SHAPES[shape][2]) == 0
        assert not np.asarray(got[i].astype(jnp.float32))[empty].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_d_lhs_is_the_product_on_a_transposed_copy_without_the_copy(shape,
                                                                    dtype):
    """What the backward pass did before it read the stack as stored: the
    forward form on ``swapaxes(rhs, 1, 2)``.  The same operands in the same
    float32 accumulation: equal to round-off, zero rows included."""
    d, f, sizes, block_f = SHAPES[shape]
    rows = int(np.sum(sizes))
    _, rhs, gs = _case(jax.random.PRNGKey(17), rows, d, f, sizes)
    rhs = (rhs / np.sqrt(d)).astype(dtype)
    layout = padded_layout(gs, rows)
    g_p = take_or_zero(
        jax.random.normal(jax.random.PRNGKey(18), (rows, f)).astype(dtype),
        layout.src)
    lhs_p = jnp.zeros((g_p.shape[0], d), dtype)
    _, vjp = jax.vjp(lambda l_p: gmm_padded(l_p, rhs, layout, block_f=block_f,
                                            interpret=True), lhs_p)
    (got,) = vjp(g_p)
    before = G._gmm_padded(g_p, jnp.swapaxes(rhs, 1, 2), layout.g_of_block,
                           128, block_f, True)
    assert got.dtype == before.dtype and got.shape == before.shape
    np.testing.assert_allclose(got.astype(jnp.float32),
                               before.astype(jnp.float32), rtol=1e-6,
                               atol=1e-6)
    assert not np.asarray(got.astype(jnp.float32))[
        np.asarray(layout.src) == rows].any()


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_the_gradient_transposes_no_matrix_stack(gated):
    """The test that keeps the copy from coming back: in the jaxpr of an
    expert FFN's gradient (two or three products in the layout) no
    ``transpose`` takes a rank-3 ``[G, ., .]`` operand — d_lhs contracts
    the stored matrices' last axis inside ``gmm_fwd``."""
    keys = jax.random.split(jax.random.PRNGKey(19), 4)
    x = jax.random.normal(keys[0], (256, 128))
    w_gate, w_up = (jax.random.normal(k, (4, 128, 256)) for k in keys[1:3])
    w_down = jax.random.normal(keys[3], (4, 256, 128))
    layout = padded_layout(jnp.array([100, 60, 0, 96], jnp.int32), 256)
    mm = lambda l, r: gmm_padded(l, r, layout, interpret=True)

    def loss(x, wg, wu, wd):
        x_p = pad_rows(x, layout.src, layout.pos[:, None])
        up = mm(x_p, wu)
        h = jax.nn.silu(mm(x_p, wg)) * up if gated else jax.nn.silu(up)
        return unpad_rows(mm(h, wd), layout.pos, layout.src).sum()

    ops = primitives(jax.grad(loss, argnums=(0, 1, 2, 3)), x, w_gate, w_up,
                     w_down)
    names = [name for name, _ in ops]
    products = 3 if gated else 2
    assert names.count("gmm_fwd") == 2 * products
    assert names.count("gmm_bwd_drhs") == products
    assert not [(name, shapes) for name, shapes in ops
                if name == "transpose" and any(len(s) == 3 for s in shapes)]
    # the d_lhs calls take the stack in the forward call's own shape
    stacks = [shapes[2] for name, shapes in ops if name == "gmm_fwd"]
    assert sorted(stacks) == sorted(
        2 * ([(4, 128, 256)] * (products - 1) + [(4, 256, 128)]))


def _olmoe_blocks(seed=0):
    """``g_of_block`` of the OLMoE cell's shape: 65,536 routed rows over 64
    experts by a uniform draw, 576 row blocks of 128."""
    counts = np.bincount(np.random.default_rng(seed).integers(0, 64, 65536),
                         minlength=64)
    layout = padded_layout(jnp.asarray(counts, jnp.int32), 65536)
    gid = np.asarray(layout.g_of_block)
    assert gid.shape == (576,) and len(set(gid)) == 64
    return gid


def _fetches(grid, index_map, gid):
    """Times a block is copied when ``grid`` is walked in order (last axis
    fastest): Pallas copies none whose index the step before had."""
    walk = [index_map(*step, gid) for step in np.ndindex(*grid)]
    return 1 + sum(a != b for a, b in zip(walk, walk[1:]))


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["rhs", "rhs_transposed"])
@pytest.mark.parametrize("d,f,block_f", [
    (2048, 1024, None), (1024, 2048, None), (2048, 1024, 512),
    (1024, 2048, 512), (2048, 1024, 128),
])
def test_a_groups_matrix_slab_is_fetched_once_per_f_block(d, f, block_f,
                                                          transpose_rhs):
    """No kernel runs: the forward grid walked through its index maps.  The
    matrix operand changes ``groups x f/bf`` times, not ``row blocks x
    f/bf`` (2.4 GB of weights a call at OLMoE's shapes, PR 31) — in the
    transposed-operand form (d_lhs on the stored ``[G, f, d]``) as well."""
    gid = _olmoe_blocks()
    bf = G._fwd_block_f(d, f, 128, 2, block_f or f, 64 << 20)
    assert bf == (block_f or f)
    spec = G._fwd_grid_spec(576 * 128, d, f, 128, bf, transpose_rhs)
    assert spec.grid == (f // bf, 576)
    assert spec.in_specs[1].block_shape == (
        (1, bf, d) if transpose_rhs else (1, d, bf))
    assert _fetches(spec.grid, spec.in_specs[1].index_map, gid) == 64 * f // bf
    # rows and result: every block of each, once per ``f`` block
    assert _fetches(spec.grid, spec.in_specs[0].index_map, gid) == 576 * f // bf
    assert _fetches(spec.grid, spec.out_specs.index_map, gid) == 576 * f // bf


@pytest.mark.parametrize("d,f,block_f", [
    (2048, 1024, None), (1024, 2048, None), (2048, 1024, 512),
    (1024, 2048, 256),
])
def test_a_groups_gradient_block_stays_resident_over_its_rows(d, f, block_f):
    """The d_rhs grid walked through its index maps: the float32 output
    block changes (is written back) ``groups x d/bd x f/bf`` times."""
    gid = _olmoe_blocks(1)
    bd, bf = G._drhs_blocks(d, f, 128, 2, block_f or max(d, f), 64 << 20)
    assert (bd, bf) == ((block_f, block_f) if block_f else (d, f))
    spec = G._drhs_grid_spec(576 * 128, d, f, 128, bd, bf)
    assert spec.grid == (d // bd, f // bf, 576)
    blocks = (d // bd) * (f // bf)
    assert _fetches(spec.grid, spec.out_specs.index_map, gid) == 64 * blocks
    # the two row operands: once per block of the other dimension
    for operand in spec.in_specs:
        assert _fetches(spec.grid, operand.index_map, gid) == 576 * blocks


@pytest.mark.parametrize("vmem_limit,fwd,drhs", [
    (64 << 20, 1024, (2048, 1024)),     # v5e, v6e: as wide as the matrices
    (32 << 20, 1024, (1024, 1024)),     # v5p: the 8 MB float32 block is out
    (16 << 20, 1024, (512, 1024)),      # v4, and Mosaic's default
    (8 << 20, 512, (512, 512)),         # the blocks of before PR 31
    (4 << 20, 128, (256, 512)),
    (1 << 10, 128, (128, 128)),         # nothing fits: the narrowest
])
def test_the_blocks_are_the_widest_that_fit_the_vmem_limit(vmem_limit, fwd,
                                                           drhs):
    assert G._fwd_block_f(2048, 1024, 128, 2, 1024, vmem_limit) == fwd
    assert G._drhs_blocks(2048, 1024, 128, 2, 2048, vmem_limit) == drhs


@pytest.mark.parametrize("dim,cap,block", [
    (1024, 512, 512), (4096, 512, 512), (384, 512, 384), (768, 512, 384),
    (640, 512, 128), (2048, 256, 256), (100, 512, 128),
])
def test_flash_attention_is_given_the_blocks_it_was(dim, cap, block):
    """``pick_block`` serves the flash kernels (gpt2's and OLMoE's steps
    compile to their kernels' text): capped at 512 from its four candidates,
    whatever ``divisor_blocks`` offers ``gmm``."""
    from bagua_tpu.ops.tiles import divisor_blocks, pick_block
    assert pick_block(dim, cap) == block
    assert pick_block(dim) == pick_block(dim, 512)
    assert divisor_blocks(dim)[0] == dim
    assert all(dim % b == 0 for b in divisor_blocks(dim))
