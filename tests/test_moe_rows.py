"""The expert layer's row kernels (``bagua_tpu/ops/moe_rows.py``) in interpret
mode on the CPU, against the ``jnp`` bodies they replace where they run —
``take_or_zero``, ``y[slots]`` summed over ``k`` — and the gates around them.
What Mosaic makes of the calls at the cells' shapes is
``tests/test_flash_attention_v5e.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.model_parallel.moe.layer import (
    _combine, _inverse_permutation, _take_index,
)
from bagua_tpu.ops import gmm
from bagua_tpu.ops import moe_rows as rows_mod
from bagua_tpu.ops.moe_rows import (
    rows_in, rows_in_supported, rows_sum, rows_sum_supported,
)
from internal.row_kernels import force_row_kernels

TOKENS, EXPERTS = 64, 8
#: (k, d, what the layout holds): the three cells' ``k`` at their two
#: widths; a rank that holds every expert, a quarter of them (the rest of a
#: token's pairs are sentinels that enter no group: SmallThinker, SDAR),
#: none (a layout that is all padding); tokens that repeat (SDAR's ``MASK``
#: copies)
CASES = [(1, 2048, "all-held"), (6, 2048, "all-held"), (8, 2048, "all-held"),
         (1, 2560, "all-held"), (6, 2560, "quarter-held"),
         (8, 2560, "all-held"), (8, 2048, "quarter-held"),
         (6, 2048, "none-held"), (8, 2048, "repeated-rows")]
cases = pytest.mark.parametrize(
    "k, d, holds", CASES, ids=[f"k{k}-d{d}-{holds}" for k, d, holds in CASES])
dtypes = pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                                 ids=["bf16", "f32"])


def bf16_ulp(x):
    x = np.abs(np.asarray(x, np.float64))
    return np.maximum(2.0 ** (np.floor(np.log2(np.maximum(x, 1e-30))) - 7),
                      2e-6)


def layer_maps(k, holds, seed=0):
    """``(reader, slots)`` as ``MoEMLP._dropless`` builds them: ``TOKENS``
    tokens to ``k`` of ``EXPERTS`` experts, of which this rank holds some;
    the layout block-aligned (128 rows a block)."""
    held = {"all-held": EXPERTS, "repeated-rows": EXPERTS,
            "quarter-held": EXPERTS // 4, "none-held": 0}[holds]
    logits = jax.random.normal(jax.random.PRNGKey(seed), (TOKENS, EXPERTS))
    flat_e = jax.lax.top_k(logits, k)[1].reshape(-1)
    n_local = max(held, 1)
    flat_e = jnp.where(flat_e < held, flat_e, n_local)
    order = jnp.argsort(flat_e)
    rank = _inverse_permutation(order)
    sizes = (flat_e[:, None] == jnp.arange(n_local)[None, :]).sum(
        0, dtype=jnp.int32)
    layout = gmm.padded_layout(sizes, TOKENS * k)
    reader = _take_index(order, layout.src)
    return reader, layout.pos[rank].reshape(TOKENS, k)


def tensors(k, d, holds, dtype, reader, seed=1):
    """Tokens ``x`` / a cotangent ``g`` [T, d], the layout's rows ``y``
    [R, d] (its padding rows zero) and the gates [T, k]."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = 3.0 * jax.random.normal(keys[0], (TOKENS, d))
    if holds == "repeated-rows":
        x = x.at[::3].set(x[0])
    live = (reader < TOKENS * k)[:, None]
    y = jnp.where(live, jax.random.normal(keys[1], (reader.shape[0], d)), 0)
    gates = jax.random.uniform(keys[2], (TOKENS, k))
    g = jax.random.normal(keys[3], (TOKENS, d))
    return x.astype(dtype), y.astype(dtype), gates, g.astype(dtype)


@dtypes
@cases
def test_rows_in_is_the_gather_to_the_bit(k, d, holds, dtype):
    """Plain: ``take_or_zero``.  Weighted, with the products: the combine's
    transpose as ``_combine_bwd`` writes it — the rows to the bit (the same
    float32 product, one rounding), the products to float32's rounding of a
    sum of ``d`` terms."""
    reader, slots = layer_maps(k, holds)
    x, y, gates, g = tensors(k, d, holds, dtype, reader)
    src = reader // k
    assert bool(jnp.any(src == TOKENS)), "no sentinel source: nothing padded"
    got = rows_in(x, src, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(gmm.take_or_zero(x, src),
                                             np.float32))
    if holds == "none-held":
        assert not bool(jnp.any(got != 0))

    d_y, products = rows_in(g, src, (gates, reader), dot=y, interpret=True)
    g_rows = gmm.take_or_zero(g, src).astype(jnp.float32)
    w = gmm.take_or_zero(gates.reshape(-1), reader)
    np.testing.assert_array_equal(
        np.asarray(d_y, np.float32),
        np.asarray((g_rows * w[:, None]).astype(dtype), np.float32))
    want = (g_rows * y.astype(jnp.float32)).sum(-1)
    np.testing.assert_allclose(products, want, rtol=0,
                               atol=1e-5 * max(float(jnp.abs(want).max()), 1))


@dtypes
@cases
def test_rows_sum_is_the_sum_over_k_to_one_ulp(k, d, holds, dtype):
    """``_combine``'s and ``_pad_rows_bwd``'s bodies: the same float32
    terms, added in slot order where ``y[slots].sum(1)`` adds in ``j``
    order, one rounding."""
    reader, slots = layer_maps(k, holds)
    _, y, gates, _ = tensors(k, d, holds, dtype, reader)
    dest = reader // k
    rows = y[slots].astype(jnp.float32)
    for weights, want in [(None, rows.sum(1)),
                          ((gates, reader), (rows * gates[..., None]).sum(1))]:
        got = rows_sum(y, dest, TOKENS, weights, interpret=True)
        assert got.dtype == dtype and got.shape == (TOKENS, d)
        want = np.asarray(want.astype(dtype), np.float64)
        got = np.asarray(got, np.float64)
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * k)
        else:
            assert np.all(np.abs(got - want) <= bf16_ulp(want))
            assert np.mean(got == want) > 0.99
        if holds == "none-held":
            assert not np.any(got != 0)


@pytest.fixture
def forced(monkeypatch):
    force_row_kernels(monkeypatch)


@pytest.mark.parametrize("k", [6, 8])
def test_pad_rows_vjp_is_the_fallbacks(forced, k):
    reader, slots = layer_maps(k, "quarter-held")
    x, _, _, _ = tensors(k, 256, "quarter-held", jnp.bfloat16, reader)
    # a padding slot's cotangent is zero in the layer (``act(0) * 0``, and
    # the combine's transpose writes zero there): ``g[slots]`` reads it for
    # a pair that entered no group, ``rows_sum`` drops the slot
    g = jnp.where((reader < TOKENS * k)[:, None], jax.random.normal(
        jax.random.PRNGKey(5), (reader.shape[0], 256)), 0)

    def grad(by_kernel):
        return jax.grad(lambda x: jnp.sum(gmm.pad_rows(
            x, reader // k, slots, by_kernel).astype(jnp.float32) * g))(x)

    got = np.asarray(grad(True), np.float64)
    want = np.asarray(grad(False), np.float64)
    assert np.all(np.abs(got - want) <= bf16_ulp(want))
    assert np.mean(got == want) > 0.99


@pytest.mark.parametrize("k", [6, 8])
@pytest.mark.parametrize("leaf", ["out", "d_y", "d_gates"])
def test_combine_vjp_is_the_fallbacks(forced, k, leaf):
    reader, slots = layer_maps(k, "quarter-held")
    _, y, gates, g = tensors(k, 256, "quarter-held", jnp.bfloat16, reader)

    def parts(by_kernel):
        out, vjp = jax.vjp(lambda y, gates: _combine(
            y, gates, slots, reader, by_kernel), y, gates)
        d_y, d_gates = vjp(g)
        return {"out": out, "d_y": d_y, "d_gates": d_gates}

    got = np.asarray(parts((True, True))[leaf], np.float64)
    want = np.asarray(parts((False, False))[leaf], np.float64)
    if leaf == "d_gates":
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * np.abs(want).max())
    else:
        assert np.all(np.abs(got - want) <= bf16_ulp(want))
        assert np.mean(got == want) > 0.99


CELLS = {"olmoe": (8192, 73728, 2048), "smallthinker": (8192, 51200, 2560),
         "sdar": (8192, 67584, 2048)}


@pytest.mark.parametrize("why, shape, dtype", [
    ("a-cell", None, jnp.bfloat16),
    ("float32", None, jnp.float32),
    ("lanes", (8192, 73728, 2000), jnp.bfloat16),
    ("dtype", (8192, 73728, 2048), jnp.float16),
    ("slots-no-row-block", (8192, 73728 + 64, 2048), jnp.bfloat16),
    ("tokens-no-tile", (8192 + 8, 73728, 2048), jnp.bfloat16),
])
def test_the_gates_refuse_what_the_kernels_do_not_cover(monkeypatch, why,
                                                        shape, dtype):
    """Off the TPU nothing is taken; on it (steered) the cells' shapes are,
    and each shape the kernels do not cover is refused by itself."""
    shapes = [shape] if shape else list(CELLS.values())
    for t, r, d in shapes:
        assert not rows_in_supported(t, r, d, dtype)
        assert not rows_sum_supported(t, r, d, dtype)
    monkeypatch.setattr(rows_mod, "_on_tpu", lambda: True)
    for t, r, d in shapes:
        assert rows_in_supported(t, r, d, dtype, with_dot=True) == (
            shape is None)
        assert rows_sum_supported(t, r, d, dtype) == (shape is None)


def test_a_call_the_kernels_do_not_cover_raises():
    x = jnp.zeros((64, 100), jnp.bfloat16)
    with pytest.raises(ValueError, match="no fallback"):
        rows_in(x, jnp.zeros((128,), jnp.int32), interpret=True)
    with pytest.raises(ValueError, match="no fallback"):
        rows_sum(jnp.zeros((128, 100), jnp.bfloat16),
                 jnp.zeros((128,), jnp.int32), 64, interpret=True)


@pytest.mark.parametrize("name", list(CELLS))
def test_the_resident_side_takes_the_columns_at_once_in_the_cells(name):
    """One pass over the index vectors: the whole source (``rows_in``), the
    whole float32 accumulator (``rows_sum``) fit the VMEM a call may ask —
    but SmallThinker's accumulator, whose rows of twenty lane tiles take
    twenty-four: two passes of ten."""
    t, r, d = CELLS[name]
    assert rows_mod.column_block(d, rows_mod._in_bytes(t, r, 2, True)) == d
    assert rows_mod.column_block(d, rows_mod._sum_bytes(t, r, 2)) == (
        d if d == 2048 else d // 2)
    # a source four times as tall is cut into column passes, not refused
    assert 0 < rows_mod.column_block(
        d, rows_mod._in_bytes(4 * t, r, 2, True)) < d


@pytest.mark.parametrize("k", [2, 8])
def test_the_layers_step_calls_the_three_kernels_and_gathers_rows_once(
        forced, k):
    """Value-and-grad of a forced layer: ``moe_rows_sum`` twice (the
    combine, the dispatch's transpose), ``moe_rows_in`` once (the combine's
    transpose), and of the gathers only the dispatch's own writes rows of
    the layer's width — no ``[T, k, d]`` array is gathered."""
    from bagua_tpu.model_parallel.moe.layer import MoEMLP
    from internal.jaxpr_walk import primitives

    layer = MoEMLP(n_experts=16, d_ff=128, k=k, dropless=True, gated=True,
                   dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 128))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    found = primitives(jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply({"params": p}, x)), argnums=(0, 1)),
        params, x)
    names = [name for name, _ in found]
    assert names.count("moe_rows_sum") == 2
    assert names.count("moe_rows_in") == 1
    wide = [shapes for name, shapes in found if name == "gather"
            and len(shapes[0]) == 2 and shapes[0][-1] == 128]
    assert len(wide) == 1 and wide[0][0] == (128 + 1, 128), wide
